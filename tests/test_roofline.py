"""Roofline HLO parser: trip-count multipliers, dot FLOPs, collective
bytes, memory model — against hand-crafted HLO and a real compiled module."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline import hlo

MINI_HLO = """
HloModule test

%body.1 (arg: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %g0 = s32[] get-tuple-element(%p), index=0
  %g1 = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %w = f32[16,16]{1,0} constant({...})
  %dot.1 = f32[8,16]{1,0} dot(f32[8,16]{1,0} %g1, f32[16,16]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%dot.1), replica_groups=[16,16]<=[256], to_apply=%add.0
  ROOT %t = (s32[], f32[8,16]) tuple(%g0, %ar)
}

%cond.1 (arg: (s32[], f32[8,16])) -> pred[] {
  %p2 = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%p2), index=0
  %c = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (x: f32[8,16]) -> f32[8,16] {
  %x = f32[8,16]{1,0} parameter(0)
  %init = (s32[], f32[8,16]) tuple(s32[] constant(0), %x)
  %wh = (s32[], f32[8,16]) while(%init), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%wh), index=1
}
"""


def test_shape_bytes():
    assert hlo.shape_bytes("f32[8,16]{1,0}") == 512
    assert hlo.shape_bytes("bf16[4,4]") == 32
    assert hlo.shape_bytes("(f32[2,2], s32[3])") == 28
    assert hlo.shape_bytes("pred[10]") == 10


def test_trip_count_multiplier_and_dot_flops():
    comps = hlo.parse_computations(MINI_HLO)
    assert set(comps) >= {"body.1", "cond.1", "main"}
    mult = hlo.compute_multipliers(comps, "main")
    assert mult["body.1"] == 10.0          # known_trip_count applied
    flops, by_dt = hlo.dot_flops(comps, mult)
    # dot: 2 * (8*16 out) * 16 contract = 4096 per trip, x10 trips
    assert flops == pytest.approx(40960.0)
    assert by_dt == {"f32": pytest.approx(40960.0)}


def test_collective_ring_model():
    comps = hlo.parse_computations(MINI_HLO)
    mult = hlo.compute_multipliers(comps, "main")
    total, by_kind = hlo.collective_bytes(comps, mult)
    # all-reduce of 512 bytes in groups of 16: 2*(15/16)*512 per trip, x10
    assert total == pytest.approx(2 * 15 / 16 * 512 * 10)
    assert "all-reduce" in by_kind


def test_group_size_parsing():
    assert hlo._group_size("replica_groups=[16,16]<=[256]") == 16
    assert hlo._group_size("replica_groups=[64,4]<=[256]") == 4
    assert hlo._group_size("replica_groups={{0,1,2,3},{4,5,6,7}}") == 4


def test_real_compiled_module_roundtrip():
    """Parse an actually-compiled scan module; trip-aware FLOPs must exceed
    cost_analysis (which counts loop bodies once) by ~the trip count."""
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    a = hlo.analyze(compiled.as_text())
    ca_flops = hlo.cost_analysis_dict(compiled)["flops"]
    per_iter = 2 * 64 * 64 * 64
    assert a.dot_flops == pytest.approx(10 * per_iter, rel=0.01)
    assert ca_flops == pytest.approx(per_iter, rel=0.1)   # the XLA gotcha
    assert a.max_trip == 10


def test_memory_model_inplace_semantics():
    """DUS counts only the update slice, not the aliased big buffer."""
    text = """
ENTRY %m (b: f32[1000,64], u: f32[1,64]) -> f32[1000,64] {
  %b = f32[1000,64]{1,0} parameter(0)
  %u = f32[1,64]{1,0} parameter(1)
  %z = s32[] constant(0)
  ROOT %d = f32[1000,64]{1,0} dynamic-update-slice(%b, %u, %z, %z)
}
"""
    comps = hlo.parse_computations(text)
    mult = hlo.compute_multipliers(comps, "m")
    mem = hlo.memory_bytes(comps, mult, set())
    # update slice read+write (+ the two s32 index scalars), not the 256 KB
    # aliased buffer
    assert mem == pytest.approx(2 * (64 * 4 + 2 * 4))


def test_glm_task_configs():
    from repro.configs.glm import GLM_CONFIGS, get_glm
    assert len(GLM_CONFIGS) == 10            # 5 datasets x 2 tasks
    c = get_glm("w8a-lr")
    assert c.async_rep_k == 10 and c.async_access == "round_robin"
    strat = c.async_strategy()
    assert strat.rep_k == 10


def test_peaks_are_keyed_by_device_kind():
    from repro.roofline import analysis

    v5e = analysis.peaks("tpu-v5-lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    assert analysis.PEAK_FLOPS == analysis.peaks(analysis.TARGET_DEVICE)["flops"]
    with pytest.raises(ValueError, match="no published peaks"):
        analysis.peaks("cpu")

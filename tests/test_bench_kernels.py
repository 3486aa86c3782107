"""Tests for the kernel trajectory producer, its store, and the gate."""
import json

import pytest

from repro.roofline import kernels as rkernels
from repro.study import claims
from repro.study.store import KernelBenchStore


# ---------------------------------------------------------------------------
# claims.check_bench_kernels: conformance + regression gate
# ---------------------------------------------------------------------------


def _row(label="glm_grad/x/float32/default", wall=1.0, match=True,
         baseline=None):
    return {"label": label, "wall_s": wall, "pallas_match": match,
            "baseline_wall_s": baseline}


def test_gate_clean_rows_pass():
    assert claims.check_bench_kernels([_row(), _row(match=None)]) == []


def test_gate_flags_oracle_mismatch():
    bad = claims.check_bench_kernels([_row(match=False)])
    assert len(bad) == 1 and "mismatch" in bad[0]


def test_gate_flags_regression_over_tolerance():
    tol = claims.KERNEL_REGRESSION_TOL
    ok = _row(wall=1.0 * (1 + tol) * 0.99, baseline=1.0)
    slow = _row(wall=1.0 * (1 + tol) * 1.05, baseline=1.0)
    assert claims.check_bench_kernels([ok]) == []
    bad = claims.check_bench_kernels([slow])
    assert len(bad) == 1 and "regressed" in bad[0]


def test_gate_ignores_missing_baseline():
    # cross-host / first-run points have no comparable committed entry
    assert claims.check_bench_kernels([_row(wall=100.0, baseline=None)]) == []


def test_gate_rejects_fully_unchecked_run():
    """Regression for the vacuous ``all({})`` bug: a run where no Pallas
    flavor was checked must not validate as green."""
    rows = [_row(match=None), _row(label="b", match=None)]
    bad = claims.check_bench_kernels(rows)
    assert len(bad) == 1 and "unchecked" in bad[0]
    # one checked row is enough to clear the blanket violation
    assert claims.check_bench_kernels(rows[:1] + [_row()]) == []


# ---------------------------------------------------------------------------
# KernelBenchStore determinism
# ---------------------------------------------------------------------------


def test_kernel_store_snapshot_sorted_and_deterministic(tmp_path):
    s = KernelBenchStore(tmp_path / "BENCH_kernels.json",
                         jsonl_path=tmp_path / "runs.jsonl")
    s.record_entry("b/label", {"wall_s": 2.0})
    s.record_entry("a/label", {"wall_s": 1.0}, cached=True)
    snap = s.snapshot()
    assert list(snap["entries"]) == ["a/label", "b/label"]
    assert "ts" not in json.dumps(snap)
    p = s.write()
    first = p.read_bytes()
    s.write()
    assert p.read_bytes() == first  # snapshot has no run-varying fields
    assert KernelBenchStore.load(p) == snap
    # run-variance goes to the sidecar only
    lines = [json.loads(l) for l in (tmp_path / "runs.jsonl").open()]
    assert len(lines) == 2 and all("ts" in l for l in lines)
    assert lines[0]["n_entries"] == 2 and lines[0]["n_cached"] == 1


# ---------------------------------------------------------------------------
# Analytic roofline annotations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel,info", [
    ("glm_grad", {"n": 512, "d": 128}),
    ("glm_sgd", {"n": 256, "d": 64}),
    ("glm_sparse", {"n": 256, "d": 512, "k": 8}),
    ("glm_sgd_sparse", {"n": 128, "d": 256, "k": 8}),
    ("glm_score", {"n": 32, "d": 512, "k": 8}),
    ("flash_attn", {"batch": 1, "heads_q": 2, "heads_kv": 1,
                    "seq_q": 64, "seq_k": 64, "head_dim": 32}),
])
def test_roofline_annotation_fields(kernel, info):
    a = rkernels.annotate(kernel, info, wall_s=1e-3)
    assert a["flops"] > 0 and a["hbm_bytes"] > 0
    assert a["bound"] in ("compute", "memory")
    assert a["tpu_bound_s"] == max(a["tpu_compute_s"], a["tpu_memory_s"])
    assert a["achieved_gflops"] == pytest.approx(a["flops"] / 1e-3 / 1e9)
    # without a measurement the derived fields are absent, not zero
    assert "achieved_gflops" not in rkernels.annotate(kernel, info)


def test_roofline_unknown_kernel_raises():
    with pytest.raises(KeyError):
        rkernels.kernel_cost("nope", {})


def test_roofline_intensity_orders_families():
    """Dense GLM gradient has ~matmul intensity; the sparse families are
    gather-bound and must price below it."""
    dense = rkernels.kernel_cost("glm_grad", {"n": 1024, "d": 512})
    sp = rkernels.kernel_cost("glm_sparse", {"n": 1024, "d": 512, "k": 8})
    assert (dense["flops"] / dense["hbm_bytes"]
            > sp["flops"] / sp["hbm_bytes"])


# ---------------------------------------------------------------------------
# Producer end-to-end (micro shapes): trajectory points + reproducibility
# ---------------------------------------------------------------------------


TINY_SHAPES = {
    "glm_grad": {"ci": dict(n=32, d=16)},
    "glm_sgd": {"ci": dict(n=16, d=8)},
    "glm_sparse": {"ci": dict(n=16, d=128, k=4)},
    "glm_sgd_sparse": {"ci": dict(n=16, d=64, k=4)},
    "flash_attn": {"ci": dict(batch=1, heads_q=2, heads_kv=1, seq_q=16,
                              seq_k=16, head_dim=8)},
}


def test_producer_trajectory_and_byte_reproducibility(tmp_path, monkeypatch):
    from benchmarks import bench_kernels, common

    monkeypatch.setattr(bench_kernels, "SHAPES", TINY_SHAPES)
    monkeypatch.setattr(common, "RESULTS_DIR", tmp_path / "res")
    out = tmp_path / "BENCH_kernels.json"

    rows = bench_kernels.run("ci", out_json=str(out))
    data = json.loads(out.read_text())
    kernels_seen = {e["kernel"] for e in data["entries"].values()}
    assert kernels_seen == set(TINY_SHAPES)  # >=1 point per family
    for e in data["entries"].values():
        assert e["wall_s"] > 0
        assert e["pallas_match"] is True  # interpret flavor checked on CPU
        assert e["roofline"]["bound"] in ("compute", "memory")
        assert {"host", "device_kind", "backend", "config"} <= set(e)
    # default + bf16 variants present for every family
    variants = {(e["kernel"], e["dtype"], e["variant"])
                for e in data["entries"].values()}
    for k in TINY_SHAPES:
        assert (k, "float32", "default") in variants
        assert (k, "bfloat16", "default") in variants
    # cold run: committed file absent -> no baselines, gate clean
    assert all(r["baseline_wall_s"] is None for r in rows)
    assert claims.check_bench_kernels(rows) == []

    first = out.read_bytes()
    rows2 = bench_kernels.run("ci", out_json=str(out))
    assert out.read_bytes() == first  # warm re-run is byte-identical
    # warm run gates against the (now committed) same-host trajectory
    assert all(r["baseline_wall_s"] == r["wall_s"] for r in rows2)
    assert claims.check_bench_kernels(rows2) == []

"""Unit tests for the per-device block/grid autotuner (repro.kernels.tune)."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels  # noqa: F401  — registers all families
from repro.kernels import common, tune


# ---------------------------------------------------------------------------
# Shape classes and cache keys
# ---------------------------------------------------------------------------


def test_shape_class_buckets_to_powers_of_two():
    a = tune.shape_class({"n": 96, "d": 50, "dtype": "float32"})
    b = tune.shape_class({"n": 128, "d": 64, "dtype": "float32"})
    assert a == b == {"d": 64, "dtype": "float32", "n": 128}


def test_shape_class_passes_non_integers_through():
    sc = tune.shape_class({"sparse": True, "dtype": "bfloat16", "n": 0})
    assert sc == {"dtype": "bfloat16", "n": 0, "sparse": True}
    assert sc["sparse"] is True  # bools survive as bools, not buckets


def test_cache_key_separates_kernels_backends_and_classes(tmp_path):
    cache = tune.TuneCache(tmp_path)
    info = {"n": 64, "d": 32, "dtype": "float32"}
    base = cache.key("glm_grad", common.REFERENCE, info)
    assert cache.key("glm_grad", common.REFERENCE, {"n": 96, "d": 50,
                                                    "dtype": "float32"}) \
        != base  # different bucket for n (128 vs 64)
    assert cache.key("glm_grad", common.PALLAS_INTERPRET, info) != base
    assert cache.key("glm_sgd", common.REFERENCE, info) != base
    # same bucket -> same key
    assert cache.key("glm_grad", common.REFERENCE,
                     {"n": 33, "d": 17, "dtype": "float32"}) \
        == cache.key("glm_grad", common.REFERENCE,
                     {"n": 64, "d": 32, "dtype": "float32"})


def test_cache_round_trip_is_canonical_json(tmp_path):
    cache = tune.TuneCache(tmp_path)
    payload = {"b": 2, "a": 1}
    cache.put("k1", payload)
    raw = (tmp_path / "k1.json").read_text()
    assert raw == '{"a":1,"b":2}'  # sorted keys, no whitespace
    assert cache.get("k1") == payload
    assert cache.get("nope") is None


# ---------------------------------------------------------------------------
# Candidate grids
# ---------------------------------------------------------------------------


def test_micro_batch_candidates_divide_n():
    cands = tune.TUNABLES["glm_sgd"].candidates({"n": 96})
    mbs = [c["micro_batch"] for c in cands]
    assert mbs == [8, 16, 32]
    assert all(common.micro_batch_ok(96, m) for m in mbs)
    # a small prime n leaves the whole epoch as one tile
    assert tune.TUNABLES["glm_sgd"].candidates({"n": 97}) \
        == ({"micro_batch": 97},)
    # a large n with no multiple of 8 dividing it has no Pallas tile
    assert tune.TUNABLES["glm_sgd"].candidates({"n": 581_011}) == ()


def test_attn_candidates_divide_both_sequences():
    cands = tune.TUNABLES["flash_attn"].candidates({"seq_q": 64, "seq_k": 128})
    assert cands
    for c in cands:
        assert 64 % c["block_q"] == 0 and 128 % c["block_k"] == 0
    # unalignable sequences produce no candidates rather than bad ones
    assert tune.TUNABLES["flash_attn"].candidates({"seq_q": 7, "seq_k": 64}) \
        == ()


def test_row_block_and_sparse_candidates_are_aligned():
    for c in tune.TUNABLES["glm_grad"].candidates({"n": 200}):
        assert c["block_rows"] % common.SUBLANE == 0
    for c in tune.TUNABLES["glm_sparse"].candidates({"n": 64, "d": 700}):
        assert c["block_rows"] % common.SUBLANE == 0
        assert c["d_block"] % common.LANE == 0


# ---------------------------------------------------------------------------
# tune / lookup / consult
# ---------------------------------------------------------------------------


def test_tune_sweeps_candidates_and_caches_winner(tmp_path):
    cache = tune.TuneCache(tmp_path)
    info = {"n": 32, "d": 16, "dtype": "float32"}
    calls = []

    def run(**cfg):
        calls.append(cfg)
        return jnp.zeros(())

    rec = tune.tune("glm_grad", common.REFERENCE, info, run, cache=cache,
                    warmup=0, iters=1)
    assert rec["config"] in [c["config"] for c in rec["candidates"]]
    assert {"schema", "kernel", "backend", "device_kind", "shape_class",
            "config", "candidates"} <= set(rec)
    assert calls  # the sweep actually ran the kernel
    # second call short-circuits on the cache (no new timings)
    n_calls = len(calls)
    rec2 = tune.tune("glm_grad", common.REFERENCE, info, run, cache=cache)
    assert rec2 == rec and len(calls) == n_calls
    # and lookup returns only the declared tunable params
    cfg = tune.lookup("glm_grad", common.REFERENCE, info, cache=cache)
    assert set(cfg) == {"block_rows"}


def test_tune_unknown_kernel_raises(tmp_path):
    with pytest.raises(KeyError, match="no tunable parameters"):
        tune.tune("nope", common.REFERENCE, {}, lambda **k: None,
                  cache=tune.TuneCache(tmp_path))


def test_lookup_filters_foreign_config_keys(tmp_path):
    cache = tune.TuneCache(tmp_path)
    info = {"n": 32, "dtype": "float32"}
    key = cache.key("glm_sgd", common.REFERENCE, info)
    cache.put(key, {"config": {"micro_batch": 4, "evil_kwarg": 99}})
    assert tune.lookup("glm_sgd", common.REFERENCE, info, cache=cache) \
        == {"micro_batch": 4}


def test_consult_defaults_to_empty_without_cache_or_env(tmp_path, monkeypatch):
    monkeypatch.delenv(tune.ENV_AUTOTUNE, raising=False)
    cache = tune.TuneCache(tmp_path)
    info = {"n": 32, "d": 16, "dtype": "float32"}
    ran = []
    assert tune.consult("glm_grad", common.REFERENCE, info,
                        lambda **c: ran.append(c), cache=cache) == {}
    assert not ran  # no sweep unless REPRO_KERNEL_AUTOTUNE=1


def test_consult_tunes_on_miss_when_env_set(tmp_path, monkeypatch):
    monkeypatch.setenv(tune.ENV_AUTOTUNE, "1")
    cache = tune.TuneCache(tmp_path)
    info = {"n": 32, "d": 16, "dtype": "float32"}

    cfg = tune.consult("glm_grad", common.REFERENCE, info,
                       lambda **c: jnp.zeros(()), cache=cache)
    assert set(cfg) == {"block_rows"}
    # winner is now cached: a later consult needs no run closure at all
    assert tune.consult("glm_grad", common.REFERENCE, info, None,
                        cache=cache) == cfg


def test_consult_without_run_closure_is_lookup_only(tmp_path, monkeypatch):
    monkeypatch.setenv(tune.ENV_AUTOTUNE, "1")
    cache = tune.TuneCache(tmp_path)
    assert tune.consult("glm_grad", common.REFERENCE,
                        {"n": 8, "d": 8, "dtype": "float32"}, None,
                        cache=cache) == {}


def test_timeable_rejects_tracers():
    import jax

    x = jnp.ones((4,))
    assert tune.timeable(x)
    seen = []
    jax.jit(lambda a: seen.append(tune.timeable(a)) or a)(x)
    assert seen == [False]


# ---------------------------------------------------------------------------
# End-to-end: dispatch-time consultation applies the cached winner
# ---------------------------------------------------------------------------


def test_glm_grad_applies_cached_winner(tmp_path, monkeypatch, glm_data):
    """A cached tuning record changes the block size an unpinned call uses."""
    from repro.kernels.glm_grad import glm_grad
    from repro.kernels.glm_grad.ref import glm_grad_ref

    monkeypatch.setenv(tune.ENV_TUNE_DIR, str(tmp_path))
    monkeypatch.delenv(tune.ENV_AUTOTUNE, raising=False)
    X, y, w = glm_data(64, 24)
    info = {"dtype": "float32", "n": 64, "d": 24}
    b = common.resolve_backend("glm_grad", info=info)
    cache = tune.TuneCache(tmp_path)
    cache.put(cache.key("glm_grad", b, info),
              {"config": {"block_rows": 32}})
    out = glm_grad("lr", w, X, y)  # unpinned -> consults the cache
    np.testing.assert_allclose(out, glm_grad_ref("lr", w, X, y),
                               rtol=1e-4, atol=2e-3)


def test_autotune_env_tunes_and_reuses(tmp_path, monkeypatch, glm_data):
    from repro.kernels.glm_grad import glm_grad

    monkeypatch.setenv(tune.ENV_TUNE_DIR, str(tmp_path))
    monkeypatch.setenv(tune.ENV_AUTOTUNE, "1")
    X, y, w = glm_data(48, 20)
    glm_grad("lr", w, X, y)
    recs = list(tmp_path.glob("*.json"))
    assert len(recs) == 1
    rec = json.loads(recs[0].read_text())
    assert rec["kernel"] == "glm_grad" and rec["candidates"]
    # the second call must reuse the record, not re-time
    stamp = recs[0].stat().st_mtime_ns
    glm_grad("lr", w, X, y)
    assert recs[0].stat().st_mtime_ns == stamp


def test_device_kind_raises_without_a_backend(monkeypatch):
    """No silent "unknown" key: a cache entry must name real hardware."""
    import jax

    def boom():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="no backend"):
        tune.device_kind()

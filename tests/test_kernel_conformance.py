"""Backend conformance harness for the kernel dispatch layer.

Every registered kernel family runs against its pure-jnp reference oracle
on every backend available on this host (CPU CI: ``pallas-interpret`` and
``reference``; TPU adds ``pallas-tpu``), in fp32 and bf16, for both GLM
losses and both dense and sparse data.  Future kernel PRs must keep this
suite green — it is the executable contract of DESIGN.md §3.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.kernels  # noqa: F401  — registers all families
from repro.kernels import common
from repro.kernels.flash_attn import flash_attention
from repro.kernels.flash_attn.ref import attention_ref
from repro.kernels.glm_grad import glm_grad
from repro.kernels.glm_grad.ref import glm_grad_ref
from repro.kernels.glm_sgd import glm_sgd_epoch
from repro.kernels.glm_sgd.ref import glm_sgd_epoch_ref
from repro.kernels.glm_sgd_csr import csr_margins, csr_sgd_epoch
from repro.kernels.glm_sgd_csr.kernel import CHUNK
from repro.kernels.glm_sgd_csr.ref import csr_margins_ref, csr_sgd_epoch_ref
from repro.kernels.glm_sgd_sparse import ell_sgd_epoch
from repro.kernels.glm_sgd_sparse.ref import ell_sgd_epoch_ref
from repro.kernels.glm_score import glm_score
from repro.kernels.glm_score.ref import glm_score_ref
from repro.kernels.glm_sparse import ell_glm_grad, ell_margins
from repro.kernels.glm_sparse.ref import ell_glm_grad_ref, ell_margins_ref

FAMILIES = ("flash_attn", "glm_grad", "glm_score", "glm_sgd",
            "glm_sgd_sparse", "glm_sparse", "glm_sparse_margins",
            "glm_sgd_csr", "glm_csr_margins")
DTYPES = (jnp.float32, jnp.bfloat16)
TASKS = ("lr", "svm")


def _f32(*arrays):
    return tuple(a.astype(jnp.float32) for a in arrays)


# ---------------------------------------------------------------------------
# Registry invariants
# ---------------------------------------------------------------------------


def test_all_families_registered_with_all_backends():
    assert set(common.registered_kernels()) >= set(FAMILIES)
    for fam in FAMILIES:
        assert common.backends_for(fam) == common.BACKEND_ORDER, fam


def test_host_availability_excludes_pallas_tpu_off_tpu():
    for fam in FAMILIES:
        avail = common.available_backends(fam)
        assert common.REFERENCE in avail
        assert common.PALLAS_INTERPRET in avail
        assert (common.PALLAS_TPU in avail) == common.on_tpu()


def test_resolve_backend_precedence(monkeypatch):
    monkeypatch.setenv(common.ENV_BACKEND, common.REFERENCE)
    # env var overrides auto ...
    assert common.resolve_backend("glm_grad") == common.REFERENCE
    # ... but explicit call-site forcing beats the env var, whether via
    # backend= or the legacy interpret= flag
    assert (common.resolve_backend("glm_grad", backend=common.PALLAS_INTERPRET)
            == common.PALLAS_INTERPRET)
    assert (common.resolve_backend("glm_grad", interpret=True)
            == common.PALLAS_INTERPRET)


def test_resolve_backend_env_override_applies_to_calls(monkeypatch, glm_data):
    X, y, w = glm_data(16, 8)
    monkeypatch.setenv(common.ENV_BACKEND, common.REFERENCE)
    out = glm_grad("lr", w, X, y)
    np.testing.assert_allclose(out, glm_grad_ref("lr", w, X, y),
                               rtol=1e-6, atol=1e-6)


def test_resolve_backend_legacy_interpret_flag():
    assert (common.resolve_backend("glm_grad", interpret=True)
            == common.PALLAS_INTERPRET)
    if not common.on_tpu():
        with pytest.raises(RuntimeError, match="needs a TPU host"):
            common.resolve_backend("glm_grad", interpret=False)


def test_resolve_backend_rejects_unknown():
    with pytest.raises(KeyError):
        common.resolve_backend("no_such_kernel")
    with pytest.raises(ValueError, match="not registered"):
        common.resolve_backend("glm_grad", backend="cuda")


def test_resolve_backend_env_unregistered_name_errors(monkeypatch):
    """A bad REPRO_KERNEL_BACKEND value fails loudly, not silently."""
    monkeypatch.setenv(common.ENV_BACKEND, "cuda")
    with pytest.raises(ValueError, match="not registered"):
        common.resolve_backend("glm_grad")


def test_resolve_backend_forced_tpu_off_tpu_errors():
    if common.on_tpu():
        pytest.skip("forcing pallas-tpu is legal on a TPU host")
    with pytest.raises(RuntimeError, match="needs a TPU host"):
        common.resolve_backend("glm_grad", backend=common.PALLAS_TPU)


def test_resolve_backend_call_site_beats_env_beats_auto(monkeypatch):
    """Full precedence chain on one kernel: auto -> env -> call site."""
    monkeypatch.delenv(common.ENV_BACKEND, raising=False)
    auto = common.resolve_backend("glm_grad")
    assert auto == common.available_backends("glm_grad")[0]
    monkeypatch.setenv(common.ENV_BACKEND, common.REFERENCE)
    assert common.resolve_backend("glm_grad") == common.REFERENCE
    assert (common.resolve_backend("glm_grad",
                                   backend=common.PALLAS_INTERPRET)
            == common.PALLAS_INTERPRET)


def test_caps_reject_sparse_calls_on_dense_only_impls():
    dense_only = common.Caps()
    assert dense_only.supports({"dtype": "float32"})
    assert not dense_only.supports({"dtype": "float32", "sparse": True})
    assert common.Caps(sparse=True).supports({"sparse": True})


def test_caps_route_huge_sparse_problem_to_reference():
    info = {"dtype": "float32", "sparse": True, "n": 10_000, "d": 1_000_000}
    assert common.resolve_backend("glm_sparse", info=info) == common.REFERENCE
    info["d"] = 20_958
    assert (common.resolve_backend("glm_sparse", info=info)
            != common.REFERENCE)


def test_glm_sparse_legacy_interpret_respects_budget(monkeypatch, ell_data):
    """interpret= picks the Pallas flavor in budget, but never forces the
    one-hot kernel onto problems the VMEM/FLOP budget excludes."""
    seen = []
    real = common.dispatch

    def spy(kernel, *a, **kw):
        seen.append(kw.get("backend"))
        return real(kernel, *a, **kw)

    monkeypatch.setattr(common, "dispatch", spy)
    values, indices, y, w = ell_data(32, 256, 4)
    ell_glm_grad("lr", w, values, indices, y, interpret=True, d_block=128)
    assert seen[-1] == common.PALLAS_INTERPRET
    big_w = jnp.zeros(40_000)  # d > _MAX_D_PALLAS
    ell_glm_grad("lr", big_w, values, indices, y, interpret=True)
    assert seen[-1] == common.REFERENCE  # caps route the call to reference


def test_caps_route_odd_head_dim_to_reference(attn_data):
    q, k, v = attn_data(1, 2, 2, 16, 16, 12)  # hd=12: not sublane-aligned
    assert (common.resolve_backend("flash_attn",
                                   info={"dtype": "float32", "head_dim": 12})
            == common.REFERENCE)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, attention_ref(q, k, v, causal=True),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# glm_grad: dense sum-gradient
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", common.available_backends("glm_grad"))
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("task", TASKS)
def test_glm_grad_conformance(backend, dtype, task, glm_data):
    X, y, w = glm_data(96, 50, dtype)
    ref = glm_grad_ref(task, *_f32(w, X, y))
    out = glm_grad(task, w, X, y, backend=backend, block_rows=16)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-3)


@pytest.mark.parametrize("backend", common.available_backends("glm_grad"))
def test_glm_grad_col_layout_conformance(backend, glm_data):
    X, y, w = glm_data(64, 40)
    ref = glm_grad_ref("lr", w, X, y)
    out = glm_grad("lr", w, X, y, backend=backend, layout="col", block_rows=16)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# glm_sgd: fused epoch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", common.available_backends("glm_sgd"))
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("task", TASKS)
@pytest.mark.parametrize("mb", [1, 4, 8, 32])
def test_glm_sgd_conformance(backend, dtype, task, mb, glm_data):
    """Every flavor matches the oracle; a forced Pallas flavor refuses a
    micro-batch that breaks Mosaic's row tiling (not 8k, not n)."""
    X, y, w = glm_data(32, 40, dtype)
    if backend != common.REFERENCE and not common.micro_batch_ok(32, mb):
        with pytest.raises(ValueError, match="multiple of 8"):
            glm_sgd_epoch(task, w, X, y, step=0.02, micro_batch=mb,
                          backend=backend)
        return
    ref = glm_sgd_epoch_ref(task, *_f32(w, X, y), 0.02, mb)
    out = glm_sgd_epoch(task, w, X, y, step=0.02, micro_batch=mb,
                        backend=backend)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_glm_sgd_caps_route_ragged_n_to_reference(glm_data):
    """Auto dispatch falls through to the ragged-tail oracle when
    micro_batch does not divide n; forcing a Pallas flavor raises."""
    X, y, w = glm_data(30, 16)  # 30 % 4 != 0
    info = {"dtype": "float32", "n": 30, "micro_batch": 4}
    assert common.resolve_backend("glm_sgd", info=info) == common.REFERENCE
    ref = glm_sgd_epoch_ref("lr", w, X, y, 0.02, 4)
    out = glm_sgd_epoch("lr", w, X, y, step=0.02, micro_batch=4)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="micro_batch"):
        glm_sgd_epoch("lr", w, X, y, step=0.02, micro_batch=4,
                      backend=common.PALLAS_INTERPRET)


# ---------------------------------------------------------------------------
# glm_sgd_sparse: fused ELL epoch (gradient + update in one launch)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "backend",
    common.available_backends("glm_sgd_sparse", info={"sparse": True}))
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("task", TASKS)
@pytest.mark.parametrize("mb", [1, 4, 8, 32])
def test_glm_sgd_sparse_conformance(backend, dtype, task, mb, ell_data):
    values, indices, y, w = ell_data(32, 200, 6, dtype)
    if backend != common.REFERENCE and not common.micro_batch_ok(32, mb):
        with pytest.raises(ValueError, match="multiple of 8"):
            ell_sgd_epoch(task, w, values, indices, y, step=0.05,
                          micro_batch=mb, backend=backend)
        return
    ref = ell_sgd_epoch_ref(task, *_f32(w, values), indices,
                            y.astype(jnp.float32), 0.05, mb)
    out = ell_sgd_epoch(task, w, values, indices, y, step=0.05,
                        micro_batch=mb, backend=backend)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-3)


def test_glm_sgd_sparse_caps_route_ragged_n_to_reference(ell_data):
    values, indices, y, w = ell_data(30, 200, 6)  # 30 % 8 != 0
    info = {"dtype": "float32", "sparse": True, "n": 30, "d": 200, "k": 6,
            "micro_batch": 8}
    assert (common.resolve_backend("glm_sgd_sparse", info=info)
            == common.REFERENCE)
    ref = ell_sgd_epoch_ref("lr", w, values, indices, y, 0.05, 8)
    out = ell_sgd_epoch("lr", w, values, indices, y, step=0.05, micro_batch=8)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="micro_batch"):
        ell_sgd_epoch("lr", w, values, indices, y, step=0.05, micro_batch=8,
                      backend=common.PALLAS_INTERPRET)


def test_glm_sgd_sparse_caps_route_over_budget_to_reference():
    """A one-hot too large for VMEM routes to the oracle automatically."""
    # the one-hot is [MB, d_pad, K] with K padded to 128 lanes
    assert common.onehot_fits(16, 384, 8)
    assert not common.onehot_fits(16, 1_000_000, 8)
    info = {"dtype": "float32", "sparse": True, "n": 64, "d": 1_000_000,
            "k": 8, "micro_batch": 16}
    assert (common.resolve_backend("glm_sgd_sparse", info=info)
            == common.REFERENCE)


# ---------------------------------------------------------------------------
# glm_sparse: ELL sum-gradient
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", common.available_backends("glm_sparse"))
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("task", TASKS)
def test_glm_sparse_conformance(backend, dtype, task, ell_data):
    values, indices, y, w = ell_data(64, 384, 8, dtype)
    ref = ell_glm_grad_ref(task, *_f32(w, values), indices, y.astype(jnp.float32))
    out = ell_glm_grad(task, w, values, indices, y, backend=backend,
                       block_rows=8, d_block=128)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-3)


@pytest.mark.parametrize("backend",
                         common.available_backends("glm_sparse_margins"))
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("n", [64, 61])   # 61: the last row tile is filler
def test_glm_sparse_margins_conformance(backend, dtype, n, ell_data):
    values, indices, _, w = ell_data(n, 300, 8, dtype)
    ref = ell_margins_ref(*_f32(w, values), indices)
    out = ell_margins(w, values, indices, backend=backend)
    assert out.shape == (n,) and out.dtype == jnp.float32
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)


def test_glm_sparse_margins_caps_route_over_budget_to_reference():
    """The whole model is one d-block: a one-hot too large for VMEM
    routes the margins to the oracle."""
    info = {"dtype": "float32", "sparse": True, "n": 64, "d": 4096, "k": 8}
    assert (common.resolve_backend("glm_sparse_margins", info=info)
            == common.REFERENCE)
    small = dict(info, d=300)
    assert (common.resolve_backend("glm_sparse_margins", info=small)
            != common.REFERENCE)


@pytest.mark.parametrize("kernel", ["glm_sgd_sparse", "glm_sparse_margins"])
def test_forced_onehot_over_budget_names_the_csr_path(kernel):
    """Forcing a one-hot Pallas flavor on rows whose one-hot does not fit
    VMEM raises, pointing at the CSR kernels, instead of handing Mosaic
    the one-hot."""
    d, k = 20_000, 40
    values = jnp.ones((16, k), jnp.float32)
    indices = jnp.zeros((16, k), jnp.int32)
    w = jnp.zeros(d, jnp.float32)
    with pytest.raises(ValueError, match="CSR path"):
        if kernel == "glm_sgd_sparse":
            ell_sgd_epoch("lr", w, values, indices, jnp.ones(16), step=0.1,
                          micro_batch=8, backend=common.PALLAS_INTERPRET)
        else:
            ell_margins(w, values, indices, backend=common.PALLAS_INTERPRET)


# ---------------------------------------------------------------------------
# glm_sgd_csr / glm_csr_margins: CSR rows, gather and scatter by index
# ---------------------------------------------------------------------------


def _csr_rows(seed, n=64, d=5_000):
    """Heavy-tailed CSR rows: log-normal widths clipped to 1..700, one row
    wider than a DMA chunk, and rows 0 and 1 (one micro-batch) sharing
    three features; d is not a multiple of the 128 lanes."""
    from repro.core import sparse

    rng = np.random.default_rng(seed)
    widths = np.clip(rng.lognormal(4.0, 1.2, n), 1, 700).astype(np.int64)
    widths[5] = CHUNK + 76
    widths[:2] = np.maximum(widths[:2], 3)
    rows = [rng.choice(d, k, replace=False).astype(np.int32) for k in widths]
    rows[1][:3] = rows[0][:3]
    vals = [rng.standard_normal(k).astype(np.float32) for k in widths]
    y = np.where(rng.random(n) < 0.5, -1.0, 1.0).astype(np.float32)
    w = (0.1 * rng.standard_normal(d)).astype(np.float32)
    m = sparse.from_csr_parts(rows, vals, d).to_rows()
    return m, jnp.asarray(y), jnp.asarray(w)


#: float32 either way, the sums in another order: the kernel adds a row's
#: products lane by lane and then across the tile, the oracle by segment
#: sum; rows of up to 1,100 products agree to about 1e-6 of their size
CSR_RTOL, CSR_ATOL = 1e-5, 1e-5


@pytest.mark.parametrize("backend", common.available_backends("glm_sgd_csr"))
@pytest.mark.parametrize("task", TASKS)
def test_glm_sgd_csr_conformance(backend, task):
    m, y, w = _csr_rows(seed=11)
    ref = csr_sgd_epoch_ref(task, w, m.offsets, m.indices, m.values, y, 0.5, 8)
    out = csr_sgd_epoch(task, w, m.offsets, m.indices, m.values, y, step=0.5,
                        micro_batch=8, backend=backend)
    assert out.shape == w.shape and out.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(ref - w))) > 0.05     # the epoch moved w
    np.testing.assert_allclose(out, ref, rtol=CSR_RTOL, atol=CSR_ATOL)


@pytest.mark.parametrize("backend",
                         common.available_backends("glm_csr_margins"))
@pytest.mark.parametrize("n", [64, 61])   # 61: the last block is filler
def test_glm_csr_margins_conformance(backend, n):
    m, _, w = _csr_rows(seed=n, n=n)
    ref = csr_margins_ref(w, m.offsets, m.indices, m.values)
    out = csr_margins(w, m.offsets, m.indices, m.values, backend=backend)
    assert out.shape == (n,) and out.dtype == jnp.float32
    dense = np.asarray(sparse_to_dense(m)) @ np.asarray(w, np.float64)
    np.testing.assert_allclose(ref, dense, rtol=CSR_RTOL, atol=CSR_ATOL)
    np.testing.assert_allclose(out, ref, rtol=CSR_RTOL, atol=CSR_ATOL)


def sparse_to_dense(m):
    """``[N, d]`` float64 of CSR rows, from the host arrays."""
    off = np.asarray(m.offsets)
    X = np.zeros((len(off) - 1, m.d))
    rows = np.repeat(np.arange(len(off) - 1), np.diff(off))
    np.add.at(X, (rows, np.asarray(m.indices)[:off[-1]]),
              np.asarray(m.values)[:off[-1]])
    return X


def test_glm_sgd_csr_refuses_untileable_micro_batch():
    m, y, w = _csr_rows(seed=3)
    with pytest.raises(ValueError, match="multiple of 8"):
        csr_sgd_epoch("lr", w, m.offsets, m.indices, m.values, y, step=0.5,
                      micro_batch=4, backend=common.PALLAS_INTERPRET)


# ---------------------------------------------------------------------------
# glm_score: fused ELL scoring (gather-dot + task link, serving path)
# ---------------------------------------------------------------------------


def _dense_scores(task, w, values, indices):
    """Dense oracle: scatter the ELL rows into a dense X, score X @ w.

    Independent of the lax.scan reference — a shared gather bug in both
    paths cannot cancel out here.
    """
    values = np.asarray(values, np.float32)
    indices = np.asarray(indices, np.int64)
    w = np.asarray(w, np.float32)
    X = np.zeros((values.shape[0], w.shape[0]), np.float32)
    for i in range(values.shape[0]):
        np.add.at(X[i], indices[i], values[i])   # duplicates accumulate
    from repro.core.glm import LINKS

    return np.asarray(LINKS[task](jnp.asarray(X @ w)), np.float32)


@pytest.mark.parametrize(
    "backend",
    common.available_backends("glm_score", info={"sparse": True}))
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("task", TASKS)
def test_glm_score_conformance(backend, dtype, task, ell_data):
    values, indices, _, w = ell_data(48, 384, 8, dtype)
    ref = _dense_scores(task, *_f32(w, values), indices)
    out = glm_score(task, w, values, indices, backend=backend, block_rows=8)
    assert out.dtype == jnp.float32
    assert out.shape == (48,)
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=2e-3)
    np.testing.assert_allclose(glm_score_ref(task, *_f32(w, values), indices),
                               ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "backend",
    common.available_backends("glm_score", info={"sparse": True}))
def test_glm_score_ragged_rows_conformance(backend, ell_data):
    """n not divisible by block_rows: filler rows are sliced off."""
    values, indices, _, w = ell_data(30, 200, 6)
    ref = _dense_scores("lr", w, values, indices)
    out = glm_score("lr", w, values, indices, backend=backend, block_rows=8)
    assert out.shape == (30,)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-3)


@pytest.mark.parametrize(
    "backend",
    common.available_backends("glm_score", info={"sparse": True}))
def test_glm_score_padding_rows_contribute_exactly_zero(backend):
    """All-padding ELL rows (value 0, index 0) have margin *exactly* 0.0:
    SVM scores exactly 0.0, LR exactly sigmoid(0) = 0.5 — bit-exact, not
    allclose, since the serving engine pads every batch with such rows."""
    d, k = 256, 4
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0, 1, d), jnp.float32)  # w[0] != 0
    values = jnp.zeros((8, k), jnp.float32)
    values = values.at[0].set(jnp.arange(1.0, k + 1))  # one real row
    indices = jnp.zeros((8, k), jnp.int32)
    indices = indices.at[0].set(jnp.arange(1, k + 1))
    svm = np.asarray(glm_score("svm", w, values, indices, backend=backend,
                               block_rows=8))
    lr = np.asarray(glm_score("lr", w, values, indices, backend=backend,
                              block_rows=8))
    assert (svm[1:] == 0.0).all(), svm
    assert (lr[1:] == 0.5).all(), lr
    assert svm[0] != 0.0 and lr[0] != 0.5  # the real row actually scored


def test_glm_score_caps_route_over_budget_to_reference():
    """A one-hot too large for VMEM routes scoring to the oracle."""
    assert common.onehot_fits(8, 512, 8)
    assert not common.onehot_fits(8, 1_000_000, 8)
    info = {"dtype": "float32", "sparse": True, "n": 32, "d": 1_000_000,
            "k": 8}
    assert common.resolve_backend("glm_score", info=info) == common.REFERENCE
    small = dict(info, d=512)
    assert common.resolve_backend("glm_score", info=small) != common.REFERENCE


@pytest.mark.parametrize("task", TASKS)
def test_glm_score_backends_agree_pairwise(task, ell_data):
    values, indices, _, w = ell_data(32, 256, 6)
    outs = [np.asarray(glm_score(task, w, values, indices, backend=b,
                                 block_rows=8))
            for b in common.available_backends("glm_score",
                                               info={"sparse": True})]
    for other in outs[1:]:
        np.testing.assert_allclose(outs[0], other, rtol=1e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# flash_attn: blocked attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", common.available_backends("flash_attn"))
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("causal,window", [(True, None), (True, 16),
                                           (False, None)])
def test_flash_attn_conformance(backend, dtype, causal, window, attn_data):
    q, k, v = attn_data(2, 4, 2, 64, 64, 32, dtype)
    kr = jnp.repeat(k, 2, axis=1)
    vr = jnp.repeat(v, 2, axis=1)
    ref = attention_ref(*_f32(q, kr, vr), causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          backend=backend, block_q=16, block_k=16)
    loose = jnp.dtype(dtype) == jnp.bfloat16
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref,
        rtol=0.05 if loose else 1e-3, atol=0.05 if loose else 2e-3)


@pytest.mark.parametrize("backend", common.available_backends("flash_attn"))
def test_flash_attn_decode_conformance(backend, attn_data):
    q, k, v = attn_data(2, 4, 2, 1, 64, 16)
    ref = attention_ref(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                        causal=True)
    out = flash_attention(q, k, v, causal=True, backend=backend,
                          block_q=1, block_k=16)
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Cross-backend agreement: the dispatch paths agree with each other, not
# just with the oracle (catches oracle-shaped bugs shared by one path).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("task", TASKS)
def test_glm_grad_backends_agree_pairwise(task, glm_data):
    X, y, w = glm_data(48, 30)
    outs = [np.asarray(glm_grad(task, w, X, y, backend=b, block_rows=16))
            for b in common.available_backends("glm_grad")]
    for other in outs[1:]:
        np.testing.assert_allclose(outs[0], other, rtol=1e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# Engine wiring: AsyncLocalSGD.kernel_backend routes replica epochs through
# the registry (dense -> glm_sgd vmapped over replicas, sparse -> glm_sparse)
# and reproduces the pure-XLA engine path on every available backend.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", common.available_backends("glm_sgd"))
@pytest.mark.parametrize("local_batch", [1, 4, 8, 16])
def test_async_engine_kernel_backend_dense(backend, local_batch, glm_data):
    from repro.core import glm, sgd

    X, y, _ = glm_data(64, 16)
    prob = glm.GLMProblem("lr", X, y, 5e-3)
    strat = sgd.AsyncLocalSGD(replicas=4, local_batch=local_batch)
    if backend != common.REFERENCE and not common.micro_batch_ok(
            64 // 4, local_batch):
        with pytest.raises(ValueError, match="multiple of 8"):
            sgd.run(prob, dataclasses.replace(strat, kernel_backend=backend),
                    3, record_time=False)
        return
    base = sgd.run(prob, strat, 3, record_time=False)
    routed = sgd.run(
        prob, dataclasses.replace(strat, kernel_backend=backend), 3,
        record_time=False)
    np.testing.assert_allclose(routed.losses, base.losses,
                               rtol=1e-4, atol=1e-4)


def test_async_engine_kernel_backend_dense_rejects_ragged_partition(glm_data):
    """local_batch must divide the partition size (n//R + rep_k)."""
    from repro.core import glm, sgd

    X, y, _ = glm_data(64, 16)
    prob = glm.GLMProblem("lr", X, y, 5e-3)
    strat = sgd.AsyncLocalSGD(replicas=4, local_batch=5,
                              kernel_backend=common.REFERENCE)
    with pytest.raises(ValueError, match="divide the"):
        sgd.make_epoch_fn(prob, strat)


@pytest.mark.parametrize(
    "backend",
    common.available_backends("glm_sparse", info={"sparse": True, "n": 64,
                                                  "d": 128}))
def test_async_engine_kernel_backend_sparse(backend):
    """Sparse replica epochs route through glm_sparse when the local update
    is full-partition (sum-gradient kernel) and through the fused
    glm_sgd_sparse epoch for mini-batch local updates; a local_batch that
    does not divide the partition must refuse rather than silently fall
    back."""
    import jax.numpy as jnp

    from repro.core import sgd
    from repro.data import synthetic

    sp = synthetic.make_sparse("sp-async", 64, 128, 5.0, 8, seed=4)
    per = 64 // 4
    prob = ("lr", sp.ell, jnp.asarray(sp.y), 0.05)
    for local_batch in (per, 8):
        base = sgd.run(
            prob, sgd.AsyncLocalSGD(replicas=4, local_batch=local_batch), 3,
            sparse_data=True, record_time=False)
        routed = sgd.run(
            prob, sgd.AsyncLocalSGD(replicas=4, local_batch=local_batch,
                                    kernel_backend=backend), 3,
            sparse_data=True, record_time=False)
        np.testing.assert_allclose(routed.losses, base.losses,
                                   rtol=1e-4, atol=1e-4)
    if backend != common.REFERENCE:   # 4 divides 16 but breaks the tiling
        with pytest.raises(ValueError, match="multiple of 8"):
            sgd.run(prob, sgd.AsyncLocalSGD(replicas=4, local_batch=4,
                                            kernel_backend=backend), 3,
                    sparse_data=True, record_time=False)
    with pytest.raises(ValueError, match="divide the"):
        sgd.make_epoch_fn(
            prob, sgd.AsyncLocalSGD(replicas=4, local_batch=5,
                                    kernel_backend=backend),
            sparse_data=True)


def test_async_strategy_name_includes_backend():
    from repro.core import sgd

    plain = sgd.AsyncLocalSGD(replicas=4)
    routed = sgd.AsyncLocalSGD(replicas=4, kernel_backend=common.REFERENCE)
    assert plain.name + f"[{common.REFERENCE}]" == routed.name

"""Per-kernel allclose vs ref.py oracles — shape/dtype sweeps (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synthetic
from repro.kernels import common
from repro.kernels.glm_grad import glm_grad
from repro.kernels.glm_grad.ref import glm_grad_ref
from repro.kernels.glm_sgd import glm_sgd_epoch
from repro.kernels.glm_sgd.ref import glm_sgd_epoch_ref
from repro.kernels.glm_sgd_sparse import ell_sgd_epoch
from repro.kernels.glm_sgd_sparse.ref import ell_sgd_epoch_ref
from repro.kernels.glm_sparse import ell_glm_grad
from repro.kernels.glm_sparse.ref import ell_glm_grad_ref
from repro.kernels.flash_attn import flash_attention
from repro.kernels.flash_attn.ref import attention_ref


# ---------------------------------------------------------------------------
# pick_block: the block it returns must always be aligned (regression: it
# used to fall back to ``size`` itself — e.g. pick_block(6, 128, 8) == 6 —
# handing Pallas a sublane-misaligned block)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("size,preferred,multiple,want", [
    (128, 128, 8, 128),   # preferred fits exactly
    (96, 128, 8, 96),     # aligned divisor <= preferred
    (64, 16, 8, 16),      # largest aligned divisor under preferred
    (200, 128, 8, 40),    # 200 = 8*25: biggest aligned divisor <= 128 is 40
    (8, 128, 8, 8),       # minimum aligned size
    (256, 128, 128, 128),  # lane-multiple constraint
])
def test_pick_block_returns_aligned_divisor(size, preferred, multiple, want):
    got = common.pick_block(size, preferred, multiple)
    assert got == want
    assert size % got == 0 and got % multiple == 0


@pytest.mark.parametrize("size", [6, 7, 13, 31, 127])  # odd / prime extents
def test_pick_block_rejects_unalignable_sizes(size):
    with pytest.raises(ValueError, match="not itself a multiple"):
        common.pick_block(size, 128, 8)


def test_pick_block_whole_extent_fallback_stays_aligned():
    # no aligned divisor <= preferred, but the extent itself is aligned:
    # one whole-extent block is the only correct answer
    assert common.pick_block(40, 4, 8) == 40


@pytest.mark.parametrize("task", ["lr", "svm"])
@pytest.mark.parametrize("layout", ["row", "col"])
@pytest.mark.parametrize("n,d", [(64, 54), (200, 16), (96, 300), (32, 128)])
def test_glm_grad_kernel(task, layout, n, d, glm_data):
    X, y, w = glm_data(n, d)
    ref = glm_grad_ref(task, w, X, y)
    out = glm_grad(task, w, X, y, layout=layout, block_rows=16)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-3)


@pytest.mark.parametrize("task", ["lr", "svm"])
@pytest.mark.parametrize("mb", [1, 4, 16])
@pytest.mark.parametrize("n,d", [(32, 54), (64, 130)])
def test_glm_sgd_kernel(task, mb, n, d, glm_data):
    X, y, w = glm_data(n, d)
    ref = glm_sgd_epoch_ref(task, w, X, y, 0.02, mb)
    out = glm_sgd_epoch(task, w, X, y, step=0.02, micro_batch=mb)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("task", ["lr", "svm"])
@pytest.mark.parametrize("n,d,k", [(64, 512, 12), (100, 700, 20), (40, 256, 6)])
def test_glm_sparse_kernel(task, n, d, k, rng):
    ds = synthetic.make_sparse("sp", n, d, k * 0.6, k, seed=int(d))
    y = jnp.asarray(ds.y)
    w = jnp.asarray(rng.normal(0, 0.1, d).astype(np.float32))
    ref = ell_glm_grad_ref(task, w, ds.ell.values, ds.ell.indices, y)
    out = ell_glm_grad(task, w, ds.ell.values, ds.ell.indices, y,
                       block_rows=8, d_block=256,
                       backend=common.available_backends("glm_sparse")[0])
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-3)


@pytest.mark.parametrize("task", ["lr", "svm"])
@pytest.mark.parametrize("mb", [1, 4, 16])
@pytest.mark.parametrize("n,d,k", [(32, 200, 6), (64, 130, 10)])
def test_ell_sgd_kernel(task, mb, n, d, k, rng):
    ds = synthetic.make_sparse("sp-sgd", n, d, k * 0.6, k, seed=int(d))
    y = jnp.asarray(ds.y)
    w = jnp.asarray(rng.normal(0, 0.1, d).astype(np.float32))
    ref = ell_sgd_epoch_ref(task, w, ds.ell.values, ds.ell.indices, y,
                            0.05, mb)
    out = ell_sgd_epoch(task, w, ds.ell.values, ds.ell.indices, y,
                        step=0.05, micro_batch=mb)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-3)


def test_glm_sparse_auto_path_picks_xla_when_huge(rng):
    """Very wide models route to the XLA gather path automatically."""
    from repro.kernels.glm_sparse.ops import pallas_path_ok
    assert not pallas_path_ok(d=1_000_000, k=16)
    assert pallas_path_ok(d=20_958, k=16)
    assert not pallas_path_ok(d=20_958, k=3_484)  # real-sim's widest row


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 16])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_flash_attention_kernel(causal, window, hq, hkv, rng):
    B, S, hd = 2, 64, 32
    q = jnp.asarray(rng.normal(0, 1, (B, hq, S, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, hkv, S, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, hkv, S, hd)).astype(np.float32))
    kr = jnp.repeat(k, hq // hkv, axis=1)
    vr = jnp.repeat(v, hq // hkv, axis=1)
    ref = attention_ref(q, kr, vr, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=16, block_k=16)
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=2e-3)


def test_flash_attention_bf16():
    rng = np.random.default_rng(7)
    B, H, S, hd = 1, 2, 32, 16
    q = jnp.asarray(rng.normal(0, 1, (B, H, S, hd)), dtype=jnp.bfloat16)
    k = jnp.asarray(rng.normal(0, 1, (B, H, S, hd)), dtype=jnp.bfloat16)
    v = jnp.asarray(rng.normal(0, 1, (B, H, S, hd)), dtype=jnp.bfloat16)
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, rtol=0.05,
                               atol=0.05)


def test_flash_attention_decode_shape():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(0, 1, (2, 4, 1, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (2, 2, 64, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (2, 2, 64, 16)).astype(np.float32))
    ref = attention_ref(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 2, 1),
                        causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=1, block_k=16)
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-3)

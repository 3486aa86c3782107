"""Each cell's timed programs compile for a TPU v5e at the cells' shapes.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip's compiler
would refuse (tiling, VMEM, shape casts).  The topology is described in a
fixture, never at import: one process at a time may load the TPU
library.  ``sparse.loss`` at w8a's size takes about a minute to compile.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

W8A = (64_696, 300, 114)
COVTYPE = (581_008, 54)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: can't describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _pallas(kernel, **kwargs):
    import repro.kernels  # noqa: F401 — registers all families
    from repro.kernels import common

    return functools.partial(common.implementation(kernel, common.PALLAS_TPU),
                             "lr", **kwargs)


def _ell(shape, n, d, k):
    return shape((d,)), shape((n, k)), shape((n, k), jnp.int32), shape((n,))


def test_w8a_epoch_kernel(shape):
    n, d, k = W8A
    c = jax.jit(_pallas("glm_sgd_sparse", step=0.05, micro_batch=8)) \
        .lower(*_ell(shape, n, d, k)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_w8a_loss(shape):
    from repro.core import sparse

    n, d, k = W8A

    def loss(w, v, i, y):
        return sparse.loss("lr", sparse.ELLMatrix(v, i, d), y, w)

    jax.jit(loss).lower(*_ell(shape, n, d, k)).compile()


def test_covtype_epoch_kernel_and_loss(shape):
    from repro.core import glm

    n, d = COVTYPE
    args = shape((d,)), shape((n, d)), shape((n,))
    c = jax.jit(_pallas("glm_sgd", step=0.01, micro_batch=8)) \
        .lower(*args).compile()
    assert "tpu_custom_call" in c.as_text()
    jax.jit(glm.LOSSES["lr"]).lower(*args).compile()


def test_score_batch_kernel(shape):
    w, v, i, _ = _ell(shape, 32, W8A[1], W8A[2])
    c = jax.jit(_pallas("glm_score", block_rows=8)).lower(w, v, i).compile()
    assert "tpu_custom_call" in c.as_text()


def test_live_replica_epoch(shape):
    """Four replicas, each 64 rows of a 256-row chunk, micro-batch 8."""
    one = _pallas("glm_sgd_sparse", step=0.5, micro_batch=8)
    W = shape((4, W8A[1]))
    v, i, y = shape((4, 64, W8A[2])), shape((4, 64, W8A[2]), jnp.int32), \
        shape((4, 64))
    c = jax.jit(jax.vmap(one)).lower(W, v, i, y).compile()
    assert "tpu_custom_call" in c.as_text()

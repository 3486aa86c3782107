"""Compile the main-path kernels for a TPU v5e at the paper's widths.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and raises what the chip's compiler
would raise (tiling rules, unsupported vector shape casts, VMEM).  The
shapes are the full Table-3 ones — covtype 581,012 x 54 dense, w8a
64,700 x 300 with ELL widths 69 (the generator's cap) and 114 (the
dataset's widest row) — so a kernel that only interpret mode accepts
fails here, on the CPU, before any chip time is spent.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

import repro.kernels  # noqa: F401 — registers all families
from repro.kernels import common

COVTYPE = (581_012, 54)
W8A = (64_700, 300)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without the chip
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


def _compile(kernel, task, shapes, **kwargs):
    """Lower and compile the registered ``pallas-tpu`` flavor."""
    fn = functools.partial(common.implementation(kernel, common.PALLAS_TPU),
                           task, **kwargs)
    return jax.jit(fn).lower(*shapes).compile()


def _dense(shape, n, d):
    return shape((d,)), shape((n, d)), shape((n,))


def _ell(shape, n, d, k):
    return (shape((d,)), shape((n, k)), shape((n, k), jnp.int32),
            shape((n,)))


@pytest.mark.parametrize("layout", ["row", "col"])
def test_glm_grad_compiles_at_covtype_width(shape, layout):
    compiled = _compile("glm_grad", "lr", _dense(shape, *COVTYPE),
                        layout=layout)
    assert "tpu_custom_call" in compiled.as_text()


def test_glm_sgd_compiles_at_covtype_width(shape):
    n = COVTYPE[0] - COVTYPE[0] % 8          # the fused epoch tiles n by 8
    compiled = _compile("glm_sgd", "lr", _dense(shape, n, COVTYPE[1]),
                        step=0.1, micro_batch=8)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [69, 114])
def test_glm_sgd_sparse_compiles_at_w8a_shape(shape, k):
    n = W8A[0] - W8A[0] % 8
    compiled = _compile("glm_sgd_sparse", "lr", _ell(shape, n, W8A[1], k),
                        step=0.1, micro_batch=8)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [69, 114])
def test_glm_sparse_compiles_at_w8a_shape(shape, k):
    compiled = _compile("glm_sparse", "svm", _ell(shape, *W8A, k))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [69, 114])
def test_glm_score_compiles_at_w8a_width(shape, k):
    w, values, indices, _ = _ell(shape, 256, W8A[1], k)
    compiled = _compile("glm_score", "lr", (w, values, indices))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["glm_sgd", "glm_sgd_sparse"])
@pytest.mark.parametrize("micro_batch", [1, 2, 4])
def test_forced_pallas_tpu_rejects_untileable_micro_batch(shape, kernel,
                                                          micro_batch):
    """A micro-batch Mosaic cannot tile raises a ValueError that names
    the rule, instead of a lowering error from inside Pallas."""
    args = (_dense(shape, 64, 54) if kernel == "glm_sgd"
            else _ell(shape, 64, 300, 69))
    with pytest.raises(ValueError, match="multiple of 8 or equal to n"):
        _compile(kernel, "lr", args, step=0.1, micro_batch=micro_batch)

"""Kernel layer: Pallas TPU kernels behind a backend dispatch registry.

Each kernel family is a ``kernel.py`` (Pallas body) / ``ops.py`` (public
wrapper + backend registration) / ``ref.py`` (pure-jnp oracle) triple.
Importing this package registers all families; ``common.dispatch`` then
routes each call to ``pallas-tpu`` / ``pallas-interpret`` / ``reference``
(see common.py for the selection rules and the ``REPRO_KERNEL_BACKEND``
override).  DESIGN.md §3 documents the layer; the conformance suite is
tests/test_kernel_conformance.py.
"""
from repro.kernels import common  # noqa: F401  (must precede family imports)
from repro.kernels.common import (  # noqa: F401
    PALLAS_INTERPRET,
    PALLAS_TPU,
    REFERENCE,
    available_backends,
    backends_for,
    dispatch,
    register_kernel,
    registered_kernels,
    resolve_backend,
)
from repro.kernels.flash_attn import flash_attention  # noqa: F401
from repro.kernels.glm_grad import glm_grad  # noqa: F401
from repro.kernels.glm_score import glm_score  # noqa: F401
from repro.kernels.glm_sgd import glm_sgd_epoch  # noqa: F401
from repro.kernels.glm_sgd_csr import csr_margins, csr_sgd_epoch  # noqa: F401
from repro.kernels.glm_sgd_sparse import ell_sgd_epoch  # noqa: F401
from repro.kernels.glm_sparse import ell_glm_grad  # noqa: F401

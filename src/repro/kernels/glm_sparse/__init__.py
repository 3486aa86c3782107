from repro.kernels.glm_sparse.ops import ell_glm_grad, ell_margins  # noqa: F401

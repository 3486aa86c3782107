from repro.kernels.glm_sgd_csr.ops import csr_margins, csr_sgd_epoch  # noqa: F401

"""Share of its roofline that the sparse fused epoch kernel
(``glm_sgd_sparse``) reaches, its work priced at the nonzeros the rows
hold: the least time of one epoch (``cost.sgd_epoch``) over the kernel's
device time per launch in the trace."""
from chipbench.metrics_common import epoch_kernel_roofline


def read(run):
    if run.window.get("nnz") is None:
        return None
    return epoch_kernel_roofline(run)

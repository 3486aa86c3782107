"""Share of its roofline that the dense fused epoch kernel (``glm_sgd``)
reaches: the least time of one epoch's useful work (``cost.sgd_epoch``)
over the kernel's device time per launch in the trace."""
from chipbench.metrics_common import epoch_kernel_roofline


def read(run):
    if run.window.get("nnz") is not None:
        return None
    return epoch_kernel_roofline(run)

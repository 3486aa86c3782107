"""Share of its roofline that the CSR margins kernel (``glm_csr_margins``)
of the loss check reaches: the least time of the loss over all rows
(``cost.loss``, priced at the nonzeros the rows hold) over the device
time per launch of the Pallas kernels in the loss program
(``jit_loss``)."""
from chipbench import cost

LOSS_PROGRAM = "jit_loss"


def read(run):
    if run.trace is None or run.window.get("nnz") is None:
        return None
    launches = seconds = 0
    for name, (n, s) in run.trace["kernels"].items():
        if name.startswith(LOSS_PROGRAM + "/"):
            launches += n
            seconds += s
    if not launches:
        return None
    w = run.window
    least = cost.least_seconds(cost.loss(w["n"], w["d"], w["nnz"]),
                               cost.peaks(run.device_kind))
    return 100.0 * least / (seconds / launches)

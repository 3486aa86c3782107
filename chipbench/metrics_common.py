"""Arithmetic that several per-layer readers share."""
from __future__ import annotations

from chipbench import cost

EPOCH_PROGRAM = "jit_epoch"


def epoch_kernel_roofline(run) -> float | None:
    """Roofline share of the Pallas kernel inside the epoch program.

    The kernels carry no name of their own, so the kernel is the
    ``tpu_custom_call`` op of the program the window drives as its epoch.
    """
    if run.trace is None:
        return None
    launches = seconds = 0
    for name, (n, s) in run.trace["kernels"].items():
        if name.startswith(EPOCH_PROGRAM + "/"):
            launches += n
            seconds += s
    if not launches:
        return None
    w = run.window
    least = cost.least_seconds(cost.sgd_epoch(w["n"], w["d"], w["nnz"]),
                               cost.peaks(run.device_kind))
    return 100.0 * least / (seconds / launches)

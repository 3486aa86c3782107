"""The whole epoch's share of the chip's peak, whatever implements it:
the least time of one epoch and its loss check (``cost.epoch_with_loss``:
one read of the data, 4 operations a nonzero for the epoch and 2 for the
loss) over the measured seconds per epoch of the traced window."""
from chipbench import cost


def read(run):
    w = run.window
    if not w.get("epochs"):
        return None
    least = cost.least_seconds(cost.epoch_with_loss(w["n"], w["d"], w["nnz"]),
                               cost.peaks(run.device_kind))
    return 100.0 * least / (w["window_s"] / w["epochs"])

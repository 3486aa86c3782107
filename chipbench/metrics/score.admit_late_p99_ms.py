"""99th percentile of how late the producer admitted a request after it
was due: a starved generator reads high here, not as a fast server."""
import numpy as np


def read(run):
    late = run.window.get("openloop", {}).get("late_s")
    if late is None or not len(late):
        return None
    return float(np.percentile(late, 99) * 1e3)

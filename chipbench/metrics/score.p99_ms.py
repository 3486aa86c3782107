"""99th percentile of the latency of every request due in the window,
from its due time to the end of the flush that scored it.  The host
freezes now and then for about 110 ms (PERF.md, section 5), and this
tail moves with how often it does (``host.stall_ms``)."""
import numpy as np


def read(run):
    lat = run.window.get("openloop", {}).get("latency_ms")
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 99))

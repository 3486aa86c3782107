"""Share of the window in which no operation ran on the device:
``1 - busy / window`` from the profiler trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]

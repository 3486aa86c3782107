"""Milliseconds of the window in which the host let no Python thread
run: the sum of the overshoots past 20 ms of a thread that sleeps 1 ms
at a time (``harness.StallMonitor``).  Freezes of the whole machine show
here; they move the serving tails and are none of the program's doing."""


def read(run):
    if run.stalls is None:
        return None
    return sum(over for _, over in run.stalls) / 1e6

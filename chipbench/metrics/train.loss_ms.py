"""Mean time of the per-epoch loss check, ending in the host read (the
benchmark's ``bench.loss`` spans), over the window."""


def read(run):
    return run.spans.mean_ms("bench.loss")

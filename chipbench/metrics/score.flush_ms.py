"""Mean time of a ``maybe_flush`` that scored a batch (the benchmark's
``bench.flush`` spans), over every such flush of the window."""


def read(run):
    return run.spans.mean_ms("bench.flush")

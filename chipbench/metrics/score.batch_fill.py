"""Rows scored per launch over the window, as a share of ``max_batch``:
counters ``serve.scored / (serve.batches * max_batch)``."""


def read(run):
    batches = run.counters.get("serve.batches", 0)
    if not batches:
        return None
    max_batch = run.cell.traffic["engine"]["max_batch"]
    return 100.0 * run.counters.get("serve.scored", 0) / (batches * max_batch)

"""Backend compiles inside the window: counter ``jax.compiles`` moved in
the window.  The serving engine and the live learner turn the counter on
(``repro.obs.trace.count_compiles``, which makes it at 0), so a window
without compiles reads 0; None where the program has no such counter."""


def read(run):
    from repro.obs import metrics

    if "jax.compiles" not in metrics.snapshot()["counters"]:
        return None
    return float(run.counters.get("jax.compiles", 0))

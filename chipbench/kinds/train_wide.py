"""Training jobs on CSR rows too wide for an ``[n, d]`` array: the
``train`` kind's jobs, window and compared numbers, on rows made by
:mod:`chipbench.data_wide` and checked against :mod:`chipbench.reference_csr`.

Set-up draws the rows on the host, hands them to the engine as
``repro.core.sparse.CSRRows`` (``CSRMatrix.to_rows``) and builds the
program with ``repro.core.sgd.make_epoch_fn``; the window is
``train.window``'s.  Correct: the models and losses of the checked epochs
against the float64 reference of the same SGD on the same rows, which
never densifies them.

The control and the faults of this kind live here (``control``,
``driver``), and so do the readings the limits are set from:

    python3 chipbench/kinds/train_wide.py --workload news.train --seeds 1,2
        [--faults half_batch,unchanged] [--control]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

import numpy as np

if __name__ == "__main__":
    _ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from chipbench import data_wide, reference_csr  # noqa: E402
from chipbench.kinds.train import Job, numbers, window  # noqa: E402

#: the control's precision, one step below the configuration's float32
CONTROL = "bf16"
FAULTS = ("unchanged", "half_batch")


def device_rows(rows: data_wide.Rows, values: np.ndarray | None = None):
    """The rows as the engine takes them (``values`` replaces theirs)."""
    from repro.core import sparse

    return sparse.CSRMatrix(rows.offsets, rows.indices,
                            rows.values if values is None else values,
                            rows.d).to_rows()


def program(rows: data_wide.Rows, config: dict, strategy):
    """``(init, epoch_fn, loss_fn)`` of the engine on the rows."""
    import jax.numpy as jnp

    from repro.core import sgd

    problem = (config["task"], device_rows(rows), jnp.asarray(rows.y),
               config["step_size"])
    init, epoch_fn, loss_fn, _ = sgd.make_epoch_fn(problem, strategy,
                                                   sparse_data=True)
    return init, epoch_fn, loss_fn


def setup(ctx, build=program) -> dict:
    from repro.core import sgd

    cfg, tr = ctx.config, ctx.traffic
    with ctx.spans.span("bench.data"):
        rows = data_wide.make(cfg, ctx.seed)
    strategy = sgd.SyncSGD(batch=tr["micro_batch"], kernel_backend=ctx.kernel)
    job = Job(*build(rows, cfg, strategy), tr["epochs_per_job"], ctx.spans)
    checked = []
    for _ in range(tr["checked_epochs"]):
        loss = job.step()
        checked.append((np.asarray(job.w, np.float64), loss))
    return {"rows": rows, "job": job, "checked": checked}


def reference_run(ctx, rows, mode: str):
    return reference_csr.sgd(rows.offsets, rows.indices, rows.values, rows.y,
                             rows.d, step=ctx.config["step_size"],
                             batch=ctx.traffic["micro_batch"],
                             epochs=ctx.traffic["checked_epochs"], mode=mode)


def check(ctx, state, window_out) -> dict:
    rows, checked = state["rows"], state["checked"]
    state.clear()                    # free the program's state first
    return numbers(checked, *reference_run(ctx, rows, "f64"))


def _driver(build):
    return types.SimpleNamespace(setup=lambda ctx: setup(ctx, build=build),
                                 window=window, check=check)


def control(mode: str = CONTROL):
    """This kind's driver with the reference at ``mode`` in the program's
    place: its epoch and loss on the same rows."""
    def build(rows, config, strategy):
        ref = reference_csr.Rows(rows.offsets, rows.indices, rows.values,
                                 rows.y, rows.d, mode)
        return (np.zeros(rows.d, np.float32),
                lambda w: ref.epoch(w, config["step_size"], strategy.batch),
                ref.loss)

    return _driver(build)


def driver(fault: str):
    """This kind's driver with ``fault`` planted under the program:

    * ``unchanged``   every epoch returns the model unchanged;
    * ``half_batch``  every micro-batch leaves out the second half of its
      rows (their values zeroed) at twice the step: the mean over the
      rest.
    """
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r}, not one of {FAULTS}")

    def build(rows, config, strategy):
        init, epoch_fn, loss_fn = program(rows, config, strategy)
        if fault == "unchanged":
            return init, (lambda w: w), loss_fn
        import jax.numpy as jnp

        from repro.core import sgd

        b = strategy.batch
        kept = np.repeat(np.arange(rows.n) % b < b // 2, rows.nnz)
        broken = (config["task"], device_rows(rows, rows.values * kept),
                  jnp.asarray(rows.y), 2 * config["step_size"])
        _, epoch_fn, _, _ = sgd.make_epoch_fn(broken, strategy,
                                              sparse_data=True)
        return init, epoch_fn, loss_fn

    return _driver(build)


def readings(cell, seed: int, faults, with_control: bool):
    """The compared numbers of the program (and of each fault, and of the
    control) on one seed, as JSON-ready dicts."""
    from chipbench import harness

    ctx = harness.Context(cell, seed, 0.0, "pallas-tpu", harness.Spans())
    t0 = time.perf_counter()
    st = setup(ctx)
    setup_s = time.perf_counter() - t0
    rows, checked = st["rows"], st["checked"]
    st.clear()
    ref = reference_run(ctx, rows, "f64")
    out = [dict(what="program", setup_s=setup_s,
                numbers=numbers(checked, *ref),
                losses=[c[1] for c in checked], ref_losses=ref[1])]
    drivers = [(f"fault_{f}", driver(f)) for f in faults]
    if with_control:
        drivers.append((f"control_{CONTROL}", control()))
    for what, drv in drivers:
        fst = drv.setup(ctx)
        out.append(dict(what=what, numbers=numbers(fst["checked"], *ref)))
        fst.clear()
    return [dict(cell=cell.name, seed=seed, **line) for line in out]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="readings the limits of "
                                "correct are set from, one line a reading")
    p.add_argument("--workload", default="news.train")
    p.add_argument("--seeds", required=True)
    p.add_argument("--faults", default="")
    p.add_argument("--control", action="store_true")
    args = p.parse_args(argv)

    from chipbench import harness

    harness.compile_cache()
    import jax

    from repro.utils import compile_cache

    if jax.default_backend() != "tpu":
        print("train_wide: readings need a TPU", file=sys.stderr)
        return 2
    compile_cache.enable()
    cell = harness.load_cell(args.workload)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    faults = [f for f in args.faults.split(",") if f]
    for seed in (int(s) for s in args.seeds.split(",")):
        for line in readings(cell, seed, faults, args.control):
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

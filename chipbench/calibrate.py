"""Readings that the limits of ``correct`` are set from, and the knee sweep.

Runs on the chip, one process for many seeds (set-up is shared through
the compile cache), and prints one JSON line per reading:

    python3 chipbench/calibrate.py readings --workload w8a.train --seeds 1,2,3
    python3 chipbench/calibrate.py knee --workload w8a.live --rates 2000,4000

``readings`` gives, for each seed, the program's compared numbers (for
training, from set-up's checked epochs: training needs no window), the
control's (the reference in the program's place at ``high`` and at
``bf16``; training's control is ``bf16``, scoring's ``high``), and each
fault's that the cell can have (:mod:`chipbench.faults`).  ``knee`` runs
a serving cell's window at each offered rate and reports what was
answered, rejected, and the latency.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
KERNEL = "pallas-tpu"


def _emit(out, **line):
    text = json.dumps(line)
    print(text, flush=True)
    out.write(text + "\n")
    out.flush()


def train_readings(cell, seed, out, faults):
    from chipbench import faults as F
    from chipbench import harness, reference
    from chipbench.kinds import train

    ctx = harness.Context(cell, seed, 0.0, KERNEL, harness.Spans())
    t0 = time.perf_counter()
    st = train.setup(ctx)
    setup_s = time.perf_counter() - t0
    rows, checked = st["rows"], st["checked"]
    st.clear()
    with reference.float64():
        ref = train.reference_run(ctx, rows, "f64")
    _emit(out, cell=cell.name, seed=seed, what="program", setup_s=setup_s,
          numbers=train.numbers(checked, *ref),
          losses=[c[1] for c in checked], ref_losses=ref[1])
    for mode in ("high", "bf16"):
        cst = F.control("train", mode).setup(ctx)
        _emit(out, cell=cell.name, seed=seed, what=f"control_{mode}",
              numbers=train.numbers(cst["checked"], *ref))
        cst.clear()
    for fault in faults:
        fst = F.driver("train", fault).setup(ctx)
        _emit(out, cell=cell.name, seed=seed, what=f"fault_{fault}",
              numbers=train.numbers(fst["checked"], *ref))
        fst.clear()


def serve_readings(cell, seed, seconds, out, faults):
    import numpy as np

    from chipbench import faults as F
    from chipbench import harness, reference
    from chipbench.kinds import live, score

    mod = {"score": score, "live": live}[cell.kind]
    ctx = harness.Context(cell, seed, seconds, KERNEL, harness.Spans())
    st = mod.setup(ctx)
    harness.settle()
    win = mod.window(ctx, st)
    loop = st["loop"]
    ok = loop.answers > 0
    pick = st["pick"][ok]
    values, indices = st["rows"].values[pick], st["rows"].indices[pick]
    versions = loop.version[ok]
    control = {}
    if cell.kind == "score":
        models = st["w"][None, :].astype(np.float64)
        for m in ("high", "bf16"):
            got = reference.scores(values, indices, models, versions, mode=m)
            control[m] = {"got": got}
        with reference.float64():
            want = reference.scores(values, indices, models, versions,
                                    mode="f64")
        for m, c in control.items():
            c["numbers"] = {"score_gap": float(np.max(np.abs(c["got"] - want)))}
    else:
        lc, steps = st["config"], st["learner"].steps
        for m in ("high", "bf16"):
            anchors = live.replay(lc, steps, st["stream"], st["rows"], m)
            got = reference.scores(values, indices, anchors, versions, mode=m)
            control[m] = {"anchors": anchors, "got": got}
        with reference.float64():
            ref = live.replay(lc, steps, st["stream"], st["rows"], "f64")
            want = reference.scores(values, indices, ref, versions, mode="f64")
        for m, c in control.items():
            c["numbers"] = {
                "model_gap": float(np.linalg.norm(c["anchors"][-1] - ref[-1])
                                   / np.linalg.norm(ref[-1])),
                "score_gap": float(np.max(np.abs(c["got"] - want)))}
    numbers = mod.check(ctx, st, win)
    summary = {k: v for k, v in win.items() if k not in ("openloop",)}
    _emit(out, cell=cell.name, seed=seed, what="program", numbers=numbers,
          window=summary, failed=win["failed"], attempted=win["attempted"])
    for m, c in control.items():
        _emit(out, cell=cell.name, seed=seed, what=f"control_{m}",
              numbers=c["numbers"])
    for fault in faults:
        drv = F.driver(cell.kind, fault)
        fst = drv.setup(ctx)
        harness.settle()
        fwin = drv.window(ctx, fst)
        _emit(out, cell=cell.name, seed=seed, what=f"fault_{fault}",
              numbers=drv.check(ctx, fst, fwin))


def knee(cell, rates, seconds, seed, out):
    import importlib

    from chipbench import harness

    mod = importlib.import_module(f"chipbench.kinds.{cell.kind}")
    for rate in rates:
        cell.traffic["rate_per_s"] = rate
        ctx = harness.Context(cell, seed, seconds, KERNEL, harness.Spans())
        st = mod.setup(ctx)
        harness.settle()
        with harness.StallMonitor() as monitor:
            win = mod.window(ctx, st)
        s = win["openloop"]
        lat = s["latency_ms"]
        _emit(out, cell=cell.name, what="knee", rate=rate,
              requests=s["requests"], answered=s["answered"],
              rejected=s["rejected"],
              p50_ms=harness.percentile(lat, 50) if len(lat) else None,
              p90_ms=harness.percentile(lat, 90) if len(lat) else None,
              p99_ms=harness.percentile(lat, 99) if len(lat) else None,
              max_ms=float(lat.max()) if len(lat) else None,
              late_p99_ms=harness.percentile(s["late_s"], 99) * 1e3,
              stall_ms=sum(o for _, o in monitor.stalls) / 1e6,
              live_rows_per_s=win["metrics"].get("live_rows_per_s"))
        st.clear()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("what", choices=("readings", "knee"))
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="1")
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--rates", default="")
    p.add_argument("--faults", default="")
    p.add_argument("--out", default=str(ROOT / ".chipbench_cache" / "calibrate.jsonl"))
    args = p.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness

    harness.compile_cache()
    import jax
    from repro.utils import compile_cache

    if jax.default_backend() != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    compile_cache.enable()
    cell = harness.load_cell(args.workload)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "a") as out:
        if args.what == "knee":
            knee(cell, [float(r) for r in args.rates.split(",")],
                 args.seconds, seeds[0], out)
        else:
            for seed in seeds:
                if cell.kind == "train":
                    train_readings(cell, seed, out, faults)
                else:
                    serve_readings(cell, seed, args.seconds, out, faults)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--profile ci|paper]
        [--only mod1,mod2] [--real] [--workers N]
        [--out-json BENCH_study.json]

``--only`` accepts unambiguous prefixes (``--only table4`` runs
``table4_sync``).  ``--real`` sweeps the paper's measured datasets via
repro.data.ingest instead of the synthetic Table-3 stand-ins; offline
it resolves the bundled fixtures, and trial-cache keys carry the
ingested content hash either way.  ``--workers N`` dispatches
cache-miss trials across N local worker subprocesses (repro.sweep):
shards are stack-aware, dead workers are requeued, and the per-worker
caches merge into the canonical trial cache — so the store output is
byte-identical to a single-host run over the same cache.  Dispatch
happens per runner call: batched sweeps (table6_optimal's advisor
space) fan out across the workers, while single-grid calls run
in-process as usual — never slower than serial (docs/SWEEPS.md).

Emits CSVs into bench_results/ and prints a summary, then validates the
paper's qualitative claims (repro.study.claims) against the measured
rows (exit 1 on violation).  Every trial the sweep executes is recorded
through repro.study.store into the structured results file (--out-json,
default BENCH_study.json) plus an append-only JSONL run log — the repo's
machine-readable perf trajectory.  Trials are cached under
bench_results/study_cache/: re-running a finished sweep is a pure cache
read and reproduces BENCH_study.json byte-for-byte.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (bench_kernels, bench_live, bench_serve, common,
                        fig8_access_path, fig11_model_replication,
                        fig14_data_replication, fig22_sync_vs_async,
                        fig24_scale, table4_sync, table6_optimal,
                        table7_async)
from repro.obs import trace
from repro.study import claims
from repro.study.store import StudyStore
from repro.utils import compile_cache

MODULES = {
    "table4_sync": table4_sync,
    "table6_optimal": table6_optimal,
    "table7_async": table7_async,
    "fig8_access_path": fig8_access_path,
    "fig11_model_replication": fig11_model_replication,
    "fig14_data_replication": fig14_data_replication,
    "fig22_sync_vs_async": fig22_sync_vs_async,
    "fig24_scale": fig24_scale,
    "bench_kernels": bench_kernels,
    "bench_serve": bench_serve,
    "bench_live": bench_live,
}


def _resolve_module(name: str) -> str | list[str]:
    """Exact module name, or an unambiguous prefix of one.

    Returns the resolved name, or the (possibly empty) list of
    colliding candidates so the caller can report ambiguity vs unknown.
    """
    if name in MODULES:
        return name
    hits = [m for m in MODULES if m.startswith(name)]
    return hits[0] if len(hits) == 1 else hits


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default="ci", choices=list(common.PROFILES))
    ap.add_argument("--only", default=None,
                    help="comma-separated module names or unambiguous "
                         "prefixes (default: all)")
    ap.add_argument("--real", action="store_true",
                    help="sweep real datasets (repro.data.ingest) instead "
                         "of the synthetic Table-3 stand-ins")
    ap.add_argument("--workers", type=int, default=1,
                    help="dispatch cache-miss trials across N local worker "
                         "subprocesses (repro.sweep; 1 = in-process)")
    ap.add_argument("--out-json", default="BENCH_study.json",
                    help="structured results path (repro.study.store)")
    args = ap.parse_args(argv)

    if args.workers < 1:
        ap.error(f"--workers must be >= 1: {args.workers}")
    compile_cache.enable()
    if args.real:
        common.set_source("real")
    if args.workers > 1:
        from repro.sweep import LocalProcessExecutor
        try:
            common.RUNNER.executor = LocalProcessExecutor(
                workers=args.workers,
                work_dir=common.RESULTS_DIR / "sweep_workers")
        except ValueError as e:     # e.g. a TPU host: one process, one chip
            ap.error(str(e))

    selected = list(MODULES)
    if args.only:
        asked = [s.strip() for s in args.only.split(",") if s.strip()]
        resolved = {s: _resolve_module(s) for s in asked}
        for s, m in resolved.items():
            if isinstance(m, list):
                if m:
                    ap.error(f"ambiguous module prefix {s!r}: matches {m}")
                ap.error(f"unknown module {s!r}; known: {list(MODULES)}")
        selected = [resolved[s] for s in asked]

    store = StudyStore(args.out_json,
                       jsonl_path=common.RESULTS_DIR / "study_runs.jsonl")
    common.RUNNER.store = store

    if trace.enabled():
        print(f"tracing -> {trace.current_path()}", flush=True)

    results = {}
    t00 = time.time()
    for name in selected:
        t0 = time.time()
        print(f"== {name} ==", flush=True)
        with trace.span("bench.module", module=name, profile=args.profile):
            results[name] = MODULES[name].run(args.profile)
        for row in results[name]:
            print("  " + ", ".join(f"{k}={common.fmt(v)}"
                                   for k, v in row.items()))
        print(f"   ({time.time()-t0:.1f}s)")

    violations = claims.validate(results)
    store.record_claims(violations, checked_modules=list(results))
    out = store.write()
    print(f"\ntotal {time.time()-t00:.1f}s; "
          f"{sum(len(v) for v in results.values())} rows; "
          f"{len(store.trials)} trials -> {out} "
          f"({common.RUNNER.cache.hits} cache hits)")
    if violations:
        print("PAPER-CLAIM VIOLATIONS:")
        for v in violations:
            print("  - " + v)
        sys.exit(1)
    print("all paper-claim checks passed")


if __name__ == "__main__":
    main()

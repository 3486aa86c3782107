"""The benchmark's one command: run one cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything
else it needs is found by name (see ``chipbench/harness.py``).  There is
no CPU fallback: without a TPU, or with fewer chips than the cell asks
for, the command prints no result and exits 2.  Every kernel family is
asked for as ``pallas-tpu``, and a run in which a family resolved to the
interpreter or fell back to another backend is not correct.

JAX's persistent compile cache is kept at ``<checkout>/.chipbench_cache/jax``,
so only the first run in a checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
KERNEL = "pallas-tpu"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness

    harness.compile_cache()
    import jax

    cell = harness.load_cell(args.workload)
    backend = jax.default_backend()
    if backend != "tpu" or len(jax.devices()) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(jax.devices())} {backend} device(s)",
              file=sys.stderr)
        return 2
    from repro.utils import compile_cache

    compile_cache.enable()
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    result = harness.run(cell, args.seed, args.seconds,
                         traced=bool(args.trace), kernel=KERNEL,
                         t_start=T_START)
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())

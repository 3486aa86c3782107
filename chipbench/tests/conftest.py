"""The benchmark's own tests, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

They put the checkout and its ``src`` on the path, as ``run.py`` does.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import time  # noqa: E402

import pytest  # noqa: E402

#: rows and request rates small enough for the Pallas interpreter
TINY_N = {"w8a-lr": 1024, "covtype-lr": 2048}
TINY_RATE = 150.0
TINY_SECONDS = 1.5


def tiny_cell(name):
    """The cell as BENCHMARK.json has it, cut to a size the CPU runs fast."""
    from chipbench import harness

    cell = harness.load_cell(name)
    cell.config["n"] = TINY_N[cell.config["name"]]
    if "rate_per_s" in cell.traffic:
        cell.traffic["rate_per_s"] = TINY_RATE
    return cell


def drive(cell, driver=None, traced=False, seed=2 ** 33 + 3):
    """Every step of ``run.py`` after its look for a chip, off the chip."""
    import jax

    from chipbench import harness

    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    return harness.run(cell, seed, TINY_SECONDS, traced=traced,
                       kernel="pallas-interpret", t_start=time.perf_counter(),
                       driver=driver)


@pytest.fixture
def tiny():
    return tiny_cell

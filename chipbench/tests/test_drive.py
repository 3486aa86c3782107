"""Each traffic kind end to end off the chip, and run.py's refusal."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, drive, tiny_cell

CELLS = ["w8a.train", "covtype.train", "w8a.score", "w8a.live"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    cell = tiny_cell(name)
    r = drive(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(cell.limits)
    json.dumps(r)


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "w8a.train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


def test_kernel_guard_sees_the_interpreter_and_fallbacks():
    from chipbench import harness

    before = {"kernel.backend.glm_sgd.pallas-tpu": 1}
    after = {"kernel.backend.glm_sgd.pallas-tpu": 2,
             "kernel.backend.glm_score.pallas-interpret": 1,
             "kernel.fallback.glm_score.pallas-tpu.caps": 1}
    assert harness.kernel_guard(before, after, "pallas-tpu") == [
        "kernel.backend.glm_score.pallas-interpret",
        "kernel.fallback.glm_score.pallas-tpu.caps"]
    host = {"kernel.fallback.glm_score.pallas-tpu.host": 1}
    assert harness.kernel_guard({}, host, "pallas-interpret") == []
    assert harness.kernel_guard({}, host, "pallas-tpu") == [
        "kernel.fallback.glm_score.pallas-tpu.host"]

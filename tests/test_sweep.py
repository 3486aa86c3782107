"""Distributed sweep scheduler: planner, merge layer, workers, executor.

The acceptance contract of the subsystem (ISSUE 4):
* the planner never splits a stack group across workers;
* cache merges are idempotent on identical payloads and raise — listing
  every key — on same-key/different-payload conflicts;
* a killed worker's partial cache survives, its unfinished keys are
  requeued, and bounded retries end in ``ShardFailure``;
* a 2-worker sweep fills a cache from which a serial re-run writes a
  byte-identical ``BENCH_study.json`` (the CI sweep-smoke invariant),
  with worker/shard/merge provenance in the JSONL sidecar only.
"""
import json

import numpy as np
import pytest

from repro.core import sgd
from repro.study import spec, store
from repro.study.runner import Runner, TrialResult
from repro.sweep import (LocalProcessExecutor, MergeConflict, Shard,
                         ShardFailure, merge_caches, plan)


def _trials(datasets=("covtype",), tasks=("lr",), steps=(1e-2, 1e-1),
            epochs=2, max_n=96):
    return list(spec.grid(
        [spec.DatasetSpec(d, max_n=max_n) for d in datasets], tasks,
        [sgd.SyncSGD()], steps=steps, epochs=epochs))


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------


def test_plan_colocates_stack_groups_and_partitions_trials():
    trials = _trials(datasets=("covtype", "w8a"), tasks=("lr", "svm"))
    shards = plan(trials, 2)
    assert {s.worker for s in shards} == {0, 1}
    # partition: every trial exactly once
    keys = [k for s in shards for k in s.keys]
    assert sorted(keys) == sorted(t.key for t in trials)
    # co-location: each stack group lives on exactly one worker
    owner = {}
    for s in shards:
        for t in s.trials:
            assert owner.setdefault(t.stack_key, s.worker) == s.worker
    # 4 groups over 2 workers balance 2/2 under the uniform weights
    assert sorted(len(s.trials) for s in shards) == [4, 4]


def test_plan_weighs_groups_by_data_volume():
    """One full-size dataset group must not share a worker with the
    fixture-sized groups: LPT balances on epochs x n x nnz, not on
    group count."""
    big = _trials(datasets=("covtype",), max_n=2048)          # 1 heavy group
    small = _trials(datasets=("w8a",), tasks=("lr", "svm"),
                    steps=(1e-3, 1e-2, 1e-1), max_n=64)       # 2 light groups
    shards = plan(big + small, 2)
    by_worker = {s.worker: {t.dataset.name for t in s.trials} for s in shards}
    assert by_worker[0] == {"covtype"}          # heavy group rides alone
    assert by_worker[1] == {"w8a"}


def test_plan_is_deterministic_and_drops_duplicates_and_empty_shards():
    trials = _trials()
    assert plan(trials, 2) == plan(trials, 2)
    # duplicates dispatch once
    assert sum(len(s.trials) for s in plan(trials + trials, 2)) == len(trials)
    # one stack group on 4 workers -> a single non-empty shard
    shards = plan(trials, 4)
    assert len(shards) == 1 and len(shards[0].trials) == len(trials)
    with pytest.raises(ValueError, match="workers"):
        plan(trials, 0)


def test_shard_round_trips_through_dict():
    shard = plan(_trials(), 1)[0]
    restored = Shard.from_dict(json.loads(json.dumps(shard.to_dict())))
    assert restored == shard
    with pytest.raises(ValueError, match="schema"):
        Shard.from_dict({"schema": -1, "worker": 0, "trials": []})


# ---------------------------------------------------------------------------
# merge layer
# ---------------------------------------------------------------------------


def _write_cache(root, entries: dict):
    root.mkdir(parents=True, exist_ok=True)
    for key, payload in entries.items():
        (root / f"{key}.json").write_text(spec.canonical_json(payload))


def test_merge_unions_and_is_idempotent_on_identical_payloads(tmp_path):
    a, b, dest = tmp_path / "a", tmp_path / "b", tmp_path / "dest"
    _write_cache(a, {"k1": {"x": 1}, "k2": {"x": 2}})
    _write_cache(b, {"k2": {"x": 2}, "k3": {"x": 3}})   # k2 identical overlap
    rep = merge_caches([a, b], dest)
    assert (rep.merged, rep.identical, rep.sources) == (3, 1, 2)
    assert sorted(p.stem for p in dest.glob("*.json")) == ["k1", "k2", "k3"]
    # re-merging the same roots is a no-op (everything byte-matches dest)
    rep2 = merge_caches([a, b], dest)
    assert (rep2.merged, rep2.identical) == (0, 4)
    # missing / empty sources are fine (dead worker with no output)
    rep3 = merge_caches([tmp_path / "nope"], dest)
    assert (rep3.merged, rep3.identical) == (0, 0)


def test_merge_conflict_raises_with_every_key_and_writes_nothing(tmp_path):
    a, b, dest = tmp_path / "a", tmp_path / "b", tmp_path / "dest"
    _write_cache(a, {"k1": {"x": 1}, "k2": {"x": 2}, "ok": {"x": 0}})
    _write_cache(b, {"k1": {"x": 9}, "k2": {"x": 8}})   # both keys conflict
    with pytest.raises(MergeConflict) as ei:
        merge_caches([a, b], dest)
    assert sorted(ei.value.keys) == ["k1", "k2"]
    assert "k1" in str(ei.value) and "k2" in str(ei.value)
    assert not dest.exists()                 # all-or-nothing: nothing written
    # conflicts against the destination are caught too
    _write_cache(dest, {"k1": {"x": 1}})
    with pytest.raises(MergeConflict) as ei:
        merge_caches([b], dest)
    assert "k1" in ei.value.keys


def test_merge_skips_tmp_files(tmp_path):
    a, dest = tmp_path / "a", tmp_path / "dest"
    _write_cache(a, {"k1": {"x": 1}})
    (a / ".k9.tmp.123").write_text("partial write")
    rep = merge_caches([a], dest)
    assert rep.merged == 1
    assert [p.stem for p in dest.glob("*.json")] == ["k1"]


# ---------------------------------------------------------------------------
# worker protocol + executor (subprocess-based; kept small)
# ---------------------------------------------------------------------------


def test_runner_rejects_executor_without_cache():
    with pytest.raises(ValueError, match="cache_dir"):
        Runner(executor=LocalProcessExecutor(workers=2))
    # post-construction attachment (benchmarks.run --workers style) is
    # validated too
    r = Runner()
    with pytest.raises(ValueError, match="cache_dir"):
        r.executor = LocalProcessExecutor(workers=2)


def test_two_worker_sweep_reproduces_serial_store_bytes(tmp_path):
    """The acceptance property behind CI's sweep-smoke job, in miniature:
    a 2-worker sweep fills the canonical cache; a serial re-run over that
    cache writes byte-identical BENCH_study.json — and the sidecar holds
    the worker/shard/merge provenance, never the snapshot."""
    trials = _trials(datasets=("covtype", "w8a"))

    def sweep(path, executor):
        st = store.StudyStore(path, jsonl_path=tmp_path / "runs.jsonl")
        Runner(cache_dir=tmp_path / "cache", store=st,
               executor=executor).run(trials)
        st.record_claims([], checked_modules=["mini"])
        return st.write().read_text()

    ex = LocalProcessExecutor(workers=2, work_dir=tmp_path / "work")
    first = sweep(tmp_path / "a.json", ex)
    second = sweep(tmp_path / "b.json", None)       # serial, warm cache
    assert first == second
    assert "sweep_shard" not in first               # provenance not in JSON

    events = [json.loads(line)
              for line in (tmp_path / "runs.jsonl").read_text().splitlines()]
    shard_events = [e for e in events if e.get("event") == "sweep_shard"]
    merge_events = [e for e in events if e.get("event") == "sweep_merge"]
    assert {e["worker"] for e in shard_events} == {0, 1}
    assert all(e["returncode"] == 0 for e in shard_events)
    assert sorted(k for e in shard_events for k in e["completed"]) == \
        sorted(t.key for t in trials)
    [merge] = merge_events
    assert merge["merged"] == len(trials) and merge["workers"] == 2
    # the serial warm run dispatched nothing
    assert sum(e.get("event") == "sweep_merge" for e in events) == 1


def test_worker_death_requeues_unfinished_and_keeps_partial_cache(tmp_path):
    """A worker killed mid-shard (fault injection: exit 17 after its first
    stack group) leaves the finished trials durably cached; the executor
    requeues exactly the unfinished keys, the retry completes them, and
    the provenance events record the whole story."""
    trials = _trials(tasks=("lr", "svm"))    # 2 stack groups x 2 trials
    st = store.StudyStore(tmp_path / "out.json",
                          jsonl_path=tmp_path / "runs.jsonl")
    ex = LocalProcessExecutor(
        workers=1, work_dir=tmp_path / "work",
        worker_args=("--fault-after", "2",
                     "--fault-flag", str(tmp_path / "flag")))
    out = Runner(cache_dir=tmp_path / "cache", store=st, executor=ex) \
        .run(trials)
    st.write()
    assert all(np.isfinite(r.final_loss) for r in out)
    assert sorted(p.stem for p in (tmp_path / "cache").glob("*.json")) == \
        sorted(t.key for t in trials)

    events = [json.loads(line)
              for line in (tmp_path / "runs.jsonl").read_text().splitlines()]
    shard_events = [e for e in events if e.get("event") == "sweep_shard"]
    assert [e["attempt"] for e in shard_events] == [0, 1]
    died, retried = shard_events
    assert died["returncode"] == 17
    assert len(died["completed"]) == 2      # first stack group survived
    assert sorted(died["requeued"]) == sorted(retried["keys"])
    assert retried["returncode"] == 0
    # the retry ran exactly the keys the dead worker left unfinished —
    # partial results are preserved, never recomputed
    assert set(retried["keys"]) == \
        {t.key for t in trials} - set(died["completed"])
    [merge] = [e for e in events if e.get("event") == "sweep_merge"]
    assert merge["retries"] == 1
    assert merge["merged"] == len(trials)


def test_sigkilled_worker_keeps_readable_partial_metrics_sidecar(
        tmp_path, monkeypatch):
    """SIGKILL durability (satellite): ``--fault-mode kill`` bypasses
    atexit entirely, so the only sidecar bytes a dead worker leaves are
    the per-stack-group ``metrics.flush()`` writes — which must be a
    complete, readable snapshot (atomic tmp+rename), tagged with the
    attempt (``shard0a0``) the executor assigned via the trace-tag env
    even though tracing is off."""
    from repro.obs import metrics, trace

    monkeypatch.delenv(trace.ENV_TRACE, raising=False)
    monkeypatch.setenv(metrics.ENV_METRICS, "1")
    monkeypatch.setenv(trace.ENV_TRACE_DIR, str(tmp_path / "tracedir"))
    trace.refresh()

    trials = _trials(tasks=("lr", "svm"))    # 2 stack groups x 2 trials
    st = store.StudyStore(tmp_path / "out.json",
                          jsonl_path=tmp_path / "runs.jsonl")
    ex = LocalProcessExecutor(
        workers=1, work_dir=tmp_path / "work",
        worker_args=("--fault-after", "2", "--fault-mode", "kill",
                     "--fault-flag", str(tmp_path / "flag")))
    out = Runner(cache_dir=tmp_path / "cache", store=st, executor=ex) \
        .run(trials)
    st.write()
    assert len(out) == len(trials)          # retry completed the shard

    events = [json.loads(line)
              for line in (tmp_path / "runs.jsonl").read_text().splitlines()]
    shard_events = [e for e in events if e.get("event") == "sweep_shard"]
    died, retried = shard_events
    assert died["returncode"] == -9         # a real SIGKILL, not exit(17)
    assert retried["returncode"] == 0

    # the killed attempt's partial sidecar survived and parses cleanly
    killed = sorted((tmp_path / "tracedir").glob("metrics-shard0a0-*.json"))
    assert killed, "SIGKILLed worker left no metrics sidecar"
    snap = json.loads(killed[0].read_text())
    assert snap["schema"] == metrics.METRICS_SCHEMA
    assert snap["counters"]                 # the first group's activity
    # no half-written tmp files anywhere (atomic rename discipline)
    assert not list((tmp_path / "tracedir").glob("*.tmp*"))


def test_retries_exhausted_raises_but_merges_completed_trials(tmp_path):
    """Exhausted retries fail the sweep — after merging what did finish
    and recording provenance, so the next attempt resumes from the
    canonical cache and the operator can see which worker died."""
    trials = _trials(tasks=("lr", "svm"))    # 2 stack groups x 2 trials
    st = store.StudyStore(tmp_path / "out.json",
                          jsonl_path=tmp_path / "runs.jsonl")
    ex = LocalProcessExecutor(workers=1, work_dir=tmp_path / "work",
                              max_retries=0,
                              worker_args=("--fault-after", "2"))
    with pytest.raises(ShardFailure, match="unfinished"):
        Runner(cache_dir=tmp_path / "cache", store=st,
               executor=ex).run(trials)
    # the first stack group completed before the injected death and was
    # merged despite the failure; the scratch dir is kept for post-mortem
    assert len(list((tmp_path / "cache").glob("*.json"))) == 2
    assert list((tmp_path / "work").glob("sweep-*"))
    # the failed sweep is still attributable: events survived the raise
    st.write()
    events = [json.loads(line)
              for line in (tmp_path / "runs.jsonl").read_text().splitlines()]
    [died] = [e for e in events if e.get("event") == "sweep_shard"]
    assert died["returncode"] == 17 and len(died["completed"]) == 2
    assert any(e.get("event") == "sweep_merge" for e in events)


def test_executor_cleans_scratch_after_success(tmp_path):
    trials = _trials(steps=(1e-2,))
    ex = LocalProcessExecutor(workers=1, work_dir=tmp_path / "work")
    Runner(cache_dir=tmp_path / "cache", executor=ex,
           dispatch_min_groups=1).run(trials)
    assert list((tmp_path / "work").glob("sweep-*")) == []


def test_single_stack_group_stays_in_process(tmp_path):
    """One stack group cannot parallelize: by default the runner executes
    it locally instead of paying a worker cold start (so --workers is
    never slower than serial on single-grid call sites)."""

    class _MustNotDispatch:
        def execute(self, trials, cache, *, stack=True):
            raise AssertionError("single-group dispatch reached executor")

    trials = _trials()      # one 2-step stack group
    out = Runner(cache_dir=tmp_path / "cache",
                 executor=_MustNotDispatch()).run(trials)
    assert [r.cached for r in out] == [False, False]


def test_dispatch_forwards_the_runners_stack_flag(tmp_path):
    """Runner(stack=False) must cache unstacked payloads even when the
    trials execute in worker subprocesses."""
    trials = _trials()      # one 2-step stack group
    ex = LocalProcessExecutor(workers=1, work_dir=tmp_path / "work")
    out = Runner(cache_dir=tmp_path / "unstacked", stack=False,
                 executor=ex, dispatch_min_groups=1).run(trials)
    assert [r.stacked for r in out] == [False, False]
    out = Runner(cache_dir=tmp_path / "stacked", executor=ex,
                 dispatch_min_groups=1).run(trials)
    assert [r.stacked for r in out] == [True, True]


def test_dispatched_results_match_in_process_results(tmp_path):
    """Worker subprocesses compute the same numbers the in-process runner
    does: same specs, same seeds, same engine."""
    trials = _trials(steps=(1e-2,), epochs=3)
    ex = LocalProcessExecutor(workers=1, work_dir=tmp_path / "work")
    dispatched = Runner(cache_dir=tmp_path / "cache", executor=ex,
                        dispatch_min_groups=1).run(trials)
    local = Runner().run(trials)
    for a, b in zip(dispatched, local):
        np.testing.assert_allclose(a.losses, b.losses, rtol=1e-5, atol=1e-6)
    # and the dispatched payloads round-trip as TrialResults from the cache
    payload = json.loads(
        (tmp_path / "cache" / f"{trials[0].key}.json").read_text())
    restored = TrialResult.from_dict(payload)
    np.testing.assert_array_equal(restored.losses, dispatched[0].losses)


def test_executor_refuses_worker_processes_on_a_tpu_host(monkeypatch):
    """One process holds the chip: subprocess workers would hang on it."""
    from repro.kernels import common as kcommon

    monkeypatch.setattr(kcommon, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="one process"):
        LocalProcessExecutor(workers=2)

"""Study subsystem: specs, trial cache, stacking, store, tuner, advisor.

The acceptance contract of the subsystem (ISSUE 2):
* ``advisor.recommend`` ranks configurations for every Table-3 synthetic
  dataset, deterministically under a fixed seed;
* the ranking has the paper's qualitative Table-6 structure — sync
  preferred where async replication hurts statistical efficiency, and
  vice versa;
* a sweep re-run hits the trial cache and reproduces the structured
  results byte-for-byte.
"""
import json
import math

import numpy as np
import pytest

from repro.core import sgd
from repro.data import synthetic
from repro.study import advisor, claims, spec, store, tuner
from repro.study.runner import Runner, TrialResult

CAPS = advisor.HostCaps(parallel_width=8, max_replicas=64, backends={})


def _trial(name="covtype", task="lr", strategy=None, step=1e-2, epochs=3,
           max_n=128):
    return spec.TrialSpec(
        dataset=spec.DatasetSpec(name, max_n=max_n), task=task,
        strategy=strategy or sgd.SyncSGD(), step=step, epochs=epochs)


# ---------------------------------------------------------------------------
# spec: keys, round-trips, grids
# ---------------------------------------------------------------------------


def test_trial_key_content_hash_is_stable_and_step_sensitive():
    a, b = _trial(step=1e-2), _trial(step=1e-2)
    assert a.key == b.key                       # same content, same key
    assert a.key != _trial(step=1e-1).key       # step is part of the key
    assert a.stack_key == _trial(step=1e-1).stack_key  # ... but not the stack
    assert a.stack_key != _trial(task="svm", step=1e-1).stack_key


def test_strategy_round_trip_through_dict():
    for s in (sgd.SyncSGD(), sgd.SyncSGD(batch=16, kernel_backend="reference"),
              sgd.AsyncLocalSGD(replicas=16, local_batch=4, rep_k=2,
                                access="round_robin",
                                kernel_backend="reference")):
        assert spec.strategy_from_dict(spec.strategy_to_dict(s)) == s


def test_trial_spec_round_trip():
    t = _trial(strategy=sgd.AsyncLocalSGD(replicas=4), epochs=7)
    assert spec.TrialSpec.from_dict(t.to_dict()) == t
    assert spec.TrialSpec.from_dict(json.loads(json.dumps(t.to_dict()))) == t


def test_dataset_spec_rejects_unknown_and_half_shapes():
    with pytest.raises(ValueError, match="unknown dataset"):
        spec.DatasetSpec("imagenet")
    with pytest.raises(ValueError, match="both n and d"):
        spec.DatasetSpec("custom", n=64)


def test_dataset_profile_matches_loaded_data():
    for ds in (spec.DatasetSpec("covtype", max_n=128),
               spec.DatasetSpec("w8a", max_n=128),
               spec.DatasetSpec("toy", n=96, d=8)):
        prof, data = ds.profile(), ds.load()
        assert (prof.n, prof.d, prof.dense) == (data.n, data.d, data.dense)


def test_grid_filters_oversized_replica_counts():
    trials = spec.grid(
        [spec.DatasetSpec("covtype", max_n=128)], ("lr",),
        [sgd.SyncSGD(), sgd.AsyncLocalSGD(replicas=64),
         sgd.AsyncLocalSGD(replicas=128)],
        steps=(1e-2, 1e-1), epochs=3)
    names = {t.strategy.name for t in trials}
    assert len(trials) == 4  # (sync + r64) x 2 steps; r128 needs n >= 256
    assert not any("r128" in n for n in names)


# ---------------------------------------------------------------------------
# runner: cache, stacking
# ---------------------------------------------------------------------------


def test_trial_cache_roundtrip_and_hit(tmp_path):
    r = Runner(cache_dir=tmp_path / "cache")
    t = _trial(epochs=3)
    first = r.run_trial(t)
    assert not first.cached
    second = r.run_trial(t)
    assert second.cached
    np.testing.assert_array_equal(first.losses, second.losses)
    np.testing.assert_array_equal(first.epoch_times, second.epoch_times)
    # a different spec is a miss
    assert not r.run_trial(_trial(epochs=4)).cached


def test_trial_cache_misses_on_another_device(tmp_path):
    """A trial cached on one device_kind is a miss on another, and an
    unstamped file is a miss too; a hit comes back without the stamp."""
    import json as _json

    from repro.study.runner import TrialCache

    cache = TrialCache(tmp_path / "cache")
    cache.put("k", {"losses": [1.0]})
    assert cache.peek("k") == {"losses": [1.0]}
    on_disk = _json.loads((tmp_path / "cache" / "k.json").read_text())
    assert on_disk[TrialCache.DEVICE_FIELD] == cache.device_kind
    other = TrialCache(tmp_path / "cache")
    other._device_kind = "tpu-v5-lite" if cache.device_kind != "tpu-v5-lite" \
        else "cpu"
    assert other.get("k") is None and other.misses == 1
    (tmp_path / "cache" / "old.json").write_text('{"losses": [1.0]}')
    assert cache.peek("old") is None


def test_interrupted_sweep_resumes_from_cache(tmp_path):
    """Only the missing trials of a partially-cached sweep are executed."""
    trials = [_trial(step=s, epochs=3) for s in (1e-3, 1e-2, 1e-1)]
    r1 = Runner(cache_dir=tmp_path / "cache")
    r1.run(trials[:2])
    r2 = Runner(cache_dir=tmp_path / "cache")
    out = r2.run(trials)
    assert [t.cached for t in out] == [True, True, False]


def test_stacked_step_grid_matches_single_runs():
    """vmap-stacked step grids reproduce per-trial runs (same program up
    to vmap) for sync and async strategies."""
    for strategy in (sgd.SyncSGD(),
                     sgd.AsyncLocalSGD(replicas=4, local_batch=2)):
        trials = [_trial(strategy=strategy, step=s, epochs=3)
                  for s in (1e-3, 1e-2, 1e-1)]
        stacked = Runner(stack=True).run(trials)
        singles = Runner(stack=False).run(trials)
        assert [t.stacked for t in stacked] == [True] * 3
        assert [t.stacked for t in singles] == [False] * 3
        for a, b in zip(stacked, singles):
            np.testing.assert_allclose(a.losses, b.losses,
                                       rtol=1e-4, atol=1e-4)


def test_kernel_backend_trials_do_not_stack():
    strat = sgd.SyncSGD(kernel_backend="reference")
    trials = [_trial(strategy=strat, step=s, epochs=2) for s in (1e-3, 1e-2)]
    out = Runner(stack=True).run(trials)
    assert [t.stacked for t in out] == [False, False]


def test_runner_records_into_store(tmp_path):
    st = store.StudyStore(tmp_path / "out.json")
    r = Runner(cache_dir=tmp_path / "cache", store=st)
    t = _trial(epochs=2)
    r.run_trial(t)
    assert t.key in st.trials
    assert st.trials[t.key]["spec"] == t.to_dict()


# ---------------------------------------------------------------------------
# store: deterministic snapshots
# ---------------------------------------------------------------------------


def test_store_snapshot_identical_across_cached_reruns(tmp_path):
    """The acceptance property behind CI's study-smoke job, in miniature:
    the same sweep run twice (second time from cache) writes
    byte-identical BENCH_study.json."""
    trials = [_trial(step=s, epochs=3) for s in (1e-2, 1e-1)]

    def sweep(path):
        st = store.StudyStore(path, jsonl_path=tmp_path / "runs.jsonl")
        Runner(cache_dir=tmp_path / "cache", store=st).run(trials)
        st.record_claims([], checked_modules=["mini"])
        return st.write().read_text()

    first = sweep(tmp_path / "a.json")
    second = sweep(tmp_path / "b.json")
    assert first == second
    # and the JSONL sidecar logged one line per sweep
    lines = (tmp_path / "runs.jsonl").read_text().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[1])["n_cached"] == 2


def test_store_snapshot_round_trips_trial_results(tmp_path):
    st = store.StudyStore(tmp_path / "out.json")
    r = Runner(cache_dir=tmp_path / "cache", store=st)
    t = _trial(epochs=2)
    res = r.run_trial(t)
    st.write()
    loaded = store.StudyStore.load(tmp_path / "out.json")
    rec = loaded["trials"][t.key]
    assert spec.TrialSpec.from_dict(rec["spec"]) == t
    restored = TrialResult.from_dict(rec)
    np.testing.assert_array_equal(restored.losses, res.losses)


# ---------------------------------------------------------------------------
# tuner
# ---------------------------------------------------------------------------


def test_tune_step_selects_converging_step():
    t = tuner.tune_step(Runner(), _trial(epochs=6),
                        steps=(1e-6, 1e-2, 1e-1))
    assert t.best_step in (1e-2, 1e-1)
    assert set(t.results) == {1e-6, 1e-2, 1e-1}
    # the winner reaches the derived target; the tiny step does not
    assert t.best_result.epochs_to(t.target) is not None
    assert t.results[1e-6].epochs_to(t.target) is None


def test_tune_step_epochs_mode_is_wall_clock_free():
    a = tuner.tune_step(Runner(), _trial(epochs=4), steps=(1e-2, 1e-1),
                        by="epochs")
    b = tuner.tune_step(Runner(), _trial(epochs=4), steps=(1e-2, 1e-1),
                        by="epochs")
    assert a.best_step == b.best_step


class _TiedRunner:
    """Stub runner: every step yields the identical loss curve (full tie)."""

    def run(self, trials):
        return [TrialResult(losses=np.array([1.0, 0.5, 0.1]),
                            epoch_times=np.array([0.1, 0.1]),
                            strategy=t.strategy.name, task=t.task)
                for t in trials]


def test_tune_step_tie_breaks_on_canonical_step_order():
    """Rank ties resolve to the smallest step, independent of the order
    the grid arrives in — multi-worker and single-host sweeps must pick
    identical steps from identical results."""
    base = _trial(epochs=2)
    for steps in [(1e-2, 1e-1, 1e-3), (1e-1, 1e-3, 1e-2), (1e-3, 1e-2, 1e-1)]:
        t = tuner.tune_step(_TiedRunner(), base, steps=steps, by="epochs")
        assert t.best_step == 1e-3


def test_tune_many_matches_per_base_tune_step():
    """One batched dispatch, same answers: tune_many is tune_step mapped
    over bases (incl. per-base target derivation)."""
    bases = [_trial(strategy=sgd.SyncSGD(), epochs=4),
             _trial(strategy=sgd.AsyncLocalSGD(replicas=4), epochs=4)]
    runner = Runner()
    many = tuner.tune_many(runner, bases, steps=(1e-6, 1e-2, 1e-1),
                           by="epochs")
    singles = [tuner.tune_step(runner, b, steps=(1e-6, 1e-2, 1e-1),
                               by="epochs") for b in bases]
    assert len(many) == 2
    for m, s in zip(many, singles):
        assert m.best_step == s.best_step
        assert m.target == s.target
        assert set(m.results) == set(s.results)


# ---------------------------------------------------------------------------
# advisor: Table 6
# ---------------------------------------------------------------------------


def test_modeled_epoch_cost_reproduces_hardware_trades():
    prof = spec.DatasetSpec("covtype", max_n=1024).profile()
    cost = lambda s: advisor.modeled_epoch_cost(prof, s, CAPS)
    # more replicas => cheaper epochs (paper Fig. 12)
    assert (cost(sgd.AsyncLocalSGD(replicas=16))
            < cost(sgd.AsyncLocalSGD(replicas=4)))
    # rep-k halos cost hardware efficiency (Fig. 15)
    assert (cost(sgd.AsyncLocalSGD(replicas=8, rep_k=10))
            > cost(sgd.AsyncLocalSGD(replicas=8)))
    # full-batch sync is the cheapest pass on a wide host (Fig. 22)
    assert cost(sgd.SyncSGD()) < cost(sgd.AsyncLocalSGD(replicas=8))
    # more frequent merges cost more
    assert (cost(sgd.AsyncLocalSGD(replicas=8, merge_every=0.25))
            > cost(sgd.AsyncLocalSGD(replicas=8, merge_every=1.0)))


@pytest.mark.parametrize("name", list(synthetic.PAPER_DATASETS))
def test_recommend_every_table3_dataset_deterministically(name):
    """recommend() returns a full ranked table for each Table-3 dataset
    and is bit-deterministic under a fixed seed (rank="modeled": no wall
    clock in the decision)."""
    max_n = 64 if name == "news" else 128
    dspec = spec.DatasetSpec(name, max_n=max_n)
    space = [sgd.SyncSGD(), sgd.AsyncLocalSGD(replicas=4, local_batch=1)]
    runner = Runner()  # shared dataset memo; no cache — both calls recompute
    recs = [advisor.recommend(dspec.profile(), CAPS, runner=runner,
                              epochs=4, steps=(1e-2, 1e-1), space=space,
                              seed=0)
            for _ in range(2)]
    for rec in recs:
        assert rec.dataset == name
        assert len(rec.ranked) == len(space)
        assert [r.score for r in rec.ranked] == sorted(
            r.score for r in rec.ranked)
        for row in rec.ranked:
            assert row.epoch_cost > 0
            assert 0 < row.hw_advantage <= 1.0
            assert np.isfinite(row.final_loss)
    assert [r.name for r in recs[0].ranked] == [r.name for r in recs[1].ranked]
    assert [r.score for r in recs[0].ranked] == [r.score for r in recs[1].ranked]
    assert recs[0].target == recs[1].target


def test_recommend_qualitative_table6_structure():
    """The paper's Table-6 finding, reproduced: on covtype async
    replication hurts statistical efficiency outright (no async config
    reaches 1% of the optimum) => sync preferred; on a larger w8a slice
    the tuned async configuration reaches the better optimum that the
    batch path cannot => async preferred.  The winner is always the
    config whose statistical-efficiency penalty is outweighed by its
    hardware advantage."""
    space = [sgd.SyncSGD(), sgd.AsyncLocalSGD(replicas=4, local_batch=1)]
    runner = Runner()

    sync_rec = advisor.recommend(
        spec.DatasetSpec("covtype", max_n=192).profile(), CAPS,
        runner=runner, epochs=8, steps=(1e-2, 1e-1), space=space)
    assert isinstance(sync_rec.best.strategy, sgd.SyncSGD)
    async_row = next(r for r in sync_rec.ranked
                     if isinstance(r.strategy, sgd.AsyncLocalSGD))
    assert async_row.epochs_to_target is None      # replication hurt: no hit
    assert math.isinf(async_row.stat_penalty)

    async_rec = advisor.recommend(
        spec.DatasetSpec("w8a", max_n=512).profile(), CAPS,
        runner=runner, epochs=10, steps=(1e-3, 1e-2, 1e-1), space=space)
    assert isinstance(async_rec.best.strategy, sgd.AsyncLocalSGD)
    sync_row = next(r for r in async_rec.ranked
                    if isinstance(r.strategy, sgd.SyncSGD))
    assert async_rec.best.epochs_to_target is not None
    assert sync_row.epochs_to_target is None       # batch path missed target

    # consistency of the trade on both: the winner minimizes
    # epochs_to x epoch_cost among candidates, i.e. wins exactly when its
    # statistical penalty is covered by its hardware advantage
    for rec in (sync_rec, async_rec):
        finite = [r for r in rec.ranked if math.isfinite(r.score)]
        assert finite and rec.best is finite[0]
        for row in finite:
            assert row.score == pytest.approx(
                row.epochs_to_target * row.epoch_cost)


def test_recommend_rank_measured_uses_wall_time():
    rec = advisor.recommend(
        spec.DatasetSpec("covtype", max_n=128).profile(), CAPS,
        runner=Runner(), epochs=4, steps=(1e-2, 1e-1),
        space=[sgd.SyncSGD()], rank="measured")
    assert rec.rank_by == "measured"
    row = rec.best
    assert row.epoch_cost == pytest.approx(row.measured_time_per_epoch_s)


def test_recommend_to_dict_serializes():
    rec = advisor.recommend(
        spec.DatasetSpec("covtype", max_n=128).profile(), CAPS,
        runner=Runner(), epochs=3, steps=(1e-2,),
        space=[sgd.SyncSGD(), sgd.AsyncLocalSGD(replicas=4)])
    dct = json.loads(json.dumps(rec.to_dict()))
    assert dct["dataset"] == "covtype"
    assert len(dct["ranked"]) == 2
    assert spec.strategy_from_dict(dct["ranked"][0]["strategy"]) == \
        rec.best.strategy


def _calibration_store(k=2e-6, U=24.0, M=3.0, caps=CAPS):
    """A synthetic BENCH_study-shaped snapshot whose measured wall times
    follow the cost model exactly, with known constants."""
    strats = [sgd.SyncSGD(), sgd.SyncSGD(batch=8), sgd.SyncSGD(batch=32),
              sgd.AsyncLocalSGD(replicas=4), sgd.AsyncLocalSGD(replicas=16),
              sgd.AsyncLocalSGD(replicas=8, rep_k=4),
              sgd.AsyncLocalSGD(replicas=8, merge_every=0.25),
              sgd.AsyncLocalSGD(replicas=4, local_batch=4)]
    trials = {}
    for name, max_n in (("covtype", 128), ("w8a", 256)):
        ds = spec.DatasetSpec(name, max_n=max_n)
        prof = ds.profile()
        for s in strats:
            t = spec.TrialSpec(ds, "lr", s, 1e-2, 4)
            base, u, m = advisor.cost_features(prof, s, caps)
            trials[t.key] = {
                "spec": t.to_dict(),
                "derived": {"time_per_epoch_s": k * (base + U * u + M * m)},
            }
    return {"trials": trials}


def test_cost_features_decomposition_matches_modeled_cost():
    prof = spec.DatasetSpec("covtype", max_n=1024).profile()
    for s in (sgd.SyncSGD(), sgd.SyncSGD(batch=16),
              sgd.AsyncLocalSGD(replicas=8, rep_k=10, merge_every=0.5)):
        base, u, m = advisor.cost_features(prof, s, CAPS)
        assert advisor.modeled_epoch_cost(prof, s, CAPS) == pytest.approx(
            base + advisor.UPDATE_OVERHEAD * u + advisor.MERGE_UNIT * m)


def test_calibrate_recovers_planted_constants_and_is_deterministic():
    snap = _calibration_store(k=2e-6, U=24.0, M=3.0)
    model = advisor.calibrate(snap, CAPS)
    assert model.source == "calibrated"
    assert model.n_trials == len(snap["trials"])
    assert model.scale == pytest.approx(2e-6)
    assert model.update_overhead == pytest.approx(24.0)
    assert model.merge_unit == pytest.approx(3.0)
    assert advisor.calibrate(snap, CAPS) == model


def test_calibrate_falls_back_below_min_trials_and_on_degenerate_fits():
    assert advisor.calibrate({"trials": {}}, CAPS) == \
        advisor.DEFAULT_COST_MODEL
    # below the floor even with valid rows
    snap = _calibration_store()
    few = {"trials": dict(list(snap["trials"].items())[2:5])}  # sync + async
    assert advisor.calibrate(few, CAPS) == advisor.DEFAULT_COST_MODEL
    assert advisor.calibrate(few, CAPS, min_trials=3).source == "calibrated"
    # sync-only stores can't identify the merge constant: rank-deficient
    sync_only = {"trials": {
        key: rec for key, rec in snap["trials"].items()
        if rec["spec"]["strategy"]["kind"] == "sync"}}
    assert advisor.calibrate(sync_only, CAPS, min_trials=3) == \
        advisor.DEFAULT_COST_MODEL
    # junk records are skipped, not fatal
    junk = {"trials": {"x": {"spec": {}},
                       "y": {"derived": {"time_per_epoch_s": -1.0}}}}
    assert advisor.calibrate(junk, CAPS) == advisor.DEFAULT_COST_MODEL


def test_calibrate_skips_records_whose_key_this_host_cannot_reproduce():
    """Wall-times measured against data this host doesn't have (stored
    key != locally recomputed key, e.g. a full-download store calibrated
    on a fixtures-only host) must not contribute features to the fit."""
    snap = _calibration_store()
    # remap every record under a foreign key: nothing is fittable
    foreign = {"trials": {f"deadbeef{i:08x}": rec for i, rec in
                          enumerate(snap["trials"].values())}}
    assert advisor.calibrate(foreign, CAPS) == advisor.DEFAULT_COST_MODEL
    # a real-dataset record this host cannot resolve at all (no download,
    # no bundled fixture) is skipped, not a crash
    mixed = dict(snap["trials"])
    mixed["feedfacefeedface"] = {
        "spec": {"dataset": {"name": "rcv1", "source": "real"},
                 "task": "lr", "strategy": {"kind": "sync"},
                 "step": 1e-2, "epochs": 4, "seed": 0},
        "derived": {"time_per_epoch_s": 1.0},
    }
    model = advisor.calibrate({"trials": mixed}, CAPS)
    assert model.source == "calibrated"
    assert model.n_trials == len(snap["trials"])    # rcv1 contributed nothing
    # a store whose keys check out still fits
    assert advisor.calibrate(snap, CAPS).source == "calibrated"


def test_calibrate_reads_a_written_store(tmp_path):
    st = store.StudyStore(tmp_path / "out.json")
    r = Runner(cache_dir=tmp_path / "cache", store=st)
    for s in (1e-3, 1e-2, 1e-1):
        r.run_trial(_trial(step=s, epochs=2))
    st.write()
    # 3 trials < floor -> defaults, via path, snapshot dict, and StudyStore
    for src in (tmp_path / "out.json", str(tmp_path / "out.json"),
                store.StudyStore.load(tmp_path / "out.json"), st):
        assert advisor.calibrate(src, CAPS) == advisor.DEFAULT_COST_MODEL


def test_recommend_rank_calibrated_uses_fitted_model():
    model = advisor.calibrate(_calibration_store(), CAPS)
    space = [sgd.SyncSGD(), sgd.AsyncLocalSGD(replicas=4, local_batch=1)]
    prof = spec.DatasetSpec("covtype", max_n=128).profile()
    rec = advisor.recommend(prof, CAPS, runner=Runner(), epochs=3,
                            steps=(1e-2,), space=space,
                            rank="calibrated", cost_model=model)
    assert rec.rank_by == "calibrated"
    for row in rec.ranked:
        assert row.epoch_cost == pytest.approx(advisor.modeled_epoch_cost(
            prof, row.strategy, CAPS, model=model))
    # no model supplied -> fixed defaults (same numbers as rank="modeled")
    rec_default = advisor.recommend(prof, CAPS, runner=Runner(), epochs=3,
                                    steps=(1e-2,), space=space,
                                    rank="calibrated")
    rec_modeled = advisor.recommend(prof, CAPS, runner=Runner(), epochs=3,
                                    steps=(1e-2,), space=space)
    assert [r.epoch_cost for r in rec_default.ranked] == \
        [r.epoch_cost for r in rec_modeled.ranked]
    with pytest.raises(ValueError, match="rank"):
        advisor.recommend(prof, CAPS, runner=Runner(), epochs=2,
                          steps=(1e-2,), space=space, rank="bogus")
    # a supplied model is never silently ignored: wrong rank is an error
    with pytest.raises(ValueError, match="cost_model"):
        advisor.recommend(prof, CAPS, runner=Runner(), epochs=2,
                          steps=(1e-2,), space=space, cost_model=model)


def test_hostcaps_detect_reads_jax_devices_and_registry():
    import jax

    caps = advisor.HostCaps.detect()
    devices = jax.devices()
    assert caps.device_count == len(devices)
    assert caps.platform == devices[0].platform
    per_device = caps.parallel_width // caps.device_count
    assert per_device >= 8      # at least the CPU lane floor
    for fam in ("glm_grad", "glm_sgd", "glm_sparse"):
        assert "reference" in caps.backends[fam]
    dct = caps.to_dict()
    assert dct["platform"] == caps.platform
    assert isinstance(dct["backends"]["glm_grad"], list)


def test_candidate_space_respects_host_and_dataset():
    prof = spec.DatasetSpec("covtype", max_n=128).profile()  # n=128
    small_caps = advisor.HostCaps(parallel_width=8, max_replicas=16,
                                  backends={"glm_grad": ("reference",)})
    space = advisor.candidate_space(prof, small_caps,
                                    kernel_backends=(None, "reference",
                                                     "pallas-tpu"))
    names = [getattr(s, "name") for s in space]
    assert "sync" in names and "sync[reference]" in names
    assert not any("pallas-tpu" in n for n in names)   # host can't run it
    assert not any(getattr(s, "replicas", 0) > 16 for s in space)
    # rep-k never exceeds the partition size
    assert all(s.rep_k < prof.n // s.replicas for s in space
               if isinstance(s, sgd.AsyncLocalSGD))


def test_candidate_space_uses_the_hosts_compiled_flavor():
    """On a TPU host the kernel candidate is the compiled flavor, and the
    interpreter is never one, even when asked for."""
    prof = spec.DatasetSpec("covtype", max_n=128).profile()
    tpu_caps = advisor.HostCaps(
        parallel_width=8, max_replicas=16, platform="tpu",
        backends={"glm_grad": ("pallas-tpu", "pallas-interpret",
                               "reference")})
    names = [s.name for s in advisor.candidate_space(prof, tpu_caps)]
    assert "sync[pallas-tpu]" in names
    assert not any("pallas-interpret" in n for n in names)
    asked = advisor.candidate_space(
        prof, tpu_caps, kernel_backends=(None, "pallas-interpret"))
    assert not any("pallas-interpret" in s.name for s in asked)
    cpu_caps = advisor.HostCaps(
        parallel_width=8, max_replicas=16,
        backends={"glm_grad": ("pallas-interpret", "reference")})
    names = [s.name for s in advisor.candidate_space(prof, cpu_caps)]
    assert "sync[pallas-interpret]" in names


# ---------------------------------------------------------------------------
# claims predicates (moved out of benchmarks/run.py)
# ---------------------------------------------------------------------------


def test_claims_table4_flags_broken_identity_and_slowdown():
    rows = [dict(dataset="covtype", task="lr",
                 paths_statistically_identical=True, speedup_sync_vs_seq=9.0)]
    assert claims.check_table4(rows) == []
    rows[0]["paths_statistically_identical"] = False
    rows[0]["speedup_sync_vs_seq"] = 0.5
    bad = claims.check_table4(rows)
    assert len(bad) == 2
    assert any("identity" in b for b in bad)


def test_claims_fig11_flags_replication_improving_statistics():
    rows = [dict(dataset="d", task="lr", replicas=1, final_loss=100.0),
            dict(dataset="d", task="lr", replicas=64, final_loss=101.0)]
    assert claims.check_fig11(rows) == []
    rows[1]["final_loss"] = 50.0   # thread beating kernel outright
    assert len(claims.check_fig11(rows)) == 1


def test_claims_fig14_flags_rep_k_hardware_inversion():
    rows = [dict(dataset="d", task="lr", rep_k=0, t_epoch_ms=1.0),
            dict(dataset="d", task="lr", rep_k=10, t_epoch_ms=1.2)]
    assert claims.check_fig14(rows) == []
    rows[1]["t_epoch_ms"] = 0.5
    assert len(claims.check_fig14(rows)) == 1


def test_claims_validate_dispatches_known_modules():
    results = {
        "table4_sync": [dict(dataset="d", task="lr",
                             paths_statistically_identical=False,
                             speedup_sync_vs_seq=2.0)],
        "unknown_module": [dict(x=1)],
    }
    bad = claims.validate(results)
    assert len(bad) == 1 and bad[0].startswith("table4")

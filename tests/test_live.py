"""Live subsystem tests: streams, the replica-merge learner, publishing,
and the train-while-serve chaos run.

The contract under test, from docs/LIVE.md:

* streams are deterministic and replayable — chunk ``i`` is a pure
  function of ``(seed, i)``; the libsvm stream re-reads the same file
  bytes into the same batches and wraps at EOF;
* the learner converges on the stream's planted model, merges only the
  alive replicas, freezes dead ones, re-seeds them from the merged
  anchor on revival, and never stalls (all-dead merges are skipped, the
  stream keeps flowing);
* the compressed (int8 + error feedback) merge path tracks the exact
  path within quantization tolerance;
* the publisher stamps every snapshot with the learner step, versions
  strictly increase, and the published model never lags training by
  more than ``every_merges * merge_every`` steps;
* under concurrent serving + kill/revive chaos, every response is
  consistent with exactly ONE published snapshot (the torn-read check),
  staleness stays inside the bound, and scoring throughput never drops
  to zero.
"""
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import sparse
from repro.core.glm import LINKS
from repro.data.ingest.libsvm import LibsvmFormatError
from repro.live import (LibsvmStream, LiveConfig, LiveLearner,
                        SnapshotPublisher, SyntheticStream)
from repro.serve.glm import GLMScoreEngine, ScoreRequest

D, NB = 32, 64


def _stream(seed=3, **kw):
    kw.setdefault("n_batch", NB)
    kw.setdefault("d", D)
    return SyntheticStream(seed=seed, **kw)


def _cfg(**kw):
    kw.setdefault("task", "lr")
    kw.setdefault("replicas", 4)
    kw.setdefault("step_size", 0.2)
    kw.setdefault("merge_every", 2)
    return LiveConfig(**kw)


def _libsvm_file(tmp_path, n_rows=25, d=10, zero_based=False):
    rng = np.random.default_rng(7)
    lo = 0 if zero_based else 1
    lines = []
    for _ in range(n_rows):
        label = int(rng.random() < 0.5)
        nnz = int(rng.integers(1, 5))
        idx = np.sort(rng.choice(np.arange(lo, d + lo), nnz, replace=False))
        feats = " ".join(f"{j}:{rng.normal():.4f}" for j in idx)
        lines.append(f"{label} {feats}")
    p = tmp_path / "stream.svm"
    p.write_text("\n".join(lines) + "\n")
    return p


# ---------------------------------------------------------------------------
# streams
# ---------------------------------------------------------------------------


def test_synthetic_stream_is_pure_function_of_seed_and_seq():
    a, b = _stream(seed=9), _stream(seed=9)
    for i in (0, 3, 17):
        ba, bb = a.batch(i), b.batch(i)
        assert ba.seq == i
        np.testing.assert_array_equal(ba.values, bb.values)
        np.testing.assert_array_equal(ba.indices, bb.indices)
        np.testing.assert_array_equal(ba.y, bb.y)
    # random access == iteration order
    it = iter(a)
    np.testing.assert_array_equal(next(it).values, a.batch(0).values)
    np.testing.assert_array_equal(next(it).values, a.batch(1).values)
    # different seeds diverge
    assert not np.array_equal(a.batch(0).values, _stream(seed=10).batch(0).values)


def test_synthetic_stream_shapes_and_holdout():
    s = _stream()
    b = s.batch(0)
    assert b.values.shape == (NB, s.ell_width)
    assert b.indices.shape == (NB, s.ell_width)
    assert b.indices.dtype == np.int32
    assert set(np.unique(b.y)) <= {-1.0, 1.0}
    ell, y = s.holdout(128)
    assert ell.values.shape == (128, s.ell_width) and len(y) == 128
    assert s.n_batch == NB              # holdout must not clobber the config
    ell2, y2 = s.holdout(128)
    np.testing.assert_array_equal(np.asarray(ell.values),
                                  np.asarray(ell2.values))
    # dense profile carries the dense view
    ds = _stream(dense=True, n_batch=8, d=6)
    db = ds.batch(0)
    assert db.X.shape == (8, 6) and ds.ell_width == 6


def test_libsvm_stream_replays_and_wraps(tmp_path):
    p = _libsvm_file(tmp_path)
    a = LibsvmStream(p, n_batch=8, d=10, ell_width=4)
    first = [a.batch() for _ in range(4)]
    assert [b.seq for b in first] == [0, 1, 2, 3]
    assert set(np.unique(first[0].y)) <= {-1.0, 1.0}   # {0,1} auto-mapped
    # replay from a fresh reader: identical bytes -> identical batches
    b0 = LibsvmStream(p, n_batch=8, d=10, ell_width=4).batch()
    np.testing.assert_array_equal(first[0].values, b0.values)
    np.testing.assert_array_equal(first[0].y, b0.y)
    # 25 rows / chunks of 8: batch 3 wrapped to the file start
    np.testing.assert_array_equal(first[3].values[1], first[0].values[0])
    # loop=False: 3 full chunks, the 1-row tail is dropped
    assert len(list(LibsvmStream(p, n_batch=8, d=10, ell_width=4,
                                 loop=False))) == 3


def test_libsvm_stream_rejects_bad_indices(tmp_path):
    p0 = _libsvm_file(tmp_path, zero_based=True)
    with pytest.raises(LibsvmFormatError, match="1-based"):
        for _ in LibsvmStream(p0, n_batch=8, d=10, ell_width=4):
            pass
    # same file read correctly as 0-based
    b = LibsvmStream(p0, n_batch=8, d=10, ell_width=4,
                     zero_based=True).batch()
    assert b.indices.max() < 10
    # out-of-range feature vs the pinned d
    with pytest.raises(LibsvmFormatError, match="out of range"):
        LibsvmStream(p0, n_batch=8, d=5, ell_width=4,
                     zero_based=True).batch()


# ---------------------------------------------------------------------------
# learner
# ---------------------------------------------------------------------------


def test_live_learner_converges_and_merges():
    s = _stream()
    lrn = LiveLearner(_cfg(), s)
    ell, y = s.holdout(256)
    l0 = lrn.loss(ell, y)
    lrn.run(40)
    assert lrn.steps == 40 and lrn.merges == 20
    assert lrn.loss(ell, y) < 0.6 * l0
    # after a merge all alive replicas hold the merged model
    W = np.asarray(lrn.W)
    anchor = np.asarray(lrn.anchor)
    for r in range(4):
        np.testing.assert_allclose(W[r], anchor, rtol=1e-6)


def test_live_learner_validates_local_batch():
    with pytest.raises(ValueError, match="local_batch must divide"):
        # per-replica partition is 16; 5 does not divide it
        LiveLearner(_cfg(local_batch=5), _stream())


def test_live_learner_compressed_merge_tracks_exact():
    s = _stream(seed=4)
    ell, y = s.holdout(256)
    exact = LiveLearner(_cfg(), s).run(30)
    comp = LiveLearner(_cfg(compress=True), s).run(30)
    le, lc = exact.loss(ell, y), comp.loss(ell, y)
    assert lc == pytest.approx(le, rel=0.05)   # int8+EF: same trajectory
    # the error-feedback buffer is live (carries nonzero residual)
    assert float(jnp.abs(comp._ef).sum()) > 0.0


def test_live_learner_kernel_dispatch_path():
    s = _stream(seed=6)
    ell, y = s.holdout(256)
    lrn = LiveLearner(_cfg(local_batch=8, replicas=2,
                           kernel_backend="pallas-interpret"), s)
    l0 = lrn.loss(ell, y)
    lrn.run(12)
    assert lrn.loss(ell, y) < l0
    # and the pure-XLA path with the same batching agrees on the merged
    # model (same data order, same math)
    ref = LiveLearner(_cfg(local_batch=8, replicas=2), _stream(seed=6))
    ref.run(12)
    np.testing.assert_allclose(np.asarray(lrn.anchor), np.asarray(ref.anchor),
                               atol=1e-4)


def test_live_learner_dead_replica_frozen_and_dropped():
    lrn = LiveLearner(_cfg(), _stream())
    lrn.run(6)
    lrn.kill(2)
    assert lrn.alive().tolist() == [True, True, False, True]
    w_dead = np.asarray(lrn.W[2]).copy()
    lrn.run(6)
    np.testing.assert_array_equal(np.asarray(lrn.W[2]), w_dead)  # frozen
    # the merge excluded the dead row: alive rows share the anchor, the
    # dead one does not
    anchor = np.asarray(lrn.anchor)
    assert not np.allclose(w_dead, anchor)
    np.testing.assert_allclose(np.asarray(lrn.W[0]), anchor, rtol=1e-6)
    # revival re-seeds from the merged model and resumes training
    lrn.revive(2)
    np.testing.assert_array_equal(np.asarray(lrn.W[2]), anchor)
    lrn.run(1)
    assert not np.allclose(np.asarray(lrn.W[2]), anchor)  # training again


def test_live_learner_all_dead_skips_merge_but_streams_on():
    lrn = LiveLearner(_cfg(), _stream())
    for r in range(4):
        lrn.kill(r)
    lrn.run(4)
    assert lrn.steps == 4               # the stream kept flowing
    assert lrn.merges == 0 and lrn.merges_skipped == 2
    np.testing.assert_array_equal(np.asarray(lrn.W),
                                  np.zeros((4, D), np.float32))
    lrn.revive(0)
    lrn.run(2)
    assert lrn.merges == 1              # consensus resumes with one replica


# ---------------------------------------------------------------------------
# publisher
# ---------------------------------------------------------------------------


def test_publisher_stamps_steps_and_bounds_staleness():
    s = _stream()
    eng = GLMScoreEngine("lr", np.zeros(D, np.float32),
                         ell_width=s.ell_width, max_batch=8)
    lrn = LiveLearner(_cfg(), s)
    pub = SnapshotPublisher(eng, every_merges=2).attach(lrn)
    assert eng.model.version == 0 and eng.model.step is None
    lrn.run(20)                          # 10 merges -> 5 publishes
    assert pub.publishes == 5
    assert eng.model.version == 5
    assert eng.model.step == 20
    versions = [h["version"] for h in pub.history]
    steps = [h["step"] for h in pub.history]
    assert versions == [1, 2, 3, 4, 5]          # strictly increasing
    assert steps == [4, 8, 12, 16, 20]          # stamped learner steps
    bound = pub.bound_steps(lrn.config.merge_every)   # 2 * 2 = 4
    # walk every step: the published model never lags more than `bound`
    for _ in range(17):
        lrn.step()
        assert pub.staleness(lrn) <= bound
    # the published snapshot really is the merged model at that step
    np.testing.assert_allclose(np.asarray(eng.model.w),
                               np.asarray(lrn.anchor), rtol=1e-6)


def test_live_step_span_tree():
    """``live.step`` covers the whole step: fetch, put, pass, and on a
    merge step ``live.merge`` and the publish that follows it."""
    from repro.obs import trace

    s = _stream()
    eng = GLMScoreEngine("lr", np.zeros(D, np.float32),
                         ell_width=s.ell_width, max_batch=8)
    lrn = LiveLearner(_cfg(merge_every=2), s)
    SnapshotPublisher(eng).attach(lrn)
    lrn.step()                              # compile outside the trace
    trace.enable()
    try:
        lrn.step()                          # step 2: merges and publishes
        lrn.step()
    finally:
        trace.disable()
    recs = [r for r in trace.records()     # no instants, no compiles
            if r.kind == "span" and r.name != trace.COMPILE_SPAN]
    steps = [r for r in recs if r.name == "live.step"]
    assert [(r.depth, r.attrs["step"], r.attrs["alive"]) for r in steps] == [
        (0, 1, 4), (0, 2, 4)]

    def children(parent):
        return [r.name for r in sorted(recs, key=lambda r: r.start_ns)
                if r.parent == parent.id]

    assert children(steps[0]) == ["live.fetch", "live.put", "live.pass",
                                  "live.merge", "live.publish"]
    assert children(steps[1]) == ["live.fetch", "live.put", "live.pass"]
    assert len(recs) == 2 + 5 + 3
    assert all(r.cpu_end_ns >= r.cpu_start_ns for r in steps)


def test_publisher_validates_period():
    eng = GLMScoreEngine("lr", np.zeros(4, np.float32), ell_width=2)
    with pytest.raises(ValueError, match="every_merges"):
        SnapshotPublisher(eng, every_merges=0)


# ---------------------------------------------------------------------------
# chaos: train while serving, kill/revive mid-stream
# ---------------------------------------------------------------------------


def _score_oracle(task, w, values, indices):
    m = float(np.sum(values * w[np.asarray(indices, np.int64)]))
    return float(LINKS[task](jnp.float32(m)))


def test_live_chaos_train_while_serving():
    """The ISSUE acceptance run: a learner trains + publishes while a
    scoring thread serves, replicas die and revive mid-stream.  Checks:
    (1) fault-run convergence lands within tolerance of the no-fault
    run; (2) every response is consistent with exactly one published
    snapshot (score matches that version's weights — no torn reads) and
    versions are non-decreasing in admission order; (3) staleness never
    exceeds the publisher bound; (4) scoring throughput is never zero.
    """
    s = _stream(seed=12)
    ell, y = s.holdout(256)
    n_steps = 48

    # -- baseline: same stream, no faults, no serving
    base = LiveLearner(_cfg(), _stream(seed=12)).run(n_steps)
    base_loss = base.loss(ell, y)

    # -- chaos run
    lrn = LiveLearner(_cfg(), s)
    eng = GLMScoreEngine("lr", np.zeros(D, np.float32),
                         ell_width=s.ell_width, max_batch=8, queue_depth=64)
    pub = SnapshotPublisher(eng, every_merges=1).attach(lrn)
    bound = pub.bound_steps(lrn.config.merge_every)
    published = {0: np.zeros(D, np.float32)}   # version -> weights
    lrn.add_merge_hook(lambda l: published.setdefault(
        eng.model.version, np.asarray(eng.model.w).copy()))

    responses, requests = [], {}
    flushes, empty_flushes = [], 0
    stop = threading.Event()
    rng = np.random.default_rng(0)

    def server():
        rid = 0
        while not stop.is_set():
            for _ in range(4):
                nn = int(rng.integers(1, s.ell_width + 1))
                idx = rng.choice(D, nn, replace=False)
                req = ScoreRequest(rid, rng.normal(0, 1, nn), idx)
                if eng.try_admit(req):
                    requests[rid] = req
                    rid += 1
            out = eng.flush()
            flushes.append(len(out))
            responses.extend(out)
        responses.extend(eng.drain())

    th = threading.Thread(target=server)
    th.start()
    try:
        for i in range(n_steps):
            lrn.step()
            lag = pub.staleness(lrn)
            assert lag is None or lag <= bound
            if i == 12:
                lrn.kill(1)
                lrn.kill(3)
            if i == 28:
                lrn.revive(1)
                lrn.revive(3)
    finally:
        stop.set()
        th.join()

    # (1) convergence within tolerance of the fault-free run
    chaos_loss = lrn.loss(ell, y)
    assert chaos_loss < 1.35 * base_loss, (chaos_loss, base_loss)

    # (2) every response consistent with exactly ONE published snapshot
    assert responses, "server thread never scored anything"
    for resp in responses:
        assert resp.model_version in published
        req = requests[resp.rid]
        want = _score_oracle("lr", published[resp.model_version],
                             np.asarray(req.values, np.float32),
                             req.indices)
        assert resp.score == pytest.approx(want, abs=1e-4), resp
    seen = [r.model_version for r in responses]
    assert seen == sorted(seen)          # single consumer: non-decreasing
    assert max(seen) >= 1                # swaps really interleaved

    # (3) the final published model is the final merged model
    np.testing.assert_allclose(np.asarray(eng.model.w),
                               np.asarray(lrn.anchor), rtol=1e-6)

    # (4) throughput never zero: every server round either admitted
    # fresh rows or the queue was full — both make the flush non-empty
    assert flushes and all(flushes), "scoring throughput dropped to zero"
    assert lrn.merges >= n_steps // lrn.config.merge_every - 1

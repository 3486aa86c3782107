"""Serve-layer tests: the LM slot engine and the GLM scoring service.

The first test suite the serve layer has ever had.  Covers the
``ServeEngine`` regression fixes (empty-prompt admission, dead ``done``
accumulator), the ``GLMScoreEngine`` admission/batching/scoring
semantics, property-based admission invariants for *both* engines
(hypothesis: arbitrary admit/tick interleavings lose nothing, duplicate
nothing, respect capacity and FIFO, and terminate), and the hot-swap
chaos test: every response under concurrent ``swap_model`` fire is
consistent with exactly one published snapshot.
"""
import functools
import inspect
import random
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    # Fallback property-test driver: the admission invariants below are
    # tier-1 and must run even without the dev extra (CI does not install
    # hypothesis — test_properties.py skips there).  This implements
    # exactly the strategy subset used in this file, drawing from a
    # seeded ``random.Random`` per example, so the tests stay
    # deterministic and still explore many interleavings.  With
    # hypothesis installed the real engine (shrinking, coverage-guided
    # generation) takes over transparently.
    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

    class st:  # noqa: N801 — mirrors ``hypothesis.strategies``
        @staticmethod
        def integers(lo, hi):
            return _Strategy(lambda rng: rng.randint(lo, hi))

        @staticmethod
        def sampled_from(items):
            return _Strategy(lambda rng: rng.choice(list(items)))

        @staticmethod
        def tuples(*ss):
            return _Strategy(lambda rng: tuple(s._draw(rng) for s in ss))

        @staticmethod
        def lists(elt, min_size=0, max_size=10):
            return _Strategy(lambda rng: [
                elt._draw(rng)
                for _ in range(rng.randint(min_size, max_size))])

        @staticmethod
        def data():
            return _Strategy(None)      # resolved by ``given`` below

    class _Data:
        def __init__(self, rng):
            self._rng = rng

        def draw(self, strategy, label=None):
            return strategy._draw(self._rng)

    def settings(max_examples=10, deadline=None):
        def deco(fn):
            fn._max_examples = max_examples
            return fn
        return deco

    def given(**gkw):
        ((name, _),) = gkw.items()      # only the data=st.data() form

        def deco(fn):
            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                for seed in range(getattr(fn, "_max_examples", 10)):
                    fn(*args, **{name: _Data(random.Random(seed))},
                       **kwargs)
            # hide the drawn param so pytest doesn't look for a fixture
            sig = inspect.signature(fn)
            wrapper.__signature__ = sig.replace(parameters=[
                p for p in sig.parameters.values() if p.name != name])
            del wrapper.__wrapped__
            return wrapper
        return deco

from repro.core.glm import LINKS
from repro.serve.engine import Request, ServeEngine
from repro.serve.glm import GLMScoreEngine, ModelSnapshot, ScoreRequest


# ---------------------------------------------------------------------------
# shared fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm_setup():
    """One tiny transformer per module — ServeEngine tests share the jit."""
    from repro import configs
    from repro.nn import transformer

    cfg = configs.reduced(configs.get("minitron-4b"))
    params, _ = transformer.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _score_engine(task="lr", d=24, k=3, **kw):
    rng = np.random.default_rng(3)
    w = rng.normal(0, 0.4, d).astype(np.float32)
    kw.setdefault("max_batch", 4)
    kw.setdefault("queue_depth", 6)
    return GLMScoreEngine(task, w, ell_width=k, **kw), w


def _req(rid, d=24, k=3, seed=None):
    rng = np.random.default_rng(rid if seed is None else seed)
    nn = int(rng.integers(1, k + 1))
    idx = rng.choice(d, nn, replace=False)
    return ScoreRequest(rid, rng.normal(0, 1, nn), idx)


def _oracle(task, w, req):
    m = float(np.sum(np.asarray(req.values, np.float32)
                     * w[np.asarray(req.indices, np.int64)]))
    return float(LINKS[task](jnp.float32(m)))


# ---------------------------------------------------------------------------
# ServeEngine regressions (the seed's untested slot loop)
# ---------------------------------------------------------------------------


def test_serve_engine_empty_prompt_admits(lm_setup):
    """Empty prompts used to raise UnboundLocalError in try_admit
    (``logits`` was only bound inside the prefill loop)."""
    cfg, params = lm_setup
    eng = ServeEngine(cfg, params, slots=2, max_len=32)
    req = Request(0, np.asarray([], np.int32), max_new=3)
    assert eng.try_admit(req)           # no crash, slot taken
    assert eng.live[0] is req
    assert req.out == []                # no prompt-conditioned token yet
    done = eng.run([req], max_ticks=20)
    assert done == [req] and req.done
    assert 1 <= len(req.out) <= req.max_new + 1
    assert all(0 <= t < cfg.vocab for t in req.out)


def test_serve_engine_run_mixed_empty_and_real_prompts(lm_setup):
    cfg, params = lm_setup
    eng = ServeEngine(cfg, params, slots=2, max_len=32)
    reqs = [Request(0, np.asarray([], np.int32), max_new=2),
            Request(1, np.asarray([1, 2], np.int32), max_new=2),
            Request(2, np.asarray([], np.int32), max_new=2)]
    done = eng.run(reqs, max_ticks=50)
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert all(r.done and len(r.out) >= 1 for r in reqs)


def test_serve_engine_run_returns_each_request_once(lm_setup):
    """run() must report every finished request exactly once (the old
    dead ``done`` accumulator duplicated this bookkeeping)."""
    cfg, params = lm_setup
    eng = ServeEngine(cfg, params, slots=2, max_len=32)
    reqs = [Request(i, np.asarray([1 + i], np.int32), max_new=2)
            for i in range(3)]
    done = eng.run(reqs, max_ticks=50)
    assert [r.rid for r in done] == [0, 1, 2]
    assert len({id(r) for r in done}) == 3


# ---------------------------------------------------------------------------
# ServeEngine admission properties (hypothesis)
# ---------------------------------------------------------------------------


@given(data=st.data())
@settings(max_examples=5, deadline=None)
def test_serve_engine_admission_properties(lm_setup, data):
    """Arbitrary admit/tick interleavings: capacity respected, FIFO
    admission, nothing lost or duplicated, every admitted request
    terminates within its max_new bound."""
    cfg, params = lm_setup
    slots = data.draw(st.integers(1, 2), label="slots")
    specs = data.draw(st.lists(
        st.tuples(st.integers(0, 2), st.integers(1, 3)),
        min_size=1, max_size=4), label="(prompt_len, max_new)")
    ops = data.draw(st.lists(st.sampled_from(["admit", "tick"]),
                             max_size=8), label="ops")
    eng = ServeEngine(cfg, params, slots=slots, max_len=32)
    pending = [Request(i, np.arange(1, 1 + p, dtype=np.int32), max_new=m)
               for i, (p, m) in enumerate(specs)]
    admitted = []
    for op in ops + ["admit", "tick"] * (4 * len(specs)):
        live = [r for r in eng.live if r is not None]
        assert len(live) <= slots
        if op == "admit" and pending:
            if eng.try_admit(pending[0]):
                admitted.append(pending.pop(0))
            else:
                assert all(r is not None for r in eng.live)  # full => reject
        else:
            eng.tick()
        if not pending and all(r is None for r in eng.live):
            break
    # FIFO: requests were admitted in submission order
    assert [r.rid for r in admitted] == sorted(r.rid for r in admitted)
    # nothing lost, nothing duplicated, everything terminated in bound
    assert len(admitted) == len(specs)
    for r in admitted:
        assert r.done
        assert 1 <= len(r.out) <= r.max_new + 1


# ---------------------------------------------------------------------------
# GLMScoreEngine: admission, padded batching, scoring
# ---------------------------------------------------------------------------


def test_score_engine_scores_match_links():
    for task in ("lr", "svm"):
        eng, w = _score_engine(task)
        reqs = [_req(i) for i in range(3)]
        for r in reqs:
            assert eng.try_admit(r)
        out = eng.flush()               # 3 real rows in an 8-row padded batch
        assert [r.rid for r in out] == [0, 1, 2]
        for resp, req in zip(out, reqs):
            assert resp.score == pytest.approx(_oracle(task, w, req),
                                               abs=1e-4)
            assert resp.model_version == 0
            assert resp.latency_s >= 0.0


def test_score_engine_bounded_fifo_rejects_when_full():
    eng, _ = _score_engine(queue_depth=2)
    assert eng.try_admit(_req(0))
    assert eng.try_admit(_req(1))
    assert not eng.try_admit(_req(2))   # bounded: reject, don't buffer
    assert len(eng) == 2
    eng.flush()
    assert eng.try_admit(_req(2))       # space freed by the flush


def test_score_engine_flush_is_fifo_across_batches():
    eng, _ = _score_engine(max_batch=2, queue_depth=8)
    for i in range(5):
        assert eng.try_admit(_req(i))
    rids = [r.rid for r in eng.drain()]
    assert rids == [0, 1, 2, 3, 4]


def test_score_engine_rejects_malformed_rows():
    eng, _ = _score_engine(k=3)
    with pytest.raises(ValueError, match="exceed"):
        eng.try_admit(ScoreRequest(0, np.ones(4), np.arange(4)))
    with pytest.raises(ValueError, match="mismatch"):
        eng.try_admit(ScoreRequest(1, np.ones(2), np.arange(3)))
    with pytest.raises(ValueError, match="unknown task"):
        GLMScoreEngine("poisson", np.ones(4), ell_width=2)


def test_score_engine_flush_deadline_with_injected_clock():
    now = [0.0]
    eng, _ = _score_engine(max_batch=4, queue_depth=8,
                           flush_deadline_s=0.5, clock=lambda: now[0])
    assert eng.try_admit(_req(0))
    assert eng.maybe_flush() == []      # 1 of 4 rows, deadline not reached
    now[0] = 0.6
    out = eng.maybe_flush()             # oldest row overdue -> flush
    assert [r.rid for r in out] == [0]
    assert out[0].latency_s == pytest.approx(0.6)
    for i in range(1, 5):
        assert eng.try_admit(_req(i))
    assert len(eng.maybe_flush()) == 4  # full batch flushes regardless


def test_score_engine_flush_span_tree_and_queue_wait_exact():
    """``serve.flush`` holds pack/put/dispatch/wait/respond, carries the
    batch's rows, rids and version; ``serve.queue_wait_us`` grows by the
    summed admission-to-dequeue wait on the engine's clock, to the us."""
    from repro.obs import metrics, trace

    now = [1.0]
    eng, _ = _score_engine(max_batch=4, queue_depth=8, clock=lambda: now[0])
    waits = metrics.counter("serve.queue_wait_us")
    trace.enable()
    try:
        for rid, t in [(5, 1.0), (6, 1.1), (7, 1.5)]:
            now[0] = t
            assert eng.try_admit(_req(rid))
        now[0] = 2.0
        before = waits.value
        out = eng.flush()
        assert waits.value - before == 1_000_000 + 900_000 + 500_000
        assert eng.flush() == []            # empty queue: no span either
    finally:
        trace.disable()
    assert [r.rid for r in out] == [5, 6, 7]
    recs = [r for r in trace.records()     # no instants, no compiles
            if r.kind == "span" and r.name != trace.COMPILE_SPAN]
    assert not any(r.name.startswith("serve.admit") for r in recs)
    [flush] = [r for r in recs if r.name == "serve.flush"]
    assert flush.depth == 0
    assert flush.attrs == {"rows": 3, "first_rid": 5, "last_rid": 7,
                           "version": 0}
    children = sorted((r for r in recs if r.parent == flush.id),
                      key=lambda r: r.start_ns)
    assert [r.name for r in children] == [
        "serve.pack", "serve.put", "serve.dispatch", "serve.wait",
        "serve.respond"]
    assert all(flush.start_ns <= r.start_ns <= r.end_ns <= flush.end_ns
               for r in children)
    assert all(a.end_ns <= b.start_ns for a, b in zip(children, children[1:]))
    assert len(recs) == 1 + len(children)


def test_score_engine_swap_model_atomic_versioning():
    eng, w = _score_engine("svm", d=24)
    assert eng.model.version == 0
    snap = eng.swap_model(np.zeros(24, np.float32))
    assert isinstance(snap, ModelSnapshot) and snap.version == 1
    assert eng.model is snap
    assert eng.try_admit(_req(7))
    (resp,) = eng.flush()
    assert resp.model_version == 1 and resp.score == 0.0
    with pytest.raises(ValueError, match="shape mismatch"):
        eng.swap_model(np.zeros(23, np.float32))


@given(data=st.data())
@settings(max_examples=20, deadline=None)
def test_score_engine_admission_properties(data):
    """Arbitrary admit/flush/maybe_flush/swap interleavings: the bounded
    queue never overfills, responses are FIFO with no loss or dup, and
    a final drain always terminates the backlog."""
    eng, w = _score_engine("lr", max_batch=3, queue_depth=5)
    n = data.draw(st.integers(1, 12), label="n_requests")
    ops = data.draw(st.lists(
        st.sampled_from(["admit", "flush", "maybe", "swap"]),
        max_size=20), label="ops")
    pending = [_req(i) for i in range(n)]
    admitted, responses, version = [], [], 0
    for op in ops:
        assert len(eng) <= eng.queue_depth
        if op == "admit" and pending:
            full = len(eng) >= eng.queue_depth
            ok = eng.try_admit(pending[0])
            assert ok == (not full)     # rejects exactly when full
            if ok:
                admitted.append(pending.pop(0))
        elif op == "flush":
            responses.extend(eng.flush())
        elif op == "maybe":
            responses.extend(eng.maybe_flush())
        else:
            version += 1
            eng.swap_model(np.roll(w, version))
    responses.extend(eng.drain())
    assert len(eng) == 0
    # FIFO, no loss, no duplication — and every response's stamped
    # version is one that was actually published
    assert [r.rid for r in responses] == [r.rid for r in admitted]
    assert all(0 <= r.model_version <= version for r in responses)


# ---------------------------------------------------------------------------
# Hot-swap chaos: concurrent swap_model vs a steady scoring stream
# ---------------------------------------------------------------------------


def test_score_engine_hot_swap_chaos():
    """Score a steady request stream while swap_model fires from another
    thread: every response must match the oracle under exactly the ONE
    snapshot version it is stamped with (never a torn mix), and the
    stream keeps flowing (throughput never drops to zero)."""
    d, k, n_swaps = 32, 4, 25
    rng = np.random.default_rng(11)
    models = {v: rng.normal(0, 0.5, d).astype(np.float32)
              for v in range(n_swaps + 1)}
    eng = GLMScoreEngine("svm", models[0], ell_width=k, max_batch=8,
                         queue_depth=32)

    stop = threading.Event()

    def swapper():
        for v in range(1, n_swaps + 1):
            eng.swap_model(models[v])
            time.sleep(0.002)
        stop.set()

    th = threading.Thread(target=swapper)
    responses, reqs, rid = [], {}, 0
    th.start()
    try:
        # keep admitting + flushing while the swapper is alive, then once
        # more after it finished so the final version is observed too
        while not stop.is_set() or rid == 0:
            for _ in range(8):
                r = _req(rid, d=d, k=k)
                reqs[rid] = r
                assert eng.try_admit(r)
                rid += 1
            batch = eng.flush()
            assert batch, "throughput dropped to zero mid-stream"
            responses.extend(batch)
    finally:
        th.join()
    # one more round after the swapper finished: the final published
    # model must actually serve
    for _ in range(8):
        r = _req(rid, d=d, k=k)
        reqs[rid] = r
        assert eng.try_admit(r)
        rid += 1
    responses.extend(eng.drain())
    assert [r.rid for r in responses] == list(range(rid))  # nothing lost

    mismatched = []
    for resp in responses:
        w_v = models[resp.model_version]        # the ONE stamped snapshot
        want = _oracle("svm", w_v, reqs[resp.rid])
        if resp.score != pytest.approx(want, abs=1e-4):
            mismatched.append((resp.rid, resp.model_version))
    assert not mismatched, f"responses inconsistent w/ snapshot: {mismatched}"
    versions = {r.model_version for r in responses}
    assert len(versions) >= 2, "swaps never interleaved with scoring"
    assert max(versions) == n_swaps     # the last published model served

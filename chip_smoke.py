"""Bring-up smoke run: the GLM train / score / live path on one TPU chip.

    python chip_smoke.py

One process, four phases, the paper's Table-3 sizes, data from a fixed
seed.  Each phase asks for the compiled Pallas kernels (``pallas-tpu``)
and checks them against the XLA path or the ``reference`` oracle:

A. dense training, covtype 581,012 x 54, LR, through ``study.Runner``:
   full-batch ``SyncSGD`` (``glm_grad``) against the XLA epoch, and
   ``SyncSGD(batch=8)`` (``glm_sgd``) against ``reference`` on n cut to
   581,008 so that 8 divides it;
B. sparse training, w8a 64,700 x 300 (ELL width 69): full-batch
   ``glm_sparse`` against the XLA sparse epoch, and ``glm_sgd_sparse``
   with micro-batch 8 against ``reference`` on n cut to 64,696;
C. scoring: ``GLMScoreEngine`` with its default backend answers 256 w8a
   rows; every score must equal ``sigmoid(sum(values * w[indices]))``,
   and ``swap_model`` must bump the served ``model_version``;
D. live: a ``LiveLearner`` on a w8a-width stream publishes into C's
   engine while its holdout loss falls.

Each phase prints one line (platform, device kind, compile seconds, the
backend every kernel family resolved to, seconds per epoch or batch,
final loss and largest difference from the reference).  The run fails
at once without a TPU, and fails if a phase that asked for
``pallas-tpu`` moved a ``kernel.backend.*.pallas-interpret`` or
``kernel.fallback.*`` counter.  The last line of a passing run is one
JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
EPOCHS = 4
TASK = "lr"
KERNEL = "pallas-tpu"
#: kernel losses and scores against their twins
LOSS_RTOL = 1e-4
SCORE_ATOL = 1e-5
#: Table-3 datasets; DatasetSpec(max_n=None) is the full size
COVTYPE_N = None
W8A_N = None
#: scoring requests and live-learner steps
REQUESTS = 256
LIVE_STEPS = 16


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_diff(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


class Phase:
    """One phase: its compile seconds, and the kernel backends it used.

    Compile seconds sum JAX's backend-compile events (a persistent-cache
    hit counts only its retrieval).  Every backend resolution moves a
    ``kernel.backend.<family>.<backend>`` counter and every auto-routing
    around a better backend a ``kernel.fallback.*`` one; a phase that
    asked for ``pallas-tpu`` may move neither the interpreter nor a
    fallback.
    """

    compile_s = 0.0

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        from repro.obs import metrics

        self._counters = dict(metrics.snapshot()["counters"])
        self._compile0 = Phase.compile_s
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def report(self, **fields) -> None:
        import jax

        from repro.obs import metrics

        moved = {k: v - self._counters.get(k, 0)
                 for k, v in metrics.snapshot()["counters"].items()
                 if v != self._counters.get(k, 0)}
        backends: dict[str, list[str]] = {}
        for k in sorted(moved):
            if k.startswith("kernel.backend."):
                family, backend = k[len("kernel.backend."):].split(".", 1)
                backends.setdefault(family, []).append(backend)
        bad = sorted(k for k in moved
                     if k.startswith("kernel.fallback.")
                     or (k.endswith(".pallas-interpret")
                         and KERNEL != "pallas-interpret"))
        dev = jax.devices()[0]
        line = {"platform": dev.platform, "device_kind": dev.device_kind,
                "compile_s": Phase.compile_s - self._compile0,
                "wall_s": time.perf_counter() - self._t0,
                "backends": backends, **fields}
        print(f"phase {self.name}: {json.dumps(line)}", flush=True)
        check(not bad, f"phase {self.name} left {KERNEL}: {bad}")
        for family, used in backends.items():
            check(KERNEL in used, f"phase {self.name}: {family} resolved to "
                                  f"{used}, not {KERNEL}")


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        Phase.compile_s += duration


def _train(problem, strategy, *, sparse_data: bool):
    """Losses, final model and steady seconds per epoch of one run."""
    import jax

    from repro.core import sgd

    init, epoch_fn, loss_fn, _ = sgd.make_epoch_fn(
        problem, strategy, sparse_data=sparse_data)
    w = init
    losses = [float(loss_fn(w))]
    times = []
    for _ in range(EPOCHS):
        t0 = time.perf_counter()
        w = jax.block_until_ready(epoch_fn(w))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss_fn(w)))
    return np.asarray(losses), w, float(np.median(times[1:]))


def phase_a() -> None:
    from repro.core import sgd
    from repro.study.runner import Runner
    from repro.study.spec import DatasetSpec, TrialSpec

    runner = Runner(cache_dir=None)
    with Phase("A") as ph:
        full = DatasetSpec("covtype", max_n=COVTYPE_N, seed=SEED)
        n = full.profile().n
        step = 0.5 / n          # full batch: step on the sum gradient
        xla = runner.run_trial(TrialSpec(full, TASK, sgd.SyncSGD(), step,
                                         EPOCHS))
        grad = runner.run_trial(TrialSpec(
            full, TASK, sgd.SyncSGD(kernel_backend=KERNEL), step, EPOCHS))
        cut = DatasetSpec("covtype", max_n=n - n % 8, seed=SEED)
        n_cut = cut.profile().n
        mb = {b: runner.run_trial(TrialSpec(
            cut, TASK, sgd.SyncSGD(batch=8, kernel_backend=b), 0.01, EPOCHS))
            for b in ("reference", KERNEL)}
        diff_grad = rel_diff(grad.losses, xla.losses)
        diff_mb = rel_diff(mb[KERNEL].losses, mb["reference"].losses)
        ph.report(n=n, n_cut_for_batch8=n_cut,
                  s_per_epoch={"xla": float(np.median(xla.epoch_times)),
                               "glm_grad": float(np.median(grad.epoch_times)),
                               "reference_b8": float(np.median(
                                   mb["reference"].epoch_times)),
                               "glm_sgd_b8": float(np.median(
                                   mb[KERNEL].epoch_times))},
                  final_loss={"glm_grad": float(grad.losses[-1]),
                              "glm_sgd_b8": float(mb[KERNEL].losses[-1])},
                  max_rel_diff={"glm_grad_vs_xla": diff_grad,
                                "glm_sgd_vs_reference": diff_mb})
    check(diff_grad <= LOSS_RTOL, f"glm_grad losses off by {diff_grad}")
    check(diff_mb <= LOSS_RTOL, f"glm_sgd losses off by {diff_mb}")
    check(grad.losses[-1] < grad.losses[0], "covtype loss did not fall")


def phase_b():
    """Returns the w8a dataset and the model glm_sparse trained on it."""
    import jax.numpy as jnp

    from repro.core import sgd, sparse
    from repro.data import synthetic

    with Phase("B") as ph:
        ds = synthetic.paper_dataset("w8a", seed=SEED, max_n=W8A_N)
        ell, y = ds.ell, jnp.asarray(ds.y)
        n, k = ell.values.shape
        step = 1.0 / n
        problem = (TASK, ell, y, step)
        xla, _, s_xla = _train(problem, sgd.SyncSGD(), sparse_data=True)
        full, w_b, s_full = _train(
            problem, sgd.SyncSGD(kernel_backend=KERNEL), sparse_data=True)
        n_cut = n - n % 8
        cut = (TASK, sparse.ELLMatrix(ell.values[:n_cut],
                                      ell.indices[:n_cut], ell.d),
               y[:n_cut], 0.05)
        ref, _, s_ref = _train(cut, sgd.SyncSGD(batch=8,
                                                kernel_backend="reference"),
                               sparse_data=True)
        mb, _, s_mb = _train(cut, sgd.SyncSGD(batch=8, kernel_backend=KERNEL),
                             sparse_data=True)
        diff_full = rel_diff(full, xla)
        diff_mb = rel_diff(mb, ref)
        ph.report(n=n, d=ell.d, ell_width=k, n_cut_for_batch8=n_cut,
                  s_per_epoch={"xla": s_xla, "glm_sparse": s_full,
                               "reference_b8": s_ref,
                               "glm_sgd_sparse_b8": s_mb},
                  final_loss={"glm_sparse": float(full[-1]),
                              "glm_sgd_sparse_b8": float(mb[-1])},
                  max_rel_diff={"glm_sparse_vs_xla": diff_full,
                                "glm_sgd_sparse_vs_reference": diff_mb})
    check(diff_full <= LOSS_RTOL, f"glm_sparse losses off by {diff_full}")
    check(diff_mb <= LOSS_RTOL, f"glm_sgd_sparse losses off by {diff_mb}")
    check(full[-1] < full[0], "w8a loss did not fall")
    return ds, w_b


def _requests(values, indices, rows):
    from repro.serve.glm import ScoreRequest

    out = []
    for r in rows:
        keep = values[r] != 0           # strip the ELL padding
        out.append(ScoreRequest(int(r), values[r][keep], indices[r][keep]))
    return out


def _expected(w, req) -> float:
    """The oracle: sigmoid(sum(values * w[indices])) in float64."""
    w = np.asarray(w, np.float64)
    m = float(np.dot(np.asarray(req.values, np.float64), w[req.indices]))
    return 1.0 / (1.0 + np.exp(-m))


def _serve(engine, reqs) -> tuple[float, int, float]:
    """Score ``reqs``: (max |score - oracle|, versions seen, s per batch)."""
    for r in reqs:
        check(engine.try_admit(r), f"request {r.rid} refused")
    t0 = time.perf_counter()
    resps = engine.drain()
    batches = -(-len(reqs) // engine.max_batch)
    s_batch = (time.perf_counter() - t0) / batches
    check(sorted(r.rid for r in resps) == sorted(r.rid for r in reqs),
          "engine lost or duplicated requests")
    w = np.asarray(engine.model.w)
    by_rid = {r.rid: r for r in reqs}
    err = max(abs(r.score - _expected(w, by_rid[r.rid])) for r in resps)
    versions = {r.model_version for r in resps}
    check(len(versions) == 1, f"one drain saw versions {versions}")
    return err, versions.pop(), s_batch


def phase_c(ds, w_b):
    """Returns the engine, for phase D to publish into."""
    from repro.serve.glm import GLMScoreEngine

    with Phase("C") as ph:
        values = np.asarray(ds.ell.values)
        indices = np.asarray(ds.ell.indices)
        k = values.shape[1]
        engine = GLMScoreEngine(TASK, w_b, ell_width=k)   # backend=None
        reqs = _requests(values, indices, range(REQUESTS))
        _serve(engine, reqs[:engine.max_batch])           # compiles
        err, v0, s_batch = _serve(engine, reqs)
        engine.swap_model(np.asarray(w_b) * 0.5)
        err_swap, v1, _ = _serve(engine, reqs[:engine.max_batch])
        ph.report(requests=REQUESTS, ell_width=k, batch=engine.max_batch,
                  s_per_batch=s_batch, versions=[v0, v1],
                  max_abs_diff={"score_vs_oracle": err,
                                "after_swap": err_swap})
    check(max(err, err_swap) <= SCORE_ATOL,
          f"scores off by {max(err, err_swap)}")
    check(v1 == v0 + 1, f"swap_model served version {v1} after {v0}")
    return engine


def phase_d(engine) -> None:
    from repro.live import (LiveConfig, LiveLearner, SnapshotPublisher,
                            SyntheticStream)

    with Phase("D") as ph:
        stream = SyntheticStream(n_batch=256, d=engine.model.w.shape[0],
                                 seed=SEED, avg_nnz=11.65,
                                 max_nnz=engine.ell_width)
        learner = LiveLearner(LiveConfig(
            task=TASK, replicas=4, local_batch=8, merge_every=2,
            step_size=0.5, kernel_backend=KERNEL), stream)
        pub = SnapshotPublisher(engine, every_merges=1).attach(learner)
        hold_ell, hold_y = stream.holdout(512)
        v0 = engine.model.version
        loss0 = learner.loss(hold_ell, hold_y)
        learner.step()                                     # compiles
        t0 = time.perf_counter()
        learner.run(LIVE_STEPS - 1)
        s_step = (time.perf_counter() - t0) / (LIVE_STEPS - 1)
        loss1 = learner.loss(hold_ell, hold_y)
        reqs = _requests(np.asarray(hold_ell.values),
                         np.asarray(hold_ell.indices), range(32))
        err, served, _ = _serve(engine, reqs)
        ph.report(steps=learner.steps, merges=learner.merges,
                  s_per_step=s_step, published_versions=[v0, served],
                  staleness=pub.staleness(learner),
                  holdout_loss=[loss0, loss1],
                  max_abs_diff={"score_vs_oracle": err})
    check(loss1 < loss0, f"live holdout loss rose: {loss0} -> {loss1}")
    check(served == v0 + learner.merges,
          f"served version {served}, expected {v0 + learner.merges}")
    check(err <= SCORE_ATOL, f"live scores off by {err}")


def main() -> int:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {backend!r}",
              file=sys.stderr)
        return 1
    from repro.kernels import common
    from repro.roofline import analysis
    from repro.utils import compile_cache

    cache_dir = compile_cache.enable()
    # the twins are fp32 references: keep TPU matmuls from rounding their
    # inputs to bf16, which would compare rounding instead of kernels
    jax.config.update("jax_default_matmul_precision", "highest")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    kind = common.device_kind()
    print(f"chip_smoke: {jax.devices()[0].device_kind} x{len(jax.devices())}"
          f" ({kind}: peaks {analysis.peaks(kind)}), compile cache "
          f"{cache_dir}", flush=True)

    phase_a()
    ds, w_b = phase_b()
    engine = phase_c(ds, w_b)
    phase_d(engine)

    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

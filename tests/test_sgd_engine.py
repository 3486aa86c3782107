"""Parallel-SGD engine: partitioning, replication, and the paper's
qualitative claims as executable assertions."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import glm, sgd
from repro.data import synthetic


def test_partition_chunk_covers_exactly():
    parts = sgd.partition_indices(64, 4, "chunk", rep_k=0)
    assert parts.shape == (4, 16)
    assert sorted(parts.reshape(-1).tolist()) == list(range(64))
    # chunk = contiguous ranges
    assert (np.diff(parts, axis=1) == 1).all()


def test_partition_round_robin_strides():
    parts = sgd.partition_indices(64, 4, "round_robin")
    assert (np.diff(parts, axis=1) == 4).all()
    assert sorted(parts.reshape(-1).tolist()) == list(range(64))


def test_partition_rep_k_halo():
    parts = sgd.partition_indices(64, 4, "chunk", rep_k=3)
    assert parts.shape == (4, 19)
    # halo of replica r = first 3 examples of replica (r+1) % 4
    for r in range(4):
        np.testing.assert_array_equal(
            parts[r, -3:], parts[(r + 1) % 4, :3])


def test_partition_rep_k_exceeds_per_wraps_across_partitions():
    """rep_k > per: the halo wraps past the next partition (cyclic stream)."""
    parts = sgd.partition_indices(16, 4, "chunk", rep_k=6)  # per = 4
    assert parts.shape == (4, 10)
    for r in range(4):
        stream = np.concatenate([parts[(r + 1) % 4, :4], parts[(r + 2) % 4, :4]])
        np.testing.assert_array_equal(parts[r, 4:], stream[:6])
    # indices stay in range even when the halo wraps all the way around
    full = sgd.partition_indices(16, 4, "chunk", rep_k=16)
    assert full.min() >= 0 and full.max() < 16


@pytest.mark.parametrize("n", [64, 66])  # 66: n % replicas != 0 (tail dropped)
def test_round_robin_and_chunk_cover_the_same_examples(n):
    """Access path changes the assignment, never the covered example set."""
    ch = sgd.partition_indices(n, 4, "chunk")
    rr = sgd.partition_indices(n, 4, "round_robin")
    assert ch.shape == rr.shape == (4, n // 4)
    assert sorted(ch.reshape(-1).tolist()) == sorted(rr.reshape(-1).tolist())
    assert sorted(ch.reshape(-1).tolist()) == list(range(4 * (n // 4)))


def test_run_result_never_converging():
    """epochs_to/time_to return None when the target is never reached."""
    res = sgd.RunResult(
        losses=np.asarray([1.0, 0.9, 0.85]),
        epoch_times=np.asarray([0.1, 0.2]),
        strategy="sync", task="lr",
    )
    assert res.epochs_to(0.5) is None
    assert res.time_to(0.5) is None
    # converging at init: zero epochs, zero time
    assert res.epochs_to(1.0) == 0
    assert res.time_to(1.0) == 0.0
    # converging mid-run sums only the epochs actually spent
    assert res.epochs_to(0.9) == 1
    assert res.time_to(0.9) == pytest.approx(0.1)


def test_merge_replicas_mean():
    W = jnp.asarray(np.arange(12, dtype=np.float32).reshape(4, 3))
    M = sgd.merge_replicas(W)
    assert M.shape == W.shape
    np.testing.assert_allclose(M[0], W.mean(0))
    np.testing.assert_allclose(M, jnp.broadcast_to(W.mean(0), W.shape))


@pytest.fixture(scope="module")
def ds():
    return synthetic.make_dense("toy", 512, 16, seed=2)


def test_paper_claim_more_replicas_worse_statistical_efficiency(ds):
    """Paper §5.2.2: 'the more replicas, the lower the statistical
    efficiency' — fewer merges of more-diverged models learn less per epoch."""
    X, y = jnp.asarray(ds.X), jnp.asarray(ds.y)
    prob = glm.GLMProblem("lr", X, y, 5e-3)
    losses = {}
    for r in (2, 16):
        res = sgd.run(prob, sgd.AsyncLocalSGD(replicas=r, local_batch=8), 6)
        losses[r] = res.losses[-1]
    assert losses[16] >= losses[2] * 0.999, losses


def test_paper_claim_rep_k_improves_statistical_efficiency(ds):
    """Paper §5.2.3: k-wise replication extracts more information per pass."""
    X, y = jnp.asarray(ds.X), jnp.asarray(ds.y)
    prob = glm.GLMProblem("lr", X, y, 5e-3)
    res0 = sgd.run(prob, sgd.AsyncLocalSGD(replicas=8, local_batch=4,
                                           rep_k=0), 6)
    resk = sgd.run(prob, sgd.AsyncLocalSGD(replicas=8, local_batch=4,
                                           rep_k=16), 6)
    assert resk.losses[-1] <= res0.losses[-1] * 1.001


def test_sync_engine_kernel_backend_matches_xla_path(ds):
    """SyncSGD routed through the kernel dispatch registry reproduces the
    inline-XLA epoch (full-batch via glm_grad, mini-batch via glm_sgd)."""
    from repro.kernels import common as kcommon

    X, y = jnp.asarray(ds.X[:64]), jnp.asarray(ds.y[:64])
    prob = glm.GLMProblem("lr", X, y, 5e-3)
    for batch in (None, 16):
        base = sgd.run(prob, sgd.SyncSGD(batch=batch), 3, record_time=False)
        for backend in kcommon.available_backends("glm_grad"):
            res = sgd.run(
                prob, sgd.SyncSGD(batch=batch, kernel_backend=backend), 3,
                record_time=False)
            np.testing.assert_allclose(res.losses, base.losses,
                                       rtol=1e-4, atol=1e-4)


def test_sync_engine_kernel_backend_sparse(ds):
    """Sparse SyncSGD routes through the registry: full-batch via the
    glm_sparse sum gradient, mini-batch via the fused glm_sgd_sparse
    epoch — both reproduce the inline-XLA path."""
    from repro.kernels import common as kcommon

    sp = synthetic.make_sparse("sp-engine", 64, 128, 5.0, 8, seed=4)
    prob = ("lr", sp.ell, jnp.asarray(sp.y), 0.05)
    for batch in (None, 16):
        base = sgd.run(prob, sgd.SyncSGD(batch=batch), 3, sparse_data=True,
                       record_time=False)
        for backend in kcommon.available_backends(
                "glm_sparse", info={"sparse": True, "n": 64, "d": 128}):
            res = sgd.run(prob, sgd.SyncSGD(batch=batch,
                                            kernel_backend=backend), 3,
                          sparse_data=True, record_time=False)
            np.testing.assert_allclose(res.losses, base.losses,
                                       rtol=1e-4, atol=1e-4)


def test_access_path_changes_assignment_not_semantics(ds):
    """row-rr vs row-ch assign different examples but both converge."""
    X, y = jnp.asarray(ds.X), jnp.asarray(ds.y)
    prob = glm.GLMProblem("lr", X, y, 5e-3)
    for access in ("chunk", "round_robin"):
        res = sgd.run(prob, sgd.AsyncLocalSGD(replicas=4, local_batch=8,
                                              access=access), 6)
        assert res.losses[-1] < res.losses[0]


def _ell_problem(task, n, d, k, padding, seed=0):
    """ELL rows of ``k`` entries; with ``padding`` each row keeps 1..k of
    them and pads the rest at index 0 with value 0."""
    from repro.core import sparse

    rng = np.random.default_rng(seed)
    values = rng.standard_normal((n, k)).astype(np.float32)
    indices = rng.integers(0, d, (n, k)).astype(np.int32)
    if padding:
        pad = np.arange(k)[None, :] >= rng.integers(1, k + 1, (n, 1))
        values[pad], indices[pad] = 0.0, 0
    y = np.where(rng.random(n) < 0.5, -1.0, 1.0).astype(np.float32)
    ell = sparse.ELLMatrix(jnp.asarray(values), jnp.asarray(indices), d)
    w = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    return (task, ell, jnp.asarray(y), 0.05), w


def _moved(before, prefix):
    from repro.obs import metrics

    after = metrics.snapshot()["counters"]
    return {k: v - before.get(k, 0) for k, v in after.items()
            if k.startswith(prefix) and v != before.get(k, 0)}


@pytest.mark.parametrize("padding", [False, True])
@pytest.mark.parametrize("n", [64, 70])  # 70: not a multiple of the row tile
@pytest.mark.parametrize("task", ["lr", "svm"])
def test_sparse_kernel_loss_matches_gather_loss(task, n, padding):
    """With a kernel backend the sparse loss takes its margins from the
    registry's glm_sparse_margins and equals sparse.loss: the launch's
    filler rows are left out of the sum."""
    from repro.core import sparse
    from repro.kernels.glm_sparse import ops
    from repro.obs import metrics

    assert (n % ops.MARGINS_BLOCK_ROWS == 0) == (n == 64)
    prob, w = _ell_problem(task, n, 128, 9, padding, seed=n)
    before = dict(metrics.snapshot()["counters"])
    _, _, loss_fn, _ = sgd.make_epoch_fn(
        prob, sgd.SyncSGD(batch=8, kernel_backend="pallas-interpret"),
        sparse_data=True)
    got = float(loss_fn(w))
    want = float(sparse.loss(task, prob[1], prob[2], w))
    assert got == pytest.approx(want, rel=1e-6)
    assert _moved(before, "kernel.backend.glm_sparse_margins.") == {
        "kernel.backend.glm_sparse_margins.pallas-interpret": 1}


def test_async_kernel_loss_takes_the_margins_kernel():
    """AsyncLocalSGD passes its kernel backend to the loss too; the loss
    reads replica 0."""
    from repro.core import sparse
    from repro.obs import metrics

    prob, w = _ell_problem("lr", 64, 128, 9, True, seed=3)
    before = dict(metrics.snapshot()["counters"])
    _, _, loss_fn, _ = sgd.make_epoch_fn(
        prob, sgd.AsyncLocalSGD(replicas=4, local_batch=8,
                                kernel_backend="pallas-interpret"),
        sparse_data=True)
    W = jnp.stack([w, 2 * w, -w, 0 * w])
    assert float(loss_fn(W)) == pytest.approx(
        float(sparse.loss("lr", prob[1], prob[2], w)), rel=1e-6)
    assert _moved(before, "kernel.backend.glm_sparse_margins.") == {
        "kernel.backend.glm_sparse_margins.pallas-interpret": 1}


@pytest.mark.parametrize("strategy,d", [
    (sgd.SyncSGD(batch=8), 128),                        # XLA engine
    (sgd.SyncSGD(batch=8, kernel_backend="pallas-interpret"), 4096),
])
def test_sparse_loss_stays_the_gather_without_a_kernel(monkeypatch,
                                                       strategy, d):
    """No kernel backend, or a model too wide for the one-hot: the loss
    is sparse.loss and the margins kernel is never resolved."""
    from repro.core import sparse
    from repro.obs import metrics

    calls = []
    gather_loss = sparse.loss

    def spy(*args):
        calls.append(args[0])
        return gather_loss(*args)

    monkeypatch.setattr(sparse, "loss", spy)
    prob, w = _ell_problem("svm", 16, d, 9, True, seed=5)
    before = dict(metrics.snapshot()["counters"])
    _, _, loss_fn, _ = sgd.make_epoch_fn(prob, strategy, sparse_data=True)
    got = float(loss_fn(w))
    assert calls == ["svm"]
    assert got == pytest.approx(float(gather_loss("svm", prob[1], prob[2], w)),
                                rel=1e-6)
    assert _moved(before, "kernel.backend.glm_sparse_margins.") == {}


def _csr_problem(task, n, d, seed=0, widest=300):
    """Heavy-tailed CSR rows (3..``widest`` nonzeros, rows 0 and 1
    sharing features) as the engine takes them, and the same rows as
    ELL."""
    from repro.core import sparse

    rng = np.random.default_rng(seed)
    widths = np.clip(rng.lognormal(3.5, 1.2, n), 3, widest).astype(np.int64)
    rows = [rng.choice(d, k, replace=False).astype(np.int32) for k in widths]
    rows[1][:3] = rows[0][:3]
    vals = [rng.standard_normal(k).astype(np.float32) for k in widths]
    host = sparse.from_csr_parts(rows, vals, d)
    y = jnp.asarray(np.where(rng.random(n) < 0.5, -1.0, 1.0), jnp.float32)
    return (task, host.to_rows(), y, 0.3), host.to_ell()


@pytest.mark.parametrize("task", ["lr", "svm"])
def test_csr_engine_matches_the_ell_oracle(task):
    """CSR rows too wide for the one-hot train on glm_sgd_csr with the
    loss on glm_csr_margins, and give the plain ELL oracle's models and
    losses over 3 epochs; no kernel falls back."""
    from repro.core import sparse
    from repro.obs import metrics

    prob, ell = _csr_problem(task, 64, 5_000, seed=7)
    before = dict(metrics.snapshot()["counters"])
    init, epoch_fn, loss_fn, _ = sgd.make_epoch_fn(
        prob, sgd.SyncSGD(batch=8, kernel_backend="pallas-interpret"),
        sparse_data=True)
    w, want = init, init
    for _ in range(3):
        w = epoch_fn(w)
        want = sparse.minibatch_epoch(task, want, ell, prob[2], 0.3, 8)
        np.testing.assert_allclose(w, want, rtol=1e-5, atol=1e-6)
        assert float(loss_fn(w)) == pytest.approx(
            float(sparse.loss(task, ell, prob[2], want)), rel=1e-5)
    assert float(jnp.linalg.norm(w)) > 0.1
    assert _moved(before, "kernel.backend.") == {
        "kernel.backend.glm_sgd_csr.pallas-interpret": 1,
        "kernel.backend.glm_csr_margins.pallas-interpret": 1}
    assert _moved(before, "kernel.fallback.") == {}


def test_csr_rows_that_fit_the_onehot_take_the_ell_kernels():
    """Where the one-hot fits, CSR rows are padded to ELL and take the
    one-hot families, with the ELL losses."""
    from repro.core import sparse
    from repro.obs import metrics

    prob, ell = _csr_problem("lr", 16, 200, seed=2, widest=20)
    before = dict(metrics.snapshot()["counters"])
    init, epoch_fn, loss_fn, _ = sgd.make_epoch_fn(
        prob, sgd.SyncSGD(batch=8, kernel_backend="pallas-interpret"),
        sparse_data=True)
    w = epoch_fn(init)
    want = sparse.minibatch_epoch("lr", init, ell, prob[2], 0.3, 8)
    np.testing.assert_allclose(w, want, rtol=1e-5, atol=1e-6)
    assert float(loss_fn(w)) == pytest.approx(
        float(sparse.loss("lr", ell, prob[2], want)), rel=1e-5)
    assert _moved(before, "kernel.backend.") == {
        "kernel.backend.glm_sgd_sparse.pallas-interpret": 1,
        "kernel.backend.glm_sparse_margins.pallas-interpret": 1}


def test_w8a_shaped_ell_still_takes_the_onehot_kernels():
    """ELL rows at w8a's width (d 300, widest row 114) resolve
    glm_sgd_sparse and glm_sparse_margins as before the CSR kernels came,
    and nothing falls back."""
    from repro.obs import metrics

    prob, w = _ell_problem("lr", 64, 300, 114, True, seed=8)
    before = dict(metrics.snapshot()["counters"])
    _, epoch_fn, loss_fn, _ = sgd.make_epoch_fn(
        prob, sgd.SyncSGD(batch=8, kernel_backend="pallas-interpret"),
        sparse_data=True)
    float(loss_fn(epoch_fn(w)))
    assert _moved(before, "kernel.backend.") == {
        "kernel.backend.glm_sgd_sparse.pallas-interpret": 1,
        "kernel.backend.glm_sparse_margins.pallas-interpret": 1}
    assert _moved(before, "kernel.fallback.") == {}


@pytest.mark.parametrize("strategy", [
    sgd.SyncSGD(kernel_backend="pallas-interpret"),
    sgd.AsyncLocalSGD(replicas=4, local_batch=8,
                      kernel_backend="pallas-interpret"),
])
def test_csr_rows_too_wide_refuse_strategies_without_a_csr_kernel(strategy):
    prob, _ = _csr_problem("lr", 64, 5_000, seed=1)
    with pytest.raises(ValueError, match="glm_sgd_csr"):
        sgd.make_epoch_fn(prob, strategy, sparse_data=True)


def test_csr_rows_run_through_sgd_run():
    """``sgd.run`` takes the CSR problem as it is; the loss falls."""
    prob, _ = _csr_problem("lr", 64, 5_000, seed=4)
    res = sgd.run(prob, sgd.SyncSGD(batch=8, kernel_backend="pallas-interpret"),
                  3, sparse_data=True, record_time=False)
    assert res.losses[-1] < res.losses[0]

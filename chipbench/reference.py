"""Plain reference of the GLM path: logistic regression by mini-batch SGD.

Written from the definitions, independent of the program under test (it
imports nothing of it):

    loss(w)  = sum_i log(1 + exp(-y_i x_i . w))
    step     w <- w - (alpha / B) * sum_{i in batch} x_i * (-y_i sigma(-y_i x_i . w))
    score(x) = sigma(x . w)

Rows are dense: sparse rows are densified first, which changes no sum.
Every product goes through :func:`_mul`, in one of four precisions:

* ``"f64"``  float64, the reference proper (run it on the CPU with
  ``float64()``);
* ``"f32"``  float32;
* ``"high"`` float32 whose products are those of a TPU matmul at
  ``precision=HIGH``: each factor split into a bfloat16 high part and a
  bfloat16 low part, and the three larger cross products summed (the
  low-times-low one is dropped).  This is the control: the reference one
  step below the configuration's float32 at ``highest``.  It is written
  out, not asked of the compiler, and the rounding to bfloat16 is done on
  the bits (:func:`_bf16`): the TPU compiler, which may keep excess
  precision, drops a float32-bfloat16-float32 pair of casts, and the
  control then reads as float32;
* ``"bf16"`` float32 whose products are of bfloat16-rounded factors, one
  pass, as a TPU matmul at its default precision.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("f64", "f32", "high", "bf16")


@contextlib.contextmanager
def float64():
    """Run the ``"f64"`` reference: 64-bit types on, arrays on the CPU."""
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        with jax.default_device(jax.devices("cpu")[0]):
            yield
    finally:
        jax.config.update("jax_enable_x64", before)


def _dtype(mode: str):
    if mode not in MODES:
        raise ValueError(f"mode {mode!r}, not one of {MODES}")
    return jnp.float64 if mode == "f64" else jnp.float32


def _bf16(x):
    """float32 ``x`` rounded to bfloat16 (to nearest, ties to even), kept
    in float32, by integer arithmetic on its bits."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _mul(a, b, mode: str):
    if mode in ("f64", "f32"):
        return a * b
    ah, al = _split(a)
    bh, bl = _split(b)
    if mode == "bf16":
        return ah * bh
    return ah * bh + (ah * bl + al * bh)


def _margins(X, w, mode):
    return jnp.sum(_mul(X, w[None, :], mode), axis=1)


def _sigmoid(m):
    return 1.0 / (1.0 + jnp.exp(-m))


@functools.partial(jax.jit, static_argnames=("mode",))
def _loss(X, y, w, mode):
    m = y * _margins(X, w, mode)
    return jnp.sum(jnp.maximum(-m, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(m))))


def _minibatch(w, Xb, yb, scale, mode):
    """Scan of SGD steps over micro-batches ``Xb [S, B, d]``."""
    def body(w, xy):
        Xk, yk = xy
        pull = -yk * _sigmoid(-yk * _margins(Xk, w, mode))
        g = jnp.sum(_mul(Xk, pull[:, None], mode), axis=0)
        return w - scale * g, None

    return jax.lax.scan(body, w, (Xb, yb))[0]


@functools.partial(jax.jit, static_argnames=("batch", "mode"))
def _epoch(w, X, y, step, batch, mode):
    n, d = X.shape
    return _minibatch(w, X.reshape(n // batch, batch, d),
                      y.reshape(n // batch, batch), step / batch, mode)


def sgd(X: np.ndarray, y: np.ndarray, *, step: float, batch: int,
        epochs: int, mode: str) -> tuple[list[np.ndarray], list[float]]:
    """``epochs`` epochs of mini-batch SGD from ``w = 0`` over all rows in
    order.  Returns the model and the loss after each epoch, in float64."""
    dt = _dtype(mode)
    X = jnp.asarray(X, dt)
    y = jnp.asarray(y, dt)
    w = jnp.zeros(X.shape[1], dt)
    ws, losses = [], []
    for _ in range(epochs):
        w = _epoch(w, X, y, jnp.asarray(step, dt), batch, mode)
        ws.append(np.asarray(w, np.float64))
        losses.append(float(_loss(X, y, w, mode)))
    return ws, losses


@functools.partial(jax.jit, static_argnames=("local_batch", "merge_every",
                                             "mode"))
def _live(chunks_X, chunks_y, order, step, local_batch, merge_every, mode,
          replicas_w):
    R, d = replicas_w.shape

    def one_step(carry, s):
        W, anchor = carry
        X = chunks_X[order[s]]
        y = chunks_y[order[s]]
        per = X.shape[0] // R
        Xp = X[:R * per].reshape(R, per // local_batch, local_batch, d)
        yp = y[:R * per].reshape(R, per // local_batch, local_batch)
        W = jax.vmap(lambda w, Xr, yr: _minibatch(
            w, Xr, yr, step / local_batch, mode))(W, Xp, yp)
        merge = (s + 1) % merge_every == 0
        mean = jnp.mean(W, axis=0)
        W = jnp.where(merge, jnp.broadcast_to(mean, W.shape), W)
        anchor = jnp.where(merge, mean, anchor)
        return (W, anchor), anchor

    (_, _), anchors = jax.lax.scan(
        one_step, (replicas_w, jnp.zeros(d, replicas_w.dtype)),
        jnp.arange(order.shape[0]))
    return anchors


def live(chunks_X: np.ndarray, chunks_y: np.ndarray, order: np.ndarray, *,
         replicas: int, local_batch: int, merge_every: int, step: float,
         mode: str) -> np.ndarray:
    """Replica-merge SGD over a stream: step ``s`` trains on chunk
    ``order[s]``; each replica takes its contiguous share of the chunk and
    runs mini-batch SGD on it; every ``merge_every`` steps the replicas are
    averaged.  Returns the merged models ``[merges + 1, d]`` in float64,
    row 0 the zero model that precedes the first merge."""
    dt = _dtype(mode)
    anchors = _live(jnp.asarray(chunks_X, dt), jnp.asarray(chunks_y, dt),
                    jnp.asarray(order, jnp.int32), jnp.asarray(step, dt),
                    local_batch, merge_every, mode,
                    jnp.zeros((replicas, chunks_X.shape[2]), dt))
    anchors = np.asarray(anchors, np.float64)
    merged = anchors[merge_every - 1::merge_every]
    return np.concatenate([np.zeros((1, anchors.shape[1])), merged])


@functools.partial(jax.jit, static_argnames=("mode",))
def _scores(values, indices, models, versions, mode):
    wg = models[versions[:, None], indices]
    return _sigmoid(jnp.sum(_mul(values, wg, mode), axis=1))


def scores(values: np.ndarray, indices: np.ndarray, models: np.ndarray,
           versions: np.ndarray, *, mode: str,
           block: int = 65536) -> np.ndarray:
    """``sigma(x_i . w)`` of padded ELL rows, row ``i`` scored by the model
    ``models[versions[i]]``; in blocks of rows, so that it fits."""
    dt = _dtype(mode)
    models = jnp.asarray(models, dt)
    out = []
    for s in range(0, len(values), block):
        out.append(np.asarray(_scores(
            jnp.asarray(values[s:s + block], dt),
            jnp.asarray(indices[s:s + block], jnp.int32), models,
            jnp.asarray(versions[s:s + block], jnp.int32), mode), np.float64))
    return np.concatenate(out) if out else np.zeros(0)

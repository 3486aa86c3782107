"""CSR sparse SGD epoch and margins Pallas kernels — gather and scatter
by index, for models too wide for the one-hot kernels.

``glm_sgd_sparse`` and ``glm_sparse`` turn the gather and scatter into
products with a one-hot over the whole feature axis, which caps them at
about a thousand features.  These kernels go by index instead.  The
model lives in VMEM for the whole launch, lane-dense as ``[d_pad/1024,
8, 128]`` fp32 (feature ``j`` is lane ``j % 128`` of sublane
``(j // 128) % 8`` of tile ``j // 1024``).  The rows are CSR: their
extents (``offsets [n+1]``) come in by scalar prefetch, and their
nonzeros stay in HBM and are DMA'd into SMEM a chunk of ``CHUNK`` slots
at a time, so the work follows the nonzeros the rows hold, not ``d`` and
not the widest row.  Per nonzero ``(j, v)``:

    gather :  acc  += where(lane_id == j % 1024, w[j // 1024] * v, 0)
    scatter:  w[j // 1024] += where(lane_id == j % 1024, c * v, 0)

where ``lane_id`` numbers the 1,024 features of an ``[8, 128]`` tile; a
row's margin is the sum of its ``acc`` tile.  Duplicate features add,
in the gather and in the scatter alike.

The epoch (``glm_sgd_csr``) is one launch of ``n / MB`` dependent grid
steps with Algorithm 1's semantics: all ``MB`` margins of a micro-batch
from the pre-step model, then one update ``w -= (alpha / MB) * sum_i
pull_i x_i``.  The margins launch (``glm_csr_margins``) is a
row-parallel grid of ``MARGIN_ROWS``-row blocks with the same gather.

Slots past the last row's end (the padding up to a whole chunk) are
never walked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

#: slots of indices and of values one DMA brings into SMEM
CHUNK = 1024
#: nonzeros a walk takes in one loop iteration (the rest one at a time)
UNROLL = 8
#: features in one ``[8, 128]`` fp32 tile of the model
TILE = common.SUBLANE * common.LANE
#: rows in one block of the margins launch
MARGIN_ROWS = 128
#: VMEM the epoch may use: the model (5.4 MB at news20's width) and the
#: pipeline's small blocks
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _pull(task, margins, y):
    if task == "lr":
        return -y * jax.nn.sigmoid(-margins)
    return -y * (margins < 1.0).astype(margins.dtype)


def _tile_ids():
    """``[8, 128]`` int32: the feature of each element, within its tile."""
    shape = (common.SUBLANE, common.LANE)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * common.LANE
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


class _Slots:
    """The nonzeros in HBM, one chunk of them at a time in SMEM.

    ``cur`` holds the chunk the buffers hold, so walks that stay inside
    one chunk DMA it once.
    """

    def __init__(self, idx_hbm, val_hbm, idx_buf, val_buf, cur, sem):
        self.idx_hbm, self.val_hbm = idx_hbm, val_hbm
        self.idx_buf, self.val_buf = idx_buf, val_buf
        self.cur, self.sem = cur, sem

    def _load(self, c):
        @pl.when(self.cur[0] != c)
        def _():
            src = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
            ci = pltpu.make_async_copy(self.idx_hbm.at[src], self.idx_buf,
                                       self.sem.at[0])
            cv = pltpu.make_async_copy(self.val_hbm.at[src], self.val_buf,
                                       self.sem.at[1])
            ci.start()
            cv.start()
            ci.wait()
            cv.wait()
            self.cur[0] = c

    def fold(self, lo, hi, body, carry):
        """``body(j, v, carry)`` over the slots ``[lo, hi)`` in order."""
        def one(t, cr):
            return body(self.idx_buf[t], self.val_buf[t], cr)

        def group(t0):
            def run(g, cr):
                for u in range(UNROLL):
                    cr = one(t0 + g * UNROLL + u, cr)
                return cr
            return run

        def chunk(c, carry):
            self._load(c)
            base = c * CHUNK
            a = jnp.maximum(lo, base) - base
            b = jnp.minimum(hi, base + CHUNK) - base
            groups = (b - a) // UNROLL
            carry = jax.lax.fori_loop(0, groups, group(a), carry)
            return jax.lax.fori_loop(a + groups * UNROLL, b, one, carry)

        return jax.lax.fori_loop(lo // CHUNK, (hi + CHUNK - 1) // CHUNK,
                                 chunk, carry)


def _spread(x):
    """``[1, 1]`` to ``[8, 128]``: Mosaic broadcasts over lanes and over
    sublanes, not both in one op (the adds keep JAX from fusing them)."""
    row = x + jnp.zeros((1, common.LANE), x.dtype)
    return row + jnp.zeros((common.SUBLANE, common.LANE), x.dtype)


def _margin(slots, w_ref, ids, lo, hi):
    """``[1, 1]``: the row ``[lo, hi)``'s ``x . w``."""
    def gather(j, v, acc):
        hit = ids == j % TILE
        return acc + jnp.where(hit, w_ref[j // TILE] * v, 0.0)

    acc = slots.fold(lo, hi, gather, jnp.zeros(ids.shape, jnp.float32))
    return jnp.sum(jnp.sum(acc, axis=1, keepdims=True), axis=0, keepdims=True)


def _epoch_kernel(task, scale, micro_batch, off_ref, y_ref, idx_hbm, val_hbm,
                  w0_hbm, out_hbm, w_s, idx_buf, val_buf, cur, sem):
    k = pl.program_id(0)
    slots = _Slots(idx_hbm, val_hbm, idx_buf, val_buf, cur, sem)

    @pl.when(k == 0)
    def _():
        cp = pltpu.make_async_copy(w0_hbm, w_s, sem.at[2])
        cp.start()
        cp.wait()
        cur[0] = -1

    ids = _tile_ids()
    base = k * micro_batch
    # every margin from the pre-step model ...
    coefs = []
    for i in range(micro_batch):
        m = _margin(slots, w_s, ids, off_ref[base + i], off_ref[base + i + 1])
        y = y_ref[i:i + 1, :]                                   # [1, 1]
        coefs.append(_spread(-scale * _pull(task, y * m, y)))

    # ... then one update
    def scatter(j, v, c):
        r = j // TILE
        w_s[r] = w_s[r] + jnp.where(ids == j % TILE, c * v, 0.0)
        return c

    for i in range(micro_batch):
        slots.fold(off_ref[base + i], off_ref[base + i + 1], scatter, coefs[i])

    @pl.when(k == pl.num_programs(0) - 1)
    def _():
        cp = pltpu.make_async_copy(w_s, out_hbm, sem.at[2])
        cp.start()
        cp.wait()


def _scratch(w_shape=None):
    shapes = [pltpu.SMEM((CHUNK,), jnp.int32), pltpu.SMEM((CHUNK,), jnp.float32),
              pltpu.SMEM((1,), jnp.int32), pltpu.SemaphoreType.DMA((3,))]
    if w_shape is not None:
        shapes.insert(0, pltpu.VMEM(w_shape, jnp.float32))
    return shapes


def csr_sgd_pallas(
    task: str,
    w0: jax.Array,       # [d_pad / 1024, 8, 128]
    offsets: jax.Array,  # [N + 1] int32
    indices: jax.Array,  # [S] int32, S a multiple of CHUNK
    values: jax.Array,   # [S]
    y: jax.Array,        # [N, 1]
    *,
    step: float,
    micro_batch: int,
    interpret: bool,
) -> jax.Array:
    n = y.shape[0]
    assert n % micro_batch == 0, (n, micro_batch)
    assert indices.shape[0] % CHUNK == 0, indices.shape
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    body = functools.partial(_epoch_kernel, task, step / micro_batch,
                             micro_batch)
    return pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // micro_batch,),
            in_specs=[pl.BlockSpec((micro_batch, 1), lambda k, off: (k, 0)),
                      any_, any_, any_],
            out_specs=any_,
            scratch_shapes=_scratch(w0.shape),
        ),
        out_shape=jax.ShapeDtypeStruct(w0.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),   # sequential: state carried
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="glm_sgd_csr",
    )(offsets, y, indices, values, w0)


def _margins_kernel(off_ref, idx_hbm, val_hbm, w_ref, m_ref, idx_buf, val_buf,
                    cur, sem):
    cur[0] = -1
    slots = _Slots(idx_hbm, val_hbm, idx_buf, val_buf, cur, sem)
    ids = _tile_ids()
    base = pl.program_id(0) * MARGIN_ROWS
    rows = jax.lax.broadcasted_iota(jnp.int32, (MARGIN_ROWS, 1), 0)

    def row(i, out):
        m = _margin(slots, w_ref, ids, off_ref[base + i],
                    off_ref[base + i + 1])
        return jnp.where(rows == i, m, out)

    m_ref[...] = jax.lax.fori_loop(
        0, MARGIN_ROWS, row, jnp.zeros((MARGIN_ROWS, 1), jnp.float32))


def csr_margins_pallas(
    w: jax.Array,        # [d_pad / 1024, 8, 128]
    offsets: jax.Array,  # [N_pad + 1] int32, N_pad a multiple of MARGIN_ROWS
    indices: jax.Array,  # [S] int32, S a multiple of CHUNK
    values: jax.Array,   # [S]
    *,
    interpret: bool,
) -> jax.Array:
    """``[N_pad, 1]`` margins ``x_i . w``; each grid step its own rows."""
    n_pad = offsets.shape[0] - 1
    assert n_pad % MARGIN_ROWS == 0 and indices.shape[0] % CHUNK == 0
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _margins_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_pad // MARGIN_ROWS,),
            in_specs=[any_, any_,
                      pl.BlockSpec(w.shape, lambda i, off: (0, 0, 0))],
            out_specs=pl.BlockSpec((MARGIN_ROWS, 1), lambda i, off: (i, 0)),
            scratch_shapes=_scratch(),
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="glm_csr_margins",
    )(offsets, indices, values, w)

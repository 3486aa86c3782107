"""Blocked (flash) attention Pallas kernel for the LM substrate.

Online-softmax attention tiled for VMEM: a [TQ, hd] query tile stays
resident while [TK, hd] key/value tiles stream through; running max /
normalizer / accumulator live in VMEM scratch.  Supports causal and
sliding-window masking; fully-masked k-tiles are skipped (no MXU work),
which makes causal attention ~2x and SWA ~S/window cheaper — the structural
optimization the roofline hillclimb for prefill shapes relies on.

Grid: (batch*heads, Sq/TQ, Sk/TK) with the k axis innermost ("arbitrary"
semantics: the scratch carries softmax state across k steps).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(
    causal, window, scale, block_q, block_k, seq_q, seq_k,
    q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s,
):
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    # global positions; query ends aligned to key ends (decode: seq_q < seq_k)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    ) + (seq_k - seq_q)
    k_pos = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    # tile-level visibility: skip fully-masked tiles entirely
    q_hi = iq * block_q + block_q - 1 + (seq_k - seq_q)
    k_lo = jk * block_k
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_lo <= q_hi)
    if window is not None:
        q_lo = iq * block_q + (seq_k - seq_q)
        k_hi = jk * block_k + block_k - 1
        run = jnp.logical_and(run, k_hi > q_lo - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                  # [TQ, hd]
        k = k_ref[0]                  # [TK, hd]
        v = v_ref[0]                  # [TK, hd]
        s = jax.lax.dot_general(      # q @ k^T
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                     # [TQ, TK]
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_s[...]             # [TQ, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)        # [TQ, TK]
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_s[...] = m_new
        acc[...] = acc[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )

    @pl.when(jk == nk - 1)
    def _():
        l = l_s[...]
        o_ref[0] = (acc[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # [BH, Sq, hd]
    k: jax.Array,  # [BH, Sk, hd]
    v: jax.Array,  # [BH, Sk, hd]
    *,
    causal: bool,
    window: int | None,
    scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> jax.Array:
    bh, sq, hd = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    grid = (bh, sq // block_q, sk // block_k)
    body = functools.partial(
        _kernel, causal, window, scale, block_q, block_k, sq, sk
    )
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),   # output accumulator
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running normalizer
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attn",
    )(q, k, v)

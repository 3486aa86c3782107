"""Public wrapper for blocked attention (GQA-aware) — registry-dispatched.

Registered flavors receive the raw GQA tensors and broadcast kv heads
inside their jitted bodies (so the repeat fuses into the compiled
computation).  The Pallas flavors carry a head-dim sublane constraint;
odd head dims auto-route to the dense ``reference`` oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.flash_attn import kernel as K
from repro.kernels.flash_attn import ref as R


def _seq_blocks_ok(info: dict) -> bool:
    """Pallas needs seq extents blockable: pinned blocks must divide the
    sequence; unpinned sequences must be sublane-aligned so ``pick_block``
    can find an aligned block (it raises on prime/odd extents now instead
    of silently returning a misaligned one)."""
    for s_key, b_key in (("seq_q", "block_q"), ("seq_k", "block_k")):
        s, b = info.get(s_key), info.get(b_key)
        if s is None:
            continue
        if b is not None:
            if s % b != 0:
                return False
        elif s % common.SUBLANE != 0:
            return False
    return True


_PALLAS_CAPS = common.Caps(head_dim_multiple=common.SUBLANE,
                           check=_seq_blocks_ok)


def _gqa_broadcast(q, k, v):
    hq, hkv = q.shape[1], k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    if hkv != hq:  # GQA: broadcast kv heads to query groups
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return k, v


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def _pallas(q, k, v, *, causal, window, block_q, block_k, interpret):
    k, v = _gqa_broadcast(q, k, v)
    b, h, sq, hd = q.shape
    sk = k.shape[2]
    scale = 1.0 / (hd ** 0.5)
    bq = block_q or common.pick_block(sq, 128, 8)
    bk = block_k or common.pick_block(sk, 128, 8)
    out = K.flash_attention_pallas(
        q.reshape(b * h, sq, hd),
        k.reshape(b * h, sk, hd),
        v.reshape(b * h, sk, hd),
        causal=causal, window=window, scale=scale,
        block_q=bq, block_k=bk, interpret=interpret,
    )
    return out.reshape(b, h, sq, hd)


@common.register_kernel("flash_attn", common.PALLAS_TPU, caps=_PALLAS_CAPS)
def _flash_attn_tpu(q, k, v, *, causal=True, window=None, block_q=None,
                    block_k=None):
    return _pallas(q, k, v, causal=causal, window=window, block_q=block_q,
                   block_k=block_k, interpret=False)


@common.register_kernel("flash_attn", common.PALLAS_INTERPRET, caps=_PALLAS_CAPS)
def _flash_attn_interpret(q, k, v, *, causal=True, window=None, block_q=None,
                          block_k=None):
    return _pallas(q, k, v, causal=causal, window=window, block_q=block_q,
                   block_k=block_k, interpret=True)


@common.register_kernel("flash_attn", common.REFERENCE,
                        caps=common.Caps(dtypes=None))
@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def _flash_attn_reference(q, k, v, *, causal=True, window=None, block_q=None,
                          block_k=None):
    del block_q, block_k
    k, v = _gqa_broadcast(q, k, v)
    return R.attention_ref(q, k, v, causal=causal, window=window)


def flash_attention(
    q: jax.Array,  # [B, Hq, Sq, hd]
    k: jax.Array,  # [B, Hkv, Sk, hd]
    v: jax.Array,  # [B, Hkv, Sk, hd]
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    backend: str | None = None,
) -> jax.Array:
    """Flash attention with GQA broadcast.  Returns [B, Hq, Sq, hd].

    Unpinned ``block_q``/``block_k`` take ``pick_block``'s sublane-aligned
    rule inside the Pallas wrapper.
    """
    hd = q.shape[3]
    assert q.shape[1] % k.shape[1] == 0, (q.shape[1], k.shape[1])
    info = {"dtype": jnp.result_type(q).name, "head_dim": hd,
            "seq_q": q.shape[2], "seq_k": k.shape[2]}
    if block_q is not None:
        info["block_q"] = block_q
    if block_k is not None:
        info["block_k"] = block_k
    return common.dispatch(
        "flash_attn", q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k,
        backend=backend, info=info,
    )


# re-export the oracle for tests
attention_ref = R.attention_ref

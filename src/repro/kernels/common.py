"""Shared kernel infrastructure: backend dispatch registry, tiling
helpers, and the ELL one-hot gather/scatter the sparse kernels share.

The paper implements every SGD primitive "in two flavors" — CPU routines
and highly-parallel GPU kernels — and picks per dataset/hardware.  The
analogue here is a three-backend registry per kernel family:

* ``pallas-tpu``        compiled Pallas (Mosaic) — the TPU runtime path;
* ``pallas-interpret``  the same Pallas kernel run by the interpreter —
                        bit-for-bit kernel logic, runs anywhere (CPU CI);
* ``reference``         the pure-jnp oracle (ref.py) — XLA-compiled,
                        the correctness ground truth and the fallback
                        when capability flags rule the Pallas path out.

Selection order (``resolve_backend``):

1. explicit call-site forcing: a ``backend=`` argument, or the legacy
   ``interpret=`` / ``force_path=`` flags;
2. the ``REPRO_KERNEL_BACKEND`` environment variable (global override —
   e.g. ``REPRO_KERNEL_BACKEND=reference`` to take Pallas entirely out
   of the picture when bisecting a numerics issue);
3. auto: the first backend of ``pallas-tpu`` → ``pallas-interpret`` →
   ``reference`` that is available on this host AND whose capability
   flags accept the call (dtype, sparsity, shape budgets).

Explicit and env overrides bypass the *capability* heuristics (forcing
is on you) but still fail fast on hard unavailability: ``pallas-tpu``
cannot lower off-TPU and raises a clear error instead of a Mosaic
backtrace.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.obs import metrics, trace

LANE = 128      # TPU minor-dim tile (VREG lanes / MXU edge)
SUBLANE = 8     # fp32 second-minor tile


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

PALLAS_TPU = "pallas-tpu"
PALLAS_INTERPRET = "pallas-interpret"
REFERENCE = "reference"

#: auto-selection preference, best first
BACKEND_ORDER = (PALLAS_TPU, PALLAS_INTERPRET, REFERENCE)

ENV_BACKEND = "REPRO_KERNEL_BACKEND"


@dataclasses.dataclass(frozen=True)
class Caps:
    """Capability flags of one kernel implementation.

    ``None`` means unconstrained.  ``check`` is a free-form predicate on
    the call-info dict for budgets that don't fit a named flag (e.g. the
    glm_sparse one-hot VMEM/FLOP budget).
    """

    dtypes: tuple[str, ...] | None = ("float32", "bfloat16")
    sparse: bool = False                      # consumes ELL sparse operands
    head_dim_multiple: int | None = None      # flash-attn lane constraint
    check: Callable[[dict], bool] | None = None

    def supports(self, info: dict[str, Any]) -> bool:
        dt = info.get("dtype")
        if self.dtypes is not None and dt is not None and dt not in self.dtypes:
            return False
        if info.get("sparse") and not self.sparse:
            return False
        hd = info.get("head_dim")
        if (self.head_dim_multiple and hd is not None
                and hd % self.head_dim_multiple != 0):
            return False
        if self.check is not None and not self.check(info):
            return False
        return True


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    kernel: str
    backend: str
    fn: Callable
    caps: Caps


_REGISTRY: dict[str, dict[str, KernelImpl]] = {}


def register_kernel(kernel: str, backend: str, *, caps: Caps | None = None):
    """Decorator: register ``fn`` as the ``backend`` flavor of ``kernel``.

    All flavors of one kernel must share a call signature; the dispatch
    layer forwards arguments verbatim.
    """
    if backend not in BACKEND_ORDER:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKEND_ORDER}")

    def deco(fn):
        _REGISTRY.setdefault(kernel, {})[backend] = KernelImpl(
            kernel, backend, fn, caps or Caps()
        )
        return fn

    return deco


def registered_kernels() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def backends_for(kernel: str) -> tuple[str, ...]:
    """Registered backends of ``kernel``, in preference order."""
    impls = _REGISTRY.get(kernel, {})
    return tuple(b for b in BACKEND_ORDER if b in impls)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _host_available(backend: str) -> bool:
    """Hard availability: can this backend run on the current host at all?"""
    return backend != PALLAS_TPU or on_tpu()


def available_backends(kernel: str, info: dict | None = None) -> tuple[str, ...]:
    """Backends of ``kernel`` runnable on this host (and, when ``info`` is
    given, whose capability flags accept the call) — what the conformance
    suite parametrizes over."""
    out = []
    for b in backends_for(kernel):
        impl = _REGISTRY[kernel][b]
        if not _host_available(b):
            continue
        if info is not None and not impl.caps.supports(info):
            continue
        out.append(b)
    return tuple(out)


def resolve_backend(
    kernel: str,
    *,
    backend: str | None = None,
    interpret: bool | None = None,
    info: dict | None = None,
) -> str:
    """Pick the backend for one call.  See module docstring for the order.

    ``interpret`` is the legacy flag the pre-registry wrappers exposed:
    True → ``pallas-interpret``, False → ``pallas-tpu``, None → auto.
    Like ``backend``, it is call-site-explicit and beats the env var.
    """
    impls = _REGISTRY.get(kernel)
    if not impls:
        raise KeyError(f"no kernel registered under {kernel!r}; "
                       f"known: {registered_kernels()}")

    forced = backend
    if forced is None and interpret is not None:
        forced = PALLAS_INTERPRET if interpret else PALLAS_TPU
    if forced is None:
        forced = os.environ.get(ENV_BACKEND) or None
    if forced is not None:
        if forced not in impls:
            raise ValueError(
                f"backend {forced!r} not registered for {kernel!r}; "
                f"registered: {backends_for(kernel)}")
        if not _host_available(forced):
            raise RuntimeError(
                f"backend {forced!r} for {kernel!r} needs a TPU host "
                f"(jax.default_backend()={jax.default_backend()!r}); "
                f"available here: {available_backends(kernel)}")
        metrics.counter(f"kernel.backend.{kernel}.{forced}").inc()
        return forced

    info = info or {}
    skipped: list[tuple[str, str]] = []
    for b in backends_for(kernel):
        if not _host_available(b):
            skipped.append((b, "host"))
            continue
        if not impls[b].caps.supports(info):
            skipped.append((b, "caps"))
            continue
        # telemetry: which flavor won, and why better-ranked ones lost.
        # Resolution happens host-side (at jit-trace time for epochs that
        # embed a kernel), so these count *resolutions*, not launches.
        metrics.counter(f"kernel.backend.{kernel}.{b}").inc()
        for sb, reason in skipped:
            metrics.counter(f"kernel.fallback.{kernel}.{sb}.{reason}").inc()
        if skipped and trace.enabled():
            trace.instant("kernel.caps_fallback", kernel=kernel, chosen=b,
                          skipped=[f"{sb}:{r}" for sb, r in skipped])
        return b
    raise RuntimeError(
        f"no backend of {kernel!r} accepts call info {info!r}; "
        f"registered: {backends_for(kernel)}")


def implementation(kernel: str, backend: str) -> Callable:
    """The registered ``backend`` flavor of ``kernel``, with no resolution
    — for compiling one flavor for a device this host does not have."""
    return _REGISTRY[kernel][backend].fn


def dispatch(
    kernel: str,
    *args,
    backend: str | None = None,
    interpret: bool | None = None,
    info: dict | None = None,
    **kwargs,
):
    """Resolve a backend and invoke the registered implementation."""
    b = resolve_backend(kernel, backend=backend, interpret=interpret, info=info)
    return implementation(kernel, b)(*args, **kwargs)


# ---------------------------------------------------------------------------
# Tiling helpers
# ---------------------------------------------------------------------------


def pad_to(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    """Zero-pad ``axis`` up to the next multiple (no-op if aligned)."""
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


def padded(size: int, multiple: int) -> int:
    return size + ((-size) % multiple)


def pick_block(size: int, preferred: int, multiple: int = 1) -> int:
    """Largest block <= preferred that divides ``size`` and is a multiple of
    ``multiple``.

    When no such block exists the only always-correct fallback is the
    whole extent as a single block — valid only if ``size`` itself is a
    multiple of ``multiple``.  A ``size`` that is not (prime/odd sizes,
    e.g. ``pick_block(6, 128, 8)``) used to fall through to ``size``
    anyway, handing kernels a sublane-misaligned block; now it raises so
    callers either pad the operand first or route to a backend without
    the alignment constraint (the dispatch caps do the latter).
    """
    best = None
    b = multiple
    while b <= min(preferred, size):
        if size % b == 0:
            best = b
        b += multiple
    if best is not None:
        return best
    if size % multiple == 0:
        return size  # single aligned block (may exceed preferred)
    raise ValueError(
        f"no block <= {preferred} divides size {size} at multiple "
        f"{multiple}, and {size} is not itself a multiple of {multiple}; "
        f"pad the operand to {padded(size, multiple)} or pick a backend "
        f"without the alignment constraint")


def micro_batch_ok(n: int, micro_batch: int) -> bool:
    """Can a fused Pallas epoch tile ``n`` rows into ``micro_batch`` blocks?

    The epoch is a fixed grid, so ``micro_batch`` must divide ``n``, and
    a ``[micro_batch, ...]`` block must obey Mosaic's tiling rule: its
    row count is a multiple of 8 or the whole array.
    """
    return (micro_batch >= 1 and n % micro_batch == 0
            and (micro_batch % SUBLANE == 0 or micro_batch == n))


def check_micro_batch(kernel: str, n: int, micro_batch: int) -> None:
    """Raise the ``ValueError`` a forced Pallas epoch gives a bad tile."""
    if not micro_batch_ok(n, micro_batch):
        raise ValueError(
            f"{kernel} Pallas flavors need micro_batch to divide n and to "
            f"be a multiple of {SUBLANE} or equal to n (Mosaic's row "
            f"tiling rule), got n={n}, micro_batch={micro_batch}; drop the "
            f"explicit backend to fall through to 'reference', or pick "
            f"such a divisor of n")


# ---------------------------------------------------------------------------
# ELL one-hot gather / scatter (sparse kernel bodies)
# ---------------------------------------------------------------------------

#: VMEM the ``[rows, d, K]`` fp32 one-hot may take.  Gather and scatter
#: each make a temporary of the same size, and the scoped VMEM default
#: is 16 MiB on v5e.
ONEHOT_VMEM_BYTES = 4 * 2 ** 20


def onehot_fits(rows: int, d: int, k: int) -> bool:
    """Does the fp32 one-hot of a ``[rows, K]`` tile over ``d`` features
    fit :data:`ONEHOT_VMEM_BYTES`, with ``d`` padded to sublanes and
    ``K`` to lanes?"""
    size = rows * padded(max(d, 1), SUBLANE) * padded(max(k, 1), LANE) * 4
    return size <= ONEHOT_VMEM_BYTES


def check_onehot(kernel: str, rows: int, d: int, k: int) -> None:
    """Raise the ``ValueError`` a forced one-hot Pallas flavor gives a
    tile whose one-hot does not fit :data:`ONEHOT_VMEM_BYTES`."""
    if not onehot_fits(rows, d, k):
        size = rows * padded(max(d, 1), SUBLANE) * padded(max(k, 1), LANE) * 4
        raise ValueError(
            f"{kernel}'s fp32 one-hot [{rows}, {d}, {k}] takes {size:,} "
            f"bytes of VMEM, over its budget of {ONEHOT_VMEM_BYTES:,}; "
            f"train rows this wide on the CSR path (sparse.CSRRows, "
            f"glm_sgd_csr / glm_csr_margins)")


def ell_onehot(idx: jax.Array, d: int) -> jax.Array:
    """One-hot ``[R, d, K]`` fp32 of an ELL index tile ``[R, K]``.

    The feature axis sits on sublanes and the ELL width stays on lanes,
    so no reshape moves data across lanes (Mosaic refuses the
    ``[R, K] -> [R*K, 1]`` shape cast).  Out-of-range ids match nothing.
    """
    rows, k = idx.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows, d, k), 1)
    return (idx[:, None, :] == iota).astype(jnp.float32)


def onehot_gather(onehot: jax.Array, w: jax.Array) -> jax.Array:
    """``w[idx]`` as ``[R, K]`` from the one-hot and a ``[d, 1]`` model.

    Exactly one term of each sum is non-zero, so the gather is exact.
    """
    return jnp.sum(onehot * w[None], axis=1)


def onehot_scatter(onehot: jax.Array, c: jax.Array) -> jax.Array:
    """Scatter-add ``[R, K]`` contributions into a ``[d, 1]`` column."""
    return jnp.sum(jnp.sum(onehot * c[:, None, :], axis=0), axis=1,
                   keepdims=True)

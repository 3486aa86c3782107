"""Per-device block/grid autotuner for the Pallas kernel families.

The paper's §5.2 finding is that kernel speed is a *configuration*
problem — the winning block/batch geometry depends on the dataset shape
and the hardware.  The repo's four-plus kernel families, however, ran at
whatever block sizes they were born with.  This module closes that gap
with the same content-hash cache idiom the study subsystem uses for
trials:

* every kernel family declares its tunable parameters and a candidate
  grid (:data:`TUNABLES`) — e.g. ``block_rows`` for ``glm_grad``,
  ``micro_batch`` for the fused SGD epochs, ``(block_q, block_k)`` for
  ``flash_attn``;
* :func:`tune` sweeps the candidates with ``median_time`` and persists
  the winner (plus the full candidate timing table) on disk, keyed by
  ``(schema, kernel, backend, device kind, shape-class, dtype)`` —
  nearby shapes share a power-of-two **shape class** so one sweep serves
  the whole bucket;
* each family's ``ops.py`` consults :func:`consult` when the caller does
  *not* pin a block size: a cached winner is applied transparently; on a
  cache miss the call falls back to the family's built-in default unless
  ``REPRO_KERNEL_AUTOTUNE=1`` is set, in which case the sweep runs right
  there (never under a jit trace — tracers cannot be timed) and is
  cached for every later call.

Cache location: ``$REPRO_TUNE_DIR``, default
``~/.cache/repro-sgd-tune``.  Invalidation is by construction: a new
device kind, backend, shape class, dtype, or a :data:`SCHEMA` bump
hashes to a different key; deleting the directory forces a full re-tune.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from pathlib import Path
from typing import Any, Callable

import jax

from repro.kernels import common
from repro.obs import metrics, trace
from repro.utils.timing import median_time

#: bump when record semantics change in a way that invalidates cached winners
SCHEMA = 1

ENV_TUNE_DIR = "REPRO_TUNE_DIR"
#: "1" -> a dispatch-time cache miss triggers the sweep (off by default:
#: unpinned call sites then simply use the family's built-in defaults)
ENV_AUTOTUNE = "REPRO_KERNEL_AUTOTUNE"


def canonical_json(obj) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def _digest(obj) -> str:
    return hashlib.sha256(canonical_json(obj).encode()).hexdigest()[:16]


def tune_dir() -> Path:
    root = os.environ.get(ENV_TUNE_DIR)
    if root:
        return Path(root)
    return Path.home() / ".cache" / "repro-sgd-tune"


def device_kind() -> str:
    """Normalized accelerator model string — part of every cache key.

    Raises whatever JAX raises when no backend can start: a cache entry
    keyed to a made-up device would be served to the wrong hardware.
    """
    kind = jax.devices()[0].device_kind
    return str(kind).strip().lower().replace(" ", "-")


def shape_class(info: dict[str, Any]) -> dict[str, Any]:
    """Bucket every integer call-info field to the next power of two.

    ``{"n": 96, "d": 50, "dtype": "float32"}`` and ``{"n": 128, "d": 64,
    ...}`` land in the same class, so one tuning sweep serves all nearby
    shapes instead of re-timing per exact size.  Non-integers (dtype
    strings, flags) pass through unchanged; bools are kept as bools.
    """
    out: dict[str, Any] = {}
    for k in sorted(info):
        v = info[k]
        if isinstance(v, bool) or not isinstance(v, int):
            out[k] = v
        elif v <= 0:
            out[k] = 0
        else:
            out[k] = 1 << max(0, v - 1).bit_length()
    return out


def timeable(*arrays) -> bool:
    """True when the arrays are concrete (a sweep can actually be timed).

    Call sites inside a jit trace see tracers; tuning there is
    impossible, so ``consult`` degrades to a pure cache lookup.
    """
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


# ---------------------------------------------------------------------------
# Candidate grids
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Tunable:
    """Tunable parameters of one kernel family + its candidate grid."""

    params: tuple[str, ...]
    candidates: Callable[[dict], tuple[dict, ...]]


def _row_block_candidates(info: dict) -> tuple[dict, ...]:
    """Row-tile sizes for kernels that pad N up to the block."""
    n_pad = common.padded(max(int(info.get("n", 0)), 1), common.SUBLANE)
    blocks = sorted({b for b in (8, 32, 128, 512) if b <= n_pad} | {n_pad})
    return tuple({"block_rows": b} for b in blocks)


def _micro_batch_candidates(info: dict) -> tuple[dict, ...]:
    """Micro-batch sizes a fused Pallas epoch can tile N with
    (:func:`repro.kernels.common.micro_batch_ok`): multiples of 8 that
    divide N, or, for a small N with none, the whole epoch as one tile."""
    n = int(info.get("n", 0))
    mbs = [b for b in (8, 16, 32, 64, 128) if common.micro_batch_ok(n, b)]
    if not mbs and 0 < n <= 128:
        mbs = [n]
    return tuple({"micro_batch": b} for b in mbs)


def _sparse_candidates(info: dict) -> tuple[dict, ...]:
    """Row tile x d-block pairs whose one-hot fits VMEM."""
    n_pad = common.padded(max(int(info.get("n", 0)), 1), common.SUBLANE)
    rows = [b for b in (8, 16, 32) if b <= n_pad] or [8]
    d = max(int(info.get("d", 0)), 1)
    k = int(info.get("k", 1))
    dbs = [db for db in (128, 256, 512) if db <= common.padded(d, 128)]
    return tuple({"block_rows": b, "d_block": db}
                 for b in rows for db in (dbs or [128])
                 if common.onehot_fits(b, db, k))


def _attn_candidates(info: dict) -> tuple[dict, ...]:
    def blocks(size):
        out = [b for b in (8, 16, 32, 64, 128, 256)
               if size and size % b == 0]
        return out or ([size] if size and size % common.SUBLANE == 0 else [])

    bqs = blocks(int(info.get("seq_q", 0)))
    bks = blocks(int(info.get("seq_k", 0)))
    return tuple({"block_q": bq, "block_k": bk} for bq in bqs for bk in bks)


TUNABLES: dict[str, Tunable] = {
    "glm_grad": Tunable(("block_rows",), _row_block_candidates),
    "glm_score": Tunable(("block_rows",), _row_block_candidates),
    "glm_sgd": Tunable(("micro_batch",), _micro_batch_candidates),
    "glm_sgd_sparse": Tunable(("micro_batch",), _micro_batch_candidates),
    "glm_sparse": Tunable(("block_rows", "d_block"), _sparse_candidates),
    "flash_attn": Tunable(("block_q", "block_k"), _attn_candidates),
}


# ---------------------------------------------------------------------------
# On-disk winner cache
# ---------------------------------------------------------------------------


class TuneCache:
    """Content-addressed winner cache: ``<root>/<key>.json`` (study idiom)."""

    def __init__(self, root: str | Path | None = None):
        self.root = Path(root) if root is not None else tune_dir()

    def key(self, kernel: str, backend: str, info: dict) -> str:
        return _digest({
            "schema": SCHEMA,
            "kernel": kernel,
            "backend": backend,
            "device_kind": device_kind(),
            "shape_class": shape_class(info),
        })

    def get(self, key: str) -> dict | None:
        try:
            with open(self.root / f"{key}.json") as f:
                return json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            return None

    def put(self, key: str, payload: dict) -> None:
        self.root.mkdir(parents=True, exist_ok=True)
        tmp = self.root / f".{key}.tmp.{os.getpid()}"
        tmp.write_text(canonical_json(payload))
        tmp.replace(self.root / f"{key}.json")  # atomic on POSIX


# ---------------------------------------------------------------------------
# Tuning + dispatch-time consultation
# ---------------------------------------------------------------------------


def lookup(kernel: str, backend: str, info: dict, *,
           cache: TuneCache | None = None) -> dict | None:
    """The cached winning config for this call class, or None.

    Only parameters the family declares tunable are returned, so a
    stale/foreign record can never inject unexpected kwargs.
    """
    tunable = TUNABLES.get(kernel)
    if tunable is None:
        return None
    cache = cache if cache is not None else TuneCache()
    rec = cache.get(cache.key(kernel, backend, info))
    if rec is None or not isinstance(rec.get("config"), dict):
        metrics.counter(f"kernel.tune_cache.miss.{kernel}").inc()
        return None
    metrics.counter(f"kernel.tune_cache.hit.{kernel}").inc()
    cfg = {k: v for k, v in rec["config"].items() if k in tunable.params}
    return cfg or None


def tune(
    kernel: str,
    backend: str,
    info: dict,
    run: Callable[..., Any],
    *,
    cache: TuneCache | None = None,
    warmup: int = 1,
    iters: int = 3,
    force: bool = False,
) -> dict:
    """Sweep the family's candidate grid and cache the fastest config.

    ``run(**config)`` must execute the kernel once with the candidate
    config and return a jax value (it is timed with device sync).
    Returns the full record::

        {"config": {...winner...},
         "candidates": [{"config": {...}, "wall_s": ...}, ...],
         "kernel": ..., "backend": ..., "device_kind": ...,
         "shape_class": {...}, "schema": SCHEMA}

    A cached record for the same key short-circuits the sweep unless
    ``force=True``.
    """
    if kernel not in TUNABLES:
        raise KeyError(f"no tunable parameters declared for {kernel!r}; "
                       f"known: {tuple(sorted(TUNABLES))}")
    cache = cache if cache is not None else TuneCache()
    key = cache.key(kernel, backend, info)
    if not force:
        rec = cache.get(key)
        if rec is not None:
            return rec

    candidates = TUNABLES[kernel].candidates(info)
    if not candidates:
        raise ValueError(f"no {kernel!r} candidates for call info {info!r}")
    table = []
    with trace.span("kernel.tune", kernel=kernel, backend=backend,
                    candidates=len(candidates)):
        for cfg in candidates:
            wall = median_time(lambda c=cfg: run(**c),
                               warmup=warmup, iters=iters)
            table.append({"config": cfg, "wall_s": wall})
    best = min(table, key=lambda r: r["wall_s"])
    rec = {
        "schema": SCHEMA,
        "kernel": kernel,
        "backend": backend,
        "device_kind": device_kind(),
        "shape_class": shape_class(info),
        "config": best["config"],
        "candidates": table,
    }
    cache.put(key, rec)
    return rec


def consult(kernel: str, backend: str, info: dict,
            run: Callable[..., Any] | None = None, *,
            cache: TuneCache | None = None) -> dict:
    """Config for an unpinned call site: cached winner, tuned, or ``{}``.

    The empty dict means "use the family's built-in default".  A sweep
    runs only when ``REPRO_KERNEL_AUTOTUNE=1`` *and* the caller could
    supply a timeable ``run`` closure (concrete arrays, not a trace).
    """
    cfg = lookup(kernel, backend, info, cache=cache)
    if cfg is not None:
        return cfg
    if run is not None and os.environ.get(ENV_AUTOTUNE) == "1":
        return dict(tune(kernel, backend, info, run, cache=cache)["config"])
    return {}

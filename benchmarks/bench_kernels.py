"""Kernel microbenchmark trajectory producer -> ``BENCH_kernels.json``.

One trajectory point per (kernel family, shape, dtype, variant):
measured wall time on this host's auto-resolved backend, the
conformance verdict of every dispatchable Pallas flavor against the
family's oracle, and the analytic roofline annotation
(``repro.roofline.kernels``).  Every point runs at the family's
built-in block geometry (variant ``default``), in float32 and with
bfloat16 inputs.

Determinism contract (same as ``BENCH_study.json``): wall times are
cached in ``bench_results/kernel_cache`` keyed by the entry identity
(kernel, shape, dtype, variant, backend, host, device kind) — a warm
re-run reads the cache and writes a byte-identical
``BENCH_kernels.json``, which CI asserts.  The >20% regression gate (``claims.check_bench_kernels``)
compares each point against the *committed* trajectory entry with the
same label, host, and device kind — cross-host timings never gate — and
its baseline lookups stay out of the snapshot so the file remains a pure
function of the caches.

Standalone:  PYTHONPATH=src python -m benchmarks.bench_kernels [ci|paper]
(exits non-zero on a conformance or regression violation).
"""
from __future__ import annotations

import hashlib
import platform

import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.data import synthetic
from repro.kernels import common as kcommon
from repro.kernels.flash_attn import flash_attention
from repro.kernels.flash_attn.ref import attention_ref
from repro.kernels.glm_grad import glm_grad
from repro.kernels.glm_grad.ref import glm_grad_ref
from repro.kernels.glm_sgd import glm_sgd_epoch
from repro.kernels.glm_sgd.ref import glm_sgd_epoch_ref
from repro.kernels.glm_sgd_sparse import ell_sgd_epoch
from repro.kernels.glm_sgd_sparse.ref import ell_sgd_epoch_ref
from repro.kernels.glm_sparse import ell_glm_grad
from repro.kernels.glm_sparse.ref import ell_glm_grad_ref
from repro.roofline import kernels as roofline
from repro.study.runner import TrialCache
from repro.study.spec import canonical_json
from repro.study.store import KernelBenchStore
from repro.utils.timing import time_stats

#: bump to invalidate every cached wall time (timing protocol changes)
TIMING_SCHEMA = 1

STEP = 0.05  # SGD-epoch step size (a compile-time constant)

# family -> per-profile benchmark shape
SHAPES = {
    "glm_grad": {"ci": dict(n=512, d=128), "paper": dict(n=4096, d=512)},
    "glm_sgd": {"ci": dict(n=256, d=64), "paper": dict(n=2048, d=256)},
    "glm_sparse": {"ci": dict(n=256, d=512, k=8),
                   "paper": dict(n=2048, d=4096, k=16)},
    "glm_sgd_sparse": {"ci": dict(n=128, d=256, k=8),
                       "paper": dict(n=1024, d=1024, k=16)},
    "flash_attn": {
        "ci": dict(batch=1, heads_q=2, heads_kv=1, seq_q=64, seq_k=64,
                   head_dim=32),
        "paper": dict(batch=2, heads_q=4, heads_kv=2, seq_q=256, seq_k=256,
                      head_dim=64),
    },
}

#: (dtype, variant) trajectory points per family; bf16 tracks input-cast
#: cost at the default geometry
VARIANTS = (("float32", "default"), ("bfloat16", "default"))


def _digest(obj) -> str:
    return hashlib.sha256(canonical_json(obj).encode()).hexdigest()[:16]


def _shape_tag(shape: dict) -> str:
    return "-".join(f"{k}{v}" for k, v in sorted(shape.items()))


class _Family:
    """One family's benchmark closure set at a concrete shape + dtype."""

    def __init__(self, info, call, oracle, tol):
        self.info = info          # dispatch/roofline call info
        self.call = call          # call(backend=...) -> jax value
        self.oracle = oracle      # oracle() -> reference output
        self.tol = tol            # (rtol, atol) for the conformance check


def _make_family(kernel: str, shape: dict, dtype: str) -> _Family:
    jdt = jnp.dtype(dtype)
    loose = dtype == "bfloat16"
    tol = (0.05, 0.05) if loose else (1e-3, 2e-3)
    rng = np.random.default_rng(7)

    if kernel in ("glm_grad", "glm_sgd"):
        n, d = shape["n"], shape["d"]
        ds = synthetic.make_dense(f"bench-{kernel}-{d}", n, d, seed=0)
        X = jnp.asarray(ds.X, dtype=jdt)
        y = jnp.asarray(ds.y, dtype=jdt)
        w = jnp.asarray(rng.normal(0, 0.1, d), dtype=jdt)
        info = {"dtype": dtype, "n": n, "d": d}
        if kernel == "glm_grad":
            call = lambda backend=None: glm_grad(  # noqa: E731
                "lr", w, X, y, backend=backend)
            oracle = lambda: glm_grad_ref(  # noqa: E731
                "lr", *(a.astype(jnp.float32) for a in (w, X, y)))
        else:
            call = lambda backend=None: glm_sgd_epoch(  # noqa: E731
                "lr", w, X, y, step=STEP, backend=backend)
            oracle = lambda: glm_sgd_epoch_ref(  # noqa: E731
                "lr", *(a.astype(jnp.float32) for a in (w, X, y)), STEP, 8)
            info["micro_batch"] = 8  # oracle comparison fixes the default
        return _Family(info, call, oracle, tol)

    if kernel in ("glm_sparse", "glm_sgd_sparse"):
        n, d, k = shape["n"], shape["d"], shape["k"]
        ds = synthetic.make_sparse(f"bench-{kernel}-{d}", n, d, k * 0.6, k,
                                   seed=0)
        vals = jnp.asarray(ds.ell.values, dtype=jdt)
        idx = jnp.asarray(ds.ell.indices)
        y = jnp.asarray(ds.y, dtype=jdt)
        w = jnp.asarray(rng.normal(0, 0.1, d), dtype=jdt)
        info = {"dtype": dtype, "sparse": True, "n": n, "d": d, "k": k}
        f32 = lambda: (w.astype(jnp.float32), vals.astype(jnp.float32),  # noqa: E731
                       idx, y.astype(jnp.float32))
        if kernel == "glm_sparse":
            call = lambda backend=None: ell_glm_grad(  # noqa: E731
                "lr", w, vals, idx, y, backend=backend)
            oracle = lambda: ell_glm_grad_ref("lr", *f32())  # noqa: E731
        else:
            call = lambda backend=None: ell_sgd_epoch(  # noqa: E731
                "lr", w, vals, idx, y, step=STEP, backend=backend)
            oracle = lambda: ell_sgd_epoch_ref(  # noqa: E731
                "lr", *f32(), STEP, 8)
            info["micro_batch"] = 8
        return _Family(info, call, oracle, tol)

    assert kernel == "flash_attn", kernel
    b, hq, hkv = shape["batch"], shape["heads_q"], shape["heads_kv"]
    sq, sk, hd = shape["seq_q"], shape["seq_k"], shape["head_dim"]
    q = jnp.asarray(rng.normal(0, 1, (b, hq, sq, hd)), dtype=jdt)
    kk = jnp.asarray(rng.normal(0, 1, (b, hkv, sk, hd)), dtype=jdt)
    v = jnp.asarray(rng.normal(0, 1, (b, hkv, sk, hd)), dtype=jdt)
    info = {"dtype": dtype, "head_dim": hd, "seq_q": sq, "seq_k": sk,
            **shape}
    call = lambda backend=None: flash_attention(  # noqa: E731
        q, kk, v, causal=True, backend=backend)
    rep = hq // hkv
    oracle = lambda: attention_ref(  # noqa: E731
        q.astype(jnp.float32),
        jnp.repeat(kk, rep, 1).astype(jnp.float32),
        jnp.repeat(v, rep, 1).astype(jnp.float32), causal=True)
    return _Family(info, call, oracle, tol)


def _conformance(kernel: str, fam: _Family) -> tuple[bool | None, list[str]]:
    """Every dispatchable non-reference flavor vs the oracle.

    Returns ``(verdict, checked_backends)`` where the verdict is None —
    not True — when no Pallas flavor could be checked at this shape (the
    old ``all({})`` fast-path green-lit exactly that case).
    """
    ref = np.asarray(fam.oracle(), dtype=np.float32)
    checks = {}
    for b in kcommon.available_backends(kernel, info=fam.info):
        if b == kcommon.REFERENCE:
            continue
        out = np.asarray(fam.call(backend=b), dtype=np.float32)
        rtol, atol = fam.tol
        checks[b] = bool(np.allclose(out, ref, rtol=rtol, atol=atol))
    if not checks:
        return None, []
    return all(checks.values()), sorted(checks)


def _baseline_wall(committed: dict | None, label: str, host: str,
                   device_kind: str) -> float | None:
    """The committed trajectory's comparable point (same host + device)."""
    entry = (committed or {}).get("entries", {}).get(label)
    if (entry and entry.get("host") == host
            and entry.get("device_kind") == device_kind):
        return entry.get("wall_s")
    return None


def run(profile: str = "ci", *, out_json: str = "BENCH_kernels.json"):
    try:
        committed = KernelBenchStore.load(out_json)
    except (FileNotFoundError, ValueError):
        committed = None
    store = KernelBenchStore(
        out_json, jsonl_path=common.RESULTS_DIR / "kernel_runs.jsonl")
    timing_cache = TrialCache(common.RESULTS_DIR / "kernel_cache")
    host = platform.node()
    device_kind = kcommon.device_kind()

    rows = []
    for kernel, shapes in SHAPES.items():
        shape = shapes[profile]
        tag = _shape_tag(shape)
        verdicts: dict[str, tuple] = {}
        for dtype, variant in VARIANTS:
            fam = _make_family(kernel, shape, dtype)
            backend = kcommon.resolve_backend(kernel, info=fam.info)
            if dtype not in verdicts:
                verdicts[dtype] = _conformance(kernel, fam)
            pallas_match, checked = verdicts[dtype]

            label = f"{kernel}/{tag}/{dtype}/{variant}"
            key = _digest({"timing_schema": TIMING_SCHEMA, "label": label,
                           "profile": profile, "backend": backend,
                           "config": {}, "host": host,
                           "device_kind": device_kind})
            payload = timing_cache.peek(key)
            if payload is None:
                stats = time_stats(fam.call, warmup=1, iters=5)
                # the snapshot commits only the median (deterministic via
                # the timing cache); dispersion goes to the JSONL sidecar
                payload = {"wall_s": stats["median"]}
                timing_cache.put(key, payload)
                cached = False
                store.record_event("timing_stats", label=label, **stats)
            else:
                cached = True

            entry = {
                "kernel": kernel,
                "shape": dict(sorted(shape.items())),
                "dtype": dtype,
                "variant": variant,
                "backend": backend,
                "config": {},   # the family's built-in geometry
                "wall_s": payload["wall_s"],
                "pallas_match": pallas_match,
                "checked_backends": checked,
                "roofline": roofline.annotate(kernel, fam.info,
                                              payload["wall_s"]),
                "host": host,
                "device_kind": device_kind,
            }
            store.record_entry(label, entry, cached=cached)
            rows.append({
                "label": label, **entry,
                "baseline_wall_s": _baseline_wall(committed, label, host,
                                                  device_kind),
            })
    out = store.write()
    print(f"wrote {out} ({len(rows)} trajectory points)")
    return rows


if __name__ == "__main__":
    import sys

    from repro.study import claims
    from repro.utils import compile_cache

    compile_cache.enable()

    profile = sys.argv[1] if len(sys.argv) > 1 else "ci"
    rows = run(profile)
    for r in rows:
        print(f"  {r['label']:48s} {1e6 * r['wall_s']:10.1f}us "
              f"match={r['pallas_match']} "
              f"bound={r['roofline']['bound']}")
    bad = claims.check_bench_kernels(rows)
    if bad:
        print("VIOLATIONS:")
        for v in bad:
            print("  - " + v)
        sys.exit(1)
    print("kernel conformance + regression gate clean")

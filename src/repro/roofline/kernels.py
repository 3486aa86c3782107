"""Analytic roofline annotations for the kernel microbenchmarks.

``analysis.py`` derives rooflines from compiled HLO artifacts; the kernel
microbenchmarks (benchmarks/bench_kernels.py) need the same three-term
framing for shapes that are *parameters*, not artifacts.  This module
prices each kernel family's useful work analytically — FLOPs actually
required by the math and the minimum HBM traffic of one launch — and
derives the TPU roofline bound from the chip constants in analysis.py.

On a CPU host the bound is not a prediction of the measured wall time
(the constants are TPU silicon); it is the shape's *position on the
roofline* — arithmetic intensity and which term would dominate on the
target hardware — recorded next to every trajectory point so kernel
regressions can be judged against what the shape can possibly do.
"""
from __future__ import annotations

from repro.roofline.analysis import HBM_BW, PEAK_FLOPS


def kernel_cost(kernel: str, info: dict) -> dict:
    """Useful FLOPs and minimum HBM bytes of one launch of ``kernel``.

    ``info`` is the same call-info dict the dispatch layer sees (n/d for
    the GLM families, k for ELL sparsity, batch/heads/seqs/head_dim for
    attention).  Sparse families are priced at their *useful* work (the
    gather/scatter math), not the one-hot work the TPU lowering
    spends to avoid irregular access — the roofline is the task's bound,
    not the implementation's.
    """
    f32 = 4
    if kernel == "glm_grad":
        n, d = info["n"], info["d"]
        flops = 4.0 * n * d                      # X@w and X^T@pull
        bytes_ = f32 * (n * d + 2 * n + 2 * d)   # X, y, margins, w, g
    elif kernel == "glm_sgd":
        n, d = info["n"], info["d"]
        flops = 4.0 * n * d                      # same math per epoch
        bytes_ = f32 * (n * d + n + 2 * d)       # model stays resident
    elif kernel == "glm_sparse":
        n, d, k = info["n"], info["d"], info["k"]
        flops = 4.0 * n * k                      # gather-dot + scatter-add
        bytes_ = 2 * f32 * n * k + f32 * n + 2 * f32 * d
    elif kernel == "glm_sgd_sparse":
        n, d, k = info["n"], info["d"], info["k"]
        flops = 4.0 * n * k
        bytes_ = 2 * f32 * n * k + f32 * n + 2 * f32 * d
    elif kernel == "glm_score":
        n, d, k = info["n"], info["d"], info["k"]
        flops = 2.0 * n * k + n                  # gather-dot + link
        bytes_ = 2 * f32 * n * k + f32 * n + f32 * d
    elif kernel == "flash_attn":
        b = info["batch"]
        hq, hkv = info["heads_q"], info["heads_kv"]
        sq, sk, hd = info["seq_q"], info["seq_k"], info["head_dim"]
        flops = 4.0 * b * hq * sq * sk * hd      # QK^T and PV
        bytes_ = f32 * b * (2 * hq * sq * hd + 2 * hkv * sk * hd)
    else:
        raise KeyError(f"no cost model for kernel {kernel!r}")
    return {"flops": flops, "hbm_bytes": float(bytes_)}


def annotate(kernel: str, info: dict, wall_s: float | None = None) -> dict:
    """Roofline terms for one trajectory point.

    Returns flops / hbm_bytes / arithmetic intensity, the TPU
    compute-bound and memory-bound times, which term binds, and — when a
    measured ``wall_s`` is given — the achieved GFLOP/s and the fraction
    of the roofline bound the measurement reached (≈1 only on the target
    silicon; an analytic context field everywhere else).
    """
    cost = kernel_cost(kernel, info)
    compute_s = cost["flops"] / PEAK_FLOPS
    memory_s = cost["hbm_bytes"] / HBM_BW
    bound_s = max(compute_s, memory_s)
    out = {
        **cost,
        "intensity_flops_per_byte": cost["flops"] / cost["hbm_bytes"],
        "tpu_compute_s": compute_s,
        "tpu_memory_s": memory_s,
        "tpu_bound_s": bound_s,
        "bound": "compute" if compute_s >= memory_s else "memory",
    }
    if wall_s:
        out["achieved_gflops"] = cost["flops"] / wall_s / 1e9
        out["roofline_fraction"] = bound_s / wall_s
    return out

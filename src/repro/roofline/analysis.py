"""Roofline analysis over the dry-run artifacts (deliverable g).

Per (arch x shape x mesh) cell, derive the three roofline terms from the
compiled partitioned HLO (see hlo.py for why the text is parsed rather than
trusting cost_analysis):

    compute_s    = dot_FLOPs_per_device / MXU peak
    memory_s     = HBM traffic proxy    / HBM bandwidth
    collective_s = wire bytes per device / ICI bandwidth

with the peaks of the target chip from :data:`PEAKS`.

plus MODEL_FLOPS (6*N_active*D train / 2*N_active*D inference), the useful-
compute ratio, the dominant term, and a one-line hillclimb suggestion.

CPU-backend caveats (documented in EXPERIMENTS.md §Methodology):
  * the host backend upcasts bf16 dot inputs to f32 — FLOPs are attributed
    at the bf16 MXU rate the TPU lowering would use, and the memory/
    collective byte totals are scaled by the measured f32/bf16 inflation
    on parameter-derived buffers (none: we report raw parsed bytes and note
    the ~2x inflation where it applies);
  * Pallas kernels don't lower on the host backend; the XLA chunked paths
    analyzed here are the kernels' fallback implementations, so kernel-side
    wins (flash attention VMEM reuse) are called out as deltas, not measured.

Usage:
    PYTHONPATH=src python -m repro.roofline.analysis [--mesh 16x16]
writes roofline.json + a markdown table to dryrun_results/.
"""
from __future__ import annotations

import argparse
import gzip
import json
import math
from pathlib import Path

#: Per-chip peaks keyed by normalized ``device_kind``
#: (:func:`repro.kernels.common.device_kind`).  TPU v5e: Google Cloud
#: documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
#: 1,600 Gbit/s of interconnect (four links of 50 GB/s; 45 GB/s is the
#: effective figure used here).
PEAKS = {
    "tpu-v5-lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 45e9},
}

#: the chip the dry-run and kernel rooflines are priced for
TARGET_DEVICE = "tpu-v5-lite"


def peaks(device_kind: str) -> dict:
    """Peaks of one chip; a chip not in :data:`PEAKS` is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None


PEAK_FLOPS = peaks(TARGET_DEVICE)["flops"]      # bf16 MXU per chip
HBM_BW = peaks(TARGET_DEVICE)["hbm_bw"]         # bytes/s per chip
ICI_BW = peaks(TARGET_DEVICE)["ici_bw"]         # effective bytes/s per link

RESULTS_DIR = Path(__file__).resolve().parents[3] / "dryrun_results"


def param_counts(arch: str) -> tuple[float, float]:
    """(total, active-per-token) parameter counts, from real init shapes."""
    import jax

    from repro import configs
    from repro.launch import specs as specs_mod

    cfg = configs.get(arch)
    shapes, _ = specs_mod.param_shapes_and_specs(cfg)
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    total = active = 0.0
    for path, leaf in flat:
        keys = [str(getattr(k, "key", "")) for k in path]
        n = math.prod(leaf.shape)
        total += n
        if cfg.moe_experts and "moe" in keys and any(
                k in ("w_up", "w_gate", "w_down") for k in keys):
            active += n * (cfg.moe_top_k / cfg.moe_experts)
        else:
            active += n
    return total, active


def model_flops(arch: str, kind: str, seq: int, gb: int) -> float:
    """Global MODEL_FLOPS per step: 6*N_active*D (train), 2*N_active*D
    (inference); D = tokens touched this step."""
    _, active = param_counts(arch)
    tokens = gb * (1 if kind == "decode" else seq)
    return (6.0 if kind == "train" else 2.0) * active * tokens


def analyze_cell(json_path: Path) -> dict | None:
    from repro.roofline import hlo

    meta = json.loads(json_path.read_text())
    if meta.get("status") != "ok":
        return {"arch": meta.get("arch"), "shape": meta.get("shape"),
                "mesh": meta.get("mesh"), "status": "fail"}
    hlo_file = meta.get("hlo_file")
    if not hlo_file or not Path(hlo_file).exists():
        return None
    text = gzip.open(hlo_file, "rt").read()
    a = hlo.analyze(text)
    n_dev = 512 if meta["mesh"] == "2x16x16" else 256

    compute_s = a.dot_flops / PEAK_FLOPS
    memory_s = a.memory_bytes / HBM_BW
    coll_s = a.collective_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)

    mf = model_flops(meta["arch"], meta["kind"], meta["seq"],
                     meta["global_batch"])
    mf_dev = mf / n_dev
    ratio = mf_dev / a.dot_flops if a.dot_flops else 0.0

    suggestions = {
        "compute_s": ("cut non-model FLOPs: causal-skip attention blocks, "
                      "drop remat recompute via selective checkpoint policy"),
        "memory_s": ("raise arithmetic intensity: larger q-chunks (fewer "
                     "K/V re-reads), bf16 intermediates, flash-attn kernel "
                     "keeps K/V tiles in VMEM on TPU"),
        "collective_s": ("hoist K/V all-gathers out of the q-chunk scan, "
                         "overlap grad all-reduce with backward, or shard "
                         "activations less aggressively"),
    }

    return {
        "arch": meta["arch"], "shape": meta["shape"], "mesh": meta["mesh"],
        "kind": meta["kind"], "status": "ok",
        "devices": n_dev,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dominant,
        "step_time_bound_s": max(terms.values()),
        "roofline_fraction": (compute_s / max(terms.values())
                              if max(terms.values()) else 0.0),
        "model_flops_global": mf, "model_flops_per_dev": mf_dev,
        "hlo_dot_flops_per_dev": a.dot_flops,
        "useful_compute_ratio": ratio,
        "collective_by_kind": a.collective_by_kind,
        "n_while": a.n_while,
        "peak_bytes": meta["memory"].get("peak_bytes"),
        "argument_bytes": meta["memory"].get("argument_bytes"),
        "suggestion": suggestions[dominant],
    }


def run(mesh: str = "16x16", pattern: str = "*"):
    rows = []
    for p in sorted(RESULTS_DIR.glob(f"{pattern}__{mesh}.json")):
        r = analyze_cell(p)
        if r:
            rows.append(r)
            print(f"{r['arch']:22s} {r['shape']:12s} "
                  f"dom={r.get('dominant','-'):13s} "
                  f"C={r.get('compute_s',0):8.3f}s "
                  f"M={r.get('memory_s',0):8.3f}s "
                  f"X={r.get('collective_s',0):8.3f}s "
                  f"useful={r.get('useful_compute_ratio',0):5.2f}",
                  flush=True)
    out = RESULTS_DIR / f"roofline_{mesh}.json"
    out.write_text(json.dumps(rows, indent=1))
    print(f"wrote {out}")
    return rows


def to_markdown(rows: list[dict]) -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | dominant "
           "| model TFLOPs/dev | useful ratio | roofline frac |\n"
           "|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        if r.get("status") != "ok":
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3f} "
            f"| {r['memory_s']:.3f} | {r['collective_s']:.3f} "
            f"| **{r['dominant'].replace('_s','')}** "
            f"| {r['model_flops_per_dev']/1e12:.2f} "
            f"| {r['useful_compute_ratio']:.2f} "
            f"| {r['roofline_fraction']:.2f} |")
    return hdr + "\n".join(lines)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--pattern", default="*")
    args = ap.parse_args()
    rows = run(args.mesh, args.pattern)
    md = to_markdown(rows)
    (RESULTS_DIR / f"roofline_{args.mesh}.md").write_text(md)

"""Roofline analysis over dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape x mesh), in seconds per step, per the brief:
  compute    = HLO_FLOPs(loop-aware, per device) / peak_FLOP/s
  memory     = HLO_bytes(per device)             / HBM_bw
  collective = collective wire bytes(per device) / ICI link bw

plus MODEL_FLOPS = 6·N·D (train) or 2·N_active·D (serve), the useful-
compute ratio, the dominant term, and a one-line "what would move it".
"""
from __future__ import annotations

import glob
import json
import os
from typing import List, Optional

from repro.common.constants import TARGET_DEVICE_KIND, chip_peaks

# the analytic model is of the target chip, whatever compiled the HLO
PEAKS = chip_peaks(TARGET_DEVICE_KIND)

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun")


def load_artifacts(art_dir: str = ART_DIR, suffix: Optional[str] = None) -> List[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        with open(path) as f:
            d = json.load(f)
        d["_file"] = os.path.basename(path)
        if suffix is None or d["_file"].endswith(suffix + ".json"):
            out.append(d)
    return out


def terms(d: dict) -> dict:
    n_dev = d["mesh"]["devices"]
    # loop-aware flops are PER DEVICE (the compiled module is the per-device
    # SPMD program); fall back to cost_analysis when the parse found nothing
    flops_dev = max(d.get("hlo_flops_loopaware", 0.0), d.get("hlo_flops", 0.0))
    bytes_dev = max(d.get("hlo_bytes_est", 0.0), d.get("hlo_bytes", 0.0))
    coll_dev = d["collectives"]["total_bytes"]
    overlapped = d["collectives"].get("overlapped_bytes", 0.0)
    t_c = flops_dev / PEAKS.flops_bf16
    t_m = bytes_dev / PEAKS.hbm_bandwidth
    t_n = coll_dev / PEAKS.ici_bandwidth_per_link
    dominant = max(("compute", t_c), ("memory", t_m), ("collective", t_n), key=lambda kv: kv[1])[0]
    model_flops_dev = d["model_flops"] / n_dev
    useful = model_flops_dev / flops_dev if flops_dev else 0.0
    step_time = max(t_c, t_m, t_n)  # overlap-optimistic bound
    mfu = model_flops_dev / PEAKS.flops_bf16 / step_time if step_time else 0.0
    return {
        "arch": d["arch"],
        "shape": d["shape"],
        "mesh": "x".join(str(s) for s in d["mesh"]["shape"]),
        "compute_s": t_c,
        "memory_s": t_m,
        "collective_s": t_n,
        # step-time brackets: a scheduler that can't hide any collective pays
        # t_c + t_n; perfect latency hiding pays max(t_c, t_n). The achieved
        # time lands between them in proportion to the overlapped fraction.
        "serialized_s": t_c + t_n,
        "overlapped_s": max(t_c, t_n),
        "overlap_ratio": overlapped / coll_dev if coll_dev else 0.0,
        "dominant": dominant,
        "model_flops": d["model_flops"],
        "useful_ratio": useful,
        "roofline_frac": mfu,  # MODEL_FLOPS-based fraction of peak at bound
        "peak_gib": d["memory"]["peak_per_device"] / 2**30,
        "resident_gib": d["memory"].get("resident_bytes", 0) / 2**30,
        "fits_hbm": d["memory"].get("resident_bytes", 0) <= PEAKS.hbm_bytes,
        "_file": d["_file"],
    }


_SUGGEST = {
    "compute": "increase arithmetic efficiency (fuse pointwise into matmuls, "
               "larger per-device tiles, reduce remat recompute)",
    "memory": "cut HBM traffic (fuse ops, bf16/int8 storage, smaller "
              "activations via sequence sharding or chunked loss)",
    "collective": "cut wire bytes (truncate-before-repartition, overlap "
                  "collectives with compute, shard to reduce resharding)",
}


def suggestion(row: dict) -> str:
    return _SUGGEST[row["dominant"]]


def markdown_table(rows: List[dict]) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s | "
           "serialized s | overlapped s | overlap | "
           "dominant | model/HLO | roofline frac | resident GiB |")
    sep = "|" + "---|" * 13
    lines = [hdr, sep]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{r['compute_s']:.3e} | {r['memory_s']:.3e} | {r['collective_s']:.3e} | "
            f"{r['serialized_s']:.3e} | {r['overlapped_s']:.3e} | "
            f"{r['overlap_ratio']:.2f} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | {r['roofline_frac']:.3f} | "
            f"{r['resident_gib']:.1f} |"
        )
    return "\n".join(lines)


def run():
    arts = load_artifacts()
    rows = [terms(d) for d in arts if not d["_file"].endswith("_nosp.json")]
    pod_rows = [r for r in rows if r["mesh"] == "16x16"]
    if not pod_rows:
        return 0.0, {"error": "no dry-run artifacts found; run launch/dryrun first"}
    dominant_counts = {}
    for r in pod_rows:
        dominant_counts[r["dominant"]] = dominant_counts.get(r["dominant"], 0) + 1
    worst = min(pod_rows, key=lambda r: r["roofline_frac"])
    best = max(pod_rows, key=lambda r: r["roofline_frac"])
    derived = {
        "cells": len(pod_rows),
        "dominant_counts": dominant_counts,
        "overlap_ratio_mean": round(
            sum(r["overlap_ratio"] for r in pod_rows) / len(pod_rows), 3
        ),
        "worst": f"{worst['arch']}/{worst['shape']} frac={worst['roofline_frac']:.3f}",
        "best": f"{best['arch']}/{best['shape']} frac={best['roofline_frac']:.3f}",
    }
    return 0.0, derived


if __name__ == "__main__":
    arts = load_artifacts()
    rows = [terms(d) for d in arts if not d["_file"].endswith("_nosp.json")]
    print(markdown_table(sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"]))))

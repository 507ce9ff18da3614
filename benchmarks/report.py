"""Generate the EXPERIMENTS.md §Dry-run and §Roofline tables from artifacts.

    PYTHONPATH=src:. python -m benchmarks.report > artifacts/tables.md
"""
from __future__ import annotations

import json
import os

from benchmarks import roofline


def dryrun_table(rows):
    hdr = ("| arch | shape | mesh | compile s | HLO GFLOP/dev | coll GB/dev | "
           "resident GiB/dev | temp GiB (ub) | collective mix |")
    sep = "|" + "---|" * 9
    out = [hdr, sep]
    for d in rows:
        mix = ",".join(
            f"{k.split('-')[-1]}:{v/1e9:.1f}G"
            for k, v in sorted(d["collectives"]["bytes_by_kind"].items(), key=lambda kv: -kv[1])[:3]
        )
        out.append(
            f"| {d['arch']} | {d['shape']} | {'x'.join(str(s) for s in d['mesh']['shape'])} | "
            f"{d['compile_s']:.1f} | {d.get('hlo_flops_loopaware', 0)/1e9:.0f} | "
            f"{d['collectives']['total_bytes']/1e9:.2f} | "
            f"{d['memory'].get('resident_bytes', 0)/2**30:.2f} | "
            f"{d['memory']['temp_bytes']/2**30:.1f} | {mix} |"
        )
    return "\n".join(out)


def roofline_table(rows):
    hdr = ("| arch | shape | compute s | memory s (ub) | collective s | dominant | "
           "MODEL/HLO flops | roofline frac | fits 16 GiB (resident) |")
    sep = "|" + "---|" * 9
    out = [hdr, sep]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3f} | {r['memory_s']:.2f} | "
            f"{r['collective_s']:.3f} | {r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_frac']:.3f} | {'yes' if r['fits_hbm'] else 'NO'} |"
        )
    return "\n".join(out)


def main():
    arts = roofline.load_artifacts()
    arts = [a for a in arts if "_nosp" not in a["_file"]]
    pod = sorted(
        (a for a in arts if a["_file"].endswith("_pod.json")),
        key=lambda a: (a["arch"], a["shape"]),
    )
    multi = sorted(
        (a for a in arts if a["_file"].endswith("_multipod.json")),
        key=lambda a: (a["arch"], a["shape"]),
    )
    print("### Dry-run — single pod 16x16 (256 chips)\n")
    print(dryrun_table(pod))
    print("\n### Dry-run — multi-pod 2x16x16 (512 chips)\n")
    print(dryrun_table(multi))
    rows = [roofline.terms(a) for a in pod]
    print("\n### Roofline — single pod (per brief: 16x16 only)\n")
    print(roofline_table(sorted(rows, key=lambda r: (r["arch"], r["shape"]))))


if __name__ == "__main__":
    main()

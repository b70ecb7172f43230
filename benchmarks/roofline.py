"""Roofline report generator: reads experiments/dryrun/*.json and emits the
per-(arch × shape × mesh) table for EXPERIMENTS.md §Roofline.

    PYTHONPATH=src python -m benchmarks.roofline [--dir experiments/dryrun]
"""
from __future__ import annotations

import argparse
import glob
import json
import os


def fmt_s(x: float) -> str:
    if x == 0:
        return "0"
    for unit, scale in [("s", 1.0), ("ms", 1e-3), ("us", 1e-6), ("ns", 1e-9)]:
        if x >= scale:
            return f"{x / scale:.2f}{unit}"
    return f"{x:.1e}s"


def load(dirname: str) -> list:
    rows = []
    for f in sorted(glob.glob(os.path.join(dirname, "*.json"))):
        d = json.load(open(f))
        rows.append(d)
    return rows


def bottleneck_sentence(r: dict) -> str:
    dom = r["roofline"]["dominant"]
    if dom == "collective":
        kinds = {k: v for k, v in r["collectives"].items()
                 if isinstance(v, dict)}
        top = max(kinds.items(),
                  key=lambda kv: kv[1]["link_bytes_per_chip"])[0] \
            if kinds else "?"
        return (f"{top} traffic dominates — reshard/overlap it")
    if dom == "memory":
        return "HBM streaming dominates — fuse/cast to cut passes"
    return "MXU-bound — increase arithmetic intensity only via algorithm"


def markdown_table(rows: list, mesh: str = "single") -> str:
    hdr = ("| arch | shape | compute | memory | collective | dominant | "
           "roofline-frac | 6ND/analytic | note |\n"
           "|---|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        if r.get("mesh") != mesh:
            continue
        if r["status"] == "skip":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | skip | "
                         f"— | — | {r['reason'][:60]} |")
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | ERROR |"
                         f" — | — | {r['error'][:60]} |")
            continue
        t = r["roofline"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {fmt_s(t['compute_s'])} | "
            f"{fmt_s(t['memory_s'])} | {fmt_s(t['collective_s'])} | "
            f"{t['dominant']} | {t['roofline_fraction']:.3f} | "
            f"{r['useful_flop_ratio']:.2f} | {bottleneck_sentence(r)} |")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    args = ap.parse_args()
    rows = load(args.dir)
    print(markdown_table(rows, args.mesh))
    ok = [r for r in rows if r["status"] == "ok" and r["mesh"] == args.mesh]
    worst = sorted(ok, key=lambda r: r["roofline"]["roofline_fraction"])[:5]
    print("\nworst roofline fractions:")
    for r in worst:
        print(f"  {r['arch']} {r['shape']}: "
              f"{r['roofline']['roofline_fraction']:.4f} "
              f"({r['roofline']['dominant']})")
    coll = sorted(ok, key=lambda r: -r["roofline"]["collective_s"])[:5]
    print("most collective-bound (abs seconds):")
    for r in coll:
        print(f"  {r['arch']} {r['shape']}: "
              f"coll={fmt_s(r['roofline']['collective_s'])} vs "
              f"comp={fmt_s(r['roofline']['compute_s'])}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()

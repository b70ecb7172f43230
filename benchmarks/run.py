"""Benchmark harness entry point — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only fig12] [--dataset deep]
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

# support both `python -m benchmarks.run` and `python benchmarks/run.py`
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

MODULES = [
    ("fig3_breakdown", "stage time breakdown (paper Fig. 3a)"),
    ("fig4_sparsity", "entry sparsity + locality CDF (Fig. 4/5)"),
    ("fig6_threshold", "remaining points & retention vs threshold (Fig. 6/7b)"),
    ("fig7_density", "density<->threshold regression (Fig. 7a)"),
    ("fig11_hitcount", "hit-count <-> distance correlation (Fig. 11b)"),
    ("fig12_qps_recall", "QPS vs recall Pareto (Fig. 12)"),
    ("fig13_ablation", "optimization ablations (Fig. 13)"),
    ("fig14_algo_only", "algorithm-only gain (Fig. 14a)"),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--dataset", default="deep",
                    choices=["deep", "sift", "tti"])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-N CI mode: every module runs in seconds; "
                         "exit code is the number of failing modules")
    args = ap.parse_args()

    if args.smoke:
        from benchmarks import common
        common.set_smoke_sizes()

    print("name,us_per_call,derived")
    failures = 0
    for mod_name, desc in MODULES:
        if args.only and args.only not in mod_name:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            import inspect
            sig = inspect.signature(mod.run)
            if "dataset" in sig.parameters:
                mod.run(dataset=args.dataset)
            else:
                mod.run()
            print(f"# {mod_name} done in {time.time() - t0:.0f}s "
                  f"({desc})", file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# {mod_name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr)
    return failures


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())

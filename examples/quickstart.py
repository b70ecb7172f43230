"""Quickstart: build a JUNO index and search it — the paper's pipeline
end-to-end on synthetic deep-like data (CPU, <1 min).

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax

from repro.core import (JunoConfig, build, exact_topk, recall_1_at_k,
                        recall_n_at_k, search)
from repro.data import DEEP_LIKE, make_dataset


def main():
    print("generating 50k-point deep-like dataset (96-d, L2)...")
    points, queries = make_dataset(DEEP_LIKE, 50_000, 128,
                                   key=jax.random.PRNGKey(0))

    print("building JUNO index (IVF k-means -> residual PQ -> density "
          "calibration)...")
    t0 = time.time()
    cfg = JunoConfig(n_clusters=256, n_entries=128, calib_queries=64)
    index = build(points, cfg)
    print(f"  built in {time.time() - t0:.1f}s: C={cfg.n_clusters} "
          f"E={cfg.n_entries} subspaces={points.shape[1] // cfg.sub_dim}")

    _, gt = exact_topk(queries, points, k=100)

    print(f"\n{'mode':8s} {'R1@100':>8s} {'R100@1k':>8s} {'ms/query':>9s}")
    for mode, label in [("H", "JUNO-H (exact selective)"),
                        ("H2", "JUNO-H2 (two-stage, beyond-paper)"),
                        ("M", "JUNO-M (reward/penalty hit count)"),
                        ("L", "JUNO-L (plain hit count)")]:
        t0 = time.time()
        _, ids = search(index, queries, nprobe=16, k=100, mode=mode)
        jax.block_until_ready(ids)
        t0 = time.time()  # warm second pass
        _, ids = search(index, queries, nprobe=16, k=100, mode=mode)
        jax.block_until_ready(ids)
        dt = (time.time() - t0) / queries.shape[0] * 1e3
        r1 = float(recall_1_at_k(ids, gt[:, 0]))
        r100 = float(recall_n_at_k(ids, gt[:, :100]))
        print(f"{mode:8s} {r1:8.3f} {r100:8.3f} {dt:9.2f}   # {label}")


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()

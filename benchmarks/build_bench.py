"""Build-pipeline benchmark: streaming throughput, store round-trip,
rebuild + hot-swap serve parity.

Exercises the full `repro.build` lifecycle at ~200k synthetic points:

1. **stream-build** the set through `build_streaming` (chunked source,
   bounded training sample) and report throughput in pts/s;
2. **round-trip** the index through the versioned artifact store
   (save → verify → load, checksummed);
3. **load spills** into a serving engine (overfill the tightest cluster
   so the side buffer carries real weight), **rebuild + swap**
   (`AnnServeEngine.swap_index`), report the rebuild wall time, and
   assert the swap preserved search results;
4. time **side-buffer-laden vs post-rebuild serve QPS** with interleaved
   passes (this box's load drifts on the seconds scale — back-to-back
   blocks would hand one engine a quiet machine, docs/benchmarks.md).

``--check``/``--smoke`` gate: post-rebuild QPS >= side-laden QPS (the
side gather is pure extra work, so a rebuild that does not win means the
swap broke something) and artifact integrity. ``--json`` records the
numbers (committed as BENCH_build.json).

    PYTHONPATH=src python benchmarks/build_bench.py [--smoke] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import jax  # noqa: E402

from benchmarks import common  # noqa: E402
from repro.build import (ArtifactStore, BuildProbe, array_source,  # noqa: E402
                         build_streaming)
from repro.core import JunoConfig, MutableJunoIndex  # noqa: E402
from repro.data import DEEP_LIKE, make_dataset  # noqa: E402
from repro.serve.ann import AnnServeEngine  # noqa: E402

# single-query H2-tier requests: the online-serving shape where the side
# buffer's per-search (Q, B) gather weighs the most relative to useful work
REQUESTS = [(1, 10, 0.85), (1, 10, 0.88), (1, 10, 0.82)]


def _spill_and_tombstone(mid: MutableJunoIndex, rng, n_spill: int,
                         n_points: int) -> int:
    """Overfill the tightest clusters until >= n_spill side entries exist,
    then tombstone one original member per spill in the same clusters.

    The mixed insert+delete shape of a real serving workload: the side
    buffer is laden AND freed slots exist, so the rebuild drains every
    spill WITHOUT growing the padded capacity — post-swap searches reuse
    the warm jit signatures (docs/building.md)."""
    import collections

    n_clusters = mid.data.ivf.point_ids.shape[0]
    free = [mid.free_slots(c) for c in range(n_clusters)]
    order = np.argsort(free)
    d = mid.data.ivf.centroids.shape[1]
    for c in order:
        if mid.side_fill >= n_spill:
            break
        c = int(c)
        cent = np.asarray(mid.data.ivf.centroids[c])
        need = mid.free_slots(c) + min(n_spill - mid.side_fill,
                                       mid.side.capacity - mid.side_fill)
        pts = (cent[None] + 0.01 * rng.standard_normal((need, d))
               ).astype(np.float32)
        mid.insert(pts)
    side_mask = np.asarray(mid.side.valid)
    per_c = collections.Counter(
        np.asarray(mid.side.cluster)[side_mask].tolist())
    victims = []
    for c, cnt in per_c.items():
        row = np.asarray(mid.data.ivf.point_ids[c])
        val = np.asarray(mid.data.ivf.valid[c])
        orig = [int(p) for p in row[val] if p < n_points]
        victims += orig[:cnt]
    mid.delete(victims)
    return mid.side_fill


def _make_trace(queries: np.ndarray, n_requests: int):
    trace, pos = [], 0
    for r in range(n_requests):
        nq, k, target = REQUESTS[r % len(REQUESTS)]
        rows = np.take(queries, range(pos, pos + nq), axis=0, mode="wrap")
        trace.append((rows, k, target))
        pos += nq
    return trace


def run(n_points: int = 200_000, n_requests: int = 96,
        n_spill: int = 256) -> dict:
    pts, queries = make_dataset(DEEP_LIKE, n_points, 64,
                                key=jax.random.PRNGKey(11))
    pts, queries = np.asarray(pts), np.asarray(queries)
    cfg = JunoConfig(n_clusters=128, n_entries=64, metric="l2",
                     calib_queries=32, kmeans_iters=8,
                     max_train_points=50_000, capacity_mult=1.05)

    # --- 1. streaming build ----------------------------------------------
    probe = BuildProbe()
    t0 = time.perf_counter()
    data = build_streaming(array_source(pts, 32768), cfg, probe=probe)
    t_build = time.perf_counter() - t0
    build_pps = n_points / t_build
    common.emit("build_bench.stream_build", t_build * 1e6,
                f"pts_per_s={build_pps:.0f};chunks={probe.chunks};"
                f"passes={probe.passes};train_rows={probe.train_rows}")

    # --- 2. artifact store round-trip ------------------------------------
    with tempfile.TemporaryDirectory() as tmp:
        store = ArtifactStore(tmp)
        t0 = time.perf_counter()
        store.put("bench", data, cfg)
        t_save = time.perf_counter() - t0
        t0 = time.perf_counter()
        loaded = store.get("bench", expect_config=cfg)   # verifying load
        t_load = time.perf_counter() - t0
    data = loaded.data
    common.emit("build_bench.store_roundtrip", (t_save + t_load) * 1e6,
                f"save_s={t_save:.2f};load_verify_s={t_load:.2f}")

    # --- 3. spill + tombstone, then rebuild + swap on a second engine ----
    engines = {}
    for name in ("laden", "rebuilt"):
        eng = AnnServeEngine(MutableJunoIndex(data, side_capacity=4096),
                             metric=cfg.metric, batch_buckets=(8, 16, 32))
        spilled = _spill_and_tombstone(eng.index, np.random.default_rng(0),
                                       n_spill, n_points)
        assert spilled >= min(n_spill, 64), f"spill failed: {spilled}"
        engines[name] = eng
    check = np.take(queries, range(32), axis=0, mode="wrap")
    r_pre = engines["rebuilt"].submit(check, k=10, mode="H2")
    engines["rebuilt"].run()
    t0 = time.perf_counter()
    engines["rebuilt"].swap_index()
    t_rebuild = time.perf_counter() - t0
    assert engines["rebuilt"].index.side_fill == 0
    r_post = engines["rebuilt"].submit(check, k=10, mode="H2")
    engines["rebuilt"].run()
    np.testing.assert_array_equal(r_pre.scores, r_post.scores)
    common.emit("build_bench.rebuild_swap", t_rebuild * 1e6,
                f"rebuild_s={t_rebuild:.2f};"
                f"side_drained={engines['laden'].index.side_fill}")

    # --- 4. side-laden vs post-rebuild serve QPS (interleaved) -----------
    trace = _make_trace(queries, n_requests)
    total_q = sum(t[0].shape[0] for t in trace)
    times = {name: [] for name in engines}
    for eng in engines.values():     # warm every signature + bucket
        for (q, k, t) in trace:
            eng.submit(q, k=k, recall_target=t)
        eng.run()
    for _ in range(3):               # interleave the timed passes
        for name, eng in engines.items():
            t0 = time.perf_counter()
            for (q, k, t) in trace:
                eng.submit(q, k=k, recall_target=t)
            eng.run()
            times[name].append(time.perf_counter() - t0)
    qps = {name: total_q / sorted(ts)[1] for name, ts in times.items()}
    speedup = qps["rebuilt"] / qps["laden"]
    common.emit("build_bench.serve_qps", 0.0,
                f"laden_qps={qps['laden']:.0f};"
                f"rebuilt_qps={qps['rebuilt']:.0f};speedup={speedup:.2f}x")
    return {"n_points": n_points, "build_pts_per_s": build_pps,
            "build_s": t_build, "store_save_s": t_save,
            "store_load_verify_s": t_load, "rebuild_s": t_rebuild,
            "laden_qps": qps["laden"], "rebuilt_qps": qps["rebuilt"],
            "speedup": speedup}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-points", type=int, default=200_000)
    ap.add_argument("--n-requests", type=int, default=96)
    ap.add_argument("--n-spill", type=int, default=256,
                    help="side-buffer entries to load before the QPS A/B")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode; implies --check (same ~200k build — the "
                         "streaming pipeline IS the thing under test)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless post-rebuild QPS >= side-laden QPS")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write throughput/rebuild/QPS numbers here")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    res = run(n_points=args.n_points, n_requests=args.n_requests,
              n_spill=args.n_spill)
    ok = res["rebuilt_qps"] >= res["laden_qps"]
    print(f"# post-rebuild {res['rebuilt_qps']:.0f} QPS vs side-laden "
          f"{res['laden_qps']:.0f} QPS -> {'OK' if ok else 'REGRESSION'}",
          file=sys.stderr)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"smoke": args.smoke, "backend": "cpu-hostpath",
                       **res}, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if (args.check or args.smoke) and not ok:
        return 1
    return 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())

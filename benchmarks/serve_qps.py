"""QPS/latency of the online ANN serving engine under a mixed workload.

Replays a request trace (many small queries with mixed k/mode/recall-target
knobs) through ``repro.serve.ann.AnnServeEngine`` with insert batches
interleaved between waves — the online-serving shape — and compares against
the seed baseline: one single-shot ``core.search()`` call per request, no
batching, query-only. The engine must win on throughput (dynamic batching
amortizes dispatch and fills the batch dimension) while also absorbing the
inserts; ``--check``/``--smoke`` turn that into a hard gate.

A second section compares FUSED vs UNFUSED serving of the high-recall
tiers: the same H2-tier (and H+H2-tier) request trace served by an engine
with ``fused=True`` — both stages of the two-stage search in one fused
scan, H folded onto the H2 signature — against a default engine. Gated
(fused H2-tier QPS >= unfused) under ``--check``/``--smoke``; ``--json``
records the numbers (committed as BENCH_fused.json).

A third section compares RT-PREFILTER vs DENSE-SCAN serving of the H2
tier on the MIPS ("tti") workload: an engine with ``prefilter="rt"``
routes each request's probe budget through the sphere-intersection
filter's survivor ranks (``repro.rt``), so geometrically prunable
queries run at nprobe 4/8 instead of 16 — smaller jitted scans, not just
masked lanes. Gated (rt H2-tier QPS >= dense-scan) under
``--check``/``--smoke``; ``--json-rt`` records the numbers (committed as
BENCH_rt.json) including both engines' recall@10 — rt pruning also
IMPROVES ip-workload H2 recall by keeping junk clusters out of stage 1.

A fourth section drives an ``AnnServeFleet`` (2 replicas × 2 shards on 8
emulated host devices) with OPEN-LOOP mixed query+insert traffic —
steady Poisson and bursty arrival profiles at ~4× the fleet's measured
closed-loop capacity — and gates TAIL LATENCY: with bounded admission
queues (``policy="shed"``) the p99 over served requests must not exceed
the unbounded-queue fleet's p99 under the identical trace, and shedding
must actually fire. Open-loop latency counts schedule slip (measured
from the intended arrival time), so an unbounded queue honestly shows
the backlog blow-up that bounded admission exists to cap. ``--json-
fleet`` records the numbers (committed as BENCH_fleet.json); see
docs/fleet.md for the methodology.

A fifth section exercises the PAGED (out-of-core) tier: the bench index
is committed to a throwaway ``ArtifactStore`` generation and served back
through ``repro.serve.paged.PagedAnnServeEngine`` — memory-mapped PQ code
shards behind an LRU hot-cluster cache sized to 1/4 of the shard bytes,
so the dataset is structurally >= 4x the cache and eviction pressure is
real. Gates, under ``--check``/``--smoke``: the paged engine returns
bit-identical ids to a resident engine over the full mixed-tier trace,
the cache actually evicts, and paged QPS stays above a floor (>= 0.25x
resident — paging trades throughput for footprint, bounded). ``--json-
paged`` records the numbers (committed as BENCH_paged.json), including
the exact-rerank tier's recall@10 uplift from the raw-vector file.

A sixth section soaks the LSM FRESHNESS tiers: a tier-enabled engine
(``max_minors > 0``) serves a fixed-signature query stream while insert
batches aimed at a full cluster spill into the exact-scored L0 delta,
promote into PQ-encoded minor generations, and fold incrementally back
into the base — >= 8 full merge cycles driven entirely by the
between-ticks ``MergeScheduler``, no stop-the-world rebuild. Gates,
under ``--check``/``--smoke``: every cycle completes (the minor
generation counter advances per cycle), per-cycle p99 stays <= 2x the
steady-state p99 (merge work must amortize, not stall the serving
path), and the end-state search matches a from-scratch
``rebuild_index`` bit-identically (scores equal; ids equal up to
exact-tie permutation). ``--json-freshness`` records the numbers
(committed as BENCH_freshness.json).

A seventh section gates the OBSERVABILITY layer (``repro.obs``): the
H2-tier trace is replayed through a plain engine and an instrumented one
(metrics registry + span tracer + sampled online-recall probe), and
under ``--check``/``--smoke`` the instrumented engine must return
bit-identical ids AND scores, hold >= 0.95x the plain engine's QPS, and
report an online recall@10 gauge within 0.05 of the offline ground-truth
recall. A fleet, a paged engine, a merge-tier engine and an
``ArtifactStore`` run alongside so the merged registry covers every
``juno_<subsystem>_*`` metric family; the merged dump must pass
``repro.obs.validate_events``. ``--emit-metrics PATH`` writes the JSONL
event dump plus a Prometheus-text sibling snapshot; ``--json-obs``
records the numbers (committed as BENCH_obs.json).

    PYTHONPATH=src python benchmarks/serve_qps.py [--smoke] [--json PATH]
        [--json-rt PATH] [--json-fleet PATH] [--json-paged PATH]
        [--json-freshness PATH] [--json-obs PATH] [--emit-metrics PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# 8 emulated host devices so the 2 replicas x 2 shards fleet topology is
# real (must be set before anything imports jax; run.py never imports this
# module, so the flag stays scoped to serve_qps runs)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks import common  # noqa: E402
from repro.build.rebuild import rebuild_index  # noqa: E402
from repro.build.store import ArtifactStore  # noqa: E402
from repro.core import search  # noqa: E402
from repro.obs import (  # noqa: E402
    MetricsRegistry, Observability, RecallProbe, to_events, validate_events,
    write_jsonl)
from repro.serve.ann import AnnServeEngine  # noqa: E402
from repro.serve.fleet import AnnServeFleet  # noqa: E402
from repro.serve.paged import PagedAnnServeEngine, PagedIndexData  # noqa: E402

# request trace knobs: (n_queries, k, mode, recall_target) cycled over
REQUEST_MIX = [
    (4, 10, "auto", 0.95),   # → H
    (2, 10, "auto", 0.85),   # → H2
    (8, 10, "auto", 0.55),   # → M
    (16, 10, "auto", 0.30),  # → L
    (1, 10, "H", 0.0),
    (4, 10, "H", 0.0),
]


def _make_trace(queries: np.ndarray, n_requests: int):
    trace, pos = [], 0
    for r in range(n_requests):
        nq, k, mode, target = REQUEST_MIX[r % len(REQUEST_MIX)]
        rows = np.take(queries, range(pos, pos + nq), axis=0, mode="wrap")
        trace.append((rows, k, mode, target))
        pos += nq
    return trace


def run(dataset: str = "deep", n_requests: int = 96, insert_every: int = 12,
        insert_batch: int = 8) -> dict:
    pts, queries, index, gt, cfg = common.get_bench_index(dataset)
    queries = np.asarray(queries)
    trace = _make_trace(queries, n_requests)
    rng = np.random.default_rng(0)
    d = queries.shape[1]
    new_points = (np.asarray(pts)[:insert_batch].mean(0)[None] +
                  rng.standard_normal(
                      (n_requests // insert_every * insert_batch, d))
                  ).astype(np.float32)

    # CPU-sized buckets: on this backend per-query cost grows with batch, so
    # right-sizing beats maximal batching (on TPU the default (8,32,128)
    # buckets fill the batch dim instead)
    engine = AnnServeEngine(index, metric=cfg.metric, side_capacity=512,
                            batch_buckets=(8, 16, 32))

    # resolve each request exactly as the engine will, so the baseline runs
    # the same kernels with the same knobs — minus batching and mutability
    resolved = [engine.route(engine.submit(q, k=k, mode=m, recall_target=t))
                for q, k, m, t in trace]
    engine.queue.clear()
    engine.completed.clear()

    # --- warm every jit signature both paths will hit (compile time out):
    # one full untimed replay for the engine (all batch buckets + the
    # side≠None trace), one pass over the request mix for the baselines
    for q, (k, mode, nprobe) in zip([t[0] for t in trace[:len(REQUEST_MIX)]],
                                    resolved[:len(REQUEST_MIX)]):
        search(index, q, nprobe=nprobe, k=k, mode=mode, metric=cfg.metric)
        search(index, q, nprobe=nprobe, k=k, mode=mode, metric=cfg.metric,
               batch=q.shape[0])
    for start in range(0, n_requests, insert_every):
        for (q, k, m, t) in trace[start:start + insert_every]:
            engine.submit(q, k=k, mode=m, recall_target=t)
        engine.run()
    engine.insert(new_points[:insert_batch])
    # FORCE a spill, then replay one full wave: the engine elides an empty
    # side buffer, so side≠None programs are distinct traces — if the first
    # spill happened mid-measurement, every active signature would recompile
    # inside the timed region and could flip the --smoke gate spuriously
    mid = engine.index
    n_clusters = mid.data.ivf.point_ids.shape[0]
    c = int(np.argmin([mid.free_slots(cc) for cc in range(n_clusters)]))
    cent = np.asarray(mid.data.ivf.centroids[c])
    spillers = (cent[None] + 0.01 * rng.standard_normal(
        (mid.free_slots(c) + 1, d))).astype(np.float32)
    engine.insert(spillers)
    assert mid.side_fill >= 1, "warmup spill failed"
    for (q, k, m, t) in trace[:insert_every]:
        engine.submit(q, k=k, mode=m, recall_target=t)
    engine.run()
    engine.completed.clear()
    n_warm_q = engine.stats["queries"]
    total_q = sum(t[0].shape[0] for t in trace)

    # --- baseline A (the acceptance comparator): seed single-shot search()
    # exactly as a seed-repo client would call it per request — default
    # batch (64) padding and all
    t0 = time.perf_counter()
    for (q, _, _, _), (k, mode, nprobe) in zip(trace, resolved):
        search(index, q, nprobe=nprobe, k=k, mode=mode, metric=cfg.metric)
    t_base = time.perf_counter() - t0
    base_qps = total_q / t_base

    # --- baseline B (informational): single-shot with exact-size batches --
    t0 = time.perf_counter()
    for (q, _, _, _), (k, mode, nprobe) in zip(trace, resolved):
        search(index, q, nprobe=nprobe, k=k, mode=mode, metric=cfg.metric,
               batch=q.shape[0])
    exact_qps = total_q / (time.perf_counter() - t0)

    # --- engine: dynamic batching + interleaved inserts -------------------
    t0 = time.perf_counter()
    ins_pos = insert_batch  # first batch consumed by warmup
    for start in range(0, n_requests, insert_every):
        for (q, k, m, t) in trace[start:start + insert_every]:
            engine.submit(q, k=k, mode=m, recall_target=t)
        engine.run()
        if ins_pos < len(new_points):
            engine.insert(new_points[ins_pos:ins_pos + insert_batch])
            ins_pos += insert_batch
    t_eng = time.perf_counter() - t0
    eng_qps = total_q / t_eng
    lat = engine.latency_stats()

    common.emit("serve_qps.baseline_single_shot", t_base / n_requests * 1e6,
                f"qps={base_qps:.0f}")
    common.emit("serve_qps.baseline_exact_batch", 0.0,
                f"qps={exact_qps:.0f}")
    common.emit("serve_qps.engine_mixed", t_eng / n_requests * 1e6,
                f"qps={eng_qps:.0f};speedup={eng_qps / base_qps:.2f}x;"
                f"p50_ms={lat['p50'] * 1e3:.1f};p95_ms={lat['p95'] * 1e3:.1f};"
                f"inserted={engine.stats['inserts']};"
                f"side_fill={engine.index.side_fill}")
    common.emit("serve_qps.batching",
                engine.stats["queries"] - n_warm_q,
                f"ticks={engine.stats['ticks']};"
                f"signatures={len(engine.stats['signatures'])};"
                f"padded_rows={engine.stats['padded_rows']}")
    fused = run_fused_tiers(index, queries, cfg)
    return {"base_qps": base_qps, "eng_qps": eng_qps, "lat": lat,
            "fused": fused}


# high-recall request mix: (n_queries, k, recall_target); >= 0.9 routes to
# the H tier, [0.8, 0.9) to H2 — exactly the tiers a fused engine serves
# through the fused two-stage kernel path
HIGH_RECALL_MIX = [(4, 10, 0.95), (2, 10, 0.85), (1, 10, 0.92),
                   (4, 10, 0.85), (8, 10, 0.88), (2, 10, 0.97)]


def run_fused_tiers(index, queries: np.ndarray, cfg,
                    n_requests: int = 48) -> dict:
    """Fused vs unfused serving of the high-recall tiers (query-only).

    Two traces: the H2 tier alone (the acceptance gate: fused must be at
    least as fast), and the combined H+H2 tier (where the fused engine
    additionally coalesces both tiers onto one jit signature)."""
    out = {}
    for tag, lo, hi in [("h2_tier", 0.8, 0.9), ("h_h2_tier", 0.8, 1.1)]:
        mix = [m for m in HIGH_RECALL_MIX if lo <= m[2] < hi]
        trace, pos = [], 0
        for r in range(n_requests):
            nq, k, target = mix[r % len(mix)]
            rows = np.take(queries, range(pos, pos + nq), axis=0, mode="wrap")
            trace.append((rows, k, target))
            pos += nq
        total_q = sum(t[0].shape[0] for t in trace)

        qps = {}
        for name, fused in [("unfused", False), ("fused", True)]:
            eng = AnnServeEngine(index, metric=cfg.metric, fused=fused,
                                 batch_buckets=(8, 16, 32))
            for _ in range(2):  # warm every signature+bucket, then time
                for (q, k, t) in trace:
                    eng.submit(q, k=k, recall_target=t)
                eng.run()
            t0 = time.perf_counter()
            for (q, k, t) in trace:
                eng.submit(q, k=k, recall_target=t)
            eng.run()
            dt = time.perf_counter() - t0
            qps[name] = total_q / dt
        speedup = qps["fused"] / qps["unfused"]
        common.emit(f"serve_qps.{tag}", 0.0,
                    f"fused_qps={qps['fused']:.0f};"
                    f"unfused_qps={qps['unfused']:.0f};"
                    f"speedup={speedup:.2f}x")
        out[tag] = {"fused_qps": qps["fused"], "unfused_qps": qps["unfused"],
                    "speedup": speedup}
    return out


# rt-prefilter request mix: H2-tier recall targets, SINGLE-query requests —
# the router shrinks a request to the max survivor rank over its queries,
# so the online-serving shape (point lookups) is where the shrink fires;
# the dynamic batcher still coalesces same-signature requests into buckets
RT_MIX = [(1, 10, 0.85), (1, 10, 0.88), (1, 10, 0.82), (1, 10, 0.85)]


def run_rt_prefilter(n_requests: int = 96) -> dict:
    """RT-prefilter vs dense-scan serving of the H2 tier (query-only).

    Runs on the "tti" (MIPS) index — the workload whose ray-plane
    geometry the sphere test prunes well (DEEP-like l2 clusters overlap
    in the projection, so there the router rarely shrinks; that neutral
    result is the documented trade-off, docs/benchmarks.md). Timing is
    the median of 3 replay passes per engine; recall@10 of both engines
    is recorded alongside (rt must not trade recall for its throughput
    — on this workload it gains both).
    """
    pts, queries, index, gt, cfg = common.get_bench_index("tti")
    queries = np.asarray(queries)
    gt10 = np.asarray(gt)[:, :10]
    trace, pos = [], 0
    for r in range(n_requests):
        nq, k, target = RT_MIX[r % len(RT_MIX)]
        rows = np.take(queries, range(pos, pos + nq), axis=0, mode="wrap")
        trace.append((rows, k, target))
        pos += nq
    total_q = sum(t[0].shape[0] for t in trace)

    engines, times = {}, {}
    for name, kw in [("scan", {}), ("rt", dict(prefilter="rt"))]:
        eng = AnnServeEngine(index, metric=cfg.metric,
                             batch_buckets=(8, 16, 32), **kw)
        for _ in range(2):   # warm every signature+bucket the trace hits
            for (q, k, t) in trace:
                eng.submit(q, k=k, recall_target=t)
            eng.run()
        engines[name], times[name] = eng, []
    # interleave the timed passes: this box's load drifts on the second
    # scale, so back-to-back blocks would hand one engine a quiet machine
    for _ in range(3):
        for name, eng in engines.items():
            t0 = time.perf_counter()
            for (q, k, t) in trace:
                eng.submit(q, k=k, recall_target=t)
            eng.run()
            times[name].append(time.perf_counter() - t0)
    out = {}
    for name, eng in engines.items():
        qps = total_q / sorted(times[name])[1]
        req = eng.submit(queries, k=10, mode="H2")
        eng.run()
        hits = (req.ids[:, :, None] == gt10[:, None, :]).any(-1)
        shrunk = sum(n for (sk, sm, sn, sb), n
                     in eng.stats["signatures"].items() if sn < 16)
        out[name] = {"qps": qps, "recall10": float(hits.mean()),
                     "shrunk_calls": int(shrunk)}
    speedup = out["rt"]["qps"] / out["scan"]["qps"]
    common.emit("serve_qps.rt_h2_tier", 0.0,
                f"rt_qps={out['rt']['qps']:.0f};"
                f"scan_qps={out['scan']['qps']:.0f};"
                f"speedup={speedup:.2f}x;"
                f"rt_recall10={out['rt']['recall10']:.3f};"
                f"scan_recall10={out['scan']['recall10']:.3f};"
                f"shrunk_calls={out['rt']['shrunk_calls']}")
    return {"dataset": "tti", "speedup": speedup, **out}


def run_fused3(n_requests: int = 96) -> dict:
    """Single-residency three-stage serving vs its composition baselines.

    Replays the rt-prefilter H2 trace (same "tti" workload — the geometry
    the sphere test prunes) through four engines:

    * ``fused3``   — ``fused=True, prefilter="rt"``: the three-stage
      RT→hit-count→ADC kernel path, probe-budget shrinking intact.
    * ``composed`` — same engine with ``fused3=False``: the rt mask
      applied OUTSIDE the fused scan (the exact path fused3 replaces).
    * ``fused``    — fused two-stage only, dense probe scan.
    * ``rt``       — rt prefilter only, composed (unfused) two-stage.

    Gates: (1) the three-stage engine's ids AND scores are bit-equal to
    the composed engine's on the full query batch — folding the sphere
    walk into the kernel is a scheduling change, never a semantics
    change; (2) three-stage H2 QPS >= max(fused-only, rt-only) — the
    single residency must compound both prior speedups, not trade one
    for the other. Timing is the median of 3 interleaved replay passes.
    """
    pts, queries, index, gt, cfg = common.get_bench_index("tti")
    queries = np.asarray(queries)
    gt10 = np.asarray(gt)[:, :10]
    trace, pos = [], 0
    for r in range(n_requests):
        nq, k, target = RT_MIX[r % len(RT_MIX)]
        rows = np.take(queries, range(pos, pos + nq), axis=0, mode="wrap")
        trace.append((rows, k, target))
        pos += nq
    total_q = sum(t[0].shape[0] for t in trace)

    variants = [
        ("fused3", dict(fused=True, prefilter="rt")),
        ("composed", dict(fused=True, prefilter="rt", fused3=False)),
        ("fused", dict(fused=True)),
        ("rt", dict(prefilter="rt")),
    ]
    engines, times = {}, {}
    for name, kw in variants:
        eng = AnnServeEngine(index, metric=cfg.metric,
                             batch_buckets=(8, 16, 32), **kw)
        for _ in range(2):   # warm every signature+bucket the trace hits
            for (q, k, t) in trace:
                eng.submit(q, k=k, recall_target=t)
            eng.run()
        engines[name], times[name] = eng, []
    # interleaved timed passes (box-load drift; see run_rt_prefilter)
    for _ in range(3):
        for name, eng in engines.items():
            t0 = time.perf_counter()
            for (q, k, t) in trace:
                eng.submit(q, k=k, recall_target=t)
            eng.run()
            times[name].append(time.perf_counter() - t0)

    out = {}
    reqs = {}
    for name, eng in engines.items():
        qps = total_q / sorted(times[name])[1]
        req = eng.submit(queries, k=10, mode="H2")
        eng.run()
        reqs[name] = req
        hits = (req.ids[:, :, None] == gt10[:, None, :]).any(-1)
        out[name] = {"qps": qps, "recall10": float(hits.mean())}
    ids_equal = bool(np.array_equal(reqs["fused3"].ids,
                                    reqs["composed"].ids))
    scores_equal = bool(np.array_equal(reqs["fused3"].scores,
                                       reqs["composed"].scores))
    baseline = max(out["fused"]["qps"], out["rt"]["qps"])
    qps_ok = out["fused3"]["qps"] >= baseline
    gate_ok = ids_equal and scores_equal and qps_ok
    common.emit("serve_qps.fused3_h2_tier", 0.0,
                f"fused3_qps={out['fused3']['qps']:.0f};"
                f"composed_qps={out['composed']['qps']:.0f};"
                f"fused_qps={out['fused']['qps']:.0f};"
                f"rt_qps={out['rt']['qps']:.0f};"
                f"speedup_vs_best={out['fused3']['qps'] / baseline:.2f}x;"
                f"ids_equal={ids_equal};scores_equal={scores_equal};"
                f"gate={'OK' if gate_ok else 'FAIL'}")
    return {"dataset": "tti", "ids_equal": ids_equal,
            "scores_equal": scores_equal,
            "speedup_vs_best": out["fused3"]["qps"] / baseline,
            "gate_ok": gate_ok, **out}


def run_paged(n_requests: int = 96, exact_rerank: int = 40) -> dict:
    """Paged (out-of-core) vs resident serving of the mixed-tier trace.

    Commits the bench index to a throwaway ``ArtifactStore`` generation,
    reopens it memory-mapped with a hot-cluster cache of 1/4 the PQ
    shard bytes (dataset >= 4x cache by construction), and replays the
    same mixed-mode trace through a ``PagedAnnServeEngine`` and a
    resident ``AnnServeEngine``. The paged engine must return the
    resident engine's ids bit-for-bit (the scoring tail is shared code,
    so this is an equality — not a tolerance — gate), must actually
    evict (otherwise the 4x pressure claim is vacuous), and must hold
    >= 0.25x resident QPS. Timing is the median of 3 interleaved
    passes. The exact-rerank tier's recall@10 against the raw-vector
    file is recorded alongside (informational — it trades extra reads
    for exact final ordering).
    """
    pts, queries, index, gt, cfg = common.get_bench_index("deep")
    queries = np.asarray(queries)
    gt10 = np.asarray(gt)[:, :10]
    trace = _make_trace(queries, n_requests)
    total_q = sum(t[0].shape[0] for t in trace)

    tmp = tempfile.mkdtemp(prefix="bench_paged_")
    try:
        store = ArtifactStore(tmp)
        version = store.put("bench", index, cfg)
        vec_path = os.path.join(tmp, "vectors.npy")
        np.save(vec_path, np.asarray(pts, np.float32))
        cluster_bytes = int(np.asarray(index.cluster_codes).nbytes)
        cache_bytes = max(1, cluster_bytes // 4)      # dataset >= 4x cache
        paged = PagedIndexData(store.path("bench", version),
                               cache_bytes=cache_bytes, expect_config=cfg,
                               vectors=vec_path)

        engines = {
            "resident": AnnServeEngine(index, metric=cfg.metric,
                                       batch_buckets=(8, 16, 32)),
            "paged": PagedAnnServeEngine(paged, metric=cfg.metric,
                                         batch_buckets=(8, 16, 32)),
        }
        # warm every signature+bucket AND check id parity request-by-request
        reqs = {}
        for name, eng in engines.items():
            for _ in range(2):
                for (q, k, m, t) in trace:
                    eng.submit(q, k=k, mode=m, recall_target=t)
                eng.run()
            reqs[name] = [eng.submit(q, k=k, mode=m, recall_target=t)
                          for (q, k, m, t) in trace]
            eng.run()
        ids_equal = all(np.array_equal(rp.ids, rr.ids) for rp, rr
                        in zip(reqs["paged"], reqs["resident"]))

        times = {name: [] for name in engines}
        # interleave the timed passes (same rationale as run_rt_prefilter)
        for _ in range(3):
            for name, eng in engines.items():
                t0 = time.perf_counter()
                for (q, k, m, t) in trace:
                    eng.submit(q, k=k, mode=m, recall_target=t)
                eng.run()
                times[name].append(time.perf_counter() - t0)
        qps = {name: total_q / sorted(ts)[1] for name, ts in times.items()}
        ratio = qps["paged"] / qps["resident"]
        cache = engines["paged"].cache_stats()

        # exact-rerank tier: same paged generation, final top-C re-scored
        # against the memory-mapped raw vectors (recall uplift on record)
        recall = {}
        for name, eng in [
                ("paged", engines["paged"]),
                ("rerank", PagedAnnServeEngine(paged, metric=cfg.metric,
                                               exact_rerank=exact_rerank,
                                               batch_buckets=(8, 16, 32)))]:
            req = eng.submit(queries, k=10, mode="H2")
            eng.run()
            hits = (req.ids[:, :, None] == gt10[:, None, :]).any(-1)
            recall[name] = float(hits.mean())

        gate_ok = (ids_equal and cache["evictions"] > 0 and ratio >= 0.25)
        common.emit("serve_qps.paged_tier", 0.0,
                    f"paged_qps={qps['paged']:.0f};"
                    f"resident_qps={qps['resident']:.0f};ratio={ratio:.2f}x;"
                    f"ids_equal={ids_equal};evictions={cache['evictions']};"
                    f"hit_rate={cache['hits'] / max(1, cache['hits'] + cache['misses']):.2f};"
                    f"recall10={recall['paged']:.3f};"
                    f"rerank_recall10={recall['rerank']:.3f};"
                    f"gate={'OK' if gate_ok else 'FAIL'}")
        return {"paged_qps": qps["paged"], "resident_qps": qps["resident"],
                "qps_ratio": ratio, "qps_floor": 0.25,
                "ids_equal": ids_equal, "cluster_bytes": cluster_bytes,
                "cache_bytes": cache_bytes,
                "dataset_over_cache": cluster_bytes / cache_bytes,
                "cache": cache, "exact_rerank": exact_rerank,
                "recall10": recall, "gate_ok": gate_ok}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# freshness soak: request sizes of one query wave, all on ONE jit
# signature (k=10, mode "M", nprobe 8) so per-cycle p99 measures merge
# interference — not mode mix
FRESH_WAVE = (1, 2, 4, 1)


def run_freshness(n_cycles: int = 8, waves_per_cycle: int = 8) -> dict:
    """Sustained mixed insert+query load across >= ``n_cycles`` merge cycles.

    One cycle: fill the L0 delta with inserts aimed at a structurally
    full cluster (every point spills), let the between-ticks scheduler
    promote the full L0 into a PQ-encoded minor generation, retire an
    equal batch of that cluster's base points, and let subsequent ticks
    fold the minor back into the freed slots — the index returns to
    quiescence with the same occupancy, ready for the next cycle. The
    query stream never stops; per-cycle latency is measured over it.

    Gates (all recorded in the returned dict): the minor-generation
    counter advances at least once per cycle (merges really ran), each
    cycle's p99 <= 2x the steady-state p99 (same jitted program, delta
    tier warm but quiescent), and the end state searches bit-identically
    to ``rebuild_index`` (scores equal; ids equal up to permutation
    within exactly-tied scores).
    """
    pts, queries, index, gt, cfg = common.get_bench_index("deep")
    queries = np.asarray(queries)
    rng = np.random.default_rng(3)
    d = queries.shape[1]

    eng = AnnServeEngine(index, metric=cfg.metric, batch_buckets=(8,),
                         side_capacity=16, max_minors=3,
                         merge_clusters_per_step=8)
    mid = eng.index
    n_clusters = mid.data.ivf.point_ids.shape[0]
    c = int(np.argmin([mid.free_slots(cc) for cc in range(n_clusters)]))
    cent = np.asarray(mid.data.ivf.centroids[c])

    def near_c(n: int) -> np.ndarray:
        return (cent[None] + 0.01 * rng.standard_normal(
            (n, d))).astype(np.float32)

    def wave() -> None:
        pos = rng.integers(0, queries.shape[0])
        for nq in FRESH_WAVE:
            rows = np.take(queries, range(pos, pos + nq), axis=0,
                           mode="wrap")
            eng.submit(rows, k=10, mode="M", nprobe=8)
            pos += nq
        eng.run()

    # fill the target cluster so every cycle's inserts spill into L0
    if mid.free_slots(c):
        eng.insert(near_c(mid.free_slots(c)))
    assert mid.free_slots(c) == 0, "freshness target cluster not full"
    # retirement pool: base-resident ids of the target cluster; cycles
    # retire from the head and append their own (folded) inserts
    row_ids = np.asarray(mid.data.ivf.point_ids[c])
    row_valid = np.asarray(mid.data.ivf.valid[c])
    pool = [int(p) for p in row_ids[row_valid]]

    def retire(n: int) -> list[int]:
        victims, keep = [], []
        while pool and len(victims) < n:
            pid = pool.pop(0)
            # delta-resident ids free no base slots yet; recycle them
            (victims if mid._loc.get(pid, (-9, 0))[0] >= 0
             else keep).append(pid)
        pool.extend(keep)
        assert len(victims) == n, "retirement pool exhausted"
        return victims

    # --- warm every program + merge path untimed: one full cycle ---------
    wave()                                   # empty-delta program
    warm_ids = eng.insert(near_c(mid.side.capacity))   # L0 fills
    for _ in range(2):
        wave()                               # ticks promote L0 -> minor
    eng.delete(retire(len(warm_ids)))        # open fold targets
    for _ in range(4):
        wave()                               # ticks fold minor -> base
    pool.extend(warm_ids)
    assert mid._minor_gen >= 1, "warmup never promoted"

    # --- steady state: quiescent delta (2 pinned L0 points keep the same
    # combined-view program hot without crossing the promote threshold) --
    pool.extend(eng.insert(near_c(2)))
    eng.completed.clear()
    for _ in range(2 * waves_per_cycle):
        wave()
    steady = eng.latency_stats()
    steady_p99 = steady["p99"]

    # --- the soak: n_cycles full spill -> promote -> fold cycles ---------
    gen0, folded0 = mid._minor_gen, eng.scheduler.stats["folded"]
    cycles = []
    for _ in range(n_cycles):
        eng.completed.clear()
        need = mid.side.capacity - mid.side_fill
        new_ids = eng.insert(near_c(need))
        half = waves_per_cycle // 2
        for _ in range(half):
            wave()                           # promotion fires between ticks
        # retire exactly one full L0 of base points: the promoted minor is
        # always full, so every fold is the single jit-warmed full-capacity
        # scatter shape and the cluster's occupancy is cycle-invariant
        eng.delete(retire(int(mid.side.capacity)))
        for _ in range(waves_per_cycle - half):
            wave()                           # folds drain between ticks
        lat = eng.latency_stats()
        cycles.append({"p99": lat["p99"], "p50": lat["p50"],
                       "minor_gen": mid._minor_gen,
                       "delta_fill": mid.delta_fill})
        pool.extend(new_ids)

    cycles_promoted = mid._minor_gen - gen0
    merges_ok = cycles_promoted >= n_cycles
    tail_ratio = max(cy["p99"] for cy in cycles) / steady_p99
    tail_ok = tail_ratio <= 2.0

    # --- end-state parity vs a from-scratch stop-the-world rebuild -------
    qq = np.concatenate([queries[:16], near_c(4)], axis=0)
    s0, i0 = mid.search(qq, nprobe=min(16, n_clusters), k=10, mode="H")
    rebuilt = rebuild_index(mid)
    s1, i1 = search(rebuilt, qq, nprobe=min(16, n_clusters), k=10,
                    mode="H", metric=cfg.metric, batch=qq.shape[0])
    s0, i0, s1, i1 = (np.asarray(x) for x in (s0, i0, s1, i1))
    scores_equal = np.array_equal(s0, s1)
    ids_strict = np.array_equal(i0, i1)
    ties_ok = scores_equal
    if scores_equal and not ids_strict:
        # lax.top_k may permute EXACTLY tied scores; ids must still agree
        # as sets within every non-boundary score level
        for r in range(s0.shape[0]):
            boundary = s0[r, -1]
            for v in np.unique(s0[r][s0[r] != boundary]):
                if set(i0[r][s0[r] == v]) != set(i1[r][s1[r] == v]):
                    ties_ok = False
    parity_ok = scores_equal and ties_ok

    gate_ok = merges_ok and tail_ok and parity_ok
    common.emit("serve_qps.freshness_soak", 0.0,
                f"cycles={cycles_promoted}/{n_cycles};"
                f"steady_p99_ms={steady_p99 * 1e3:.1f};"
                f"worst_cycle_p99_ms={max(cy['p99'] for cy in cycles) * 1e3:.1f};"
                f"tail_ratio={tail_ratio:.2f};"
                f"folded={eng.scheduler.stats['folded'] - folded0};"
                f"parity={'bit' if ids_strict else 'tie' if parity_ok else 'FAIL'};"
                f"gate={'OK' if gate_ok else 'FAIL'}")
    return {"n_cycles": n_cycles, "cycles_promoted": cycles_promoted,
            "waves_per_cycle": waves_per_cycle,
            "side_capacity": int(mid.side.capacity),
            "steady_p99_ms": steady_p99 * 1e3,
            "tail_ratio": tail_ratio, "tail_bound": 2.0,
            "cycles": [{"p99_ms": cy["p99"] * 1e3, "p50_ms": cy["p50"] * 1e3,
                        "minor_gen": cy["minor_gen"],
                        "delta_fill": cy["delta_fill"]} for cy in cycles],
            "scheduler": dict(eng.scheduler.stats),
            "scores_equal": scores_equal, "ids_strict": ids_strict,
            "parity_ok": parity_ok, "merges_ok": merges_ok,
            "tail_ok": tail_ok, "gate_ok": gate_ok}


def run_obs(n_requests: int = 63, emit: str | None = None) -> dict:
    """Instrumented vs plain serving of the H2 tier, plus metric coverage.

    The cost side: the H2-tier trace replayed through a plain engine and
    one carrying a full observability bundle (registry + tracer + recall
    probe sampling every 8th H2 request). Instrumentation is host-side
    bookkeeping only, so the gates are strict: ids AND scores bit-equal,
    instrumented QPS >= 0.95x plain (best of 9 interleaved passes), and
    the online recall@10 gauge within 0.05 of the offline recall
    against the committed ground truth.

    The coverage side: a 2-replica fleet (``obs=True``), a paged engine
    over a throwaway ``ArtifactStore`` generation, and a merge-tier
    engine driven through an L0 spill all run briefly so the merged
    registry contains every ``juno_<subsystem>_*`` family; the combined
    event dump must validate clean. ``emit`` writes the JSONL dump and a
    ``.txt`` Prometheus-text snapshot next to it.
    """
    pts, queries, index, gt, cfg = common.get_bench_index("deep")
    pts = np.asarray(pts, np.float32)
    queries = np.asarray(queries)
    gt10 = np.asarray(gt)[:, :10]
    mix = [m for m in HIGH_RECALL_MIX if 0.8 <= m[2] < 0.9]
    trace, pos = [], 0
    for r in range(n_requests):
        nq, k, target = mix[r % len(mix)]
        rows = np.take(queries, range(pos, pos + nq), axis=0, mode="wrap")
        trace.append((rows, k, target))
        pos += nq
    total_q = sum(t[0].shape[0] for t in trace)

    # the default n_requests is chosen coprime to the probe cadence: the
    # deterministic round-robin sampler then rotates through DIFFERENT
    # requests on every replay pass instead of aliasing onto the same
    # few (which would bias the online estimate by whatever those
    # particular queries happen to score)
    probe = RecallProbe(pts, k=10, every=8, metric=cfg.metric)
    obs = Observability(recall=probe)
    engines = {
        "plain": AnnServeEngine(index, metric=cfg.metric,
                                batch_buckets=(8, 16, 32)),
        "obs": AnnServeEngine(index, metric=cfg.metric,
                              batch_buckets=(8, 16, 32), obs=obs),
    }
    # warm every signature+bucket, then check parity request-by-request
    reqs = {}
    for name, eng in engines.items():
        for _ in range(2):
            for (q, k, t) in trace:
                eng.submit(q, k=k, recall_target=t)
            eng.run()
        reqs[name] = [eng.submit(q, k=k, recall_target=t)
                      for (q, k, t) in trace]
        eng.run()
    ids_equal = all(np.array_equal(a.ids, b.ids)
                    for a, b in zip(reqs["obs"], reqs["plain"]))
    scores_equal = all(np.array_equal(a.scores, b.scores)
                       for a, b in zip(reqs["obs"], reqs["plain"]))

    times = {name: [] for name in engines}
    # interleaved timed passes (box-load drift; see run_rt_prefilter) —
    # 9 of them, scored BEST-of rather than median: the effect under
    # test is a few-percent overhead bound, far below this box's
    # pass-to-pass load swing, and each engine's best pass is its
    # quiet-machine cost — the number the bound is actually about
    for _ in range(9):
        for name, eng in engines.items():
            t0 = time.perf_counter()
            for (q, k, t) in trace:
                eng.submit(q, k=k, recall_target=t)
            eng.run()
            times[name].append(time.perf_counter() - t0)
    qps = {name: total_q / min(ts) for name, ts in times.items()}
    ratio = qps["obs"] / qps["plain"]

    # online (sampled gauge) vs offline (full ground truth) recall@10
    req = engines["plain"].submit(queries, k=10, mode="H2")
    engines["plain"].run()
    hits = (np.asarray(req.ids)[:, :, None] == gt10[:, None, :]).any(-1)
    offline = float(hits.mean())
    online = probe.estimate("H2")
    recall_delta = abs(online - offline)

    # --- coverage: run every instrumented subsystem at least briefly -----
    fleet = AnnServeFleet(index, n_replicas=2, shards_per_replica=1,
                          metric=cfg.metric, batch_buckets=(8,), obs=True)
    for i in range(8):
        fleet.submit(np.take(queries, range(i * 2, i * 2 + 2), axis=0,
                             mode="wrap"), k=10, mode="M", nprobe=8)
    fleet.run()

    # merge tiers: fill the fullest cluster, spill one full L0, let the
    # between-ticks scheduler promote it (juno_merge_* series)
    rng = np.random.default_rng(5)
    d = queries.shape[1]
    mobs = Observability()
    meng = AnnServeEngine(index, metric=cfg.metric, batch_buckets=(8,),
                          side_capacity=8, max_minors=2, obs=mobs)
    mid = meng.index
    n_clusters = mid.data.ivf.point_ids.shape[0]
    c = int(np.argmin([mid.free_slots(cc) for cc in range(n_clusters)]))
    cent = np.asarray(mid.data.ivf.centroids[c])
    fill = (cent[None] + 0.01 * rng.standard_normal(
        (mid.free_slots(c) + mid.side.capacity, d))).astype(np.float32)
    meng.insert(fill)
    for _ in range(4):
        meng.submit(queries[:2], k=10, mode="M", nprobe=8)
        meng.run()

    # paged serving off a throwaway store generation (juno_store_*,
    # juno_cache_*, juno_paged_* series)
    tmp = tempfile.mkdtemp(prefix="bench_obs_")
    try:
        sreg = MetricsRegistry()
        store = ArtifactStore(tmp, registry=sreg)
        version = store.put("bench", index, cfg)
        store.verify("bench", version)
        cluster_bytes = int(np.asarray(index.cluster_codes).nbytes)
        paged = PagedIndexData(store.path("bench", version),
                               cache_bytes=max(1, cluster_bytes // 4),
                               expect_config=cfg)
        pobs = Observability()
        peng = PagedAnnServeEngine(paged, metric=cfg.metric,
                                   batch_buckets=(8, 16, 32), obs=pobs)
        for (q, k, t) in trace[:8]:
            peng.submit(q, k=k, recall_target=t)
        peng.run()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    merged = MetricsRegistry()
    for reg in (obs.registry, fleet.merged_registry(), mobs.registry,
                pobs.registry, sreg):
        merged.merge(reg)
    prefixes = ("juno_engine_", "juno_fleet_", "juno_cache_", "juno_paged_",
                "juno_merge_", "juno_store_", "juno_recall_")
    names = {name for name, _, _ in merged.metrics()}
    missing = [p for p in prefixes
               if not any(n.startswith(p) for n in names)]

    events = to_events(merged, obs.tracer,
                       extra_meta={"bench": "serve_qps.run_obs",
                                   "dataset": "deep"})
    for tracer in (fleet.obs.tracer, mobs.tracer, pobs.tracer):
        events.extend(tracer.to_events())
    problems = validate_events(events)
    if emit:
        write_jsonl(emit, events)
        snap = os.path.splitext(emit)[0] + ".txt"
        with open(snap, "w") as fh:
            fh.write(merged.render_text())

    gate_ok = (ratio >= 0.95 and ids_equal and scores_equal
               and recall_delta <= 0.05 and not missing and not problems)
    common.emit("serve_qps.obs_h2_tier", 0.0,
                f"obs_qps={qps['obs']:.0f};plain_qps={qps['plain']:.0f};"
                f"ratio={ratio:.2f}x;ids_equal={ids_equal};"
                f"scores_equal={scores_equal};"
                f"recall10_online={online:.3f};"
                f"recall10_offline={offline:.3f};"
                f"series={len(merged)};events={len(events)};"
                f"problems={len(problems)};"
                f"gate={'OK' if gate_ok else 'FAIL'}")
    return {"obs_qps": qps["obs"], "plain_qps": qps["plain"],
            "qps_ratio": ratio, "qps_floor": 0.95,
            "ids_equal": ids_equal, "scores_equal": scores_equal,
            "recall10_online": online, "recall10_offline": offline,
            "recall_delta": recall_delta, "recall_bound": 0.05,
            "series": len(merged), "n_events": len(events),
            "missing_prefixes": missing, "validate_problems": problems,
            "gate_ok": gate_ok}


# fleet traffic: (n_queries,) request sizes cycled over, all on ONE jit
# signature (k=10, mode "M", nprobe 8) so the tail measures queueing and
# batching — not compile blips or mode mix — under overload
FLEET_MIX = (1, 2, 4, 1)
FLEET_INSERT_EVERY = 24     # an insert batch every this many events
FLEET_INSERT_ROWS = 4


def _fleet_arrivals(n_events: int, rate: float, profile: str,
                    rng: np.random.Generator) -> np.ndarray:
    """Arrival-time offsets (seconds) for an open-loop trace.

    "steady" draws i.i.d. exponential gaps (Poisson arrivals at
    ``rate``). "bursty" alternates bursts of 24 events at 4x rate with
    silences of 18/rate, which preserves the long-run rate while
    concentrating arrivals — the profile bounded admission exists for.
    """
    if profile == "steady":
        gaps = rng.exponential(1.0 / rate, n_events)
    elif profile == "bursty":
        gaps = []
        while len(gaps) < n_events:
            gaps.extend(rng.exponential(1.0 / (4 * rate),
                                        min(24, n_events - len(gaps))))
            gaps[-1] += 18.0 / rate      # inter-burst silence
        gaps = np.asarray(gaps[:n_events])
    else:
        raise ValueError(f"unknown profile {profile!r}")
    return np.cumsum(gaps)


def _fleet_events(queries: np.ndarray, new_points: np.ndarray,
                  n_events: int):
    """Mixed query+insert event payloads (arrival times added per profile)."""
    events, pos, ins = [], 0, 0
    for i in range(n_events):
        if i and i % FLEET_INSERT_EVERY == 0 and ins < len(new_points):
            events.append(("insert",
                           new_points[ins:ins + FLEET_INSERT_ROWS]))
            ins += FLEET_INSERT_ROWS
            continue
        nq = FLEET_MIX[i % len(FLEET_MIX)]
        rows = np.take(queries, range(pos, pos + nq), axis=0, mode="wrap")
        events.append(("query", rows))
        pos += nq
    return events


def _fleet_replay(fleet: AnnServeFleet, events, offsets) -> dict:
    """Open-loop replay: submit each event at its intended time, stepping
    the fleet while waiting; latency is measured from the INTENDED arrival
    (schedule slip counts against the server — no coordinated omission)."""
    fleet.reset_metrics()
    base = time.perf_counter()
    for (kind, payload), t_off in zip(events, offsets):
        target = base + t_off
        while time.perf_counter() < target:
            if fleet.pending:
                fleet.step()
            else:
                time.sleep(min(2e-4, max(0.0, target - time.perf_counter())))
        if kind == "insert":
            fleet.insert(payload)
        else:
            fleet.submit(payload, k=10, mode="M", nprobe=8, t_arrival=target)
    fleet.run()
    return fleet.latency_summary()


def _warm_fleet(fleet: AnnServeFleet, queries: np.ndarray,
                rng: np.random.Generator) -> None:
    """Warm the single fleet signature on every replica, spill included.

    Forces one side-buffer spill first so the side≠None search trace is
    the one timed throughout (the sharded path always passes the side
    buffer, but the unsharded fallback elides an empty one — a first
    spill mid-measurement would recompile inside the timed region).
    """
    eng = fleet.engines[0]
    n_clusters = eng.index.data.ivf.point_ids.shape[0]
    c = int(np.argmin([eng.index.free_slots(cc) for cc in range(n_clusters)]))
    cent = np.asarray(eng.index.data.ivf.centroids[c])
    spillers = (cent[None] + 0.01 * rng.standard_normal(
        (eng.index.free_slots(c) + 1, queries.shape[1]))).astype(np.float32)
    fleet.insert(spillers)
    assert eng.index.side_fill >= 1, "fleet warmup spill failed"
    for _ in range(2):
        for i in range(12):
            fleet.submit(np.take(queries, range(i * 4, i * 4 + 4), axis=0,
                                 mode="wrap"), k=10, mode="M", nprobe=8)
        fleet.run()


def run_fleet(n_events: int = 120) -> dict:
    """Tail latency of bounded vs unbounded admission under overload.

    Topology: 2 replicas × 2 shards when >= 4 devices are visible (the
    CI/default path — the module forces 8 emulated host devices), else
    2 unsharded replicas. Method: measure the fleet's CLOSED-LOOP
    capacity (rows/s with the trace submitted all at once), then replay
    the mixed query+insert trace open-loop at ~4× that rate — a
    structural overload no calibration error can undo — through two
    identically-warmed fleets: bounded admission (``policy="shed"``,
    per-replica queue ≈ 0.15 s of capacity) and unbounded
    (``policy="queue"``). Gate, per arrival profile: bounded p99 <=
    unbounded p99 AND bounded shed > 0. The unbounded fleet serves
    everything but its tail absorbs the whole backlog drain; the bounded
    fleet converts that tail into explicit typed rejections — the SLO
    trade this layer exists to make (docs/fleet.md).
    """
    import jax

    pts, queries, index, gt, cfg = common.get_bench_index("deep")
    queries = np.asarray(queries)
    rng = np.random.default_rng(7)
    d = queries.shape[1]
    new_points = (np.asarray(pts)[:64].mean(0)[None] + rng.standard_normal(
        (n_events // FLEET_INSERT_EVERY * FLEET_INSERT_ROWS + FLEET_INSERT_ROWS,
         d))).astype(np.float32)
    spr = 2 if jax.device_count() >= 4 else 1
    fleet_kw = dict(n_replicas=2, shards_per_replica=spr,
                    metric=cfg.metric, batch_buckets=(8,))

    events = _fleet_events(queries, new_points, n_events)
    query_rows = sum(p.shape[0] for k, p in events if k == "query")

    # closed-loop capacity: same fleet shape, trace submitted all at once
    calib = AnnServeFleet(index, **fleet_kw)
    _warm_fleet(calib, queries, rng)
    t0 = time.perf_counter()
    for kind, payload in events:
        if kind == "query":
            calib.submit(payload, k=10, mode="M", nprobe=8)
    calib.run()
    capacity = query_rows / (time.perf_counter() - t0)          # rows/s
    mean_rows = query_rows / sum(1 for k, _ in events if k == "query")
    rate = 4.0 * capacity / mean_rows                           # events/s
    # per-replica admission bound = 20 ms of fleet capacity: under 4x
    # overload the backlog reaches ~40% of the trace per replica, far past
    # this bound, so shedding fires structurally — while the bound still
    # caps a served request's queue wait at ~tens of ms
    max_queue = max(8, int(0.02 * capacity))                    # rows/replica

    fleets = {
        "bounded": AnnServeFleet(index, policy="shed", max_queue=max_queue,
                                 **fleet_kw),
        "unbounded": AnnServeFleet(index, policy="queue",
                                   max_queue=1 << 30, **fleet_kw),
    }
    for f in fleets.values():
        _warm_fleet(f, queries, rng)

    out = {"devices": jax.device_count(), "n_replicas": 2,
           "shards_per_replica": spr, "capacity_qps": capacity,
           "overload_rate_qps": rate * mean_rows, "max_queue_rows": max_queue,
           "n_events": n_events, "profiles": {}}
    for profile in ("steady", "bursty"):
        offsets = _fleet_arrivals(len(events), rate, profile,
                                  np.random.default_rng(11))
        # interleave two passes per variant and keep each variant's best-
        # p99 pass: this box's load drifts on the second scale, and the
        # structural effect under test (bounded wait vs backlog drain) is
        # 5-10x — far larger than pass-to-pass drift after interleaving
        passes = {name: [] for name in fleets}
        for _ in range(2):
            for name, f in fleets.items():
                passes[name].append(_fleet_replay(f, events, offsets))
        res = {name: min(ps, key=lambda s: s["p99"])
               for name, ps in passes.items()}
        ok = (res["bounded"]["p99"] <= res["unbounded"]["p99"]
              and res["bounded"]["shed"] > 0)
        res["gate_ok"] = ok
        out["profiles"][profile] = res
        common.emit(f"serve_qps.fleet_{profile}", 0.0,
                    f"bounded_p99_ms={res['bounded']['p99'] * 1e3:.1f};"
                    f"unbounded_p99_ms={res['unbounded']['p99'] * 1e3:.1f};"
                    f"shed={res['bounded']['shed']};"
                    f"served={res['bounded']['served']};"
                    f"gate={'OK' if ok else 'FAIL'}")
    out["gate_ok"] = all(p["gate_ok"] for p in out["profiles"].values())
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="deep",
                    choices=["deep", "sift", "tti"])
    ap.add_argument("--n-requests", type=int, default=96)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-N CI mode; implies --check")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless engine QPS >= single-shot QPS")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write fused-vs-unfused + engine QPS numbers here")
    ap.add_argument("--json-rt", default=None, metavar="PATH",
                    help="write rt-prefilter vs dense-scan numbers here")
    ap.add_argument("--json-fused3", default=None, metavar="PATH",
                    help="write three-stage vs composition-baseline "
                         "numbers here")
    ap.add_argument("--json-fleet", default=None, metavar="PATH",
                    help="write fleet tail-latency numbers here")
    ap.add_argument("--json-paged", default=None, metavar="PATH",
                    help="write paged-vs-resident serving numbers here")
    ap.add_argument("--json-freshness", default=None, metavar="PATH",
                    help="write LSM-freshness merge-cycle soak numbers here")
    ap.add_argument("--json-obs", default=None, metavar="PATH",
                    help="write instrumented-vs-plain observability "
                         "numbers here")
    ap.add_argument("--emit-metrics", default=None, metavar="PATH",
                    help="write the merged juno.obs.v1 JSONL event dump "
                         "here (+ a .txt Prometheus-text snapshot)")
    args = ap.parse_args()
    if args.smoke:
        common.set_smoke_sizes()
    print("name,us_per_call,derived")
    res = run(dataset=args.dataset, n_requests=args.n_requests)
    ok = res["eng_qps"] >= res["base_qps"]
    print(f"# engine {res['eng_qps']:.0f} QPS vs single-shot "
          f"{res['base_qps']:.0f} QPS -> {'OK' if ok else 'REGRESSION'}",
          file=sys.stderr)
    f = res["fused"]["h2_tier"]
    fused_ok = f["fused_qps"] >= f["unfused_qps"]
    print(f"# H2 tier fused {f['fused_qps']:.0f} QPS vs unfused "
          f"{f['unfused_qps']:.0f} QPS -> "
          f"{'OK' if fused_ok else 'REGRESSION'}", file=sys.stderr)
    rt_res = run_rt_prefilter()
    rt_ok = rt_res["rt"]["qps"] >= rt_res["scan"]["qps"]
    print(f"# H2 tier rt-prefilter {rt_res['rt']['qps']:.0f} QPS vs "
          f"dense-scan {rt_res['scan']['qps']:.0f} QPS -> "
          f"{'OK' if rt_ok else 'REGRESSION'}", file=sys.stderr)
    fused3_res = run_fused3()
    fused3_ok = fused3_res["gate_ok"]
    print(f"# H2 tier three-stage {fused3_res['fused3']['qps']:.0f} QPS vs "
          f"max(fused {fused3_res['fused']['qps']:.0f}, "
          f"rt {fused3_res['rt']['qps']:.0f}) QPS, "
          f"ids_equal={fused3_res['ids_equal']}, "
          f"scores_equal={fused3_res['scores_equal']} -> "
          f"{'OK' if fused3_ok else 'REGRESSION'}", file=sys.stderr)
    fleet_res = run_fleet()
    fleet_ok = fleet_res["gate_ok"]
    for prof, pres in fleet_res["profiles"].items():
        print(f"# fleet {prof}: bounded p99 "
              f"{pres['bounded']['p99'] * 1e3:.1f} ms vs unbounded "
              f"{pres['unbounded']['p99'] * 1e3:.1f} ms "
              f"(shed {pres['bounded']['shed']}) -> "
              f"{'OK' if pres['gate_ok'] else 'REGRESSION'}",
              file=sys.stderr)
    paged_res = run_paged(n_requests=args.n_requests)
    paged_ok = paged_res["gate_ok"]
    print(f"# paged tier {paged_res['paged_qps']:.0f} QPS vs resident "
          f"{paged_res['resident_qps']:.0f} QPS "
          f"({paged_res['qps_ratio']:.2f}x, ids_equal="
          f"{paged_res['ids_equal']}, evictions="
          f"{paged_res['cache']['evictions']}) -> "
          f"{'OK' if paged_ok else 'REGRESSION'}", file=sys.stderr)
    fresh_res = run_freshness()
    fresh_ok = fresh_res["gate_ok"]
    print(f"# freshness soak: {fresh_res['cycles_promoted']}/"
          f"{fresh_res['n_cycles']} merge cycles, tail ratio "
          f"{fresh_res['tail_ratio']:.2f} (bound 2.0), rebuild parity "
          f"{'bit' if fresh_res['ids_strict'] else 'tie'} -> "
          f"{'OK' if fresh_ok else 'REGRESSION'}", file=sys.stderr)
    obs_res = run_obs(emit=args.emit_metrics)
    obs_ok = obs_res["gate_ok"]
    print(f"# obs H2 tier instrumented {obs_res['obs_qps']:.0f} QPS vs "
          f"plain {obs_res['plain_qps']:.0f} QPS "
          f"({obs_res['qps_ratio']:.2f}x, ids_equal={obs_res['ids_equal']}, "
          f"recall10 online {obs_res['recall10_online']:.3f} vs offline "
          f"{obs_res['recall10_offline']:.3f}, "
          f"{obs_res['series']} series) -> "
          f"{'OK' if obs_ok else 'REGRESSION'}", file=sys.stderr)
    if args.json_obs:
        with open(args.json_obs, "w") as fh:
            json.dump({"smoke": args.smoke, "backend": "cpu-hostpath",
                       "dataset": "deep", **obs_res},
                      fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.json_freshness:
        with open(args.json_freshness, "w") as fh:
            json.dump({"smoke": args.smoke, "backend": "cpu-hostpath",
                       "dataset": "deep", **fresh_res},
                      fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.json_paged:
        with open(args.json_paged, "w") as fh:
            json.dump({"smoke": args.smoke, "backend": "cpu-hostpath",
                       "dataset": "deep", **paged_res},
                      fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.json_fused3:
        with open(args.json_fused3, "w") as fh:
            json.dump({"smoke": args.smoke, "backend": "cpu-hostpath",
                       "h2_tier": fused3_res}, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.json_fleet:
        with open(args.json_fleet, "w") as fh:
            json.dump({"smoke": args.smoke, "backend": "cpu-hostpath",
                       **fleet_res}, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.json_rt:
        with open(args.json_rt, "w") as fh:
            json.dump({"smoke": args.smoke, "backend": "cpu-hostpath",
                       "h2_tier": rt_res}, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"dataset": args.dataset, "smoke": args.smoke,
                       "backend": "cpu-hostpath",
                       "engine_vs_single_shot": {
                           "engine_qps": res["eng_qps"],
                           "single_shot_qps": res["base_qps"]},
                       **res["fused"]}, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if (args.check or args.smoke) and not (ok and fused_ok and rt_ok
                                           and fused3_ok and fleet_ok
                                           and paged_ok and fresh_ok
                                           and obs_ok):
        return 1
    return 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())

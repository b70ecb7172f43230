#!/usr/bin/env python3
"""Bring-up smoke of the JUNO vector-search serving path on a TPU.

    python chip_smoke.py                # one chip: phase A (l2), phase B (ip)
    python chip_smoke.py --four-chips   # four chips: sharded index + fleet

One process, no child processes. It drives the path a deployment runs —
streaming offline build (``repro.build.build_streaming``), a
``MutableJunoIndex`` served by ``AnnServeEngine.submit``/``run``, live
inserts and deletes — and checks every kernel path against the jnp
reference (``impl="ref"``) on the same chip and the same index:

* phase A — DEEP-shaped corpus (96-d, l2; the Deep1B base set of the
  NeurIPS'21 big-ann-benchmarks track), N = 10,000,000 points, C = 4096
  clusters, E = 256 entries, 2-d subspaces. Mixed-recall_target requests
  (batch sizes 1 to 128, k = 10) reach the L, M, H2 and H tiers, then the
  fused H2 engine serves; then a few hundred inserts and deletes.
* phase B — Text-to-Image-shaped corpus (200-d, inner product),
  N = 2,000,000, served with ``prefilter="rt"`` unfused (the RT kernel)
  and fused (the three-stage kernel).
* ``--four-chips`` — the phase-A configuration sharded over a 2x2 mesh by
  ``make_distributed_search``, and ``AnnServeFleet(n_replicas=2,
  shards_per_replica=2)``, each compared with the one-chip search at full
  probe coverage, where the two must agree.

Data is synthetic (``repro.data.synthetic``) from ``--seed``, made on the
device. ``--n-deep``/``--n-tti`` cut N only; a cut is printed. Each check
prints one ``check`` line; any failed check, or no TPU, or no importable
``repro`` package, exits non-zero. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
and is printed only when every check passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

K = 10
N_DEEP = 10_000_000
N_TTI = 2_000_000
N_DEEP_FOUR = 2_000_000     # four-chip phase (full-coverage compare)
N_QUERIES = 128
CHUNK = 1 << 20             # rows per generated / streamed chunk
N_MUTATE = 300              # points inserted, and deleted, in phase A
#: query rows of the four-chip full-coverage compare: every cluster is
#: probed, so the one-chip search gathers FULL_ROWS·C·P·S bytes of codes
FULL_ROWS = 4
#: the kernel path may trail the reference by at most this much recall@10
RECALL_SLACK = 0.01
#: least mean id overlap (top-10) between kernel and reference paths
MIN_OVERLAP = 0.99
#: batch buckets of the reference engines: the jnp reference gathers f32
#: LUT values per (query, probe, point), which at 128 queries and the
#: phase-A index exceeds the chip's 16 GB (compiled for v5e beforehand)
REF_BUCKETS = (32,)
#: largest |kernel - reference| score over shared ids, relative to the
#: largest |reference score| among them (floored at 1). Sound runs read
#: about one f32 ulp (6.1e-05 at l2 distances near 1e3, 1.2e-07 on ip
#: scores near 1); the reference with its LUT einsum rounded to bf16 read
#: 5.537 on the fused H2 path. On hit-count paths (scores are integer
#: counts up to S) any count difference exceeds the limit.
MAX_SCORE_GAP_REL = 1e-4


class Checks:
    """Prints one line per check and remembers whether all passed."""

    def __init__(self):
        self.failed = []

    def info(self, name, **vals):
        """A reading with no pass/fail."""
        print(f"info  {name} " + " ".join(f"{k}={v}" for k, v in vals.items()),
              flush=True)

    def check(self, name, ok, **vals):
        """A reading that must hold; a failure is remembered."""
        print(f"check {name} {'ok' if ok else 'FAIL'} "
              + " ".join(f"{k}={v}" for k, v in vals.items()), flush=True)
        if not ok:
            self.failed.append(name)


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def exact_gt(queries, chunks, metric):
    """Exact top-K ids over the chunked corpus (``exact_topk`` per chunk at
    ``Precision.HIGHEST``, merged)."""
    import jax
    import jax.numpy as jnp
    from repro.core import exact_topk
    s_all, i_all, base = [], [], 0
    for c in chunks:
        s, i = exact_topk(queries, c, k=K, metric=metric, chunk=65536)
        s_all.append(s)
        i_all.append(i + base)
        base += c.shape[0]
    s = jnp.concatenate(s_all, axis=1)
    i = jnp.concatenate(i_all, axis=1)
    _, sel = jax.lax.top_k(-s if metric == "l2" else s, K)
    return np.asarray(jnp.take_along_axis(i, sel, axis=1))


def recall(ids, gt):
    """Mean recall@K of (Q, K) ids against (Q, K) ground truth."""
    return float(np.mean([len(set(a) & set(b)) / K
                          for a, b in zip(ids, gt)]))


def overlap(ids_a, ids_b):
    """Mean id overlap of two (Q, K) results: per query, shared ids over
    the larger of the two id sets. Sentinel slots (id < 0, returned when
    fewer than K points survive the filters) are not ids, so two equal
    results overlap 1.0 however many sentinels they carry."""
    out = []
    for a, b in zip(ids_a, ids_b):
        sa, sb = set(a[a >= 0].tolist()), set(b[b >= 0].tolist())
        out.append(len(sa & sb) / max(len(sa), len(sb)) if sa or sb else 1.0)
    return float(np.mean(out))


def score_gap(ids_a, s_a, ids_b, s_b):
    """Largest |score difference| over the (query, id) pairs both paths
    returned with finite scores — the distance agreement of the paths —
    and the largest |score| of path b over those pairs (its scale)."""
    gap, scale = 0.0, 0.0
    for ia, sa, ib, sb in zip(ids_a, s_a, ids_b, s_b):
        sb_of = {i: s for i, s in zip(ib, sb) if np.isfinite(s)}
        for i, s in zip(ia, sa):
            if i in sb_of and np.isfinite(s):
                gap = max(gap, abs(float(s) - float(sb_of[i])))
                scale = max(scale, abs(float(sb_of[i])))
    return gap, scale


def build_index(spec, n, cfg, seed, checks, tag):
    """Generate the corpus on the device and stream it through the build.
    Returns (index, queries (Q, D), exact top-K ids (Q, K))."""
    import jax
    from repro.build import build_streaming
    from repro.data import make_dataset_chunked
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    chunks, queries = make_dataset_chunked(spec, n, N_QUERIES, key,
                                           chunk=CHUNK)
    jax.block_until_ready(chunks)
    t_data = time.perf_counter() - t0
    t0 = time.perf_counter()
    index = build_streaming(lambda: (np.asarray(c) for c in chunks), cfg,
                            key=jax.random.fold_in(key, 1))
    jax.block_until_ready(index)
    t_build = time.perf_counter() - t0
    gt = exact_gt(queries, chunks, cfg.metric)
    del chunks
    gc.collect()
    checks.info(f"{tag}/build", n=n, dim=spec.dim, metric=cfg.metric,
                clusters=cfg.n_clusters,
                capacity=index.ivf.point_ids.shape[1],
                data_s=f"{t_data:.3f}", build_s=f"{t_build:.3f}",
                peak_bytes=peak_bytes())
    return index, queries, gt


def requests(queries, target):
    """Requests of 1, 16 and 111 rows (batch sizes 1 to 128) covering the
    N_QUERIES queries in order, all at one recall target."""
    q = np.asarray(queries)
    return [(q[lo:hi], target) for lo, hi in ((0, 1), (1, 17), (17, 128))]


def serve(engine, reqs):
    """Submit every request, run the engine; returns (ids, scores) per
    request and the wall seconds."""
    t0 = time.perf_counter()
    handles = [engine.submit(q, k=K, recall_target=t) for q, t in reqs]
    engine.run()
    dt = time.perf_counter() - t0
    return [(h.ids, h.scores) for h in handles], dt


def custom_calls(engine, queries, sigs):
    """``tpu_custom_call`` count of each compiled search the engine has
    dispatched for the (k, mode, nprobe, bucket) signatures ``sigs``."""
    import jax.numpy as jnp
    out = {}
    side = engine.index.delta_view()
    for (k, mode, nprobe, bucket) in sigs:
        fn, kw = engine.search_call(k, mode, nprobe, side)
        qb = jnp.asarray(np.resize(np.asarray(queries), (bucket,
                                                        queries.shape[1])))
        text = fn.lower(engine.index.data, qb, **kw).compile().as_text()
        out[(mode, nprobe, bucket)] = text.count(
            'custom_call_target="tpu_custom_call"')
    return out


def compare_path(checks, tag, eng, ref, reqs, gt):
    """Serve ``reqs`` through the kernel engine (warm-up, then a timed warm
    pass) and once through the reference engine, and check the kernel path
    against ground truth and the reference."""
    seen_k = set(eng.stats["signatures"])
    seen_r = set(ref.stats["signatures"])
    _, warm_k = serve(eng, reqs)
    got_k, t_k = serve(eng, reqs)
    got_r, t_r = serve(ref, reqs)       # one pass: compile included
    ids_k = np.concatenate([g[0] for g in got_k])
    ids_r = np.concatenate([g[0] for g in got_r])
    s_k = np.concatenate([g[1] for g in got_k])
    s_r = np.concatenate([g[1] for g in got_r])
    rk, rr = recall(ids_k, gt), recall(ids_r, gt)
    ov = overlap(ids_k, ids_r)
    gap, scale = score_gap(ids_k, s_k, ids_r, s_r)
    gap_limit = MAX_SCORE_GAP_REL * max(1.0, scale)
    n_cc = custom_calls(eng, reqs[-1][0],
                        set(eng.stats["signatures"]) - seen_k)
    n_cc_ref = custom_calls(ref, reqs[-1][0],
                            set(ref.stats["signatures"]) - seen_r)
    checks.info(f"{tag}/recall", kernel=f"{rk:.4f}", ref=f"{rr:.4f}")
    checks.check(f"{tag}/recall_vs_ref", rk >= rr - RECALL_SLACK,
                 kernel=f"{rk:.4f}", ref=f"{rr:.4f}", slack=RECALL_SLACK)
    checks.check(f"{tag}/id_overlap", ov >= MIN_OVERLAP, overlap=f"{ov:.4f}",
                 min=MIN_OVERLAP, sentinels_kernel=int((ids_k < 0).sum()),
                 sentinels_ref=int((ids_r < 0).sum()))
    checks.check(f"{tag}/score_gap", gap <= gap_limit, max_abs=gap,
                 limit=gap_limit, scale=scale)
    checks.check(f"{tag}/tpu_custom_calls",
                 bool(n_cc) and all(v > 0 for v in n_cc.values()),
                 kernel=n_cc, ref=n_cc_ref)
    checks.info(f"{tag}/time", warmup_s=f"{warm_k:.3f}", serve_s=f"{t_k:.6f}",
                ref_first_pass_s=f"{t_r:.3f}", queries=len(ids_k),
                peak_bytes=peak_bytes())


def phase_a(args, checks):
    """DEEP-shaped l2 corpus: L/M/H2/H tiers, fused H2, inserts/deletes."""
    from repro.core import JunoConfig, MutableJunoIndex
    from repro.data import DEEP_LIKE, make_dataset_chunked
    from repro.kernels.ops import default_impl
    from repro.serve import AnnServeEngine
    import jax
    cfg = JunoConfig(n_clusters=4096, n_entries=256, sub_dim=2, metric="l2")
    index, queries, gt = build_index(DEEP_LIKE, args.n_deep, cfg, args.seed,
                                     checks, "A")
    mindex = MutableJunoIndex(index, side_capacity=256)
    del index
    kw = dict(metric="l2")
    eng = AnnServeEngine(mindex, batch_buckets=(128,), **kw)
    ref = AnnServeEngine(mindex, impl="ref", batch_buckets=REF_BUCKETS,
                         **kw)
    checks.info("A/impl", engine=eng.impl or default_impl(),
                reference=ref.impl)
    for target, mode in ((0.3, "L"), (0.6, "M"), (0.85, "H2"), (0.95, "H")):
        compare_path(checks, f"A/{mode}", eng, ref,
                     requests(queries, target), gt)
    compare_path(checks, "A/H2_fused",
                 AnnServeEngine(mindex, fused=True, batch_buckets=(128,),
                                **kw),
                 AnnServeEngine(mindex, fused=True, impl="ref",
                                batch_buckets=REF_BUCKETS, **kw),
                 requests(queries, 0.85), gt)

    # live inserts and deletes on the kernel path: an acknowledged insert
    # is found for its own vector; a deleted id never comes back
    chunks, _ = make_dataset_chunked(DEEP_LIKE, N_MUTATE, 1,
                                     jax.random.PRNGKey(args.seed + 7),
                                     chunk=N_MUTATE)
    newpts = np.asarray(chunks[0])
    new_ids = np.asarray(eng.insert(newpts))
    got, _ = serve(eng, [(newpts[i:i + 100], 0.95)
                         for i in range(0, len(newpts), 100)])
    ids = np.concatenate([g[0] for g in got])
    found = sum(new_ids[j] in ids[j] for j in range(len(new_ids)))
    checks.check("A/insert_found", found == len(new_ids),
                 inserted=len(new_ids), found=found,
                 side_fill=mindex.side_fill)
    rng = np.random.default_rng(args.seed)
    old = rng.choice(np.unique(gt[gt >= 0]), size=N_MUTATE // 2,
                     replace=False)
    dead = np.concatenate([new_ids[: N_MUTATE // 2], old])
    removed = eng.delete(dead.tolist())
    probe = np.concatenate([np.asarray(queries), newpts])
    got, _ = serve(eng, [(probe[i:i + 128], t)
                         for t in (0.3, 0.6, 0.85, 0.95)
                         for i in range(0, len(probe), 128)])
    back = int(np.isin(np.concatenate([g[0].ravel() for g in got]),
                       dead).sum())
    checks.check("A/deleted_absent", back == 0 and removed == len(dead),
                 deleted=len(dead), removed=removed, returned=back)
    checks.info("A/end", peak_bytes=peak_bytes())


def phase_b(args, checks):
    """TTI-shaped ip corpus: rt prefilter unfused and fused (three-stage)."""
    from repro.core import JunoConfig, MutableJunoIndex
    from repro.data import TTI_LIKE
    from repro.serve import AnnServeEngine
    cfg = JunoConfig(n_clusters=4096, n_entries=256, sub_dim=2, metric="ip")
    index, queries, gt = build_index(TTI_LIKE, args.n_tti, cfg,
                                     args.seed + 1, checks, "B")
    mindex = MutableJunoIndex(index, side_capacity=256)
    del index
    mindex.ensure_rt_grid(metric="ip")
    for tag, fused in (("B/rt_H2", False), ("B/rt_H2_fused3", True)):
        kw = dict(metric="ip", prefilter="rt", fused=fused)
        compare_path(checks, tag,
                     AnnServeEngine(mindex, batch_buckets=(128,), **kw),
                     AnnServeEngine(mindex, impl="ref",
                                    batch_buckets=REF_BUCKETS, **kw),
                     requests(queries, 0.85), gt)
    checks.info("B/end", peak_bytes=peak_bytes())


def four_chips(args, checks):
    """Sharded index and 2x2 fleet, each against the one-chip search at
    full probe coverage, plus recall@10 at serving nprobe."""
    import jax
    import jax.numpy as jnp
    from repro.core import JunoConfig, search
    from repro.data import DEEP_LIKE
    from repro.dist.distributed_index import (make_distributed_search,
                                              shard_index)
    from repro.serve.fleet import AnnServeFleet
    cfg = JunoConfig(n_clusters=4096, n_entries=256, sub_dim=2, metric="l2")
    index, queries, gt = build_index(DEEP_LIKE, args.n_deep, cfg, args.seed,
                                     checks, "4")
    c = cfg.n_clusters
    q_full = queries[:FULL_ROWS]

    # reference: one-chip search at full coverage (every cluster probed)
    s_1, i_1 = search(index, q_full, nprobe=c, k=K, mode="H",
                      batch=FULL_ROWS)
    s_1, i_1 = np.asarray(s_1), np.asarray(i_1)

    mesh = jax.make_mesh((4,), ("data",))
    sharded = shard_index(index, mesh)
    places = [s.device for s in sharded.cluster_codes.addressable_shards]
    rows = {s.data.shape[0] for s in sharded.cluster_codes.addressable_shards}
    checks.check("4/sharded/placement", len(set(places)) == 4 and
                  rows == {c // 4}, devices=len(set(places)),
                  rows_per_shard=sorted(rows))
    full = make_distributed_search(mesh, c // 4, K, mode="H")
    s_d, i_d = (np.asarray(x) for x in full(sharded, q_full))
    checks.check("4/sharded/full_coverage_ids", np.array_equal(i_d, i_1),
                 equal_rows=int((i_d == i_1).all(axis=1).sum()),
                 rows=len(i_1))
    checks.check("4/sharded/full_coverage_scores",
                 np.allclose(s_d, s_1, rtol=1e-5, atol=1e-3),
                 max_abs=float(np.max(np.abs(s_d - s_1))))
    serving = make_distributed_search(mesh, 16 // 4, K, mode="H")
    _, i_s = serving(sharded, jnp.asarray(queries))
    checks.info("4/sharded/recall_at_serving_nprobe", local_nprobe=4,
                recall=f"{recall(np.asarray(i_s), gt):.4f}")
    del sharded, full, serving
    gc.collect()

    fleet = AnnServeFleet(index, n_replicas=2, shards_per_replica=2,
                          batch_buckets=(FULL_ROWS,))
    used = set()
    for r, e in enumerate(fleet.engines):
        shards = e.index.data.cluster_codes.addressable_shards
        devs_r = {s.device for s in shards}
        checks.check(f"4/fleet/replica{r}_placement",
                     len(devs_r) == 2 and not (devs_r & used),
                     devices=sorted(d.id for d in devs_r))
        used |= devs_r
        # full coverage: every cluster probed (the default lattice stops
        # at 32 probes, so this check widens it on these engines only)
        e.NPROBE_BUCKETS = (c,)
    req = fleet.submit(np.asarray(q_full), k=K, mode="H", nprobe=c)
    fleet.run()
    checks.check("4/fleet/full_coverage_ids", np.array_equal(req.ids, i_1),
                 equal_rows=int((req.ids == i_1).all(axis=1).sum()),
                 rows=len(i_1))
    for e in fleet.engines:
        e.NPROBE_BUCKETS = type(e).NPROBE_BUCKETS
    reqs = [fleet.submit(np.asarray(queries[i:i + FULL_ROWS]), k=K,
                         mode="H", nprobe=16)
            for i in range(0, N_QUERIES, FULL_ROWS)]
    fleet.run()
    ids = np.concatenate([r.ids for r in reqs])
    checks.info("4/fleet/recall_at_serving_nprobe", nprobe=16,
                recall=f"{recall(ids, gt):.4f}", peak_bytes=peak_bytes())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded/fleet path")
    ap.add_argument("--n-deep", type=int, default=None,
                    help="phase-A corpus size (a cut of N, printed)")
    ap.add_argument("--n-tti", type=int, default=N_TTI,
                    help="phase-B corpus size (a cut of N, printed)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devs[0].platform}); nothing "
              "was run", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return 2
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not importable ({e}); run "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    full_deep = N_DEEP_FOUR if args.four_chips else N_DEEP
    if args.n_deep is None:
        args.n_deep = full_deep
    checks = Checks()
    checks.info("device", platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs), compile_cache=cache)
    for name, n, full in (("deep", args.n_deep, N_DEEP),
                          ("tti", args.n_tti, N_TTI)):
        if n < full and not (args.four_chips and name == "tti"):
            checks.info(f"cut/{name}", n=n, published_n=full)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args, checks)
    else:
        phase_a(args, checks)
        gc.collect()
        phase_b(args, checks)
    checks.info("total", seconds=f"{time.perf_counter() - t0:.3f}")
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed: "
              + ", ".join(checks.failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

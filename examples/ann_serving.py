"""Distributed ANN serving: the paper's engine sharded over a device mesh,
with batched query requests — the end-to-end driver for the serving kind.

Runs on 8 virtual host devices (set before jax import) to demonstrate the
actual multi-chip SPMD program; the same code targets the 256/512-chip
production meshes via launch/mesh.py.

    PYTHONPATH=src python examples/ann_serving.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import numpy as np

from repro.core import JunoConfig, build, exact_topk, recall_1_at_k
from repro.data import DEEP_LIKE, make_dataset
from repro.dist.distributed_index import (DistributedMutableIndex,
                                          make_distributed_search,
                                          shard_index)
from repro.serve import AnnServeEngine, AnnServeFleet


def serve_online(index, points, queries, gt):
    """Online serving: dynamic batching + recall routing + live mutation."""
    engine = AnnServeEngine(index, batch_buckets=(8, 16, 32))
    reqs = [engine.submit(queries[i * 4:(i + 1) * 4], k=10,
                          recall_target=[0.95, 0.85, 0.55, 0.3][i % 4])
            for i in range(16)]
    t0 = time.time()
    served = engine.run()
    print(f"engine: {served} queries in {time.time() - t0:.2f}s over "
          f"{engine.stats['ticks']} ticks "
          f"({len(engine.stats['signatures'])} jit signatures); "
          f"modes routed: "
          f"{sorted({s[1] for s in engine.stats['signatures']})}")
    r1 = np.mean([float(recall_1_at_k(r.ids, gt[i * 4:(i + 1) * 4, 0]))
                  for i, r in enumerate(reqs)])
    print(f"mean R1@10 across SLAs = {r1:.3f}")

    # live mutation: insert → searchable; delete → gone; no rebuild anywhere
    new = np.asarray(queries[:4]) * 1.0
    ids = engine.insert(new)
    req = engine.submit(new, k=10, mode="H", nprobe=16)
    engine.run()
    hits = sum(ids[j] in req.ids[j] for j in range(4))
    engine.delete(ids[:2])
    print(f"inserted 4 (found {hits}/4), deleted 2, "
          f"side buffer fill: {engine.index.side_fill}")

    # fused two-stage serving: H and H2 tiers coalesce onto one signature
    feng = AnnServeEngine(index, batch_buckets=(8, 16, 32), fused=True)
    freqs = [feng.submit(queries[i * 4:(i + 1) * 4], k=10,
                         recall_target=[0.95, 0.85][i % 2])
             for i in range(8)]
    feng.run()
    fr1 = np.mean([float(recall_1_at_k(r.ids, gt[i * 4:(i + 1) * 4, 0]))
                   for i, r in enumerate(freqs)])
    print(f"fused engine: H+H2 tiers in {feng.stats['ticks']} tick(s) "
          f"({len(feng.stats['signatures'])} signature), "
          f"mean R1@10 = {fr1:.3f}")


def serve_rt_prefilter(index, queries, gt):
    """RT-prefilter serving: sphere-intersection pruning + probe shrink."""
    eng = AnnServeEngine(index, batch_buckets=(8, 16, 32), prefilter="rt")
    reqs = [eng.submit(queries[i], k=10, recall_target=0.85)
            for i in range(32)]
    eng.run()
    r1 = np.mean([float(recall_1_at_k(r.ids[None] if r.ids.ndim == 1
                                      else r.ids, gt[i:i + 1, 0]))
                  for i, r in enumerate(reqs)])
    nprobes = sorted({s[2] for s in eng.stats["signatures"]})
    print(f"rt-prefilter engine: 32 point lookups in "
          f"{eng.stats['ticks']} tick(s), probe budgets routed to "
          f"{nprobes}, mean R1@10 = {r1:.3f} "
          f"(grid: {eng.index.rt_grid.n_cells} cells, "
          f"cap {eng.index.rt_grid.capacity})")


def serve_fleet(index, queries):
    """Replica fleet: 2 replicas x 2 shards, admission control, tail stats."""
    fleet = AnnServeFleet(index, n_replicas=2, shards_per_replica=2,
                          policy="shed", max_queue=64, batch_buckets=(8, 16))
    reqs = [fleet.submit(queries[i * 2:(i + 1) * 2], k=10, mode="M",
                         nprobe=8) for i in range(24)]
    fleet.run()
    fleet.insert(np.asarray(queries[:4]))          # fans out to both replicas
    fleet.fail_replica(0)                          # routing-level failover
    more = [fleet.submit(queries[i * 2:(i + 1) * 2], k=10, mode="M",
                         nprobe=8) for i in range(4)]
    fleet.run()
    fleet.restore_replica(0)
    summ = fleet.latency_summary()
    per = [dict(c) for c in fleet.stats["per_replica"]]
    print(f"fleet (2x2 on {fleet.engines[0].index.n_shards}-shard "
          f"sub-meshes): served {summ['served']} "
          f"(shed {summ['shed']}, rerouted {summ['rerouted']}), "
          f"p50/p95/p99 = {summ['p50'] * 1e3:.0f}/{summ['p95'] * 1e3:.0f}/"
          f"{summ['p99'] * 1e3:.0f} ms, per-replica {per}")
    assert all(r.done for r in reqs + more)


def serve_distributed_mutable(index, queries, mesh):
    """Sharded mutable serving: inserts routed to the owning shard."""
    dmi = DistributedMutableIndex(index, mesh, side_capacity=128)
    dsearch = dmi.searcher(local_nprobe=2, k=10, mode="H")
    ids = dmi.insert(np.asarray(queries[:8]))
    _, got = dsearch(dmi.data, queries[:8], dmi.side)
    hits = sum(ids[j] in np.asarray(got)[j] for j in range(8))
    print(f"distributed insert: {hits}/8 found through the sharded engine "
          f"(scatter routed by owning cluster, side fill {dmi.side_fill})")


def main():
    print(f"devices: {len(jax.devices())}")
    points, queries = make_dataset(DEEP_LIKE, 40_000, 256,
                                   key=jax.random.PRNGKey(1))
    cfg = JunoConfig(n_clusters=64, n_entries=64, calib_queries=48)
    index = build(points, cfg)
    _, gt = exact_topk(queries, points, k=100)

    mesh = jax.make_mesh((8,), ("data",))
    sharded = shard_index(index, mesh)
    print("index sharded:", sharded.cluster_codes.sharding)

    dsearch = make_distributed_search(mesh, local_nprobe=2, k=100, mode="H2")

    # batched request loop (16 requests of 16 queries each)
    total_q, t_total = 0, 0.0
    recalls = []
    for i in range(16):
        qb = queries[i * 16:(i + 1) * 16]
        t0 = time.time()
        scores, ids = dsearch(sharded, qb)
        jax.block_until_ready(ids)
        t_total += time.time() - t0
        total_q += qb.shape[0]
        recalls.append(float(recall_1_at_k(ids, gt[i * 16:(i + 1) * 16, 0])))
    print(f"served {total_q} queries in {t_total:.2f}s "
          f"({total_q / t_total:.0f} QPS on CPU-interp mesh)")
    print(f"mean R1@100 = {np.mean(recalls):.3f}")

    serve_online(index, points, queries, gt)
    serve_rt_prefilter(index, np.asarray(queries), gt)
    serve_distributed_mutable(index, queries, mesh)
    serve_fleet(index, np.asarray(queries))


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()

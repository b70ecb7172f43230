"""Public-API docstring gate.

Every symbol a user reaches through the documented entry points —
``repro.core``'s index/search API, the serving engine, the ``ops.*``
kernel dispatchers and the ``repro.rt`` builders — must carry a
non-trivial docstring (shape/dtype contracts live there; docs/kernels.md
and docs/serving.md link to them instead of duplicating). CI additionally
runs ruff's pydocstyle D1xx subset over the same modules (the docs-check
job); this test keeps the guarantee in tier 1 where no ruff is installed.
"""
import inspect

import pytest

import repro.build as build
import repro.core as core
import repro.dist.distributed_index as dist_index
import repro.rt as rt
from repro.build.merge import fold_step, load_minor, save_minor
from repro.kernels import autotune
from repro.core.freshness import (MergeScheduler, MinorGeneration,
                                  combined_delta, promote_l0)
from repro.core.juno import MutableIndexBase, MutableJunoIndex
from repro.dist.distributed_index import DistributedMutableIndex
from repro.kernels import ops
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       Observability, RecallProbe, Span, Tracer,
                       exact_topk_ids, read_jsonl, registry_from_events,
                       to_events, validate_events, write_jsonl)
from repro.serve.ann import AnnRequest, AnnServeEngine
from repro.serve.fleet import (AnnServeFleet, FleetRequest, LatencyHistogram,
                               Rejection)
from repro.serve.paged import (ClusterCache, PagedAnnServeEngine,
                               PagedIndexData, PagedJunoIndex)

PUBLIC = [
    # repro.core index lifecycle
    core.JunoConfig, core.build, core.search, core.exact_topk,
    core.recall_1_at_k, core.recall_n_at_k, core.SideBuffer,
    core.empty_side_buffer,
    # mutable index
    MutableJunoIndex, MutableIndexBase.insert, MutableIndexBase.delete,
    MutableIndexBase.compact, MutableJunoIndex.search,
    MutableJunoIndex.ensure_rt_grid,
    # LSM freshness tiers + incremental merges
    MutableIndexBase.enable_tiers, MutableIndexBase.delta_view,
    MutableIndexBase.delta_snapshot, MinorGeneration, combined_delta,
    promote_l0, MergeScheduler, MergeScheduler.maybe_step,
    MergeScheduler.step, MergeScheduler.drain,
    fold_step, save_minor, load_minor,
    # serving engine
    AnnServeEngine, AnnRequest, AnnServeEngine.__init__,
    AnnServeEngine.submit, AnnServeEngine.route, AnnServeEngine.step,
    AnnServeEngine.run, AnnServeEngine.insert, AnnServeEngine.delete,
    AnnServeEngine.compact,
    # kernel dispatchers
    ops.build_selective_lut, ops.masked_adc_scan, ops.hit_count_scan,
    ops.fused_two_stage_scan, ops.fused_three_stage_scan,
    ops.rt_sphere_hits, ops.filter_scores,
    ops.slab_onehot_dot,
    # autotune pass
    autotune.KernelConfig, autotune.KernelConfig.validate, autotune.tune,
    autotune.candidates, autotune.save_cache, autotune.load_cache,
    autotune.ensure_tuned, autotune.set_config, autotune.active_config,
    # rt builders
    rt.CentroidGrid, rt.build_grid, rt.query_radius, rt.survivor_mask,
    rt.routing_state, rt.probe_budget, rt.update_radii, rt.save_grid,
    rt.load_grid, rt.sphere_hits, rt.sphere_hits_host,
    # out-of-core build / artifact store / rebuild (repro.build)
    build.build_streaming, build.build_streaming_sharded, build.array_source,
    build.BuildProbe, build.split_shards, build.merge_shards,
    build.save_index, build.load_index, build.verify_artifact,
    build.config_hash, build.ArtifactStore, build.ArtifactStore.put,
    build.ArtifactStore.get, build.ArtifactStore.versions,
    build.ArtifactStore.latest, build.ArtifactError, build.rebuild_index,
    # rebuild/hot-swap wiring
    MutableJunoIndex.swap_data, AnnServeEngine.swap_index,
    DistributedMutableIndex.swap_data,
    DistributedMutableIndex.rebuild_shard, DistributedMutableIndex.rebuild,
    # distributed search/update factories
    dist_index.make_distributed_search, dist_index.make_distributed_insert,
    dist_index.make_distributed_delete,
    dist_index.make_distributed_row_update, dist_index.index_pspecs,
    dist_index.shard_index, DistributedMutableIndex,
    DistributedMutableIndex.searcher,
    # fleet layer
    AnnServeFleet, AnnServeFleet.__init__, AnnServeFleet.submit,
    AnnServeFleet.step, AnnServeFleet.run, AnnServeFleet.insert,
    AnnServeFleet.delete, AnnServeFleet.compact,
    AnnServeFleet.fail_replica, AnnServeFleet.restore_replica,
    AnnServeFleet.latency_summary, AnnServeFleet.reset_metrics,
    FleetRequest, FleetRequest.trace, Rejection,
    LatencyHistogram, LatencyHistogram.add, LatencyHistogram.merge,
    LatencyHistogram.percentile, LatencyHistogram.summary,
    # observability layer (repro.obs)
    Counter, Counter.inc, Counter.merge, Gauge, Gauge.set, Gauge.merge,
    Histogram, Histogram.add, Histogram.merge, Histogram.percentile,
    Histogram.summary, MetricsRegistry, MetricsRegistry.counter,
    MetricsRegistry.gauge, MetricsRegistry.histogram,
    MetricsRegistry.merge, MetricsRegistry.snapshot,
    MetricsRegistry.render_text, Span, Tracer, Tracer.span, Tracer.record,
    Observability, Observability.child, RecallProbe, RecallProbe.observe,
    RecallProbe.estimate, exact_topk_ids, to_events, write_jsonl,
    read_jsonl, validate_events, registry_from_events,
    AnnServeFleet.merged_registry, build.ArtifactStore.verify,
    # paged (out-of-core) serving tier
    ClusterCache, ClusterCache.get, ClusterCache.put, ClusterCache.stats,
    PagedIndexData, PagedIndexData.__init__, PagedIndexData.fetch_cluster,
    PagedIndexData.gather, PagedIndexData.fetch_vectors,
    PagedIndexData.adopt_cache, PagedIndexData.stats,
    PagedJunoIndex, PagedJunoIndex.swap_data, PagedJunoIndex.search,
    PagedJunoIndex.ensure_rt_grid,
    PagedAnnServeEngine, PagedAnnServeEngine.__init__,
    PagedAnnServeEngine.compact, PagedAnnServeEngine.swap_index,
    PagedAnnServeEngine.cache_stats,
]


def _name(obj):
    mod = getattr(obj, "__module__", "?")
    qual = getattr(obj, "__qualname__", getattr(obj, "__name__", repr(obj)))
    return f"{mod}.{qual}"


@pytest.mark.parametrize("obj", PUBLIC, ids=_name)
def test_public_symbol_has_docstring(obj):
    doc = inspect.getdoc(obj)
    assert doc and len(doc.split()) >= 5, (
        f"{_name(obj)} lacks a meaningful docstring")


def test_public_modules_have_docstrings():
    import repro.build.merge
    import repro.build.pipeline
    import repro.build.rebuild
    import repro.build.store
    import repro.core.freshness
    import repro.core.juno
    import repro.dist.distributed_index
    import repro.kernels.autotune
    import repro.kernels.fused_three_stage
    import repro.kernels.fused_two_stage
    import repro.kernels.ref
    import repro.obs
    import repro.obs.export
    import repro.obs.recall
    import repro.obs.registry
    import repro.obs.trace
    import repro.rt.grid
    import repro.rt.intersect
    import repro.serve.ann
    import repro.serve.fleet
    import repro.serve.paged
    for mod in [core, rt, ops, build, repro.core.juno, repro.core.freshness,
                repro.serve.ann,
                repro.serve.fleet, repro.serve.paged, repro.rt.grid,
                repro.rt.intersect,
                repro.kernels.ref, repro.kernels.fused_two_stage,
                repro.kernels.fused_three_stage, repro.kernels.autotune,
                repro.dist.distributed_index,
                repro.build.pipeline, repro.build.store, repro.build.rebuild,
                repro.build.merge, repro.obs, repro.obs.registry,
                repro.obs.trace, repro.obs.export, repro.obs.recall]:
        assert mod.__doc__ and len(mod.__doc__.split()) >= 10, mod.__name__

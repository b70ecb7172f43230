"""`repro.obs` — unified observability for the JUNO serving stack.

One package ties together the stack's previously fragmented telemetry
(`FleetRequest.trace()` segments, `LatencyHistogram`, paged-cache
counters, engine timestamps) behind three primitives:

* :class:`MetricsRegistry` of :class:`Counter` / :class:`Gauge` /
  :class:`Histogram` series under the ``juno_<subsystem>_<name>``
  naming scheme, mergeable fail-closed across fleet replicas
  (``repro.obs.registry``);
* a span :class:`Tracer` nesting enqueue → batch → rt-probe → kernel
  dispatch → paged fault-in → merge per request
  (``repro.obs.trace``);
* JSONL + Prometheus-text exporters with a fail-closed schema check
  (``repro.obs.export``, ``tools/obs_report.py``), and a sampled
  exact-rerank :class:`RecallProbe` feeding online ``recall@k`` gauges
  per recall tier (``repro.obs.recall``).

The package is numpy + stdlib only — importable without jax, which it
imports on first use to make each live span a ``jax.profiler`` span too
(``repro.obs.trace.annotation``) — and all
instrumentation is host-side: enabling it never adds jit arguments,
never widens the engine's signature lattice, and leaves served ids and
scores bit-identical (pinned by ``tests/test_obs.py``). Subsystems
accept an :class:`Observability` bundle (or a bare registry) and stay
fully functional with it absent.
"""
from .export import (SCHEMA, read_jsonl, registry_from_events, to_events,
                     validate_events, write_jsonl)
from .recall import RecallProbe, exact_topk_ids
from .registry import Counter, Gauge, Histogram, MetricsRegistry
from .trace import Span, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "Span", "Tracer", "RecallProbe", "exact_topk_ids",
    "Observability", "SCHEMA", "to_events", "write_jsonl", "read_jsonl",
    "validate_events", "registry_from_events",
]


class Observability:
    """Bundle of registry + tracer (+ optional recall probe) for one scope.

    Engines, fleets and stores take one of these instead of three
    separate objects. ``registry`` and ``tracer`` default to fresh
    instances; ``recall`` stays None unless a shadow probe is wanted.
    The probe binds its gauges to the FIRST registry it meets
    (:meth:`RecallProbe.bind`), so a fleet can hand the same probe to
    every replica while the estimates land in the fleet-level registry.
    """

    def __init__(self, registry: MetricsRegistry = None,
                 tracer: Tracer = None, recall: RecallProbe = None):
        """Assemble a bundle, creating registry/tracer when not given."""
        self.registry = registry if registry is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer()
        self.recall = recall
        if recall is not None:
            recall.bind(self.registry)

    def child(self, registry: MetricsRegistry = None) -> "Observability":
        """Derive a per-replica bundle: own registry, shared tracer/probe.

        The fleet merges the child registries back into one view via
        :meth:`MetricsRegistry.merge`; the tracer is shared because span
        ids must be unique across the whole process for parent links to
        resolve in one dump.
        """
        return Observability(
            registry=registry if registry is not None else MetricsRegistry(),
            tracer=self.tracer, recall=self.recall)

    def events(self, extra_meta: dict = None) -> list:
        """Schema-stamped JSONL events for this bundle's registry + spans."""
        return to_events(self.registry, self.tracer, extra_meta=extra_meta)

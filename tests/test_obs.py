"""Observability layer: ``repro.obs`` primitives + serving integration.

Two halves. The primitives half pins the registry's merge algebra —
counter folds commute, gauge merges respect the declared aggregation and
fail closed on disagreement, histogram merges fail closed on bucket-edge
mismatch, kind conflicts raise — plus the JSONL export round-trip (a
dump rebuilds into an identical registry and validates clean) and the
tracer's nesting/ordering guarantees. The integration half pins the
property the whole layer is built around: instrumentation is host-side
bookkeeping ONLY, so an engine with a live registry/tracer/recall-probe
returns bit-identical ids and scores and compiles the identical jit
signature lattice as an uninstrumented one, across the resident and
paged tiers — and the deprecated dict-shaped stats remain consistent
views of the registry series that replaced them. A third half pins the
profiler twin of every live span: the tracer's spans, and the engine's
spans with no bundle attached, reach ``jax.profiler``'s trace with their
names, attributes and nesting, and the H2 program names its stages in
the ops' ``op_name`` metadata.
"""
import glob
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.build import ArtifactStore
from repro.core import JunoConfig, build
from repro.data import DEEP_LIKE, make_dataset
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       Observability, RecallProbe, Tracer, exact_topk_ids,
                       read_jsonl, registry_from_events, to_events,
                       validate_events, write_jsonl)
from repro.serve.ann import AnnServeEngine
from repro.serve.fleet import AnnServeFleet, LatencyHistogram
from repro.serve.paged import PagedAnnServeEngine, PagedIndexData


# ---------------------------------------------------------------------------
# registry primitives: merge algebra, fail-closed everywhere
# ---------------------------------------------------------------------------

def test_counter_merge_commutative():
    a, b = Counter(), Counter()
    a.inc(3)
    a.inc(4.5)
    b.inc(10)
    ab, ba = Counter(), Counter()
    ab.merge(a)
    ab.merge(b)
    ba.merge(b)
    ba.merge(a)
    assert ab.value == ba.value == 17.5


def test_gauge_agg_semantics_and_mismatch():
    last, mx = Gauge(agg="last"), Gauge(agg="max")
    last.set(3.0)
    other = Gauge(agg="last")
    other.set(7.0)
    last.merge(other)
    assert last.value == 7.0            # other wins: it has updates
    fresh = Gauge(agg="last")           # no updates → no new information
    last.merge(fresh)
    assert last.value == 7.0
    with pytest.raises(ValueError):
        last.merge(mx)                  # agg disagreement: no right answer


def test_histogram_merge_requires_identical_edges():
    a = Histogram()
    b = Histogram()
    for v in (0.001, 0.01, 0.1):
        a.add(v)
        b.add(v * 2)
    n_before = a.n
    a.merge(b)
    assert a.n == n_before + b.n
    # same bucket COUNT is not enough — the edges themselves must match
    skewed = Histogram(lo=1e-5, hi=5000.0)
    assert len(skewed._counts) == len(Histogram()._counts)
    with pytest.raises(ValueError):
        Histogram().merge(skewed)


def test_registry_kind_and_bucketing_conflicts_raise():
    reg = MetricsRegistry()
    reg.counter("juno_test_total")
    with pytest.raises(ValueError):
        reg.gauge("juno_test_total")    # same series, different kind
    reg.histogram("juno_test_seconds")
    with pytest.raises(ValueError):
        reg.histogram("juno_test_seconds", lo=1e-5, hi=5000.0)
    other = MetricsRegistry()
    other.histogram("juno_test_seconds", lo=1e-5, hi=5000.0)
    with pytest.raises(ValueError):
        reg.merge(other)                # fail-closed across registries too


def test_registry_merge_sums_and_copies():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("juno_x_total", mode="H").inc(2)
    b.counter("juno_x_total", mode="H").inc(3)
    b.counter("juno_only_b_total").inc(1)
    a.merge(b)
    assert a.snapshot()['juno_x_total{mode="H"}'] == 5
    assert a.snapshot()["juno_only_b_total"] == 1
    b.counter("juno_only_b_total").inc(1)   # deep copy: no aliasing back
    assert a.snapshot()["juno_only_b_total"] == 1


def test_metric_name_scheme_enforced():
    reg = MetricsRegistry()
    for bad in ("Juno_x", "juno x", "9juno", "juno-x"):
        with pytest.raises(ValueError):
            reg.counter(bad)


# ---------------------------------------------------------------------------
# tracer: nesting, ordering, bounded buffer
# ---------------------------------------------------------------------------

def test_tracer_nesting_and_order():
    tr = Tracer()
    with tr.span("tick", trace_id="t1"):
        with tr.span("dispatch", rows=8):
            pass
        with tr.span("merge"):
            pass
    spans = {s.name: s for s in tr.spans()}
    assert spans["dispatch"].parent_id == spans["tick"].span_id
    assert spans["merge"].parent_id == spans["tick"].span_id
    assert spans["dispatch"].trace_id == "t1"       # inherited from parent
    assert spans["tick"].parent_id is None
    # spans are appended on CLOSE: children precede their parent
    names = [s.name for s in tr.spans()]
    assert names.index("dispatch") < names.index("merge") < names.index("tick")
    assert all(s.t_end >= s.t_start for s in tr.spans())


def test_tracer_retro_record_and_bounded_buffer():
    tr = Tracer(max_spans=3)
    with tr.span("serve") as root:
        tr.record("queue", 1.0, 2.0, parent=root)
    assert [s.name for s in tr.spans()] == ["queue", "serve"]
    for i in range(5):
        tr.record(f"extra_{i}", 0.0, 1.0)
    assert len(tr.spans()) == 3         # deque bounded
    assert tr.dropped == 4              # 2 + 5 recorded, 3 kept


# ---------------------------------------------------------------------------
# export: JSONL round-trip + fail-closed validation
# ---------------------------------------------------------------------------

def _sample_bundle():
    obs = Observability()
    obs.registry.counter("juno_engine_requests_total", mode="H").inc(4)
    obs.registry.gauge("juno_engine_queue_rows", agg="sum").set(3)
    h = obs.registry.histogram("juno_engine_request_seconds")
    for v in (0.001, 0.02, 0.5):
        h.add(v)
    with obs.tracer.span("engine.tick", trace_id="r1"):
        with obs.tracer.span("engine.dispatch"):
            pass
    return obs


def test_jsonl_round_trip(tmp_path):
    obs = _sample_bundle()
    events = obs.events(extra_meta={"who": "test"})
    assert validate_events(events) == []
    path = str(tmp_path / "dump.jsonl")
    write_jsonl(path, events)
    back = read_jsonl(path)
    assert back == events
    rebuilt = registry_from_events(back)
    assert rebuilt.snapshot() == obs.registry.snapshot()
    assert rebuilt.render_text() == obs.registry.render_text()


def test_validate_flags_corruption(tmp_path):
    obs = _sample_bundle()
    events = obs.events()
    no_meta = [ev for ev in events if ev.get("event") != "meta"]
    assert validate_events(no_meta)
    bad_hist = [dict(ev) for ev in events]
    for ev in bad_hist:
        if ev.get("kind") == "histogram":
            ev["counts"] = ev["counts"][:-1]        # truncated state
    assert validate_events(bad_hist)
    bad_span = [dict(ev) for ev in events]
    for ev in bad_span:
        if ev.get("event") == "span" and ev["parent_id"] is not None:
            ev["parent_id"] = "no-such-span"
    assert validate_events(bad_span)


# ---------------------------------------------------------------------------
# recall probe: exactness at every=1
# ---------------------------------------------------------------------------

class _FakeReq:
    """Duck-typed request shell: just what RecallProbe.observe reads."""

    def __init__(self, queries, ids, k):
        """Hold queries, returned ids and the requested depth."""
        self.queries, self.ids, self.k = queries, ids, k


def test_recall_probe_every1_exact():
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((200, 8)).astype(np.float32)
    q = rng.standard_normal((6, 8)).astype(np.float32)
    exact = exact_topk_ids(q, vecs, 10)
    probe = RecallProbe(vecs, k=10, every=1)
    reg = MetricsRegistry()
    probe.bind(reg)
    probe.observe(_FakeReq(q, exact, 10), "H")
    assert probe.estimate("H") == 1.0
    half = exact.copy()
    half[:, 5:] = -1                    # blow away half the hits
    probe.observe(_FakeReq(q, half, 10), "H")
    assert probe.estimate("H") == pytest.approx(0.75)
    snap = reg.snapshot()
    assert snap['juno_recall_samples_total{mode="H"}'] == 12
    assert snap['juno_recall_online_at_k{k="10",mode="H"}'] == (
        pytest.approx(0.75))


def test_latency_histogram_is_obs_histogram():
    lh = LatencyHistogram()
    assert isinstance(lh, Histogram)
    oh = Histogram()
    lh.add(0.01)
    oh.merge(lh)                        # identical bucketing by definition
    assert oh.n == 1


# ---------------------------------------------------------------------------
# serving integration: zero result impact, identical lattice, live series
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def obs_env(tmp_path_factory):
    pts, q = make_dataset(DEEP_LIKE, 4000, 32, key=jax.random.PRNGKey(9))
    pts, q = np.asarray(pts), np.asarray(q)
    cfg = JunoConfig(n_clusters=16, n_entries=16, calib_queries=12,
                     kmeans_iters=4, capacity_mult=1.2)
    idx = build(pts, cfg)
    root = tmp_path_factory.mktemp("obs_store")
    store = ArtifactStore(str(root))
    assert store.put("main", idx, cfg) == 1
    return pts, q, cfg, idx, store


def _mixed_wave(eng, q):
    reqs = [eng.submit(q[:5], k=10, mode="H", nprobe=8),
            eng.submit(q[5:9], k=10, mode="H2", nprobe=8),
            eng.submit(q[9:12], k=10, mode="H"),
            eng.submit(q[12:16], k=10, mode="H2")]
    eng.run()
    return reqs


@pytest.mark.parametrize("tier", ["resident", "paged"])
def test_obs_on_off_bit_parity(obs_env, tier):
    pts, q, cfg, idx, store = obs_env

    def make(obs):
        if tier == "resident":
            return AnnServeEngine(idx, obs=obs)
        paged = PagedIndexData(store.path("main", 1), expect_config=cfg)
        return PagedAnnServeEngine(paged, obs=obs)

    plain, inst = make(None), make(Observability(
        recall=RecallProbe(pts, k=10, every=1)))
    r_plain, r_inst = _mixed_wave(plain, q), _mixed_wave(inst, q)
    for a, b in zip(r_plain, r_inst):
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.scores, b.scores)
    # the jit signature lattice must be untouched by instrumentation
    assert plain.stats["signatures"] == inst.stats["signatures"]
    snap = inst.obs.registry.snapshot()
    assert snap['juno_engine_requests_total{mode="H"}'] == 2
    assert snap['juno_engine_requests_total{mode="H2"}'] == 2
    assert snap['juno_recall_online_at_k{k="10",mode="H"}'] > 0.0
    if tier == "paged":
        assert snap["juno_paged_faults_total"] > 0


def test_engine_spans_nest_under_ticks(obs_env):
    _, q, cfg, idx, _ = obs_env
    obs = Observability()
    eng = AnnServeEngine(idx, obs=obs)
    _mixed_wave(eng, q)
    spans = obs.tracer.spans()
    by_id = {s.span_id: s for s in spans}
    names = {s.name for s in spans}
    assert {"engine.tick", "engine.dispatch", "engine.merge",
            "engine.enqueue"} <= names
    for s in spans:
        if s.name in ("engine.dispatch", "engine.merge", "engine.enqueue"):
            assert by_id[s.parent_id].name == "engine.tick"
    # every request's trace_id shows up on its enqueue span
    enq = {s.trace_id for s in spans if s.name == "engine.enqueue"}
    assert len(enq) == 4


def test_fleet_merged_registry_sums_replicas(obs_env):
    _, q, cfg, idx, _ = obs_env
    fleet = AnnServeFleet(idx, n_replicas=2, shards_per_replica=1, obs=True)
    for i in range(6):
        fleet.submit(q[i * 2:i * 2 + 2], k=10, mode="M", nprobe=8)
    fleet.run()
    merged = fleet.merged_registry()
    snap = merged.snapshot()
    assert snap["juno_fleet_submitted_total"] == 6
    served = sum(v for k, v in snap.items()
                 if k.startswith("juno_fleet_served_total"))
    assert served == 6
    # replica child registries fold in: engine query totals sum to the
    # fleet-wide query count
    assert snap["juno_engine_queries_total"] == 12
    # per-request fleet spans carry the queue/compute/merge children
    roots = [s for s in fleet.obs.tracer.spans() if s.name == "fleet.request"]
    assert len(roots) == 6
    kids = [s for s in fleet.obs.tracer.spans()
            if s.parent_id in {r.span_id for r in roots}]
    assert len(kids) == 3 * len(roots)


def test_cache_stats_alias_matches_registry(obs_env):
    pts, q, cfg, idx, store = obs_env
    paged = PagedIndexData(store.path("main", 1), expect_config=cfg)
    obs = Observability()
    eng = PagedAnnServeEngine(paged, obs=obs)
    _mixed_wave(eng, q)
    stats = eng.cache_stats()           # deprecated dict-shaped alias
    snap = obs.registry.snapshot()
    assert snap["juno_cache_hits_total"] == stats["hits"]
    assert snap["juno_cache_misses_total"] == stats["misses"]
    assert snap["juno_cache_evictions_total"] == stats["evictions"]
    assert snap["juno_cache_bytes"] == stats["bytes"]


def test_observability_child_shares_tracer_and_probe():
    probe = RecallProbe(np.zeros((4, 2), np.float32), k=1)
    parent = Observability(recall=probe)
    child = parent.child()
    assert child.tracer is parent.tracer
    assert child.recall is parent.recall
    assert child.registry is not parent.registry
    child.registry.counter("juno_x_total").inc()
    assert "juno_x_total" not in parent.registry.snapshot()


# ---------------------------------------------------------------------------
# the profiler twin: spans and search stages on the device trace's clock
# ---------------------------------------------------------------------------

def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler trace; its host events as (name, start
    ns, end ns, stats dict), in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    return sorted(((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats))
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events),
                  key=lambda e: e[1])


def test_tracer_span_reaches_the_profiler(tmp_path):
    tracer = Tracer()

    def work():
        with tracer.span("engine.tick", trace_id="7", bucket=128):
            with tracer.span("engine.dispatch", mode="H2"):
                pass

    evs = {e[0]: e for e in _profiled(tmp_path, work)}
    tick, disp = evs["engine.tick"], evs["engine.dispatch"]
    assert tick[3]["bucket"] == 128 and str(tick[3]["trace_id"]) == "7"
    assert disp[3]["mode"] == "H2"
    assert tick[1] <= disp[1] <= disp[2] <= tick[2]
    # the ring buffer holds the same spans, nested the same way
    rec = {sp.name: sp for sp in tracer.spans()}
    assert rec["engine.dispatch"].parent_id == rec["engine.tick"].span_id


def test_engine_without_obs_writes_its_spans_to_the_profiler(obs_env,
                                                             tmp_path):
    _, q, cfg, idx, _ = obs_env
    eng = AnnServeEngine(idx, batch_buckets=(8,))
    eng.submit(q[:5], k=10, mode="H2", nprobe=8)
    eng.run()                                    # compile outside the trace

    def work():
        eng.submit(q[5:10], k=10, mode="H2", nprobe=8)
        eng.run()

    evs = [e for e in _profiled(tmp_path, work)
           if e[0].startswith("engine.")]
    assert [e[0] for e in evs] == [
        "engine.tick", "engine.pick", "engine.dispatch", "engine.launch",
        "engine.readback", "engine.merge"]
    tick, pick, disp, launch, back, merge = evs

    def inside(child, parent):
        return parent[1] <= child[1] <= child[2] <= parent[2]

    assert all(inside(e, tick) for e in (pick, disp, merge))
    assert inside(launch, disp) and inside(back, disp)
    assert pick[2] <= disp[1] and disp[2] <= merge[1]
    assert launch[2] <= back[1]
    assert disp[3]["bucket"] == 8 and disp[3]["rows"] == 5


def test_h2_program_names_its_stages_in_op_metadata(obs_env):
    _, q, cfg, idx, _ = obs_env
    eng = AnnServeEngine(idx, batch_buckets=(8,))
    fn, kw = eng.search_call(10, "H2", 8, None)
    text = fn.lower(eng.index.data, q[:8], **kw).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    scopes = {c for n in names for c in n.split("/")}
    assert {"search.stage_a", "search.gather", "search.lut",
            "search.hit_count", "search.top_c", "search.rerank"} <= scopes


def test_obs_imports_and_traces_without_jax():
    code = """
import sys
class NoJax:
    def find_spec(self, name, path=None, target=None):
        if name == "jax" or name.startswith("jax."):
            raise ImportError("jax is blocked")
sys.meta_path.insert(0, NoJax())
import repro.obs
tracer = repro.obs.Tracer()
with tracer.span("engine.tick", bucket=8):
    pass
assert [s.name for s in tracer.spans()] == ["engine.tick"]
assert not any(m == "jax" or m.startswith("jax.") for m in sys.modules)
"""
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr

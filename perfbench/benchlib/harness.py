"""One run of one benchmark cell, found by name in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``perfbench/``, found by name:

* ``configs/<config>.json``: dataset, index and serving settings, the
  guarantees, the precision stated for the query path;
* ``traffic/<traffic>.json``: the parameters the generator reads
  (``benchlib/traffic.py``);
* ``limits/<workload>.json``: the limit of each number that decides
  ``correct`` (``benchlib/compare.py``);
* ``metrics/<metric>.py``: a reader with ``read(reading)`` that returns the
  per-layer metric, or None where the run has nothing to read;
* ``peaks.json``: the chip's peaks, keyed by ``device_kind``.

The program under test is imported from the checkout's ``src/`` only.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
#: the persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: rows per block of the reference (about 2.2 GB of temporaries at 10M)
REF_BLOCK = 32
#: rows per block of the probed-points count (stage A only)
PROBE_BLOCK = 1024


class BenchError(RuntimeError):
    """The run cannot measure: no chip, an unknown device, missing files."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ---- finding the cell's files ---------------------------------------------
def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise BenchError(f"missing file {path}") from e


def cell_files(workload: str, root: Path = ROOT) -> dict:
    """The cell's entry and its configuration, traffic, limits and metric
    entries, read from ``BENCHMARK.json`` and the files it names."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    bdir = root / BENCH_DIR.name
    return {
        "cell": cell,
        "config": load_json(root / conf["file"]),
        "traffic": load_json(bdir / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(bdir / "limits" / f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
        "peaks": load_json(bdir / "peaks.json"),
    }


def load_reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = root / BENCH_DIR.name / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---- device and program ---------------------------------------------------
def check_device(devices, chips: int, peaks: dict) -> dict:
    """The peaks of the device the run measures on; raises where there is no
    TPU, too few chips, or a device the peaks table does not hold."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise BenchError(f"no TPU: JAX finds {found}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return peaks[kind]


def import_program(root: Path = ROOT):
    """The program's modules, from ``<root>/src`` and nowhere else."""
    src = root / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise BenchError(f"the program is not in this checkout ({src})")
    sys.path.insert(0, str(src))
    import repro
    if Path(repro.__file__).resolve().parents[1] != src.resolve():
        raise BenchError(f"repro imported from {repro.__file__}, not {src}")
    from repro.build import build_streaming
    from repro.core import JunoConfig, MutableJunoIndex
    from repro.serve import AnnServeEngine
    return dict(build_streaming=build_streaming, JunoConfig=JunoConfig,
                MutableJunoIndex=MutableJunoIndex,
                AnnServeEngine=AnnServeEngine)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache in the checkout (every program
    kept), unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA backend compilations while ``active``."""

    def __init__(self):
        import jax.monitoring
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ---- set-up ----------------------------------------------------------------
def index_state(data):
    """The served index as the reference reads it (``reference.IndexState``)."""
    from benchlib import reference as ref
    import jax.numpy as jnp
    d = data.density
    return ref.IndexState(
        centroids=data.ivf.centroids, entries=data.codebook.entries,
        entry_sq=data.codebook.entry_sq, dens_grid=d.grid, dens_lo=d.lo,
        dens_hi=d.hi, coeffs=d.coeffs, tau_min=jnp.asarray(d.tau_min),
        tau_max=jnp.asarray(d.tau_max), point_ids=data.ivf.point_ids,
        codes=data.codes,
        slot_codes=ref.slot_codes(data.codes, data.ivf.point_ids),
        labels=data.ivf.labels)


def ids_not_once(point_ids: np.ndarray, n_points: int) -> int:
    """How many corpus ids ``0..n_points-1`` the served cluster layout does
    not hold exactly once, plus the held ids that are no corpus id."""
    held = point_ids[point_ids >= 0].astype(np.int64)
    outside = int(np.sum(held >= n_points))
    times = np.bincount(held[held < n_points], minlength=n_points)
    return int(np.sum(times != 1)) + outside


def request_groups(traffic: dict) -> list[np.ndarray]:
    """Pool rows of the closed loop's requests over one lap of the pool."""
    from benchlib.traffic import pool_rows
    r, n = traffic["rows_per_request"], traffic["n_pool"]
    return [pool_rows(s, r, n) for s in range(0, n, r)]


@dataclasses.dataclass
class Served:
    """What the window served: per request, its pool rows and the engine's
    request object."""
    rows: list
    reqs: list
    t0: float
    t_end: float


# ---- the run ----------------------------------------------------------------
@dataclasses.dataclass
class Built:
    """A built index with its query pool and the evaluation set's exact
    answers (None where the traffic has no evaluation set)."""
    index: object
    pool: np.ndarray
    gt: np.ndarray | None
    setup: dict


def build(prog, conf: dict, seed: int, n_pool: int, n_eval: int,
          k: int) -> Built:
    """Corpus and query pool from the seed, on the device; the program's
    streaming build; exact top-k of the first ``n_eval`` pool queries."""
    import jax
    from benchlib import datagen, groundtruth
    data = conf["dataset"]
    k_data, k_build = jax.random.split(datagen.seed_key(seed))
    setup = {}
    t = time.perf_counter()
    chunks = datagen.corpus_chunks(data, k_data)
    pool_dev = datagen.query_pool(data, k_data, n_pool)
    jax.block_until_ready((chunks, pool_dev))
    pool = np.asarray(pool_dev)
    setup["data_s"] = time.perf_counter() - t

    t = time.perf_counter()
    index = prog["build_streaming"](
        lambda: (np.asarray(c) for c in chunks),
        prog["JunoConfig"](**conf["index"]), key=k_build)
    jax.block_until_ready(index)
    setup["build_s"] = time.perf_counter() - t

    t = time.perf_counter()
    gt = None
    if n_eval:
        gt = groundtruth.exact_topk(pool_dev[:n_eval], chunks, k=k,
                                    metric=data["metric"])
    del chunks, pool_dev
    gc.collect()
    setup["ground_truth_s"] = time.perf_counter() - t
    return Built(index=index, pool=pool, gt=gt, setup=setup)


def make_engine(prog, conf: dict, trf: dict, index):
    """The engine the cell's traffic asks for, over a ``MutableJunoIndex``."""
    if trf["loop"] != "closed":
        raise BenchError(f"traffic loop {trf['loop']!r}: the generator "
                         f"drives closed loops only")
    mindex = prog["MutableJunoIndex"](
        index, side_capacity=conf["serve"]["side_capacity"])
    return prog["AnnServeEngine"](
        mindex, metric=conf["dataset"]["metric"],
        batch_buckets=tuple(trf["batch_buckets"]), **trf["engine"])


@dataclasses.dataclass
class Window:
    """What one measured window served, and the compilations inside it."""
    served: Served
    rows: int
    compiles: int


def measure(engine, trf: dict, pool: np.ndarray, seconds: float) -> Window:
    """Drive the engine with the cell's traffic for ``seconds``."""
    counter = CompileCounter()
    q0 = engine.stats["queries"]
    counter.active = True
    served = closed_loop(engine, trf, pool, seconds)
    counter.active = False
    return Window(served=served, rows=engine.stats["queries"] - q0,
                  compiles=counter.count)


def stage_a_precision(devices) -> str:
    """The stated stage-A precision: the device's default matmul precision
    (bf16 operands on a TPU, f32 elsewhere)."""
    return "bf16" if devices[0].platform == "tpu" else "f32"


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, root: Path = ROOT, require_tpu: bool = True,
        files: dict | None = None) -> dict:
    """Set up, measure, compare; return the result line's object (the
    compared numbers last, under ``checks``)."""
    files = files or cell_files(workload, root)
    cell, conf, trf = files["cell"], files["config"], files["traffic"]
    import jax
    devices = jax.devices()
    if require_tpu:
        peaks = check_device(devices, cell["chips"], files["peaks"])
    else:
        peaks = files["peaks"].get(devices[0].device_kind, {})
    prog = import_program(root)
    cache = use_compile_cache() if require_tpu else "off"
    from benchlib import groundtruth, reference as ref
    from benchlib import traffic as tlib
    from benchlib.compare import judge

    metric = conf["dataset"]["metric"]
    built = build(prog, conf, seed, trf["n_pool"], trf["n_eval"], trf["k"])
    setup = built.setup
    t = time.perf_counter()
    engine = make_engine(prog, conf, trf, built.index)
    setup["engine_s"] = time.perf_counter() - t
    t = time.perf_counter()
    sigs = warm_up(engine, trf, built.pool)
    setup["warm_s"] = time.perf_counter() - t
    log("setup " + " ".join(f"{k}={v:.3f}" for k, v in setup.items())
        + f" signatures={sorted(sigs)} compile_cache={cache}")

    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    if trace:   # host spans and device ops; no Python call tracing
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_process
    win = measure(engine, trf, built.pool, seconds)
    if trace:
        jax.profiler.stop_trace()
    if win.compiles:
        log(f"warning compilations_in_window={win.compiles}")
    mem = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    memory_peak = max(m.get("peak_bytes_in_use", 0) for m in mem)

    served = win.served
    ids, scores, qrows, failed = answers(served, trf["k"])
    metrics = {"setup_s": {"value": setup_s, "unit": "s"},
               "qps": {"value": win.rows / (served.t_end - served.t0),
                       "unit": "queries/s"}}
    if trf["n_eval"]:   # answered in the window, or now where it ran out
        rec = groundtruth.recall_per_query(
            finish_eval(engine, trf, built.pool, served), built.gt)
        metrics["recall_at_10"] = {"value": float(rec.mean()),
                                   "unit": "fraction"}
        log(f"recall n_eval={len(rec)} mean={rec.mean():.6f} "
            f"sigma={rec.std():.6f} "
            f"sigma_over_sqrt_n={rec.std() / np.sqrt(len(rec)):.6f}")

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell["chips"], "memory_peak_bytes": int(memory_peak)}
    result = {"correct": False, "attempted": len(served.reqs),
              "failed": failed,
              "metrics": {m["name"]: metrics[m["name"]]
                          for m in files["end_to_end"]},
              "device": device}

    # the comparison, once the window has closed and the peak is read; the
    # engine is freed, the served index is read as data
    data = engine.index.data
    del engine
    gc.collect()
    t = time.perf_counter()
    st = index_state(data)
    pick = tlib.sample_rows(len(qrows), trf["check_sample"], seed)
    kw = dict(nprobe=trf["nprobe"], metric=metric,
              stage_a=stage_a_precision(devices))
    values = check_answers(st, built.pool[qrows[pick]], ids[pick],
                           scores[pick], k=trf["k"], **kw)
    values["ids_not_once"] = ids_not_once(np.asarray(data.ivf.point_ids),
                                          conf["dataset"]["n_points"])
    log(f"reference compared={len(pick)} seconds="
        f"{time.perf_counter() - t:.3f} " + " ".join(
            f"{k}={v}" for k, v in values.items()))

    if trace:
        from benchlib import tracefile
        tr = tracefile.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        window_rows = np.concatenate(served.rows)
        reading = Reading(
            trace=tr, peaks=peaks, window_s=tr.window_s(),
            probes=len(window_rows) * trf["nprobe"],
            probed_points=int(ref.run_blocks(
                functools.partial(ref.probed_points, st, **kw),
                built.pool[window_rows], PROBE_BLOCK).sum()),
            subspaces=int(st.codes.shape[1]),
            entries=int(st.entry_sq.shape[1]))
        per_layer = {}
        for m in files["per_layer"]:
            v = load_reader(m["name"], root)(reading)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = per_layer
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}

    checks = judge(values, files["limits"])
    result["correct"] = failed == 0 and all(c["ok"] for c in checks)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    return result


def warm_up(engine, trf: dict, pool: np.ndarray) -> set:
    """Compile and load every search signature this cell's traffic uses,
    and no other; returns them."""
    k, mode, nprobe = trf["k"], trf["mode"], trf["nprobe"]
    # one request per distinct routed signature of the pool's requests
    seen = {}
    for rows in request_groups(trf):
        req = engine.submit(pool[rows], k=k, mode=mode, nprobe=nprobe)
        seen.setdefault(engine.route(req), rows)
    engine.queue.clear()
    for _ in range(2):
        for sig in sorted(seen):
            engine.submit(pool[seen[sig]], k=k, mode=mode, nprobe=nprobe)
            engine.run()
    engine.completed.clear()
    return set(engine.stats["signatures"])


def closed_loop(engine, trf, pool, seconds) -> Served:
    """One client: send the next request when the last is answered, until
    the window's time is up."""
    from benchlib.traffic import pool_rows
    r, n = trf["rows_per_request"], trf["n_pool"]
    rows, reqs = [], []
    start = 0
    t0 = time.perf_counter()
    with annotate("bench.window"):
        while time.perf_counter() - t0 < seconds:
            idx = pool_rows(start, r, n)
            with annotate("bench.submit"):
                req = engine.submit(pool[idx], k=trf["k"], mode=trf["mode"],
                                    nprobe=trf["nprobe"])
            with annotate("bench.step"):
                engine.run()
            rows.append(idx)
            reqs.append(req)
            start += r
    return Served(rows=rows, reqs=reqs, t0=t0,
                  t_end=max(q.t_done for q in reqs))


def answers(served: Served, k: int):
    """(ids, scores, pool row) per answered query, and the count of requests
    that got no answer of the asked shape."""
    ids, scores, qrows = [], [], []
    failed = 0
    for idx, req in zip(served.rows, served.reqs):
        if (not req.done or req.ids is None
                or np.shape(req.ids) != (len(idx), k)):
            failed += 1
            continue
        ids.append(np.asarray(req.ids))
        scores.append(np.asarray(req.scores, np.float64))
        qrows.append(idx)
    if not ids:
        return (np.zeros((0, k), np.int64), np.zeros((0, k)),
                np.zeros((0,), np.int64), failed)
    return (np.concatenate(ids), np.concatenate(scores),
            np.concatenate(qrows), failed)


def finish_eval(engine, trf, pool, served: Served) -> np.ndarray:
    """(n_eval, k) ids of the evaluation set: the window's first answers to
    pool rows 0..n_eval-1, and, where the window closed before all were
    answered, the rest served now (untimed) by the same engine."""
    from benchlib.traffic import pool_rows
    n_eval, r, k = trf["n_eval"], trf["rows_per_request"], trf["k"]
    got = np.full((n_eval, k), -1, np.int64)
    have = np.zeros(n_eval, bool)
    for idx, req in zip(served.rows, served.reqs):
        m = (idx < n_eval) & ~have[np.minimum(idx, n_eval - 1)]
        if req.ids is not None and m.any():
            got[idx[m]] = np.asarray(req.ids)[m]
            have[idx[m]] = True
    start = int(np.argmin(have)) if not have.all() else n_eval
    while start < n_eval:
        idx = pool_rows(start, r, trf["n_pool"])
        req = engine.submit(pool[idx], k=k, mode=trf["mode"],
                            nprobe=trf["nprobe"])
        engine.run()
        m = (idx < n_eval) & ~have[np.minimum(idx, n_eval - 1)]
        got[idx[m]] = np.asarray(req.ids)[m]
        have[idx[m]] = True
        start += r
    return got


def check_answers(st, queries, ids, scores, *, k, nprobe, metric,
                  stage_a) -> dict:
    """The comparison's numbers for served answers of ``queries``."""
    from benchlib import compare, reference as ref
    search = functools.partial(ref.search_block, st, k=k, nprobe=nprobe,
                               metric=metric, stage_a=stage_a)
    ref_s, _ = ref.run_blocks(search, queries, REF_BLOCK)
    score = functools.partial(ref.score_of, st, metric=metric,
                              stage_a=stage_a)
    ref_of = ref.run_blocks(score, queries, REF_BLOCK, ids.astype(np.int32))
    return compare.numbers(scores, ref_s, ref_of, metric)


@dataclasses.dataclass
class Reading:
    """What a per-layer reader may read."""
    trace: object         # tracefile.Trace of the window
    peaks: dict           # this device's row of peaks.json
    window_s: float
    probes: int           # (query row, probed cluster) pairs in the window
    probed_points: int    # live points in those probed clusters
    subspaces: int
    entries: int

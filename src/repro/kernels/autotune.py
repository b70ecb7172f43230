"""Measured autotune pass for the fused Pallas kernels (ROADMAP item 2).

The fused kernels expose result-invariant knobs — tile shapes ``bq``/``bp``
and the hit-count accumulation dtype ``acc`` on the TPU path, the θ-selection
strategy ``topc_impl`` on the host path (see ``fused_two_stage`` /
``fused_three_stage``: every option produces bit-identical outputs, pinned
by tests/test_autotune.py). This module picks between them by measurement:

* ``tune(kernel)`` times each candidate :class:`KernelConfig` on a small
  synthetic problem (median of ``repeats`` wall-clock runs, compiled call
  only) and returns the winner. Candidates are deduplicated down to the
  knobs that are *effective* on the current backend (off-TPU only
  ``topc_impl`` reaches the dispatched host path, on TPU only
  ``bq``/``bp``/``acc_dtype`` do), and ties break deterministically toward
  the earlier candidate in the canonical enumeration order — repeated
  tuning under timing jitter cannot oscillate between equivalent configs.
* ``save_cache``/``load_cache`` persist winners per backend as JSON keyed
  by ``(schema, backend)``. Loading FAILS CLOSED: a corrupt file, a schema
  bump, another backend's cache, or out-of-domain field values all return
  ``None`` (caller retunes) — a stale cache is never silently applied.
* ``set_config``/``active_config`` hold the process-global active configs
  that ``kernels.ops`` dispatchers consult. Configs are read at TRACE
  time: install them (``ensure_tuned`` or ``set_config``) before the first
  search dispatch — changing them later does not retrace already-compiled
  signatures (by the same token, tuning can never widen an engine's jit
  signature lattice; pinned in tests/test_recall_matrix.py).

Every knob is benign under mis-selection — a wrong cache entry could only
ever cost speed, but the fail-closed load refuses even that.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from .fused_two_stage import ACC_DTYPES

SCHEMA_VERSION = 1

#: kernels this pass knows how to tune (and the ops dispatchers consult)
KERNELS = ("fused_two_stage", "fused_three_stage")

TOPC_IMPLS = ("sort", "topk")

# canonical candidate axes — enumeration order is the deterministic
# tie-break order, so keep these stable across releases
# (tiles the chip accepts: bq a multiple of 8 on the sublane axis, bp a
# multiple of 128 on the lane axis — the kernels round other values up)
_BQ = (8, 16)
_BP = (128, 256)


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One point in the tuning space; defaults reproduce the untuned path.

    ``bq``/``bp``/``acc_dtype`` steer the Pallas kernel (TPU), ``topc_impl``
    the host path — all four are result-invariant by construction.
    """

    bq: int = 8
    bp: int | None = None
    topc_impl: str = "sort"
    acc_dtype: str = "f32"

    def validate(self) -> bool:
        """True iff every field is in the domain the kernels accept."""
        return (isinstance(self.bq, int) and not isinstance(self.bq, bool)
                and self.bq >= 1
                and (self.bp is None
                     or (isinstance(self.bp, int)
                         and not isinstance(self.bp, bool) and self.bp >= 1))
                and self.topc_impl in TOPC_IMPLS
                and self.acc_dtype in ACC_DTYPES)


_active: dict[str, KernelConfig] = {}


def active_config(kernel: str) -> KernelConfig:
    """Config the ops dispatchers apply for ``kernel`` (default if unset)."""
    return _active.get(kernel, KernelConfig())


def set_config(kernel: str, config: KernelConfig) -> None:
    """Install ``config`` as the process-global active config for ``kernel``.

    Takes effect for signatures traced AFTER this call (see module
    docstring) — install before the first search dispatch.
    """
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; expected one of "
                         f"{KERNELS}")
    if not config.validate():
        raise ValueError(f"invalid config for {kernel!r}: {config}")
    _active[kernel] = config


def reset() -> None:
    """Drop all active configs (every kernel back to defaults)."""
    _active.clear()


def backend_name() -> str:
    """The backend string cache entries are keyed on."""
    return jax.default_backend()


def _effective_key(config: KernelConfig, backend: str):
    """The knob subset that can reach the dispatched path on ``backend``."""
    if backend == "tpu":
        return (config.bq, config.bp, config.acc_dtype)
    return (config.topc_impl,)


def candidates(backend: str | None = None) -> list[KernelConfig]:
    """Canonically-ordered candidate configs, deduplicated per backend.

    Two configs differing only in knobs the ``backend`` cannot exercise
    would measure identically; only the first (canonical order) survives.
    """
    backend = backend or backend_name()
    out, seen = [], set()
    for bq, bp, topc, acc in itertools.product(_BQ, (None,) + _BP,
                                               TOPC_IMPLS, ACC_DTYPES):
        cfg = KernelConfig(bq=bq, bp=bp, topc_impl=topc, acc_dtype=acc)
        key = _effective_key(cfg, backend)
        if key not in seen:
            seen.add(key)
            out.append(cfg)
    return out


def _two_stage_problem(seed: int = 0):
    """Small synthetic (lut, table, codes, valid, cap_c) tuning workload."""
    rng = np.random.default_rng(seed)
    q, n_probe, p, s, e = 8, 4, 64, 8, 16
    lut = jnp.asarray(rng.normal(size=(q, n_probe, s, e)), jnp.float32)
    table = jnp.asarray(rng.integers(-1, 2, size=(q, n_probe, s, e)),
                        jnp.int8)
    codes = jnp.asarray(rng.integers(0, e, size=(q, n_probe, p, s)),
                        jnp.uint8)
    valid = jnp.asarray(rng.random(size=(q, n_probe, p)) < 0.9)
    return lut, table, codes, valid, 32


def _three_stage_problem(seed: int = 0):
    """The two-stage workload plus a tiny synthetic RT grid."""
    lut, table, codes, valid, cap_c = _two_stage_problem(seed)
    rng = np.random.default_rng(seed + 1)
    q, n_probe = codes.shape[:2]
    n_cells, cap = 9, 8
    q0 = jnp.asarray(rng.normal(size=(q,)), jnp.float32)
    q1 = jnp.asarray(rng.normal(size=(q,)), jnp.float32)
    radius = jnp.asarray(rng.random(size=(q,)), jnp.float32)
    c0 = jnp.asarray(rng.normal(size=(n_cells, cap)), jnp.float32)
    c1 = jnp.asarray(rng.normal(size=(n_cells, cap)), jnp.float32)
    reach = jnp.asarray(np.abs(rng.normal(size=(n_cells, cap))), jnp.float32)
    slot_idx = jnp.asarray(
        rng.integers(0, n_cells * cap, size=(q, n_probe)), jnp.int32)
    return (lut, table, codes, valid, q0, q1, radius, c0, c1, reach,
            slot_idx, cap_c)


def _run_fn(kernel: str, config: KernelConfig, problem):
    """A zero-arg callable running ``kernel`` with ``config`` applied."""
    from . import fused_three_stage as _f3
    from . import fused_two_stage as _f2
    on_tpu = backend_name() == "tpu"
    if kernel == "fused_two_stage":
        lut, table, codes, valid, cap_c = problem
        if on_tpu:
            return lambda: _f2.fused_two_stage(
                lut, table, codes, valid, cap_c=cap_c, bq=config.bq,
                bp=config.bp, acc=config.acc_dtype)
        return lambda: _f2.fused_two_stage_host(
            lut, table, codes, valid, cap_c=cap_c,
            topc_impl=config.topc_impl)
    if kernel == "fused_three_stage":
        (lut, table, codes, valid, q0, q1, radius, c0, c1, reach, slot_idx,
         cap_c) = problem
        if on_tpu:
            return lambda: _f3.fused_three_stage(
                lut, table, codes, valid, q0, q1, radius, c0, c1, reach,
                slot_idx, cap_c=cap_c, bq=config.bq, bp=config.bp,
                acc=config.acc_dtype)
        return lambda: _f3.fused_three_stage_host(
            lut, table, codes, valid, q0, q1, radius, c0, c1, reach,
            slot_idx, cap_c=cap_c, topc_impl=config.topc_impl)
    raise ValueError(f"unknown kernel {kernel!r}")


def _block(out):
    """Block until every array in a pytree of outputs is ready."""
    for leaf in jax.tree_util.tree_leaves(out):
        leaf.block_until_ready()


def tune(kernel: str, *, repeats: int = 5, problem=None) -> KernelConfig:
    """Measure every effective candidate for ``kernel``; return the winner.

    One warmup call per candidate absorbs compilation, then ``repeats``
    timed runs; the score is the median. Winner = min (median, canonical
    index) — the index tie-break keeps re-tuning deterministic when two
    configs measure identically.
    """
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; expected one of "
                         f"{KERNELS}")
    if problem is None:
        problem = (_two_stage_problem() if kernel == "fused_two_stage"
                   else _three_stage_problem())
    best = None
    for idx, cfg in enumerate(candidates()):
        fn = _run_fn(kernel, cfg, problem)
        _block(fn())                                  # compile + warm
        times = []
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            _block(fn())
            times.append(time.perf_counter() - t0)
        med = sorted(times)[len(times) // 2]
        if best is None or (med, idx) < best[:2]:
            best = (med, idx, cfg)
    return best[2]


def default_cache_path() -> Path:
    """Cache location: ``$REPRO_AUTOTUNE_CACHE`` or a per-user default."""
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "autotune.json"


def save_cache(configs: dict[str, KernelConfig], path: Path | str,
               *, backend: str | None = None) -> None:
    """Write ``configs`` as the JSON cache for ``backend`` (atomic-enough:
    deterministic serialization, parents created)."""
    for kernel, cfg in configs.items():
        if kernel not in KERNELS or not cfg.validate():
            raise ValueError(f"refusing to cache invalid entry "
                             f"{kernel!r}: {cfg}")
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    doc = {
        "schema": SCHEMA_VERSION,
        "backend": backend or backend_name(),
        "configs": {k: dataclasses.asdict(v)
                    for k, v in sorted(configs.items())},
    }
    path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")


def load_cache(path: Path | str,
               *, backend: str | None = None) -> dict[str, KernelConfig] | None:
    """Load a cache written by :func:`save_cache` — FAIL CLOSED.

    Returns the config dict only when the file parses, the schema version
    matches, the backend matches, every kernel name is known, and every
    field validates. Anything else → ``None`` (caller retunes); a stale or
    foreign cache is never silently applied.
    """
    path = Path(path)
    try:
        doc = json.loads(path.read_text())
    except (OSError, ValueError):
        return None
    if not isinstance(doc, dict):
        return None
    if doc.get("schema") != SCHEMA_VERSION:
        return None
    if doc.get("backend") != (backend or backend_name()):
        return None
    raw = doc.get("configs")
    if not isinstance(raw, dict):
        return None
    out = {}
    for kernel, fields in raw.items():
        if kernel not in KERNELS or not isinstance(fields, dict):
            return None
        if set(fields) != {f.name for f in dataclasses.fields(KernelConfig)}:
            return None
        try:
            cfg = KernelConfig(**fields)
        except TypeError:
            return None
        if not cfg.validate():
            return None
        out[kernel] = cfg
    return out


def ensure_tuned(path: Path | str | None = None, *, repeats: int = 3,
                 kernels: tuple[str, ...] = KERNELS) -> dict[str, KernelConfig]:
    """Load cached winners (or tune and cache them) and install as active.

    The one-call orchestrator: cache hit → install, zero measurement; miss
    (absent/corrupt/stale/foreign — :func:`load_cache` fails closed) →
    retune every requested kernel, save, install. Call once at process
    start, BEFORE the first search dispatch (trace-time read, see module
    docstring).
    """
    path = Path(path) if path is not None else default_cache_path()
    configs = load_cache(path)
    if configs is None or any(k not in configs for k in kernels):
        configs = {k: tune(k, repeats=repeats) for k in kernels}
        save_cache(configs, path)
    for kernel in kernels:
        set_config(kernel, configs[kernel])
    return dict(configs)

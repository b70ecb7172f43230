"""Autotune layer: cache round-trips, fail-closed loads, result parity.

The tuner (``kernels.autotune``) picks launch parameters, never results:
every knob it searches — (bQ, bP) tiling, top-C threshold implementation,
LUT accumulation dtype — is result-invariant by kernel contract, so a
tuned config must be bit-identical to the default one on both fused
kernels. The JSON cache is keyed on (schema, backend) and MUST fail
closed: a corrupt, stale, foreign-backend or schema-drifted file returns
``None`` (→ retune), never a silently misapplied config. The measured
``tune()`` search itself runs under the slow ``autotune`` marker (own CI
job); everything else here is deterministic tier 1.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.autotune import (KERNELS, KernelConfig, active_config,
                                    backend_name, candidates, ensure_tuned,
                                    load_cache, save_cache, set_config)


@pytest.fixture(autouse=True)
def _reset_active():
    autotune.reset()
    yield
    autotune.reset()


# ---------------------------------------------------------------------------
# config + candidate enumeration
# ---------------------------------------------------------------------------
def test_default_config_valid():
    cfg = KernelConfig()
    assert cfg.validate()
    assert active_config("fused_two_stage") == cfg
    assert active_config("fused_three_stage") == cfg


@pytest.mark.parametrize("bad", [
    dict(bq=0), dict(bq=-2), dict(bq=True), dict(bp=0), dict(bp=True),
    dict(topc_impl="quickselect"), dict(acc_dtype="f64"),
])
def test_config_validate_rejects(bad):
    assert not dataclasses.replace(KernelConfig(), **bad).validate()


def test_set_config_rejects_unknown_kernel():
    with pytest.raises(ValueError):
        set_config("fused_four_stage", KernelConfig())


def test_set_config_rejects_invalid_config():
    with pytest.raises(ValueError):
        set_config("fused_two_stage",
                   dataclasses.replace(KernelConfig(), acc_dtype="f64"))


def test_candidates_deduped_and_deterministic():
    """The search space collapses to the backend's effective knobs, keeps
    the first representative per effective key (deterministic tie-break),
    and always contains the default config."""
    for backend in ["cpu", "tpu"]:
        cs = candidates(backend)
        assert cs == candidates(backend)            # deterministic
        keys = [autotune._effective_key(c, backend) for c in cs]
        assert len(keys) == len(set(keys))          # deduped
        # the default path is always among the measured candidates
        assert autotune._effective_key(KernelConfig(), backend) in keys
    assert len(candidates("cpu")) == len(autotune.TOPC_IMPLS)


# ---------------------------------------------------------------------------
# cache round-trip: deterministic across runs
# ---------------------------------------------------------------------------
def test_cache_round_trip_deterministic(tmp_path):
    path = tmp_path / "autotune.json"
    configs = {"fused_two_stage": KernelConfig(bq=8, bp=128),
               "fused_three_stage": KernelConfig(topc_impl="topk",
                                                 acc_dtype="bf16")}
    save_cache(configs, path)
    blob1 = path.read_bytes()
    loaded = load_cache(path)
    assert loaded == configs
    save_cache(loaded, path)                         # save→load→save
    assert path.read_bytes() == blob1                # byte-identical
    assert blob1.endswith(b"\n")


def test_ensure_tuned_uses_cache_without_retuning(tmp_path, monkeypatch):
    """A valid cache short-circuits measurement entirely — ensure_tuned
    must install the cached configs and never call tune()."""
    path = tmp_path / "autotune.json"
    configs = {k: KernelConfig(bq=2, topc_impl="topk") for k in KERNELS}
    save_cache(configs, path)

    def boom(*a, **k):
        raise AssertionError("tune() ran despite a valid cache")
    monkeypatch.setattr(autotune, "tune", boom)
    got = ensure_tuned(path)
    assert got == configs
    for k in KERNELS:
        assert active_config(k) == configs[k]


# ---------------------------------------------------------------------------
# fail-closed loads: never misuse a stale/foreign/corrupt cache
# ---------------------------------------------------------------------------
def _valid_blob():
    return {"schema": autotune.SCHEMA_VERSION, "backend": backend_name(),
            "configs": {k: dataclasses.asdict(KernelConfig())
                        for k in KERNELS}}


def _corruptions():
    blob = _valid_blob()
    out = {"truncated-json": json.dumps(blob)[:-9],
           "not-a-dict": json.dumps([1, 2, 3]),
           "empty": ""}
    b = _valid_blob(); b["schema"] = autotune.SCHEMA_VERSION + 1
    out["schema-bump"] = json.dumps(b)
    b = _valid_blob(); b["backend"] = "definitely-not-" + backend_name()
    out["foreign-backend"] = json.dumps(b)
    b = _valid_blob(); b["configs"]["fused_four_stage"] = \
        dataclasses.asdict(KernelConfig())
    out["unknown-kernel"] = json.dumps(b)
    b = _valid_blob(); b["configs"][KERNELS[0]]["bq"] = -4
    out["invalid-field-value"] = json.dumps(b)
    b = _valid_blob(); b["configs"][KERNELS[0]]["block_q"] = \
        b["configs"][KERNELS[0]].pop("bq")
    out["field-set-drift"] = json.dumps(b)
    b = _valid_blob(); b["configs"][KERNELS[0]]["topc_impl"] = 7
    out["wrong-field-type"] = json.dumps(b)
    return out


@pytest.mark.parametrize("name", sorted(_corruptions()))
def test_load_fails_closed(tmp_path, name):
    path = tmp_path / "autotune.json"
    path.write_text(_corruptions()[name])
    assert load_cache(path) is None


def test_load_missing_file_is_none(tmp_path):
    assert load_cache(tmp_path / "nope.json") is None


def test_ensure_tuned_retunes_on_corrupt_cache(tmp_path, monkeypatch):
    """Corrupt cache → retune and REWRITE, never silently reuse."""
    path = tmp_path / "autotune.json"
    path.write_text("{not json")
    calls = []

    def fake_tune(kernel, **kw):
        calls.append(kernel)
        return KernelConfig()
    monkeypatch.setattr(autotune, "tune", fake_tune)
    got = ensure_tuned(path)
    assert sorted(calls) == sorted(KERNELS)
    assert load_cache(path) == got                   # rewritten, valid now


# ---------------------------------------------------------------------------
# tuned vs default: launch parameters must not change results
# ---------------------------------------------------------------------------
def _problem():
    """Q = 20 and P = 300: every candidate tiling splits the query axis
    (padded tail) and each probe's points (padded lane tail) into several
    blocks, and the candidates' tilings differ from one another."""
    rng = np.random.default_rng(0)
    q, n_probe, p, s, e, cap_c = 20, 3, 300, 6, 16, 12
    lut = rng.standard_normal((q, n_probe, s, e)).astype(np.float32)
    table = rng.integers(-1, 2, (q, n_probe, s, e)).astype(np.int8)
    codes = rng.integers(0, e, (q, n_probe, p, s)).astype(np.uint8)
    valid = rng.random((q, n_probe, p)) < 0.85
    return lut, table, codes, valid, cap_c


def test_tuned_configs_bit_identical_two_stage():
    """Every candidate config the tuner may pick returns bit-identical
    counts/cand and allclose distances from the two-stage kernel — on
    the host path (topc_impl) and the interpret-mode kernel (bq/bp/acc),
    i.e. the full effective-knob set of both backends."""
    from repro.kernels.fused_two_stage import (fused_two_stage,
                                               fused_two_stage_host)
    lut, table, codes, valid, cap_c = _problem()
    base_h = fused_two_stage_host(lut, table, codes, valid, cap_c=cap_c,
                                  metric="l2")
    base_k = fused_two_stage(lut, table, codes, valid, cap_c=cap_c,
                             metric="l2", interpret=True)
    for cfg in candidates("cpu") + candidates("tpu"):
        h = fused_two_stage_host(lut, table, codes, valid, cap_c=cap_c,
                                 metric="l2", topc_impl=cfg.topc_impl)
        k = fused_two_stage(lut, table, codes, valid, cap_c=cap_c,
                            metric="l2", bq=cfg.bq, bp=cfg.bp,
                            acc=cfg.acc_dtype, interpret=True)
        for base, got in [(base_h, h), (base_k, k)]:
            np.testing.assert_array_equal(np.asarray(base[0]),
                                          np.asarray(got[0]))
            np.testing.assert_array_equal(np.asarray(base[2]),
                                          np.asarray(got[2]))
            np.testing.assert_allclose(np.asarray(base[3]),
                                       np.asarray(got[3]), rtol=1e-5,
                                       atol=1e-5)


def test_tuned_configs_bit_identical_three_stage():
    """Same invariance for the three-stage kernel, probe verdicts
    included."""
    from repro.kernels.fused_three_stage import (fused_three_stage,
                                                 fused_three_stage_host)
    lut, table, codes, valid, cap_c = _problem()
    rng = np.random.default_rng(1)
    g, cap, q, n_probe = 3, 8, lut.shape[0], lut.shape[1]
    c0 = rng.random((g * g, cap)).astype(np.float32)
    c1 = rng.random((g * g, cap)).astype(np.float32)
    reach = np.abs(rng.normal(0, 0.2, (g * g, cap))).astype(np.float32)
    reach[:, cap // 2:] = -np.inf
    args = (rng.random(q).astype(np.float32),
            rng.random(q).astype(np.float32),
            rng.random(q).astype(np.float32),
            c0, c1, reach,
            rng.integers(0, g * g * cap, (q, n_probe)).astype(np.int32))
    base_h = fused_three_stage_host(lut, table, codes, valid, *args,
                                    cap_c=cap_c, metric="l2")
    base_k = fused_three_stage(lut, table, codes, valid, *args,
                               cap_c=cap_c, metric="l2", interpret=True)
    for cfg in candidates("cpu") + candidates("tpu"):
        h = fused_three_stage_host(lut, table, codes, valid, *args,
                                   cap_c=cap_c, metric="l2",
                                   topc_impl=cfg.topc_impl)
        k = fused_three_stage(lut, table, codes, valid, *args, cap_c=cap_c,
                              metric="l2", bq=cfg.bq, bp=cfg.bp,
                              acc=cfg.acc_dtype, interpret=True)
        for base, got in [(base_h, h), (base_k, k)]:
            np.testing.assert_array_equal(np.asarray(base[0]),
                                          np.asarray(got[0]))
            np.testing.assert_array_equal(np.asarray(base[2]),
                                          np.asarray(got[2]))
            np.testing.assert_array_equal(np.asarray(base[4]),
                                          np.asarray(got[4]))
            np.testing.assert_allclose(np.asarray(base[3]),
                                       np.asarray(got[3]), rtol=1e-5,
                                       atol=1e-5)


# ---------------------------------------------------------------------------
# the measured search itself (slow; own CI job)
# ---------------------------------------------------------------------------
@pytest.mark.autotune
def test_measured_tune_round_trips(tmp_path):
    """End-to-end: tune both kernels on the bundled micro-problems, cache,
    reload — the reloaded configs validate, match what was tuned, and a
    second ensure_tuned() run installs them without retuning."""
    path = tmp_path / "autotune.json"
    got = ensure_tuned(path, repeats=3)
    assert sorted(got) == sorted(KERNELS)
    for cfg in got.values():
        cfg.validate()
    assert load_cache(path) == got
    autotune.reset()
    again = ensure_tuned(path, repeats=3)
    assert again == got
    for k in KERNELS:
        assert active_config(k) == got[k]

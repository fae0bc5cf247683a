"""Constant-resolution contract for the calibration pass
(core/calibrate.py + the ``calibrated:`` tuning-cache namespace):
probes measure every serving-path constant finite and positive,
``resolve_constants`` prefers calibrated entries per constant with
torn/mis-versioned entries falling back silently to the hand-set
defaults, the ``choose_*`` decisions respond monotonically to the
constants that price them, the serving engine provably prices its
decisions from the calibrated set, and ``REPRO_DEFAULT_CONSTANTS``
reproduces the default decisions bit-for-bit."""

import dataclasses
import json

import jax
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro import configs
from repro.core import autotune, calibrate
from repro.models import transformer as T
from repro.serve import telemetry
from repro.serve.engine import ServeConfig, ServingEngine

SYNTH = {"dispatch_s": 3e-6, "page_lookup_s": 7e-8,
         "hbm_bandwidth": 2e10, "chunk_dispatch_s": 9e-6,
         "draft_token_s": 4e-6, "prefix_hash_s": 1e-6}

# Cost ladder for the monotonicity properties (indices drawn by
# hypothesis; the ladder itself is deterministic).
COSTS = tuple(float(c) for c in np.geomspace(1e-7, 1e-2, 12))


@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    """Isolated tuning cache + no force-defaults env leakage."""
    path = tmp_path / "cache.json"
    monkeypatch.setattr(autotune, "TUNING_CACHE_PATH", str(path))
    monkeypatch.setattr(autotune, "_tuning_cache", None)
    monkeypatch.delenv(autotune.DEFAULT_CONSTANTS_ENV, raising=False)
    return path


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def fast_results():
    """One fast probe pass for the whole module (the chunk probe runs a
    real engine); persist=False keeps the committed cache untouched."""
    return calibrate.run_calibration(fast=True, persist=False)


# ----------------------------------------------------------------------------
# Probes: every constant measured, finite, positive
# ----------------------------------------------------------------------------

def test_probes_cover_every_constant_finite_positive(fast_results):
    assert set(fast_results) == set(autotune.CALIBRATED_NAMES)
    assert len(fast_results) >= 5
    for name, r in fast_results.items():
        assert np.isfinite(r.value) and r.value > 0, (name, r)
        assert r.n_trials > 0
        assert np.isfinite(r.spread) and r.spread >= 0
        assert r.unit


def test_page_lookup_probe_reports_its_regression(fast_results):
    d = fast_results["page_lookup_s"].detail
    assert fast_results["page_lookup_s"].unit == "s/block"
    assert d["page_size"] == 8               # one page per visited block
    assert np.isfinite(d["slope_paged_s"])
    assert np.isfinite(d["slope_contig_s"])
    assert len(d["tables"]) >= 3


def test_probe_result_rejects_nonfinite():
    with pytest.raises(AssertionError):
        calibrate.ProbeResult("dispatch_s", float("nan"), "s", 1, 0.0)
    with pytest.raises(AssertionError):
        calibrate.ProbeResult("dispatch_s", 0.0, "s", 1, 0.0)
    with pytest.raises(AssertionError):
        calibrate.ProbeResult("not_a_constant", 1.0, "s", 1, 0.0)


# ----------------------------------------------------------------------------
# Cache namespace: record / load / resolve round trip
# ----------------------------------------------------------------------------

def test_record_load_resolve_roundtrip(tmp_cache):
    for name, v in SYNTH.items():
        autotune.record_calibration(name, v, n_trials=5, spread=0.1,
                                    timestamp=123.0)
    for name, v in SYNTH.items():
        hit = autotune.load_calibration(name)
        assert hit["value"] == v
        assert hit["n_trials"] == 5
        assert hit["schema_version"] == autotune.CALIBRATION_SCHEMA_VERSION
    const = autotune.resolve_constants()
    assert const.source == "calibrated"
    assert const.dispatch_s == SYNTH["dispatch_s"]
    assert const.page_lookup_s == SYNTH["page_lookup_s"]
    assert const.hbm_bandwidth == SYNTH["hbm_bandwidth"]
    assert const.chunk_dispatch_s == SYNTH["chunk_dispatch_s"]
    assert const.draft_token_s == SYNTH["draft_token_s"]
    assert const.prefix_hash_s == SYNTH["prefix_hash_s"]
    assert const.timestamp == 123.0
    rep = autotune.calibration_report()
    assert rep["source"] == "calibrated"
    for name in autotune.CALIBRATED_NAMES:
        row = rep["constants"][name]
        assert row["measured"] == SYNTH[name]
        assert np.isfinite(row["drift_ratio"]) and row["drift_ratio"] > 0
        assert row["n_trials"] == 5


def test_record_rejects_nonfinite_and_unknown(tmp_cache):
    with pytest.raises(AssertionError):
        autotune.record_calibration("dispatch_s", float("inf"))
    with pytest.raises(AssertionError):
        autotune.record_calibration("dispatch_s", -1e-6)
    with pytest.raises(AssertionError):
        autotune.record_calibration("made_up_constant", 1.0)


def test_torn_or_misversioned_entries_fall_back_per_constant(tmp_cache):
    blob = {
        autotune.calibration_key("page_lookup_s"): {
            "schema_version": autotune.CALIBRATION_SCHEMA_VERSION,
            "value": 7e-8, "backend": "cpu", "mesh": "dev1",
            "n_trials": 3, "timestamp": 1.0},
        autotune.calibration_key("chunk_dispatch_s"): "torn garbage",
        autotune.calibration_key("draft_token_s"): {
            "schema_version": 999, "value": 1e-6},
        autotune.calibration_key("hbm_bandwidth"): {
            "schema_version": autotune.CALIBRATION_SCHEMA_VERSION,
            "value": -4.0},
        autotune.calibration_key("prefix_hash_s"): {
            "schema_version": autotune.CALIBRATION_SCHEMA_VERSION,
            "value": "not a number"},
    }
    tmp_cache.write_text(json.dumps(blob))
    autotune._tuning_cache = None
    assert autotune.load_calibration("page_lookup_s")["value"] == 7e-8
    for broken in ("chunk_dispatch_s", "draft_token_s", "hbm_bandwidth",
                   "prefix_hash_s", "dispatch_s"):
        assert autotune.load_calibration(broken) is None
    const = autotune.resolve_constants()          # never raises
    assert const.source == "calibrated"
    assert const.page_lookup_s == 7e-8            # the one valid entry
    assert const.chunk_dispatch_s == autotune.CHUNK_DISPATCH_S
    assert const.draft_token_s == autotune.NGRAM_DRAFT_S
    assert const.prefix_hash_s == autotune.PREFIX_HASH_S
    assert const.hbm_bandwidth is None
    assert const.dispatch_s is None


def test_env_switch_forces_defaults(tmp_cache, monkeypatch):
    autotune.record_calibration("chunk_dispatch_s", 1e-3, n_trials=3,
                                spread=0.0, timestamp=1.0)
    assert autotune.resolve_constants().source == "calibrated"
    monkeypatch.setenv(autotune.DEFAULT_CONSTANTS_ENV, "1")
    assert autotune.resolve_constants() == autotune.DEFAULT_CONSTANTS
    monkeypatch.setenv(autotune.DEFAULT_CONSTANTS_ENV, "0")
    assert autotune.resolve_constants().source == "calibrated"


def test_run_calibration_persists_under_calibrated_keys(tmp_cache):
    # Synthetic persistence path (probe values injected via the public
    # API): every CALIBRATED_NAMES key lands in the calibrated: namespace
    # with metadata, and the validator's shape holds.
    for name, v in SYNTH.items():
        autotune.record_calibration(name, v, n_trials=4, spread=0.2,
                                    unit="s", timestamp=9.0)
    raw = json.loads(tmp_cache.read_text())
    keys = [k for k in raw if k.startswith(autotune.CALIBRATED_PREFIX)]
    assert len(keys) == len(autotune.CALIBRATED_NAMES)
    for k in keys:
        e = raw[k]
        assert e["schema_version"] == autotune.CALIBRATION_SCHEMA_VERSION
        assert e["value"] > 0 and e["n_trials"] == 4
        assert isinstance(e["backend"], str) and isinstance(e["mesh"], str)


# ----------------------------------------------------------------------------
# Decisions respond monotonically to the constants that price them
# ----------------------------------------------------------------------------

@given(st.integers(min_value=0, max_value=len(COSTS) - 1),
       st.integers(min_value=0, max_value=len(COSTS) - 1))
def test_chunk_no_smaller_under_bigger_dispatch_cost(i, j):
    if i > j:
        i, j = j, i
    lo = dataclasses.replace(autotune.DEFAULT_CONSTANTS,
                             chunk_dispatch_s=COSTS[i])
    hi = dataclasses.replace(autotune.DEFAULT_CONSTANTS,
                             chunk_dispatch_s=COSTS[j])
    c_lo, _ = autotune.choose_prefill_chunk(4096, 16, 4, 128, 8,
                                            constants=lo)
    c_hi, _ = autotune.choose_prefill_chunk(4096, 16, 4, 128, 8,
                                            constants=hi)
    assert c_hi >= c_lo, (COSTS[i], COSTS[j], c_lo, c_hi)


@given(st.integers(min_value=0, max_value=len(COSTS) - 1),
       st.integers(min_value=0, max_value=len(COSTS) - 1))
def test_spec_k_no_larger_under_bigger_draft_cost(i, j):
    if i > j:
        i, j = j, i
    lengths = [256, 512, 1024, 2048]
    lo = dataclasses.replace(autotune.DEFAULT_CONSTANTS,
                             draft_token_s=COSTS[i])
    hi = dataclasses.replace(autotune.DEFAULT_CONSTANTS,
                             draft_token_s=COSTS[j])
    k_lo, _ = autotune.choose_spec_k(lengths, 16, 4, 128, 8, 0.7, 4e9,
                                     constants=lo)
    k_hi, _ = autotune.choose_spec_k(lengths, 16, 4, 128, 8, 0.7, 4e9,
                                     constants=hi)
    assert k_hi <= k_lo, (COSTS[i], COSTS[j], k_lo, k_hi)


def test_constants_argument_defaults_to_the_handset_set():
    # constants=None must be the pre-calibration arithmetic exactly —
    # the bit-for-bit reproducibility contract every existing caller
    # (tests, bench cells) relies on.
    plain = autotune.prefill_chunk_model(4096, 256, 16, 4, 128, 8)
    pinned = autotune.prefill_chunk_model(
        4096, 256, 16, 4, 128, 8, constants=autotune.DEFAULT_CONSTANTS)
    assert plain == pinned


# ----------------------------------------------------------------------------
# The engine provably prices choose_* from the calibrated set
# ----------------------------------------------------------------------------

def test_engine_prices_chunk_from_calibrated_set(tmp_cache, model,
                                                 monkeypatch):
    cfg, params = model
    # A huge measured chunk-dispatch cost: the chunk model amortizes it
    # with a bigger chunk than the defaults would pick.
    autotune.record_calibration("chunk_dispatch_s", 2e-3, n_trials=3,
                                spread=0.0, timestamp=42.0)
    scfg = ServeConfig(max_len=512, batch=2, eos_id=-1, paged=True,
                       page_size=8, chunk_size=None)
    eng = ServingEngine(params, cfg, scfg)
    assert eng.constants.source == "calibrated"
    assert eng.constants.chunk_dispatch_s == 2e-3
    expect, _ = autotune.choose_prefill_chunk(
        512, cfg.n_heads, cfg.n_kv_heads, cfg.dhead, 8,
        constants=eng.constants)
    assert eng.chunk == expect
    default_chunk, _ = autotune.choose_prefill_chunk(
        512, cfg.n_heads, cfg.n_kv_heads, cfg.dhead, 8)
    assert eng.chunk != default_chunk    # the decision provably moved
    # Forcing defaults reproduces the pre-calibration decision
    # bit-for-bit, same cache contents.
    monkeypatch.setenv(autotune.DEFAULT_CONSTANTS_ENV, "1")
    eng2 = ServingEngine(params, cfg, scfg)
    assert eng2.constants == autotune.DEFAULT_CONSTANTS
    assert eng2.chunk == default_chunk


def test_drift_report_carries_constant_provenance(tmp_cache, model):
    cfg, params = model
    autotune.record_calibration("page_lookup_s", 7e-8, n_trials=3,
                                spread=0.1, timestamp=7.0)
    eng = ServingEngine(params, cfg, ServeConfig(
        max_len=32, batch=2, eos_id=-1, paged=True, page_size=8,
        chunk_size=8))
    rep = telemetry.drift_report(eng)
    assert rep["constants"]["source"] == "calibrated"
    cal = rep["calibration"]
    assert cal["source"] == "calibrated"
    row = cal["constants"]["page_lookup_s"]
    assert row["measured"] == 7e-8
    assert row["drift_ratio"] == pytest.approx(
        7e-8 / autotune.PAGE_LOOKUP_S)
    assert cal["constants"]["chunk_dispatch_s"]["measured"] is None

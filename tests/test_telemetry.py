"""Observability contract for the serving engine (serve/telemetry.py):
tracing is *observational* — the traced engine's token streams are
bit-identical to an untraced engine's on every path (greedy, sampled,
speculative, faulted, preempting) — the event trace reconciles exactly
against the legacy counter views and the page pool's conservation law,
ring eviction bounds memory without corrupting aggregates, compile
detection is exact, the spans land in a profiler trace as ``serve.*``
events (and a disabled telemetry writes none), and the
model-vs-measured drift gate records finite positive ratios."""

import json

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import autotune
from repro.models import transformer as T
from repro.serve import profile, telemetry, traffic
from repro.serve.engine import Request, ServeConfig, ServingEngine, SLOClass
from repro.serve.faults import FaultInjector, canonical_schedule
from repro.serve.paged import PageAllocator


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_smoke("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _scfg(**kw):
    base = dict(max_len=64, batch=2, eos_id=-1, paged=True, page_size=8,
                chunk_size=8)
    base.update(kw)
    return ServeConfig(**base)


def _tcfg(**kw):
    base = dict(rate=2.0, n_requests=24, seed=7, vocab=128,
                classes=(traffic.TrafficClass(
                    "default", prompt_lo=4, prompt_hi=20,
                    out_lo=2, out_hi=6),))
    base.update(kw)
    return traffic.TrafficConfig(**base)


def _overload_kw():
    """Engine knobs that exercise shed, preemption and degradation."""
    return dict(n_pages=17,
                classes=(SLOClass("default", ttft_slo=8, tpot_slo=4.0),),
                max_queue=4, max_preemptions=3, degrade=True)


def _run(model, scfg_kw, tcfg_kw, injector_fn=None):
    cfg, params = model
    eng = ServingEngine(params, cfg, _scfg(**scfg_kw))
    arr = traffic.TrafficGenerator(_tcfg(**tcfg_kw)).arrivals()
    inj = injector_fn() if injector_fn else None
    res = traffic.run_open_loop(eng, arr, max_ticks=2000, injector=inj)
    if inj is not None:
        inj.finish(eng)
    assert res["unresolved"] == []
    return eng, arr


# ----------------------------------------------------------------------------
# Parity: the traced engine's streams are bit-identical to the untraced's
# ----------------------------------------------------------------------------

def _assert_parity(model, scfg_kw, tcfg_kw, injector_fn=None):
    traced, _ = _run(model, dict(scfg_kw, telemetry=True), tcfg_kw,
                     injector_fn)
    plain, _ = _run(model, dict(scfg_kw, telemetry=False), tcfg_kw,
                    injector_fn)
    assert traced.outcome == plain.outcome
    assert traced.finished == plain.finished
    assert traced.ticks == plain.ticks
    return traced, plain


def test_traced_is_bit_identical_greedy_overload(model):
    """Greedy decoding through shed + preemption + degradation: tracing
    must not move a single token or terminal outcome."""
    traced, _ = _assert_parity(
        model, _overload_kw(), dict(rate=3.0, n_requests=24))
    # The workload actually exercised the interesting paths. (Preemption
    # needs a pool squeeze — conservative admission never over-commits —
    # so the faulted test below covers it.)
    assert traced.telemetry.counters.get("shed", 0) >= 1
    assert traced.telemetry.counters.get("degrade_enter", 0) >= 1


def test_traced_is_bit_identical_sampled(model):
    """Temperature sampling: the per-(rid, index) sampling keys make the
    stream deterministic, so tracing must preserve it exactly."""
    _assert_parity(model, dict(_overload_kw(), temperature=0.7, seed=3),
                   dict(rate=2.0, n_requests=16))


def test_traced_is_bit_identical_spec_plus_faults(model):
    """Speculative decoding under the canonical fault schedule — the
    worst-case interleaving of spans and events."""
    spec_kw = dict(_overload_kw(), spec_k=2, draft="ngram",
                   spec_adapt_every=4, spec_probe_every=4)
    inj = lambda: FaultInjector(canonical_schedule(t0=4, dwell=8, gap=6))
    traced, _ = _assert_parity(
        model, spec_kw, dict(rate=1.5, n_requests=24), inj)
    assert traced.telemetry.counters.get("spec_verify", 0) >= 1
    assert traced.telemetry.counters.get("preempt", 0) >= 1


# ----------------------------------------------------------------------------
# Reconciliation: the trace IS the bookkeeping (counters are views)
# ----------------------------------------------------------------------------

def test_outcome_accounting_reconciles_with_trace(model):
    """Every submitted rid reaches exactly one terminal event, and the
    legacy counter views agree with the ring event-by-event (capacity
    large enough that nothing evicts). Runs the canonical fault schedule
    so shed, preemption *and* admission holds all appear."""
    eng, arr = _run(
        model,
        dict(_scfg_kw_spec(), spec_adapt_every=4, spec_probe_every=4,
             trace_capacity=65536),
        dict(rate=1.5, n_requests=24),
        lambda: FaultInjector(canonical_schedule(t0=4, dwell=8, gap=6)))
    assert eng.preemptions >= 1 and eng.admission_rejections >= 1
    tel = eng.telemetry
    assert tel.dropped_events == 0

    # One submit event per offered request.
    submits = tel.events_of("submit")
    assert len(submits) == len(arr)

    # Exactly one terminal event (shed | finish) per rid.
    terminal = {}
    for _, _, kind, p in tel.events_of("shed") + tel.events_of("finish"):
        assert p["rid"] not in terminal, f"double terminal for {p['rid']}"
        terminal[p["rid"]] = kind
    assert set(terminal) == {a.rid for a in arr}

    # Counter views == ring counts == legacy structures.
    assert len(tel.events_of("shed")) == eng.telemetry.counters["shed"] \
        == sum(eng.shed_by_class.values())
    preempts = tel.events_of("preempt")
    assert len(preempts) == eng.preemptions == len(eng.preemption_log)
    for (_, _, _, p), (rid, rclass, n_gen) in zip(preempts,
                                                  eng.preemption_log):
        assert (p["rid"], p["rclass"], p["n_generated"]) == \
            (rid, rclass, n_gen)
    assert len(tel.events_of("admit_hold")) == eng.admission_rejections
    # Degradation transitions pair up (possibly still degraded at drain).
    ent, ext = tel.events_of("degrade_enter"), tel.events_of("degrade_exit")
    assert len(ent) - len(ext) in (0, 1)
    assert eng.downshifts == len(ent)


def test_page_events_reconcile_with_pool_conservation(model):
    """Sum of page_alloc/page_free event sizes == the allocator's
    cumulative counters (every engine alloc/free is traced), and the
    conservation law holds after drain."""
    eng, _ = _run(model, dict(_overload_kw(), trace_capacity=65536),
                  dict(rate=3.0, n_requests=24))
    tel = eng.telemetry
    allocd = sum(p["n"] for _, _, _, p in tel.events_of("page_alloc"))
    freed = sum(p["n"] for _, _, _, p in tel.events_of("page_free"))
    assert allocd == eng.pool.pages_allocated
    assert freed == eng.pool.pages_freed
    assert eng.pool.pages_allocated - eng.pool.pages_freed \
        == eng.pool.pages_in_use == 0
    occ = eng.pool.occupancy()
    assert occ["pages_allocated"] == allocd
    assert occ["pages_freed"] == freed
    assert occ["high_water"] >= 1


def test_spec_verify_events_reconcile(model):
    eng, _ = _run(model, dict(_scfg_kw_spec(), trace_capacity=65536),
                  dict(rate=1.5, n_requests=16))
    tel = eng.telemetry
    ev = tel.events_of("spec_verify")
    assert len(ev) >= 1
    assert sum(p["proposed"] for _, _, _, p in ev) == \
        tel.counters["spec_proposed"]
    assert sum(p["accepted"] for _, _, _, p in ev) == eng.spec_accepted
    assert sum(p["emitted"] for _, _, _, p in ev) == eng.spec_emitted
    assert len(ev) == eng.spec_ticks


def _scfg_kw_spec():
    return dict(_overload_kw(), spec_k=2, draft="ngram")


# ----------------------------------------------------------------------------
# Ring bounds memory; aggregates stay exact through eviction
# ----------------------------------------------------------------------------

def test_ring_eviction_keeps_aggregates_exact(model):
    small, _ = _run(model, dict(_overload_kw(), trace_capacity=16),
                    dict(rate=3.0, n_requests=24))
    big, _ = _run(model, dict(_overload_kw(), trace_capacity=65536),
                  dict(rate=3.0, n_requests=24))
    assert small.telemetry.dropped_events > 0
    assert len(small.telemetry.events) == 16
    assert small.telemetry.counters == big.telemetry.counters
    assert small.shed_by_class == big.shed_by_class
    assert small.preemption_log == big.preemption_log


def test_disabled_telemetry_keeps_counters_exact(model):
    """telemetry=False drops the rings and the clocks, never the
    aggregates: the legacy counter views must still be exact."""
    off, _ = _run(model, dict(_overload_kw(), telemetry=False),
                  dict(rate=3.0, n_requests=24))
    on, _ = _run(model, _overload_kw(), dict(rate=3.0, n_requests=24))
    assert len(off.telemetry.events) == 0
    assert off.telemetry.span_stats() == {}
    assert off.telemetry.tick_stats()["n"] == 0
    assert off.telemetry.counters == on.telemetry.counters
    assert off.admission_rejections == on.admission_rejections
    assert off.shed_by_class == on.shed_by_class


# ----------------------------------------------------------------------------
# Spans: exact compile detection + per-tick histogram
# ----------------------------------------------------------------------------

def test_compile_flags_and_tick_histogram(model):
    """One decode executable and one chunk executable -> exactly one
    compile-flagged span each; the tick histogram counts every tick."""
    cfg, params = model
    eng = ServingEngine(params, cfg, _scfg(n_pages=17))
    for rid in range(3):
        eng.submit(Request(rid=rid, prompt=np.arange(
            3, 3 + 9 + rid, dtype=np.int32), max_new=4))
    eng.run_until_drained()
    st = eng.telemetry.span_stats()
    assert st["decode"]["compile_n"] == 1 == eng.decode_traces
    assert st["prefill_chunk"]["compile_n"] == 1
    assert sum(eng.prefill_traces.values()) == 1
    assert st["decode"]["execute_n"] == st["decode"]["n"] - 1
    assert st["decode"]["execute_mean_s"] > 0
    ts = eng.telemetry.tick_stats()
    assert ts["n"] == eng.ticks
    assert ts["p99_s"] >= ts["p50_s"] > 0
    assert ts["total_s"] == pytest.approx(
        ts["mean_s"] * ts["n"])


# ----------------------------------------------------------------------------
# Profiler: the spans on the device's clock, as serve.* host events
# ----------------------------------------------------------------------------

def _profiled(model, tmp_path, **scfg_kw):
    """A tiny paged engine drained under a profiler session; returns the
    engine and the serve.* spans read back from the trace."""
    cfg, params = model
    eng = ServingEngine(params, cfg, _scfg(n_pages=17, **scfg_kw))
    for rid in range(3):
        eng.submit(Request(rid=rid, prompt=np.arange(
            3, 3 + 9 + 8 * rid, dtype=np.int32), max_new=4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    return eng, profile.load(str(tmp_path))["spans"]


def test_profiler_trace_holds_the_tick_phases(model, tmp_path):
    """Each decode tick's serve.tick span holds the decode dispatch, then
    the fetch that waits for it, then the record loop; every request's
    first admission carries its rid and queue wait."""
    eng, spans = _profiled(model, tmp_path)
    ticks = [s for s in spans if s[2] == "serve.tick"]
    assert len(ticks) == eng.ticks
    assert [s[3]["tick"] for s in ticks] == list(range(1, eng.ticks + 1))

    def first(name, a, b):
        return min((s for s in spans if s[2] == name and a <= s[0]
                    and s[1] <= b), default=None)

    n_decode = 0
    for a, b, _, meta in ticks:
        disp = first("serve.decode.dispatch", a, b)
        if disp is None:
            continue
        n_decode += 1
        fetch = first("serve.decode.fetch", a, b)
        rec = first("serve.record", fetch[1], b)
        assert disp[1] <= fetch[0] and rec is not None
        assert disp[3]["tick"] == fetch[3]["tick"] == meta["tick"]
        assert rec[3]["n_finished"] >= 0
    assert n_decode == eng.telemetry.span_stats()["decode"]["n"]
    admits = [s for s in spans if s[2] == "serve.admit.request"]
    assert sorted(s[3]["rid"] for s in admits) == [0, 1, 2]
    assert all(s[3]["queue_ms"] >= 0 for s in admits)
    assert profile.queue_ms(spans).keys() == {0, 1, 2}
    names = {s[2] for s in spans}
    assert {"serve.admit", "serve.prefill", "serve.prefill_chunk",
            "serve.prefill_fetch", "serve.pages", "serve.decode",
            "serve.positions"} <= names


def test_profiled_tokens_bit_identical(model, tmp_path):
    """A profiler session moves no token."""
    on, _ = _profiled(model, tmp_path)
    cfg, params = model
    off = ServingEngine(params, cfg, _scfg(n_pages=17))
    for rid in range(3):
        off.submit(Request(rid=rid, prompt=np.arange(
            3, 3 + 9 + 8 * rid, dtype=np.int32), max_new=4))
    off.run_until_drained()
    assert on.finished == off.finished and on.ticks == off.ticks


def test_disabled_telemetry_writes_no_profiler_event(model, tmp_path):
    eng, spans = _profiled(model, tmp_path, telemetry=False)
    assert eng.ticks > 0 and len(eng.finished) == 3
    assert spans == []


def test_metrics_flat_and_summary_wall_clock(model):
    tcls = (traffic.TrafficClass("default", prompt_lo=4, prompt_hi=20,
                                 out_lo=2, out_hi=6,
                                 ttft_ms=1e6, tpot_ms=1e6),)
    eng, arr = _run(model, _overload_kw(),
                    dict(rate=2.0, n_requests=12, classes=tcls))
    s = traffic.summarize(eng, arr, classes=tcls)
    assert s["tick_wall_s_mean"] > 0
    assert s["tick_wall_s_p99"] >= s["tick_wall_s_p50"]
    d = s["by_class"]["default"]
    assert d["ttft_ms_p50"] == pytest.approx(
        d["ttft_p50"] * s["tick_wall_s_mean"] * 1e3)
    # Absurdly loose ms targets -> full attainment (plumbing check).
    assert d["ttft_ms_slo_attainment"] == 1.0
    assert d["tpot_ms_slo_attainment"] == 1.0


def test_traffic_class_rejects_nonpositive_ms_targets():
    with pytest.raises(AssertionError):
        traffic.TrafficClass("x", ttft_ms=0.0)
    with pytest.raises(AssertionError):
        traffic.TrafficClass("x", tpot_ms=-1.0)


# ----------------------------------------------------------------------------
# Drift gate: model vs measured, persisted under serve_measured:
# ----------------------------------------------------------------------------

def test_drift_report_finite_and_persisted(model, tmp_path, monkeypatch):
    monkeypatch.setattr(autotune, "TUNING_CACHE_PATH",
                        str(tmp_path / "cache.json"))
    monkeypatch.setattr(autotune, "_tuning_cache", None)
    eng, _ = _run(model, _scfg_kw_spec(), dict(rate=1.5, n_requests=16))
    rep = telemetry.drift_report(eng, persist=True)
    assert rep["schema_version"] == telemetry.TRACE_SCHEMA_VERSION
    assert "decode" in rep or "spec_verify" in rep
    assert "prefill_chunk" in rep
    for comp in ("decode", "prefill_chunk", "spec_verify"):
        row = rep.get(comp)
        if row is None:
            continue
        assert row["measured_s"] > 0
        assert row["modeled_s"] > 0
        assert row["ratio"] == pytest.approx(
            row["measured_s"] / row["modeled_s"])
        assert row["n_spans"] >= 1
    with open(autotune.TUNING_CACHE_PATH) as f:
        cache = json.load(f)
    keys = [k for k in cache if k.startswith(autotune.SERVE_MEASURED_PREFIX)]
    assert keys
    for k in keys:
        assert cache[k]["time_s"] > 0


def test_drift_ratio_sentinel():
    assert autotune.drift_ratio(1.0, 2.0) == 0.5
    assert autotune.drift_ratio(0.0, 2.0) == 0.0
    assert autotune.drift_ratio(1.0, 0.0) == 0.0
    assert autotune.drift_ratio(float("nan"), 2.0) == 0.0
    assert autotune.drift_ratio(float("inf"), 2.0) == 0.0


# ----------------------------------------------------------------------------
# Telemetry core unit behavior + allocator counters (no model)
# ----------------------------------------------------------------------------

def test_emit_rejects_unknown_kind():
    tel = telemetry.Telemetry()
    with pytest.raises(AssertionError):
        tel.emit(0, "not_a_kind", rid=1)


def test_reset_clears_rings_and_aggregates():
    tel = telemetry.Telemetry(capacity=4)
    for i in range(6):
        tel.emit(i, "admit", rid=i, rclass="default")
    with tel.span("decode", 0):
        pass
    with tel.span("tick", 0):
        pass
    assert tel.dropped_events == 2
    assert tel.tick_stats()["n"] == 1
    tel.reset()
    assert len(tel.events) == 0 and tel.span_stats() == {}
    assert tel.dropped_events == 0
    assert tel.counters == {} and tel.tick_stats()["n"] == 0


def test_page_allocator_cumulative_counters():
    pool = PageAllocator(n_pages=9, page_size=8)
    pool.alloc(0, 3)
    pool.alloc(1, 2)
    pool.free_slot(0)
    pool.alloc(2, 4)
    assert pool.pages_allocated == 9
    assert pool.pages_freed == 3
    assert pool.pages_allocated - pool.pages_freed == pool.pages_in_use == 6
    assert pool.occupancy()["pages_allocated"] == 9
    pool.reset()
    assert pool.pages_allocated == pool.pages_freed == 0

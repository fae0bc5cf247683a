"""Structured observability for the serving engine (paper ethos: observe).

The source paper dissects Volta by instrumenting tight loops and reading
the clocks; this module applies the same probe-and-compare discipline to
our own serving stack. Two surfaces, one bookkeeping home:

  * **Event trace** — a ring-buffered, schema-versioned stream of typed
    tick events (``admit``, ``shed``, ``preempt``, ``degrade_enter`` /
    ``degrade_exit``, ``spec_verify`` with accept counts,
    ``prefill_chunk``, ``page_alloc`` / ``page_free``, ``probe_tick``,
    ``prefix_hit`` / ``prefix_miss`` / ``cow_copy`` / ``prefix_evict``,
    terminal outcomes) emitted from the engine's existing decision
    points. The legacy ad-hoc counters (``admission_rejections``,
    ``shed_by_class``, ``preemption_log``, spec stats) are *views over
    this trace's aggregates*, not parallel bookkeeping: the aggregate
    side of ``emit`` runs even when tracing is disabled (and even after
    ring eviction), so the counters stay exact while the ring bounds
    memory.
  * **Spans** — one API, ``Telemetry.span(name, tick, slot, **meta)``,
    around every host phase of a tick (admission, page-table updates,
    prefill chunks, the decode dispatch and the fetch that waits for it,
    recording, position resets). Each span enters a
    ``jax.profiler.TraceAnnotation`` named ``serve.<name>`` that carries
    its metadata: under a profiler session (``launch/serve.py
    --profile-dir``) the spans land in the trace's host plane, on the
    same clock as the device's ops, so every idle gap of the device is
    named by the engine phase the host was in. With no session the
    annotation costs well under a microsecond. Each span also keeps a
    ``perf_counter`` aggregate (``span_stats()``, with exact first-call
    ``compile`` flags from the engine's trace counters), and the
    ``tick`` span feeds a per-tick wall-time histogram
    (``tick_stats()``). Spans wrap code the engine already runs: no
    device syncs or host<->device transfers are added, and the traced
    engine's token streams are bit-identical to an untraced engine's
    (gated by tests/test_telemetry.py). ``serve.profile`` reads the
    spans back from a trace.

``drift_report`` is the model-vs-measured gate: it compares the
``core.autotune`` cost-model predictions (``paged_decode_model``,
``prefill_chunk_model``, ``spec_decode_model``) against the measured
execute-phase spans for the same configuration — the direct on-ramp for
the ROADMAP's microbenchmark-calibrated cost models. On a CPU test
backend the ratios are far from 1 (the models price TPU HBM streams);
the gate is that they are *finite, positive, and recorded*.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

TRACE_SCHEMA_VERSION = 1

# Typed event kinds (schema v1). ``emit`` asserts membership so a typo'd
# kind fails loudly in tests instead of minting an unqueryable stream.
EVENT_KINDS = frozenset({
    "submit",         # request entered the queue
    "admit",          # request installed into a slot
    "admit_hold",     # pool-exhausted admission hold (everyone waits)
    "shed",           # terminal: clean reject (queue_full/capacity/...)
    "finish",         # terminal: done | forced:* (partial stream kept)
    "preempt",        # slot evicted back to the queue
    "degrade_enter",  # ladder: clean -> degraded transition
    "degrade_exit",   # ladder: degraded -> clean transition
    "spec_verify",    # one slot's verify outcome (proposed/accepted)
    "prefill_chunk",  # one prompt chunk written through the page table
    "page_alloc",     # pages granted to a slot
    "page_free",      # a freed slot's pages returned to the pool
    "probe_tick",     # k=1 trial tick while speculation is disabled
    "prefix_hit",     # admission mapped cached prefix pages (refcounts)
    "prefix_miss",    # admission probed the prefix index and found none
    "cow_copy",       # copy-on-write split of a shared page
    "prefix_evict",   # LRU reclaim of cached-idle prefix pages
})


class _Span:
    """Context manager around one host phase: a profiler annotation
    ``serve.<name>`` for as long as it is open, and a wall-clock
    aggregate when it closes. ``compile`` is set by the caller from the
    engine's trace-time counter delta (exact first-call detection); it
    must be assigned *inside* the block. ``note(**meta)`` adds metadata
    known only at the end of the span."""

    __slots__ = ("_tel", "name", "tick", "compile", "_ann", "_t0")

    def __init__(self, tel: "Telemetry", name: str, tick: int, meta: dict):
        self._tel = tel
        self.name = name
        self.tick = tick
        self.compile = False
        self._ann = TraceAnnotation("serve." + name, tick=tick, **meta)

    def note(self, **meta) -> None:
        self._ann.set_metadata(**meta)

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dur = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._tel._record_span(self, dur)


class _NullSpan:
    """Shared no-op span for disabled telemetry (zero per-call garbage)."""

    __slots__ = ("compile",)

    def __init__(self):
        self.compile = False

    def note(self, **meta) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


class Telemetry:
    """One engine's observability state: event ring + aggregates + spans.

    Aggregates (``counters``, ``shed_by_class``, ``preemption_log``) are
    updated by every ``emit``/``count`` call regardless of ``enabled`` —
    they are the backing store for the engine's legacy counter views and
    must stay exact. The *ring buffers* (events, tick times), the spans
    with their profiler annotations and the ``perf_counter`` reads are
    what ``enabled`` gates: a disabled engine pays only dict arithmetic.
    """

    def __init__(self, enabled: bool = True, capacity: int = 4096):
        assert capacity >= 1, capacity
        self.enabled = enabled
        self.capacity = capacity
        self.schema_version = TRACE_SCHEMA_VERSION
        # Ring entries: (t_rel_s, tick, kind, payload_dict).
        self.events: deque = deque(maxlen=capacity)
        # Ring entries: (tick, dur_s) — percentile window.
        self.tick_times: deque = deque(maxlen=capacity)
        self.dropped_events = 0          # ring evictions (aggregates exact)
        # Aggregates (exact over the whole run, never evicted):
        self.counters: Dict[str, Any] = {}
        self.shed_by_class: Dict[str, int] = {}
        self.preemption_log: List[Tuple[int, str, int]] = []
        # name -> [n, total_s, max_s, compile_n, compile_s]
        self._span_agg: Dict[str, List] = {}
        self._tick_n = 0
        self._tick_total_s = 0.0
        self._epoch = time.perf_counter()

    # -- recording ------------------------------------------------------------

    def count(self, key: str, n: int = 1) -> None:
        """Bump an aggregate counter with no ring event (high-frequency
        accounting like per-tick context-row sums)."""
        self.counters[key] = self.counters.get(key, 0) + n

    def emit(self, tick: int, kind: str, **payload) -> None:
        """Record one typed event. Aggregates always update; the ring
        entry is appended only when tracing is enabled."""
        assert kind in EVENT_KINDS, kind
        # .item(): numpy scalars (token counts, lengths) must not leak
        # into the aggregates or the ring.
        payload = {k: (v.item() if hasattr(v, "item") else v)
                   for k, v in payload.items()}
        c = self.counters
        c[kind] = c.get(kind, 0) + 1
        if kind == "shed":
            rc = payload["rclass"]
            self.shed_by_class[rc] = self.shed_by_class.get(rc, 0) + 1
        elif kind == "preempt":
            self.preemption_log.append(
                (payload["rid"], payload["rclass"], payload["n_generated"]))
        elif kind == "spec_verify":
            c["spec_proposed"] = c.get("spec_proposed", 0) \
                + payload["proposed"]
            c["spec_accepted"] = c.get("spec_accepted", 0) \
                + payload["accepted"]
            c["spec_emitted"] = c.get("spec_emitted", 0) \
                + payload["emitted"]
        if not self.enabled:
            return
        if len(self.events) == self.capacity:
            self.dropped_events += 1
        self.events.append(
            (time.perf_counter() - self._epoch, tick, kind, payload))

    def span(self, name: str, tick: int, slot: Optional[int] = None,
             **meta):
        """Span context manager around one host phase (see ``_Span``);
        ``slot`` and ``meta`` ride on its profiler annotation. The
        ``tick`` span, around a whole tick, also feeds ``tick_stats()``.
        A no-op when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        if slot is not None:
            meta["slot"] = slot
        return _Span(self, name, tick, meta)

    def _record_span(self, sp: _Span, dur: float) -> None:
        agg = self._span_agg.get(sp.name)
        if agg is None:
            agg = self._span_agg[sp.name] = [0, 0.0, 0.0, 0, 0.0]
        agg[0] += 1
        agg[1] += dur
        agg[2] = max(agg[2], dur)
        if sp.compile:
            agg[3] += 1
            agg[4] += dur
        if sp.name == "tick":
            self._tick_n += 1
            self._tick_total_s += dur
            self.tick_times.append((sp.tick, dur))

    def reset(self) -> None:
        """Drop everything — rings, aggregates, epoch. The bench warm-up
        boundary: compile spans and warm-up events must not pollute the
        measured cells."""
        self.events.clear()
        self.tick_times.clear()
        self.dropped_events = 0
        self.counters.clear()
        self.shed_by_class.clear()
        self.preemption_log.clear()
        self._span_agg.clear()
        self._tick_n = 0
        self._tick_total_s = 0.0
        self._epoch = time.perf_counter()

    # -- queries --------------------------------------------------------------

    def events_of(self, kind: Optional[str] = None) -> List[Tuple]:
        """Ring events, optionally filtered by kind (recent window only —
        use the aggregates for exact whole-run totals)."""
        if kind is None:
            return list(self.events)
        assert kind in EVENT_KINDS, kind
        return [e for e in self.events if e[2] == kind]

    def tick_stats(self) -> Dict[str, float]:
        """Whole-tick wall-time histogram. ``mean_s``/``total_s`` are
        exact over the run; percentiles cover the ring window."""
        if not self._tick_n:
            return {"n": 0, "total_s": 0.0, "mean_s": 0.0,
                    "p50_s": 0.0, "p99_s": 0.0, "max_s": 0.0}
        durs = [d for _, d in self.tick_times]
        return {"n": self._tick_n,
                "total_s": self._tick_total_s,
                "mean_s": self._tick_total_s / self._tick_n,
                "p50_s": float(np.percentile(durs, 50)),
                "p99_s": float(np.percentile(durs, 99)),
                "max_s": float(max(durs))}

    def span_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name aggregates with trace-vs-execute separation:
        ``compile_*`` isolates first-call (tracing+compile) cost,
        ``execute_mean_s`` is the steady-state mean the cost models are
        judged against."""
        out = {}
        for name, (n, total, mx, cn, cs) in self._span_agg.items():
            en = n - cn
            out[name] = {
                "n": n, "total_s": total, "mean_s": total / n, "max_s": mx,
                "compile_n": cn, "compile_s": cs, "execute_n": en,
                "execute_mean_s": (total - cs) / en if en else 0.0,
            }
        return out


# -- model-vs-measured drift gate ---------------------------------------------


def drift_report(engine, persist: bool = False) -> Dict[str, Any]:
    """Compare the autotune cost models against measured execute spans
    for this engine's own configuration (paged engines only).

    Components (present when the engine measured execute-phase spans for
    them):

      * ``decode`` — measured mean plain-decode span vs
        ``paged_decode_model(...)["paged_s"]`` at the run's mean context
        length and active-slot count (tracked host-side per tick, no
        device syncs).
      * ``prefill_chunk`` — measured mean chunk span vs
        ``prefill_chunk_model(...)["prefill_s"]`` for one chunk.
      * ``spec_verify`` — measured mean verify span vs
        ``spec_decode_model(...)["spec_tick_s"]`` at the measured accept
        rate.

    Each component carries ``measured_s``, ``modeled_s`` and ``ratio``
    (= measured/modeled, ``autotune.drift_ratio``) — modeled under the
    constant set the engine actually priced its decisions with
    (``engine.constants``) — plus ``modeled_default_s``/``ratio_default``
    under the hand-set defaults, so a calibrated run shows both how far
    the model drifted and how much calibration closed the gap. The
    report also embeds which set was active (``constants``) and the
    per-constant measured-vs-assumed rollup
    (``calibration`` = ``autotune.calibration_report``). With
    ``persist=True`` the measurements are written into the persistent
    tuning cache under the ``serve_measured:`` key namespace — the
    substrate the calibration pass reads alongside the hand-set
    constants.
    """
    from repro.core import autotune
    from repro.models import transformer as T

    assert engine.pool is not None, "drift_report needs a paged engine"
    tel = engine.telemetry
    cfg, scfg = engine.cfg, engine.scfg
    stats = tel.span_stats()
    c = tel.counters

    def mean_geom(rows_key: str, slots_key: str, n_spans: int):
        slot_ticks = c.get(slots_key, 0)
        rows = c.get(rows_key, 0)
        mean_len = max(1, int(round(rows / max(1, slot_ticks))))
        mean_slots = max(1, int(round(slot_ticks / max(1, n_spans))))
        return mean_len, mean_slots

    out: Dict[str, Any] = {"schema_version": TRACE_SCHEMA_VERSION}
    geom = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.dhead, page_size=scfg.page_size)
    const = getattr(engine, "constants", None)
    if const is None:
        const = autotune.resolve_constants(
            mesh_shape=getattr(engine, "mesh", None))

    def cell(measured, model_fn, **kw):
        """measured vs the model priced under the engine's active
        constant set (headline) and under the defaults (comparison)."""
        modeled = model_fn(constants=const, **kw)
        modeled_default = modeled if const.source == "default" \
            else model_fn(constants=autotune.DEFAULT_CONSTANTS, **kw)
        return {
            "measured_s": measured, "modeled_s": modeled,
            "ratio": autotune.drift_ratio(measured, modeled),
            "modeled_default_s": modeled_default,
            "ratio_default": autotune.drift_ratio(measured,
                                                  modeled_default)}

    dec = stats.get("decode")
    if dec and dec["execute_n"]:
        mean_len, mean_slots = mean_geom(
            "decode_context_rows", "decode_slot_ticks", dec["n"])
        out["decode"] = dict(cell(
            dec["execute_mean_s"],
            lambda **kw: autotune.paged_decode_model(
                scfg.max_len, [mean_len] * mean_slots, **geom,
                **kw)["paged_s"]),
            n_spans=dec["execute_n"], mean_context=mean_len,
            mean_slots=mean_slots)

    pc = stats.get("prefill_chunk")
    if pc and pc["execute_n"]:
        out["prefill_chunk"] = dict(cell(
            pc["execute_mean_s"],
            lambda **kw: autotune.prefill_chunk_model(
                engine.chunk, engine.chunk, **geom, **kw)["prefill_s"]),
            n_spans=pc["execute_n"], chunk=engine.chunk)

    sv = stats.get("spec_verify")
    if sv and sv["execute_n"] and engine.spec_k:
        mean_len, mean_slots = mean_geom(
            "verify_context_rows", "verify_slot_ticks", sv["n"])
        proposed = c.get("spec_proposed", 0)
        rate = c.get("spec_accepted", 0) / proposed if proposed else 0.0
        out["spec_verify"] = dict(cell(
            sv["execute_mean_s"],
            lambda **kw: autotune.spec_decode_model(
                [mean_len] * mean_slots, k=engine.spec_k,
                accept_rate=rate,
                param_bytes=T.active_param_count(cfg) * 2.0,
                **geom, **kw)["spec_tick_s"]),
            n_spans=sv["execute_n"], spec_k=engine.spec_k,
            accept_rate=rate)

    out["constants"] = {"source": const.source, "backend": const.backend,
                        "mesh": const.mesh,
                        "timestamp": const.timestamp}
    out["calibration"] = autotune.calibration_report(
        mesh_shape=getattr(engine, "mesh", None))

    if persist:
        ident = (f"{cfg.n_heads}h{cfg.n_kv_heads}kv{cfg.dhead}d"
                 f":page{scfg.page_size}:chunk{engine.chunk}")
        for comp in ("decode", "prefill_chunk", "spec_verify"):
            cell = out.get(comp)
            if cell is None:
                continue
            autotune.record_serve_measurement(f"{comp}:{ident}", {
                "time_s": cell["measured_s"],
                "modeled_s": cell["modeled_s"],
                "ratio": cell["ratio"],
                "n": cell["n_spans"],
                "source": "serve.telemetry",
            })
    return out

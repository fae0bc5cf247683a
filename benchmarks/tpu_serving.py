"""Serving bench: prefill + decode tokens/sec through the engine, plus the
modeled naive-vs-fast-path decode attention comparison.

Two kinds of numbers:

* **Measured** — wall-clock tokens/sec of ``ServingEngine`` on the smoke
  model (interpret-mode kernels on CPU, native on TPU): prefill tok/s,
  decode tok/s, and the prefill executable count (buckets, not prompts).
* **Modeled** — the autotuner's attention cost model priced at production
  shape (``decode_32k``): every slot attending the full 32k cache (the
  seed engine) vs flash decode streaming only each slot's live context.
  This is the speedup the skipped-load machinery buys, reportable even
  off-TPU.

Paged vs contiguous rides in both: the measured run repeats through a
paged engine (same prompts, half-size page pool, chunked prefill) and
reports the HBM rows each cache layout actually holds; the modeled
``decode_32k`` cell prices the paged variant (page-table-lookup overhead,
reservation ratio) over a long-tailed stagger of slot lengths — the
serving distribution where flat ``slots * max_len`` reservations waste the
most.

Chunked prefill adds two cells: ``prefill_chunked_interleave`` (measured —
decode tokens that land *while* a long prompt is mid-prefill, the
head-of-line stall the chunk scheduler removes) and ``prefill_chunked_32k``
(modeled — the autotune chunk cost model's chosen chunk vs whole-prompt
prefill: total-time overhead paid, interleave latency bought back).

Speculative decoding adds two more: ``spec_decode_accept`` (measured — the
n-gram drafter on a repetitive prompt through the spec engine: accepted
drafts per verify tick, stream parity with the plain greedy engine, one
verify executable) and ``spec_decode_32k`` (modeled —
``autotune.choose_spec_k`` pricing accept-rate against verify-width
overhead at production shape, including the regime where it returns k=0
and disables speculation).

Prefix caching adds two: ``prefix_cache_hit`` (measured — shared-prefix
waves through the paged engine with the cache off then on: byte-identical
streams, suffix-only TTFT for the four concurrent sharers, pool high
water strictly below the uncached engine's, hit/COW counters reconciled
against the allocator) and ``prefix_cache_32k`` (modeled —
``autotune.choose_prefix_cache`` pricing suffix-only prefill plus the
probe/COW tax at an 8k cached prefix on a 32k prompt, including the
hit-rate-0 regime where it disables itself).

Distributed serving adds the last two: ``tp_pool_capacity`` (measured —
an 8-host-device subprocess runs the same request mix through the
single-device and mesh-sharded engines: token-stream parity flag, page
tables spanning devices, 1-vs-8 pool capacity at the same ``n_pages``,
and exactly one decode executable per mesh) and ``tp_decode_32k``
(modeled — ``autotune.tp_decode_model``: the weight-stream term sharded
by the mesh degree vs the per-layer activation all-reduces + unembed
ring gather it buys them with, plus the x8 pool-capacity headline).

  PYTHONPATH=src python -m benchmarks.tpu_serving --out BENCH_serving.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import autotune
from repro.models import transformer as T
from repro.serve import traffic
from repro.serve.engine import (Request, ServeConfig, ServingEngine,
                                greedy_generate)

ARCH = "qwen3-4b"
N_REQUESTS = 6
MAX_NEW = 8
MAX_LEN = 64
BATCH = 4


PAGE_SIZE = 8           # smoke-model pages (production: 128+, MXU-aligned)


def _run_engine(params, cfg, prompts, serve_cfg: ServeConfig) -> dict:
    eng = ServingEngine(params, cfg, serve_cfg)
    # Warm every executable the timed run will hit (compile time is not
    # serving throughput). Contiguous: one prompt per bucket. Paged: the
    # single chunk executable — one multi-chunk prompt covers it.
    if eng.pool is None:
        buckets = {eng.bucket_for(len(p)) for p in prompts}
        for wid, b in enumerate(sorted(buckets)):
            eng.submit(Request(rid=-1 - wid,
                               prompt=np.resize(prompts[0], b), max_new=2))
    else:
        warm_len = min(eng.chunk + 1, serve_cfg.max_len - 2)
        eng.submit(Request(rid=-1, prompt=np.resize(prompts[0], warm_len),
                           max_new=2))
    eng.run_until_drained()
    if eng.pool is not None:
        # Report the timed run's pool pressure, not the warm-up's.
        eng.pool.high_water = eng.pool.pages_in_use
        eng.admission_rejections = 0

    t0 = time.perf_counter()
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p, max_new=MAX_NEW))
    finished = eng.run_until_drained()
    dt = time.perf_counter() - t0
    prefill_toks = sum(len(p) for p in prompts)
    decode_toks = sum(len(v) for rid, v in finished.items() if rid >= 0)
    out = {
        "prefill_tokens": prefill_toks,
        "decode_tokens": decode_toks,
        "wall_s": dt,
        "tokens_per_s": (prefill_toks + decode_toks) / dt,
        "prefill_executables": len(eng.prefill_traces),
        "prefill_buckets": sorted(eng.prefill_traces),
        "cache_hbm_rows": T.cache_hbm_rows(eng.caches),
    }
    if eng.pool is not None:
        occ = eng.pool.occupancy()
        out["pool_high_water_pages"] = occ["high_water"]
        out["admission_rejections"] = eng.admission_rejections
        out["prefill_chunk"] = eng.chunk
        out["preemptions"] = eng.preemptions
    return out


def _measured() -> dict:
    cfg = configs.get_smoke(ARCH)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, cfg.vocab, size=rng.randint(4, 17))
               .astype(np.int32) for _ in range(N_REQUESTS)]
    contig = _run_engine(params, cfg, prompts,
                         ServeConfig(max_len=MAX_LEN, batch=BATCH,
                                     eos_id=-1))
    # Paged: same prompts through a pool holding half the contiguous
    # reservation — the engine must stay correct *and* cheaper-resident.
    # Prompts stream through the page table in 8-row chunks (one chunk
    # executable total; see prefill_executables == 1 in the output).
    n_pages = 1 + BATCH * MAX_LEN // PAGE_SIZE // 2
    paged = _run_engine(params, cfg, prompts,
                        ServeConfig(max_len=MAX_LEN, batch=BATCH,
                                    eos_id=-1, paged=True,
                                    page_size=PAGE_SIZE, n_pages=n_pages,
                                    chunk_size=PAGE_SIZE))
    contig["paged"] = paged
    contig["paged_rows_ratio"] = (paged["cache_hbm_rows"]
                                  / contig["cache_hbm_rows"])
    return contig


def _measured_interleave() -> dict:
    """Long-prompt interleave cell: three slots decoding while a 48-token
    prompt streams in 8-row chunks — every mid-prefill tick must land one
    decode token per active slot (the head-of-line stall the bucketed
    row-cache prefill used to impose is gone)."""
    cfg = configs.get_smoke(ARCH)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(1)
    scfg = ServeConfig(max_len=64, batch=4, eos_id=-1, paged=True,
                       page_size=8, chunk_size=8)
    eng = ServingEngine(params, cfg, scfg)
    eng.submit(Request(rid=-1, prompt=rng.randint(2, cfg.vocab, 9)
                       .astype(np.int32), max_new=2))      # warm both fns
    eng.run_until_drained()
    for rid in range(3):
        eng.submit(Request(rid=rid, prompt=rng.randint(2, cfg.vocab, 7)
                           .astype(np.int32), max_new=40))
    eng.tick()                                 # all three decoding
    long_prompt = rng.randint(2, cfg.vocab, 48).astype(np.int32)
    eng.submit(Request(rid=9, prompt=long_prompt, max_new=2))
    decoded_before = sum(len(eng.slots[i].generated) for i in range(3))
    t0 = time.perf_counter()
    mid_ticks = 0
    eng.tick()                                 # admit + first chunk
    while 3 in eng._prefilling:
        eng.tick()
        mid_ticks += 1
    dt = time.perf_counter() - t0
    decoded_during = sum(len(eng.slots[i].generated)
                         for i in range(3)) - decoded_before
    eng.run_until_drained()
    return {
        "long_prompt_len": len(long_prompt),
        "prefill_chunks": -(-len(long_prompt) // scfg.chunk_size),
        "mid_prefill_ticks": mid_ticks,
        "decode_slots": 3,
        "decode_tokens_during_prefill": decoded_during,
        "wall_s": dt,
        "prefill_executables": len(eng.prefill_traces),
    }


def _measured_spec() -> dict:
    """spec_decode_accept cell: the n-gram (prompt-lookup) drafter over a
    period-4 repetitive prompt, spec_k=4. The stream the smoke model
    greedily settles into is periodic, so once the history repeats the
    drafter lands whole 4-token drafts per verify tick — the accepted
    tokens that amortize the per-tick dispatch + weight stream. Parity
    with the plain greedy engine is asserted, not assumed."""
    cfg = configs.get_smoke(ARCH)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(3)
    motif = rng.randint(2, cfg.vocab, 4).astype(np.int32)
    prompt = np.tile(motif, 6)                   # 24 tokens, period 4
    max_new, spec_k = 48, 4
    ref = np.asarray(greedy_generate(
        params, cfg, jnp.asarray(prompt)[None], max_new,
        max_len=128)[0]).tolist()
    eng = ServingEngine(params, cfg,
                        ServeConfig(max_len=128, batch=2, eos_id=-1,
                                    paged=True, page_size=8, chunk_size=8,
                                    spec_k=spec_k, draft="ngram"))
    # Warm the chunk + verify executables (compile time is not serving
    # throughput), then reset the accept counters for the timed run.
    eng.submit(Request(rid=-1, prompt=rng.randint(2, cfg.vocab, 9)
                       .astype(np.int32), max_new=6))
    eng.run_until_drained()
    eng.spec_ticks = eng.spec_accepted = eng.spec_emitted = 0

    t0 = time.perf_counter()
    eng.submit(Request(rid=0, prompt=prompt, max_new=max_new))
    finished = eng.run_until_drained()
    dt = time.perf_counter() - t0
    ticks = max(1, eng.spec_ticks)
    return {
        "spec_k": spec_k,
        "draft": "ngram",
        "prompt_len": len(prompt),
        "decode_tokens": len(finished[0]),
        "verify_ticks": eng.spec_ticks,
        "accepted": eng.spec_accepted,
        "accepted_per_tick": eng.spec_accepted / ticks,
        "emitted_per_tick": eng.spec_emitted / ticks,
        "accept_rate": eng.spec_accepted / (spec_k * ticks),
        "greedy_parity": finished[0] == ref,
        "wall_s": dt,
        "tokens_per_s": len(finished[0]) / dt,
        "verify_executables": eng.verify_traces,
        "prefill_executables": len(eng.prefill_traces),
    }


def _modeled_spec() -> dict:
    """spec_decode_32k cell: choose_spec_k at production shape — verify
    width priced against the fixed per-tick weight stream it amortizes
    (the paper's latency-hiding arithmetic at serving granularity). Also
    reports the disable regime: a 1 GB model draft at 5% accept must come
    back k=0."""
    cfg = configs.get_config(ARCH)
    max_len = 32768
    lengths = np.geomspace(256, max_len, 128).astype(int)
    param_bytes = T.active_param_count(cfg) * 2.0        # bf16
    k, terms = autotune.choose_spec_k(
        lengths, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.dhead, page_size=256, accept_rate=0.7,
        param_bytes=param_bytes)
    k_low, _ = autotune.choose_spec_k(
        lengths, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.dhead, page_size=256, accept_rate=0.05,
        param_bytes=param_bytes, draft_bytes=1e9)
    out = dict(terms)
    out.update({
        "max_len": max_len,
        "param_bytes": param_bytes,
        "k_at_low_accept_model_draft": k_low,
    })
    return out


def _modeled_chunked() -> dict:
    """prefill_chunked_32k: the autotune chunk cost model at production
    shape — chosen chunk vs whole-prompt (row-cache-equivalent) prefill:
    the total-time overhead chunking pays, and the interleave latency it
    buys back for concurrent decode slots."""
    cfg = configs.get_config(ARCH)
    page_size = 256
    chunk, terms = autotune.choose_prefill_chunk(
        32768, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.dhead, page_size=page_size)
    whole = autotune.prefill_chunk_model(
        32768, 32768, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.dhead, page_size=page_size)
    out = dict(terms)
    out.update({
        "page_size": page_size,
        "whole_prompt_prefill_s": whole["prefill_s"],
        "whole_prompt_latency_s": whole["interleave_latency_s"],
        "prefill_overhead_frac":
            terms["prefill_s"] / whole["prefill_s"] - 1.0,
        "latency_reduction":
            whole["interleave_latency_s"] / terms["interleave_latency_s"],
    })
    return out


def _modeled() -> dict:
    """decode_32k cell: 128 slots, 32k cache, uniformly ragged contexts."""
    cfg = configs.get_config(ARCH)
    max_len = 32768
    lengths = np.linspace(512, max_len, 128).astype(int)
    out = autotune.decode_attn_speedup(
        max_len, lengths, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.dhead)
    out["max_len"] = max_len
    out["mean_context"] = float(lengths.mean())
    return out


def _modeled_paged() -> dict:
    """Paged decode_32k: long-tailed staggered lengths (geomspace — most
    contexts short, a few at max_len, the shape real serving traffic has),
    256-row pages."""
    cfg = configs.get_config(ARCH)
    max_len = 32768
    lengths = np.geomspace(256, max_len, 128).astype(int)
    out = autotune.paged_decode_model(
        max_len, lengths, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.dhead, page_size=256)
    out["max_len"] = max_len
    out["mean_context"] = float(lengths.mean())
    return out


PREFIX_LEN = 24


def _measured_prefix() -> dict:
    """prefix_cache_hit cell: session traffic (a two-session
    ``TrafficClass``, 24-token shared prefixes, six arrivals) through
    the paged engine with the prefix cache off then on. The first
    arrival of each session publishes its prefix; the remaining sharers
    ride it. The on-engine must emit byte-identical streams while
    prefilling only each sharer's suffix — TTFT drops to the suffix
    chunk count — and must hold strictly fewer pages at high water than
    the off-engine: one resident copy per distinct prefix while the
    sharers decode concurrently."""
    cfg = configs.get_smoke(ARCH)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    arr = traffic.TrafficGenerator(traffic.TrafficConfig(
        rate=1.0, n_requests=N_REQUESTS, seed=5, vocab=cfg.vocab,
        classes=(traffic.TrafficClass(
            "chat", sessions=2, prefix_len=PREFIX_LEN,
            prompt_lo=4, prompt_hi=9, out_lo=MAX_NEW,
            out_hi=MAX_NEW),))).arrivals()
    first = {}
    for a in arr:                             # session publishers
        first.setdefault(a.prompt[:PREFIX_LEN].tobytes(), a.rid)
    pubs = sorted(first.values())
    sharers = [a.rid for a in arr if a.rid not in pubs]
    by_rid = {a.rid: a for a in arr}
    wrng = np.random.RandomState(9)

    def run(on):
        eng = ServingEngine(params, cfg, ServeConfig(
            max_len=MAX_LEN, batch=BATCH, eos_id=-1, paged=True,
            page_size=PAGE_SIZE, chunk_size=PAGE_SIZE, prefix_cache=on))
        eng.submit(Request(rid=-1, prompt=wrng.randint(
            2, cfg.vocab, PAGE_SIZE + 1).astype(np.int32), max_new=2))
        eng.run_until_drained()               # warm the executables
        if eng.prefix is not None:
            eng.prefix.clear()                # timed run seeds its own
        eng.pool.high_water = eng.pool.pages_in_use
        for rid in pubs:
            eng.submit(Request(rid=rid, prompt=by_rid[rid].prompt.copy(),
                               max_new=MAX_NEW))
        eng.run_until_drained()
        t0 = eng.ticks
        for rid in sharers:
            eng.submit(Request(rid=rid, prompt=by_rid[rid].prompt.copy(),
                               max_new=MAX_NEW))
        # run_until_drained returns the cumulative finished dict — drop
        # the warm-up rid (its prompt differs between the two runs).
        streams = {rid: s for rid, s in eng.run_until_drained().items()
                   if rid >= 0}
        ttft = [eng.first_token_tick[rid] - t0 for rid in sharers]
        return streams, sum(ttft) / len(ttft), eng

    off_streams, ttft_off, eng_off = run(False)
    on_streams, ttft_on, eng = run(True)
    return {
        "prefix_len": PREFIX_LEN,
        "page_size": PAGE_SIZE,
        "sessions": 2,
        "publishers": len(pubs),
        "sharers": len(sharers),
        "stream_parity": on_streams == off_streams,
        "ttft_ticks_uncached": ttft_off,
        "ttft_ticks_hit": ttft_on,
        "ttft_reduction": ttft_off / max(ttft_on, 1e-9),
        "prefix_hits": eng.prefix_hits,
        "prefix_misses": eng.prefix_misses,
        "hit_pages": eng.prefix_hit_pages,
        "cow_copies": eng.cow_copies,
        "index_entries": len(eng.prefix),
        "high_water_pages_uncached": eng_off.pool.high_water,
        "high_water_pages_cached": eng.pool.high_water,
        "reservation_ratio": (eng.pool.high_water
                              / max(1, eng_off.pool.high_water)),
        "counters_reconcile": (
            eng.prefix_hit_pages == eng.pool.shared_mappings
            and eng.cow_copies == eng.pool.cow_count),
    }


def _modeled_prefix() -> dict:
    """prefix_cache_32k cell: ``autotune.choose_prefix_cache`` at
    production shape — an 8k-row session prefix on a 32k prompt at 60%
    hit rate: suffix-only prefill plus the COW split and probe tax vs
    prefilling from row 0, and the disable regime (hit rate 0 must come
    back off — the probe tax buys nothing)."""
    cfg = configs.get_config(ARCH)
    on, terms = autotune.choose_prefix_cache(
        32768, prefix_rows=8192, hit_rate=0.6, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.dhead, page_size=256)
    on_zero, _ = autotune.choose_prefix_cache(
        32768, prefix_rows=8192, hit_rate=0.0, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.dhead, page_size=256)
    out = dict(terms)
    out.update({
        "max_len": 32768,
        "page_size": 256,
        "enabled": on,
        "enabled_at_zero_hit_rate": on_zero,
    })
    return out


TP_DEVICES = 8

_TP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, sys.argv[1])
import jax, numpy as np
from repro import configs
from repro.launch import mesh as mesh_lib
from repro.models import transformer as T
from repro.serve.engine import Request, ServeConfig, ServingEngine

cfg = configs.get_smoke("qwen3-4b")
params = T.init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.RandomState(2)
prompts = [rng.randint(2, cfg.vocab, n).astype(np.int32)
           for n in (9, 14, 6, 12)]
kw = dict(max_len=64, batch=4, eos_id=-1, paged=True, page_size=4,
          chunk_size=8, n_pages=64)

def run(mesh):
    eng = ServingEngine(params, cfg, ServeConfig(**kw), mesh=mesh)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p.copy(), max_new=10))
    spans = {}
    orig = eng.tick
    def tick():
        n = orig()
        for rid, pages in eng.pool.slot_pages.items():
            spans[rid] = spans.get(rid, set()) | {
                eng.pool.device_of(p) for p in pages}
        return n
    eng.tick = tick
    out = {k: list(v) for k, v in eng.run_until_drained().items()}
    return out, eng, spans

ref, e1, _ = run(None)
got, e8, spans = run(mesh_lib.make_mesh((8,), ("model",)))
print("TP_RESULTS:" + json.dumps({
    "n_devices": e8.pool.n_devices,
    "parity": ref == got,
    "capacity_1dev": e1.pool.capacity,
    "capacity_tp": e8.pool.capacity,
    "max_device_span": max(len(v) for v in spans.values()),
    "decode_executables_1dev": e1.decode_traces,
    "decode_executables_tp": e8.decode_traces,
    "preemptions_tp": e8.preemptions,
}))
"""


def _measured_tp() -> dict:
    """tp_pool_capacity cell: the acceptance oracle, measured — same
    prompts through the 1-device and 8-device engines in a subprocess
    with 8 host devices (the bench process itself sees one)."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "src")
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as f:
        f.write(_TP_SCRIPT)
        script = f.name
    try:
        # Pin the CPU: the child wants 8 virtual host devices, never the
        # chip this process may hold.
        proc = subprocess.run([sys.executable, script, src],
                              capture_output=True, text=True, timeout=600,
                              env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("TP_RESULTS:")][0]
        return json.loads(line[len("TP_RESULTS:"):])
    finally:
        os.unlink(script)


def _modeled_tp() -> dict:
    """tp_decode_32k cell: one decode tick 1-dev vs tensor-parallel at
    production shape — the sharded weight stream vs the activation
    collectives it costs, and the x(mesh) pool-capacity headline."""
    cfg = configs.get_config(ARCH)
    max_len = 32768
    lengths = np.geomspace(256, max_len, 128).astype(int)
    param_bytes = T.active_param_count(cfg) * 2.0        # bf16
    out = autotune.tp_decode_model(
        lengths, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.dhead, page_size=256, param_bytes=param_bytes,
        d_model=cfg.d_model, n_layers=cfg.n_layers, n_devices=TP_DEVICES)
    out["max_len"] = max_len
    out["param_bytes"] = param_bytes
    return out


def _measured_calibration() -> dict:
    """calibration_probes cell: run the microbenchmark calibration pass
    (core/calibrate.py — fast mode, the probes' CI shape), persist the
    measured constants under the tuning cache's ``calibrated:``
    namespace, and report measured-vs-assumed per constant. After this
    cell, ``resolve_constants`` prefers the measured set — the bench
    asserts that loop actually closed."""
    from repro.core import calibrate

    results = calibrate.run_calibration(fast=True, persist=True)
    report = autotune.calibration_report()
    resolved = autotune.resolve_constants()
    rows = {}
    for name, r in results.items():
        rows[name] = {
            "measured": r.value,
            "assumed": report["constants"][name]["assumed"],
            "drift_ratio": report["constants"][name]["drift_ratio"],
            "n_trials": r.n_trials,
            "spread": r.spread,
            "unit": r.unit,
        }
    return {
        "schema_version": autotune.CALIBRATION_SCHEMA_VERSION,
        "backend": report["backend"],
        "mesh": report["mesh"],
        "n_measured": len(rows),
        "resolved_source": resolved.source,
        "constants": rows,
    }


def run():
    m = _measured()
    c = _modeled()
    p = _modeled_paged()
    il = _measured_interleave()
    ck = _modeled_chunked()
    sp = _measured_spec()
    sk = _modeled_spec()
    pfx = _measured_prefix()
    pfk = _modeled_prefix()
    tpm = _measured_tp()
    tpk = _modeled_tp()
    cal = _measured_calibration()
    return [
        ("calibration_probes",
         f"measured={cal['n_measured']};source={cal['resolved_source']};"
         f"page_lookup_drift="
         f"{cal['constants']['page_lookup_s']['drift_ratio']:.2g}"),
        ("measured",
         f"{m['tokens_per_s']:.1f}tok/s;prefill={m['prefill_tokens']};"
         f"decode={m['decode_tokens']};"
         f"executables={m['prefill_executables']}"),
        ("measured_paged",
         f"{m['paged']['tokens_per_s']:.1f}tok/s;"
         f"rows_ratio={m['paged_rows_ratio']:.2f};"
         f"chunk={m['paged']['prefill_chunk']};"
         f"executables={m['paged']['prefill_executables']}"),
        ("modeled_decode_32k",
         f"naive={c['naive_s']*1e3:.3f}ms;fast={c['fast_s']*1e3:.3f}ms;"
         f"speedup={c['speedup']:.2f}x"),
        ("paged_decode_32k",
         f"reservation={p['reservation_ratio']:.2f};"
         f"overhead={p['lookup_overhead_frac']*100:.1f}%;"
         f"tok/s={p['tokens_per_s_paged']:.0f}"),
        ("prefill_chunked_interleave",
         f"decode_toks_mid_prefill={il['decode_tokens_during_prefill']};"
         f"chunks={il['prefill_chunks']};"
         f"executables={il['prefill_executables']}"),
        ("prefill_chunked_32k",
         f"chunk={ck['chunk']};"
         f"overhead={ck['prefill_overhead_frac']*100:.1f}%;"
         f"latency/{ck['latency_reduction']:.0f}"),
        ("spec_decode_accept",
         f"accepted/tick={sp['accepted_per_tick']:.2f};"
         f"emitted/tick={sp['emitted_per_tick']:.2f};"
         f"verify_executables={sp['verify_executables']}"),
        ("spec_decode_32k",
         f"k={sk['chosen_k']};speedup={sk['speedup']:.2f}x;"
         f"accept={sk['accept_rate']:.2f};"
         f"k_low_accept={sk['k_at_low_accept_model_draft']}"),
        ("prefix_cache_hit",
         f"parity={pfx['stream_parity']};"
         f"ttft={pfx['ttft_ticks_hit']:.1f}/{pfx['ttft_ticks_uncached']:.1f}t;"
         f"reservation={pfx['reservation_ratio']:.2f};"
         f"cow={pfx['cow_copies']}"),
        ("prefix_cache_32k",
         f"speedup={pfk['speedup']:.2f}x;"
         f"ttft_frac_hit={pfk['ttft_frac_hit']:.2f};"
         f"on={pfk['enabled']};zero_hit_on={pfk['enabled_at_zero_hit_rate']}"),
        ("tp_pool_capacity",
         f"parity={tpm['parity']};devices={tpm['n_devices']};"
         f"span={tpm['max_device_span']};"
         f"executables={tpm['decode_executables_tp']}"),
        ("tp_decode_32k",
         f"speedup={tpk['speedup']:.2f}x;"
         f"collective={tpk['collective_frac']*100:.0f}%;"
         f"pool_x{tpk['pool_capacity_ratio']:.0f}"),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    payload = {"measured": _measured(), "modeled_decode_32k": _modeled(),
               "paged_decode_32k": _modeled_paged(),
               "prefill_chunked_interleave": _measured_interleave(),
               "prefill_chunked_32k": _modeled_chunked(),
               "spec_decode_accept": _measured_spec(),
               "spec_decode_32k": _modeled_spec(),
               "prefix_cache_hit": _measured_prefix(),
               "prefix_cache_32k": _modeled_prefix(),
               "tp_pool_capacity": _measured_tp(),
               "tp_decode_32k": _modeled_tp(),
               "calibration_probes": _measured_calibration()}
    print(json.dumps(payload, indent=1))
    assert payload["modeled_decode_32k"]["speedup"] > 1.0
    # Acceptance: paged holds < 50% of the contiguous reservation at
    # decode_32k with staggered slot lengths.
    assert payload["paged_decode_32k"]["reservation_ratio"] < 0.5
    assert payload["measured"]["paged_rows_ratio"] < 1.0
    # Acceptance: one chunk executable regardless of prompt-length mix,
    # and decode ticks land tokens while the long prompt is mid-prefill.
    assert payload["measured"]["paged"]["prefill_executables"] == 1
    assert payload["prefill_chunked_interleave"][
        "decode_tokens_during_prefill"] > 0
    assert payload["prefill_chunked_interleave"]["prefill_executables"] == 1
    assert payload["prefill_chunked_32k"]["latency_reduction"] > 1.0
    # Acceptance: the n-gram drafter lands > 1 accepted token per verify
    # tick on the repetitive prompt, the stream is the plain greedy
    # engine's, and exactly one verify executable was traced; the modeled
    # cell speculates profitably at accept=0.7 and disables (k=0) for the
    # low-accept model draft.
    assert payload["spec_decode_accept"]["accepted_per_tick"] > 1.0
    assert payload["spec_decode_accept"]["greedy_parity"]
    assert payload["spec_decode_accept"]["verify_executables"] == 1
    assert payload["spec_decode_32k"]["chosen_k"] >= 1
    assert payload["spec_decode_32k"]["speedup"] > 1.0
    assert payload["spec_decode_32k"]["k_at_low_accept_model_draft"] == 0
    # Acceptance: cached admissions stream bit-identically to the
    # uncached engine while prefilling only the suffix (TTFT strictly
    # below uncached with >= 2 concurrent sharers), the shared pool's
    # high water sits strictly below the uncached engine's, and the
    # hit/COW telemetry reconciles with the allocator's refcount totals;
    # the modeled cell speculates profitably at 60% hit rate and
    # disables itself at hit rate 0 (the probe tax buys nothing).
    pfx = payload["prefix_cache_hit"]
    assert pfx["stream_parity"]
    assert pfx["sharers"] >= 2 and pfx["prefix_hits"] >= 2
    assert pfx["ttft_ticks_hit"] < pfx["ttft_ticks_uncached"]
    assert pfx["reservation_ratio"] < 1.0
    assert pfx["counters_reconcile"]
    assert payload["prefix_cache_32k"]["enabled"]
    assert payload["prefix_cache_32k"]["speedup"] > 1.0
    assert not payload["prefix_cache_32k"]["enabled_at_zero_hit_rate"]
    # Acceptance: the mesh-sharded engine's streams are bit-identical to
    # the single-device engine's, a slot's page table spans devices, the
    # same n_pages gives the same capacity on either mesh, and each mesh
    # compiled exactly one decode executable.
    tp = payload["tp_pool_capacity"]
    assert tp["parity"]
    assert tp["max_device_span"] >= 2
    assert tp["capacity_tp"] == tp["capacity_1dev"]
    assert tp["decode_executables_tp"] == 1
    assert tp["decode_executables_1dev"] == 1
    assert payload["tp_decode_32k"]["speedup"] > 1.0
    assert payload["tp_decode_32k"]["pool_capacity_ratio"] == TP_DEVICES
    # Acceptance: the calibration pass measured >= 5 constants (finite
    # positive, with a recorded drift ratio against the hand-set
    # assumption) and resolve_constants now prefers the measured set.
    cal = payload["calibration_probes"]
    assert cal["n_measured"] >= 5, cal
    assert cal["resolved_source"] == "calibrated", cal
    for name, row in cal["constants"].items():
        assert row["measured"] > 0 and row["assumed"] > 0, (name, row)
        assert row["drift_ratio"] > 0, (name, row)
    if args.out:
        # Read-modify-write: breaking_point.py merges its cells into the
        # same BENCH json, so a rerun here must not clobber them.
        existing = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                existing = json.load(f)
        existing.update(payload)
        with open(args.out, "w") as f:
            json.dump(existing, f, indent=1)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()

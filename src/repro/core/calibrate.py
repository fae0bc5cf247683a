"""Microbenchmark calibration for the serving-path cost constants.

The paper's method is to discover the constants the vendor won't
disclose by probing: pointer-chase ladders for latency, streamed copies
for bandwidth, per-instruction timing for CPI. This module turns the
same idiom on our own serving hot path — the hand-set constants in
``core/autotune`` (``PAGE_LOOKUP_S``, ``CHUNK_DISPATCH_S``,
``NGRAM_DRAFT_S``, ``PREFIX_HASH_S``, the assumed ``hbm_bandwidth``)
become *measured* per backend+mesh:

  dispatch_s        best-of-N wall time of a tiny jitted kernel — the
                    floor every executable launch pays on this runtime.
  page_lookup_s     sweep page-table sizes through the real
                    ``flash_decode_paged`` executable at fixed context,
                    time the contiguous ``flash_decode`` at the same
                    lengths with one page per K/V block, and regress
                    both against visited blocks: the *difference of
                    slopes* is the per-block cost of walking the table
                    (the pchase trick — vary one knob, read the marginal
                    cost off the line, subtract the part a contiguous
                    layout also pays).
  hbm_bandwidth     timed device round-trips of an ``a + 1`` stream at
                    serving-relevant sizes, per dtype; the best observed
                    rate (2 x nbytes per call: read + write).
  chunk_dispatch_s  steady-state ``prefill_chunk`` execute span from a
                    tiny real engine run (telemetry's compile/execute
                    separation is the warm-up boundary).
  draft_token_s     best-of-N host n-gram draft proposal over a
                    motif-rich history, per proposed token.
  prefix_hash_s     best-of-N chained page-digest walk (hash + table
                    probe) per page — what the prefix cache pays to
                    recognize a shared prompt.

Results persist in the tuning cache under the schema-versioned
``calibrated:{backend}:{mesh}:{name}`` namespace with probe metadata
(n_trials, spread, unit, timestamp); ``autotune.resolve_constants``
reads them back and the serving engine prices every ``choose_*``
decision from the measured set. ``REPRO_DEFAULT_CONSTANTS=1`` forces
the documented defaults for reproducibility.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from repro.core import autotune


@dataclasses.dataclass(frozen=True)
class ProbeResult:
    """One measured constant plus the evidence behind it."""

    name: str
    value: float
    unit: str
    n_trials: int
    spread: float            # (max - min) / min over kept trials
    detail: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        assert self.name in autotune.CALIBRATED_NAMES, self.name
        assert np.isfinite(self.value) and self.value > 0, \
            (self.name, self.value)


def _best_of(fn: Callable[[], Any], n: int,
             warmup: int = 2) -> Tuple[float, float, int]:
    """Best-of-N wall timing: min is the signal (one clean run with no
    interference), (max-min)/min is the spread the cache entry records
    so a noisy probe is visible downstream."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    best = min(times)
    spread = (max(times) - best) / best if best > 0 else 0.0
    return best, spread, n


# -- probes -------------------------------------------------------------------


def probe_dispatch(fast: bool = False) -> ProbeResult:
    """Executable dispatch floor: a jitted kernel too small to compute
    anything measurable, so its round-trip *is* the launch overhead."""
    import jax
    import jax.numpy as jnp

    x = jnp.zeros((8,), jnp.float32)
    f = jax.jit(lambda a: a + 1.0)
    n = 10 if fast else 30
    best, spread, n = _best_of(lambda: f(x).block_until_ready(), n)
    return ProbeResult("dispatch_s", best, "s/dispatch", n, spread,
                       {"probe": "tiny_kernel_best_of_n"})


def probe_page_lookup(fast: bool = False) -> ProbeResult:
    """Page-walk slope: time ``flash_decode_paged`` across page-table
    sizes and subtract the contiguous ``flash_decode`` slope at the same
    context lengths, streamed in page-sized blocks — the residual marginal
    cost per visited K block (one page of one kv head) is the table lookup
    itself."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    batch, kvh, heads, d = 2, 1, 2, 32
    page_size = 8
    tables = (2, 4, 8) if fast else (2, 4, 8, 16)
    n = 3 if fast else 7
    key = jax.random.PRNGKey(0)
    visited, t_paged, t_contig = [], [], []
    for n_tables in tables:
        max_len = n_tables * page_size
        n_pages = batch * n_tables + 1          # page 0 is the null page
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (batch, heads, d), jnp.float32)
        k_pages = jax.random.normal(
            kk, (n_pages, page_size, kvh, d), jnp.float32)
        v_pages = jax.random.normal(
            kv, (n_pages, page_size, kvh, d), jnp.float32)
        page_table = np.arange(
            1, batch * n_tables + 1, dtype=np.int32).reshape(batch, n_tables)
        lengths = np.full((batch,), max_len, np.int32)
        k_flat = k_pages[page_table.reshape(-1)].reshape(
            batch, max_len, kvh, d)
        v_flat = v_pages[page_table.reshape(-1)].reshape(
            batch, max_len, kvh, d)
        tp, _, _ = _best_of(
            lambda: ops.flash_decode_paged(
                q, k_pages, v_pages, page_table,
                lengths).block_until_ready(), n)
        tc, _, _ = _best_of(
            lambda: ops.flash_decode(
                q, k_flat, v_flat, lengths,
                block_k=page_size).block_until_ready(), n)
        visited.append(batch * kvh * n_tables)   # K blocks (pages)/call
        t_paged.append(tp)
        t_contig.append(tc)
    slope_paged = float(np.polyfit(visited, t_paged, 1)[0])
    slope_contig = float(np.polyfit(visited, t_contig, 1)[0])
    # Interpret-mode noise can push the difference negative; clamp to a
    # positive floor so the constant stays priceable.
    value = max(slope_paged - slope_contig, 1e-10)
    spread = (max(t_paged) - min(t_paged)) / max(min(t_paged), 1e-12)
    return ProbeResult(
        "page_lookup_s", value, "s/block", n * len(tables), spread,
        {"probe": "table_sweep_slope", "tables": list(tables),
         "page_size": page_size,
         "slope_paged_s": slope_paged, "slope_contig_s": slope_contig})


def probe_hbm_stream(fast: bool = False) -> ProbeResult:
    """Device stream rate: jitted ``a + 1`` moves 2 x nbytes (read +
    write); the best observed rate across dtypes is what the serving
    models should price weight and KV streams with."""
    import jax
    import jax.numpy as jnp

    elems = (1 << 18) if fast else (1 << 21)     # 1 MiB / 8 MiB at f32
    n = 5 if fast else 15
    rates = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        a = jnp.ones((elems,), dtype)
        f = jax.jit(lambda x: x + 1)
        best, _, _ = _best_of(lambda: f(a).block_until_ready(), n)
        nbytes = elems * a.dtype.itemsize
        rates[np.dtype(dtype).name] = 2.0 * nbytes / best
    value = max(rates.values())
    spread = (max(rates.values()) - min(rates.values())) \
        / max(min(rates.values()), 1e-12)
    return ProbeResult(
        "hbm_bandwidth", value, "bytes/s", n * len(rates), spread,
        {"probe": "stream_copy", "rates_by_dtype": rates,
         "elems": elems})


def probe_chunk_dispatch(fast: bool = False) -> ProbeResult:
    """Steady-state chunked-prefill step cost from a real tiny engine:
    warm one drained run (compiles), reset telemetry, drain a second —
    the ``prefill_chunk`` execute-span mean is the measured per-chunk
    dispatch+step cost the prefill model's ``dispatch_s`` term prices."""
    import jax
    from repro import configs
    from repro.models import transformer as T
    from repro.serve.engine import Request, ServeConfig, ServingEngine

    cfg = configs.get_smoke("qwen3-4b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, ServeConfig(
        max_len=32, batch=2, eos_id=-1, paged=True, page_size=8,
        chunk_size=8))
    rng = np.random.default_rng(0)

    def drain(rid0: int):
        for i in range(2):
            prompt = rng.integers(0, 64, size=24).astype(np.int32)
            eng.submit(Request(rid=rid0 + i, prompt=prompt, max_new=2))
        eng.run_until_drained()

    drain(0)                       # warm: compile every chunk bucket
    eng.telemetry.reset()
    drain(100)
    st = eng.telemetry.span_stats()["prefill_chunk"]
    assert st["execute_n"] > 0, st
    return ProbeResult(
        "chunk_dispatch_s", st["execute_mean_s"], "s/chunk",
        int(st["execute_n"]),
        (st["max_s"] - st["execute_mean_s"]) / max(st["execute_mean_s"],
                                                   1e-12),
        {"probe": "engine_chunk_span", "chunk": eng.chunk})


def probe_draft_token(fast: bool = False) -> ProbeResult:
    """Host n-gram draft cost per proposed token over a motif-rich
    history (every suffix has a continuation, so the scan always pays
    its full lookup)."""
    from repro.serve.spec import NgramDraft

    draft = NgramDraft()
    history = np.tile(np.arange(16, dtype=np.int32), 64)
    k = 4
    n = 10 if fast else 30
    best, spread, n = _best_of(lambda: draft.propose(history, k), n)
    return ProbeResult(
        "draft_token_s", max(best / k, 1e-12), "s/token", n, spread,
        {"probe": "ngram_propose", "k": k, "history": len(history)})


def probe_prefix_hash(fast: bool = False) -> ProbeResult:
    """Prefix-cache recognition cost per page: the chained page-digest
    walk (hash the page's tokens into the parent digest, probe the
    digest table) that admission pays per prompt page."""
    from repro.serve import paged

    n_pages = 16 if fast else 64
    page_size = 8
    rng = np.random.default_rng(0)
    chunks = [paged.token_bytes(
        rng.integers(0, 1 << 15, size=page_size).astype(np.int32))
        for _ in range(n_pages)]
    table: Dict[bytes, int] = {}

    def walk():
        parent = paged.ROOT_DIGEST
        for chunk in chunks:
            parent = paged._page_digest(parent, chunk)
            table.get(parent)
        return parent

    n = 5 if fast else 15
    best, spread, n = _best_of(walk, n)
    return ProbeResult(
        "prefix_hash_s", max(best / n_pages, 1e-12), "s/page", n, spread,
        {"probe": "digest_chain", "pages": n_pages})


# -- the pass -----------------------------------------------------------------

PROBES: Dict[str, Callable[[bool], ProbeResult]] = {
    "dispatch_s": probe_dispatch,
    "page_lookup_s": probe_page_lookup,
    "hbm_bandwidth": probe_hbm_stream,
    "chunk_dispatch_s": probe_chunk_dispatch,
    "draft_token_s": probe_draft_token,
    "prefix_hash_s": probe_prefix_hash,
}
assert tuple(PROBES) == autotune.CALIBRATED_NAMES


def run_calibration(fast: bool = False, persist: bool = True,
                    mesh_shape=None,
                    backend: Optional[str] = None
                    ) -> Dict[str, ProbeResult]:
    """Run every probe; with ``persist`` write each result into the
    tuning cache's ``calibrated:`` namespace (schema-versioned, with
    n_trials/spread/unit/timestamp metadata) so ``resolve_constants``
    prefers it from the next engine construction on."""
    results: Dict[str, ProbeResult] = {}
    for name, probe in PROBES.items():
        res = probe(fast)
        results[name] = res
        if persist:
            autotune.record_calibration(
                name, res.value, mesh_shape=mesh_shape, backend=backend,
                n_trials=res.n_trials, spread=res.spread, unit=res.unit,
                timestamp=time.time(), fast=bool(fast))
    return results

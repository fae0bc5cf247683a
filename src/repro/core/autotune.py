"""Microbenchmark-informed kernel/sharding tuning (the paper's Ch.1 thesis,
TPU-idiomatic).

The paper's demonstration is that measured microarchitectural parameters
(register banks, reuse caches) let a human beat the compiler's schedule.
The TPU transfer is mechanical rather than manual: the dissected hardware
model (VMEM capacity, MXU tile, HBM/ICI bandwidths — the quantities probed
by ``benchmarks/tpu_*.py``) drives an analytical search over Pallas
BlockSpec shapes and over sharding layouts.

The GEMM cost model uses the classic blocked-matmul traffic formula: with
C-stationary accumulation and (bm, bk, bn) tiles, A is streamed N/bn times,
B M/bm times and C once, so tile choice trades VMEM footprint against HBM
traffic — exactly the working-set-vs-capacity trade the paper's ch.3
geometry tables exist to inform.

Serving-path cost constants
---------------------------

The serving cost models price fixed per-step costs with the constants
below. Each has a documented hand-set default (the reproducible
fallback) and — since the calibration pass (``core.calibrate``, run via
``python -m repro.launch.calibrate``) — a *measured* value probed on the
actual backend, persisted in the tuning cache under the ``calibrated:``
namespace and preferred by ``resolve_constants``:

===================  ========  ========================================
constant             default   measured by (``core.calibrate`` probe)
===================  ========  ========================================
``PAGE_LOOKUP_S``    5e-8 s    page-walk slope: ``flash_decode_paged``
                               (one page per step) vs contiguous
                               ``flash_decode`` in page-sized blocks
                               across a page-table-size sweep,
                               regressed per visited K/V block
``CHUNK_DISPATCH_S`` 5e-6 s    per-chunk execute span of the chunked
                               prefill executable (telemetry spans,
                               compile-separated)
``PREFIX_HASH_S``    2e-6 s    timed blake2b digest + index probe per
                               page of tokens (``serve.paged``)
``NGRAM_DRAFT_S``    2e-6 s    timed ``NgramDraft.propose`` per drafted
                               token
``dispatch_s``       (none)    best-of-N tiny-kernel dispatch latency
                               (no default term — reporting baseline is
                               ``CHUNK_DISPATCH_S``)
``hbm_bandwidth``    TPUSpec   timed device copies per dtype at
                               serving-relevant sizes (stream rate)
===================  ========  ========================================

Every model/``choose_*`` entry point takes ``constants=`` (a
``ServeConstants``); None means the hand-set defaults, so existing
callers and committed bench cells are bit-for-bit unchanged. The
serving engine resolves once per construction via
``resolve_constants()``; ``REPRO_DEFAULT_CONSTANTS=1`` forces the
defaults for reproducibility.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
from typing import Iterable, List, Optional, Tuple

from repro.core import hwmodel


@dataclasses.dataclass(frozen=True)
class GemmProblem:
    m: int
    k: int
    n: int
    in_bytes: int = 2          # bf16
    acc_bytes: int = 4         # fp32 accumulator


@dataclasses.dataclass(frozen=True)
class GemmConfig:
    bm: int
    bk: int
    bn: int

    def vmem_bytes(self, p: GemmProblem) -> int:
        # Double-buffered input tiles + resident fp32 accumulator tile.
        return (2 * (self.bm * self.bk + self.bk * self.bn) * p.in_bytes
                + self.bm * self.bn * p.acc_bytes)


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def mxu_efficiency(dim_m: int, dim_k: int, dim_n: int,
                   tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU) -> float:
    """Fraction of MXU work that is useful for a (m,k,n) matmul tile — the
    padding-cliff law that ``benchmarks/tpu_mxu.py`` dissects: each dim pads
    to the systolic edge (lanes) or the sublane pack."""
    d = tpu.mxu_dim
    pad_m = _ceil_div(dim_m, 8) * 8          # sublane granularity
    pad_k = _ceil_div(dim_k, d) * d
    pad_n = _ceil_div(dim_n, d) * d
    useful = dim_m * dim_k * dim_n
    padded = pad_m * pad_k * pad_n
    return useful / padded


def gemm_cost(p: GemmProblem, c: GemmConfig,
              tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU) -> Tuple[float, dict]:
    """Modeled execution time (seconds) of the blocked GEMM, plus terms."""
    flops = 2.0 * p.m * p.k * p.n
    eff = mxu_efficiency(min(c.bm, p.m), min(c.bk, p.k), min(c.bn, p.n), tpu)
    compute_s = flops / (tpu.peak_bf16_flops * eff)
    # HBM traffic in bytes (C-stationary): A x (N/bn), B x (M/bm), C once.
    a_reads = _ceil_div(p.n, c.bn)
    b_reads = _ceil_div(p.m, c.bm)
    traffic = (p.m * p.k * a_reads + p.k * p.n * b_reads) * p.in_bytes \
        + p.m * p.n * p.in_bytes
    memory_s = traffic / tpu.hbm_bandwidth
    t = max(compute_s, memory_s)
    return t, {"compute_s": compute_s, "memory_s": memory_s,
               "traffic_bytes": traffic, "mxu_efficiency": eff}


def candidate_blocks(p: GemmProblem,
                     tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU,
                     vmem_fraction: float = 0.5) -> List[GemmConfig]:
    """Hardware-aligned candidate tiles that fit the VMEM budget."""
    budget = int(tpu.vmem_bytes * vmem_fraction)
    dims = [128, 256, 512, 1024, 2048]
    out = []
    for bm in dims:
        if bm > max(p.m, 128):
            continue
        for bk in dims:
            if bk > max(p.k, 128):
                continue
            for bn in dims:
                if bn > max(p.n, 128):
                    continue
                c = GemmConfig(bm, bk, bn)
                if c.vmem_bytes(p) <= budget:
                    out.append(c)
    return out or [GemmConfig(128, 128, 128)]


def choose_gemm_block(p: GemmProblem,
                      tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU
                      ) -> Tuple[GemmConfig, dict]:
    """Pick the minimum-modeled-time tile (the autotuner's decision)."""
    best, best_t, best_terms = None, float("inf"), None
    for c in candidate_blocks(p, tpu):
        t, terms = gemm_cost(p, c, tpu)
        if t < best_t:
            best, best_t, best_terms = c, t, terms
    return best, dict(best_terms, time_s=best_t)


NAIVE_BLOCK = GemmConfig(128, 128, 128)


def tuning_gain(p: GemmProblem,
                tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU) -> dict:
    """Naive-vs-tuned comparison — the Ch.1 '+15.4%' analogue, reported by
    ``benchmarks/fig_4_8.py`` and exercised e2e in examples/autotune_gemm.py."""
    t_naive, naive_terms = gemm_cost(p, NAIVE_BLOCK, tpu)
    cfg, terms = choose_gemm_block(p, tpu)
    return {
        "naive": {"config": dataclasses.astuple(NAIVE_BLOCK), **naive_terms,
                  "time_s": t_naive},
        "tuned": {"config": dataclasses.astuple(cfg), **terms},
        "speedup": t_naive / terms["time_s"],
    }


# ----------------------------------------------------------------------------
# Attention block selection (flash prefill + flash decode).
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnProblem:
    """One flash-attention launch: ``batch * n_heads`` independent rows of a
    (sq x skv x head_dim) attention, causally masked or not.

    For flash *decode* set ``sq`` to the GQA group size (queries per KV head)
    and ``n_heads`` to ``n_kv_heads`` — that is exactly the row shape the
    decode kernel runs per (slot, kv head) grid step.
    """

    sq: int
    skv: int
    n_heads: int
    head_dim: int
    batch: int = 1
    causal: bool = True
    in_bytes: int = 2          # bf16


@dataclasses.dataclass(frozen=True)
class AttnBlock:
    block_q: int
    block_k: int

    def vmem_bytes(self, p: AttnProblem) -> int:
        # Double-buffered q/k/v input tiles + fp32 scores tile + the
        # m/l/acc online-softmax scratch that persists across K steps.
        d = p.head_dim
        return (2 * (self.block_q + 2 * self.block_k) * d * p.in_bytes
                + self.block_q * self.block_k * 4
                + self.block_q * (d + 2) * 4)


def _attn_visited_blocks(p: AttnProblem, c: AttnBlock) -> int:
    """Number of (q-block, k-block) grid steps the skipped-load causal grid
    actually visits — the quantity the scalar-prefetch map shrinks."""
    nq = _ceil_div(p.sq, c.block_q)
    nk = _ceil_div(p.skv, c.block_k)
    if not p.causal:
        return nq * nk
    off = p.skv - p.sq          # query i attends keys <= i + off
    total = 0
    for qi in range(nq):
        last_row = min(qi * c.block_q + c.block_q - 1, p.sq - 1)
        total += min(_ceil_div(last_row + off + 1, c.block_k), nk)
    return total


def attn_cost(p: AttnProblem, c: AttnBlock,
              tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU
              ) -> Tuple[float, dict]:
    """Modeled execution time (seconds) of the flash kernel, plus terms.

    Same three prices as ``gemm_cost``: MXU compute at the padded-tile
    efficiency, HBM streaming traffic, and the VMEM footprint acting as a
    hard feasibility constraint (handled by ``candidate_attn_blocks``).
    K/V re-stream once per *visited* q-block — the skipped-load causal grid
    (and the per-slot length clamp in flash decode) shows up as fewer
    visited blocks, hence less traffic and fewer MXU steps.
    """
    rows = p.batch * p.n_heads
    visited = _attn_visited_blocks(p, c)
    bq = min(c.block_q, p.sq)
    bk = min(c.block_k, p.skv)
    # Two matmuls per visited block: QK^T (bq,d)x(d,bk) and PV (bq,bk)x(bk,d).
    flops = rows * visited * 4.0 * bq * bk * p.head_dim
    eff = min(mxu_efficiency(bq, p.head_dim, bk, tpu),
              mxu_efficiency(bq, bk, p.head_dim, tpu))
    compute_s = flops / (tpu.peak_bf16_flops * eff)
    # HBM traffic: Q and O touched once per row; K/V streamed per visit.
    qo_bytes = rows * 2 * p.sq * p.head_dim * p.in_bytes
    kv_bytes = rows * visited * 2 * bk * p.head_dim * p.in_bytes
    memory_s = (qo_bytes + kv_bytes) / tpu.hbm_bandwidth
    t = max(compute_s, memory_s)
    return t, {"compute_s": compute_s, "memory_s": memory_s,
               "traffic_bytes": qo_bytes + kv_bytes,
               "visited_blocks": visited, "mxu_efficiency": eff}


def candidate_attn_blocks(p: AttnProblem,
                          tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU,
                          vmem_fraction: float = 0.5) -> List[AttnBlock]:
    budget = int(tpu.vmem_bytes * vmem_fraction)
    dims = [128, 256, 512, 1024]
    out = []
    for bq in dims:
        if bq > max(p.sq, 128):
            continue
        for bk in dims:
            if bk > max(p.skv, 128):
                continue
            c = AttnBlock(bq, bk)
            if c.vmem_bytes(p) <= budget:
                out.append(c)
    return out or [AttnBlock(128, 128)]


NAIVE_ATTN_BLOCK = AttnBlock(128, 128)

# Persistent tuning cache: problem -> chosen block, refreshed write-through.
# Lives next to the benchmark artifacts so TPU-measured entries and modeled
# entries share one file; all IO is best-effort (read-only images just
# re-derive the analytical choice).
_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".."))
TUNING_CACHE_PATH = os.environ.get(
    "REPRO_ATTN_TUNING_CACHE",
    os.path.join(_REPO_ROOT, "benchmarks", "artifacts",
                 "attn_tuning_cache.json"))
_tuning_cache: Optional[dict] = None


def _mesh_key(mesh_shape=None) -> str:
    """Normalize a mesh/device-count descriptor into a cache-key token.

    Accepts a ``{axis: size}`` mapping, an object with a ``.shape``
    mapping (a jax Mesh), a string, or None — None keys by the process's
    visible device count. Tuned entries are only portable across runs
    that *partition identically*: a block shape measured fastest on one
    chip can lose once per-device operand slices shrink 8x, so single-
    and multi-device runs must not clobber each other's entries.
    """
    if mesh_shape is None:
        try:
            import jax
            return f"dev{jax.device_count()}"
        except Exception:            # jax-less analytical use
            return "dev1"
    if isinstance(mesh_shape, str):
        return mesh_shape
    shape = getattr(mesh_shape, "shape", mesh_shape)
    if hasattr(shape, "items"):
        return "mesh(" + ",".join(
            f"{a}={int(n)}" for a, n in sorted(dict(shape).items())) + ")"
    return "mesh(" + ",".join(str(int(n)) for n in tuple(shape)) + ")"


def _cache_key(p: AttnProblem, tpu: hwmodel.TPUSpec,
               mesh_shape=None) -> str:
    # Keyed by the live backend first (as ``calibration_key`` is): an
    # entry a CPU run wrote is never read by a run on the chip.
    return (f"{_backend_key()}:{tpu.name}:{_mesh_key(mesh_shape)}"
            f":sq={p.sq}:skv={p.skv}"
            f":h={p.n_heads}:d={p.head_dim}:b={p.batch}"
            f":causal={int(p.causal)}:bytes={p.in_bytes}")


def _load_tuning_cache() -> dict:
    global _tuning_cache
    if _tuning_cache is None:
        try:
            with open(TUNING_CACHE_PATH) as f:
                loaded = json.load(f)
            if not isinstance(loaded, dict):
                raise ValueError(
                    f"cache root is {type(loaded).__name__}, not object")
            _tuning_cache = loaded
        except OSError:
            # Missing or unreadable (permissions, transient IO): the file
            # may still hold good TPU-measured entries — leave it alone.
            _tuning_cache = {}
        except ValueError:
            # Torn concurrent write / truncated file / non-object root:
            # discard the bad file (so the next write-through rebuilds it
            # from scratch) and fall back to re-deriving analytically.
            _tuning_cache = {}
            try:
                os.remove(TUNING_CACHE_PATH)
            except OSError:
                pass
    return _tuning_cache


def _store_tuning_cache(key: str, entry: dict) -> None:
    cache = _load_tuning_cache()
    cache[key] = entry
    try:
        os.makedirs(os.path.dirname(TUNING_CACHE_PATH), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(TUNING_CACHE_PATH),
                                   suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, TUNING_CACHE_PATH)
    except OSError:
        pass                       # read-only image: in-memory cache only


# Measured serving-path timings (``serve.telemetry.drift_report``) share
# the persistent tuning cache under their own key namespace, so the
# calibration pass the ROADMAP names reads model-vs-measured evidence
# from the same file the block-shape tuner already maintains. Entries:
# {"time_s": measured mean span, "modeled_s", "ratio", "n", "source"}.
SERVE_MEASURED_PREFIX = "serve_measured:"


def record_serve_measurement(name: str, entry: dict) -> None:
    """Persist one measured serving-span entry (keyed by component and
    engine geometry) into the tuning cache."""
    assert isinstance(entry.get("time_s"), float) and entry["time_s"] > 0, \
        entry
    _store_tuning_cache(SERVE_MEASURED_PREFIX + name, dict(entry))


def load_serve_measurement(name: str) -> Optional[dict]:
    return _load_tuning_cache().get(SERVE_MEASURED_PREFIX + name)


def drift_ratio(measured_s: float, modeled_s: float) -> float:
    """measured/modeled with a 0.0 sentinel for missing or degenerate
    inputs — downstream gates require the ratio finite and > 0, so a
    run that never measured (or a model that priced 0) fails the gate
    instead of sneaking through as inf/nan."""
    if not (math.isfinite(measured_s) and math.isfinite(modeled_s)):
        return 0.0
    if measured_s <= 0.0 or modeled_s <= 0.0:
        return 0.0
    return measured_s / modeled_s


def choose_attn_block(p: AttnProblem,
                      tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU,
                      use_cache: bool = True,
                      mesh_shape=None) -> Tuple[AttnBlock, dict]:
    """Minimum-modeled-time (block_q, block_k), persisted across processes.

    The cache key includes backend *and* mesh shape/device count
    (``mesh_shape``; None -> the process's device count), so single- and
    multi-device runs keep separate entries instead of clobbering each
    other — the per-device problem a kernel sees under SPMD is a
    different problem."""
    key = _cache_key(p, tpu, mesh_shape)
    if use_cache:
        hit = _load_tuning_cache().get(key)
        if hit is not None:
            # A torn write can leave a structurally-broken entry even when
            # the file parses; treat any malformed hit as a miss (the
            # write-through below overwrites it with a good one).
            try:
                blk = AttnBlock(int(hit["block_q"]), int(hit["block_k"]))
                terms, time_s = dict(hit["terms"]), float(hit["time_s"])
            except (KeyError, TypeError, ValueError):
                hit = None
            # Entries persist across cost-model/hardware-spec changes (and
            # may be TPU-measured or hand-edited): only trust ones still in
            # the feasible candidate set, else re-derive.
            if hit is not None and blk in candidate_attn_blocks(p, tpu):
                return blk, dict(terms, time_s=time_s, cached=True)
    best, best_t, best_terms = None, float("inf"), None
    for c in candidate_attn_blocks(p, tpu):
        t, terms = attn_cost(p, c, tpu)
        if t < best_t:
            best, best_t, best_terms = c, t, terms
    if use_cache:
        _store_tuning_cache(key, {"block_q": best.block_q,
                                  "block_k": best.block_k,
                                  "time_s": best_t, "terms": best_terms})
    return best, dict(best_terms, time_s=best_t)


def decode_attn_speedup(max_len: int, lengths: Iterable[int], n_heads: int,
                        n_kv_heads: int, head_dim: int,
                        tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU) -> dict:
    """Modeled naive-vs-fast decode attention cost for one engine tick.

    Naive: every slot attends over the full ``max_len`` cache (the seed
    engine's behavior). Fast: flash decode clamps each slot's K/V stream to
    its actual length. Reported by ``benchmarks/tpu_serving.py``.
    """
    group = max(1, n_heads // n_kv_heads)

    def tick_cost(ls):
        t = 0.0
        for length in ls:
            p = AttnProblem(sq=group, skv=max(int(length), 1),
                            n_heads=n_kv_heads, head_dim=head_dim,
                            causal=False)
            c, _ = choose_attn_block(p, tpu, use_cache=False)
            t += attn_cost(p, c, tpu)[0]
        return t

    lengths = list(lengths)
    naive = tick_cost([max_len] * len(lengths))
    fast = tick_cost(lengths)
    return {"naive_s": naive, "fast_s": fast,
            "speedup": naive / fast if fast else float("inf")}


# ----------------------------------------------------------------------------
# Serving-path cost constants: hand-set defaults + measured calibration.
# ----------------------------------------------------------------------------

# Per-visited-block cost of resolving the page table: one dependent scalar
# load off the prefetched table before the K/V DMA can issue — the roofline
# analogue of the paper's TLB-miss penalty (ch. 3: address translation sits
# on the load's critical path; here it is one SMEM lookup deep).
PAGE_LOOKUP_S = 5e-8

# Per-chunk dispatch overhead of the chunked-prefill executable: one host
# enqueue + kernel launch per chunk (the fixed cost small chunks pay more
# often — the MXU-efficiency side of the chunk-size trade).
CHUNK_DISPATCH_S = 5e-6

# Host-side cost of one prefix-index level: a blake2b digest over one
# page of tokens plus a dict probe (``serve.paged.PrefixIndex``).
PREFIX_HASH_S = 2e-6

# Host-side cost of one n-gram-lookup drafted token (a numpy scan of the
# slot's history — no model, no HBM).
NGRAM_DRAFT_S = 2e-6

# Calibrated constants persist in the tuning cache under their own
# schema-versioned namespace, one entry per (backend, mesh, constant):
#
#   calibrated:cpu:dev1:page_lookup_s ->
#     {"schema_version": 1, "value": 3.1e-8, "n_trials": 5,
#      "spread": 0.12, "backend": "cpu", "mesh": "dev1",
#      "timestamp": ..., ...probe metadata}
#
# ``resolve_constants`` reads them back per constant: a torn or
# mis-versioned entry falls back to that constant's hand-set default
# without failing the others.
CALIBRATED_PREFIX = "calibrated:"
CALIBRATION_SCHEMA_VERSION = 1

# Env switch forcing the documented defaults (skip every ``calibrated:``
# entry) — the reproducibility escape hatch; launch CLIs expose it as
# ``--default-constants``.
DEFAULT_CONSTANTS_ENV = "REPRO_DEFAULT_CONSTANTS"


@dataclasses.dataclass(frozen=True)
class ServeConstants:
    """One resolved set of serving-path cost constants.

    ``source`` says where the numbers came from: ``"default"`` (the
    hand-set module constants — the documented fallback) or
    ``"calibrated"`` (``core.calibrate`` probes read back from the
    tuning cache for this backend+mesh). ``hbm_bandwidth`` and
    ``dispatch_s`` are None in the default set: the models then price
    HBM streams straight from the ``TPUSpec`` and carry no separate
    dispatch term — exactly the pre-calibration arithmetic, so forcing
    defaults reproduces the old decisions bit-for-bit.
    """

    page_lookup_s: float = PAGE_LOOKUP_S
    chunk_dispatch_s: float = CHUNK_DISPATCH_S
    prefix_hash_s: float = PREFIX_HASH_S
    draft_token_s: float = NGRAM_DRAFT_S
    dispatch_s: Optional[float] = None     # measured executable dispatch
    hbm_bandwidth: Optional[float] = None  # None -> the TPUSpec's rate
    source: str = "default"                # "default" | "calibrated"
    backend: str = ""
    mesh: str = ""
    timestamp: float = 0.0

    def apply_tpu(self, tpu: hwmodel.TPUSpec) -> hwmodel.TPUSpec:
        """The spec the models should price HBM streams with: the
        measured stream rate when calibrated, the assumed spec itself
        otherwise (same object -> identical default math)."""
        if self.hbm_bandwidth is None:
            return tpu
        return dataclasses.replace(tpu, hbm_bandwidth=self.hbm_bandwidth)


DEFAULT_CONSTANTS = ServeConstants()

# Probe targets, in report order. ``assumed_constants()`` maps each to
# the hand-set value the drift ratio is taken against.
CALIBRATED_NAMES = ("dispatch_s", "page_lookup_s", "hbm_bandwidth",
                    "chunk_dispatch_s", "draft_token_s", "prefix_hash_s")


def assumed_constants(tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU) -> dict:
    """Hand-set value per calibrated constant (the drift baseline).
    ``dispatch_s`` has no model term of its own; its baseline is the
    chunk-dispatch constant, which prices the same enqueue+launch."""
    return {"dispatch_s": CHUNK_DISPATCH_S,
            "page_lookup_s": PAGE_LOOKUP_S,
            "hbm_bandwidth": tpu.hbm_bandwidth,
            "chunk_dispatch_s": CHUNK_DISPATCH_S,
            "draft_token_s": NGRAM_DRAFT_S,
            "prefix_hash_s": PREFIX_HASH_S}


def _backend_key(backend: Optional[str] = None) -> str:
    if backend is not None:
        return backend
    try:
        import jax
        return jax.default_backend()
    except Exception:              # jax-less analytical use
        return "cpu"


def calibration_key(name: str, mesh_shape=None,
                    backend: Optional[str] = None) -> str:
    return (f"{CALIBRATED_PREFIX}{_backend_key(backend)}"
            f":{_mesh_key(mesh_shape)}:{name}")


def record_calibration(name: str, value: float, mesh_shape=None,
                       backend: Optional[str] = None, **meta) -> None:
    """Persist one probed constant under the ``calibrated:`` namespace."""
    assert name in CALIBRATED_NAMES, name
    value = float(value)
    assert math.isfinite(value) and value > 0, (name, value)
    entry = {"schema_version": CALIBRATION_SCHEMA_VERSION,
             "value": value,
             "backend": _backend_key(backend),
             "mesh": _mesh_key(mesh_shape)}
    entry.update(meta)
    _store_tuning_cache(calibration_key(name, mesh_shape, backend), entry)


def load_calibration(name: str, mesh_shape=None,
                     backend: Optional[str] = None) -> Optional[dict]:
    """One constant's validated cache entry, or None. A torn write, a
    schema-version mismatch, or a non-finite value reads as None (that
    constant falls back to its default), never an exception."""
    hit = _load_tuning_cache().get(
        calibration_key(name, mesh_shape, backend))
    if not isinstance(hit, dict):
        return None
    try:
        if int(hit["schema_version"]) != CALIBRATION_SCHEMA_VERSION:
            return None
        v = float(hit["value"])
    except (KeyError, TypeError, ValueError):
        return None
    if not (math.isfinite(v) and v > 0):
        return None
    return hit


def resolve_constants(mesh_shape=None,
                      backend: Optional[str] = None) -> ServeConstants:
    """The constants the serving engine prices its decisions with.

    Prefers calibrated entries (``core.calibrate`` probes for this
    backend+mesh) constant by constant; any constant without a valid
    entry keeps its hand-set default. With ``REPRO_DEFAULT_CONSTANTS``
    set — or no valid entries at all — this is exactly
    ``DEFAULT_CONSTANTS``, the documented reproducible fallback.
    """
    if os.environ.get(DEFAULT_CONSTANTS_ENV, "").strip() not in ("", "0"):
        return DEFAULT_CONSTANTS
    found, ts = {}, 0.0
    for name in CALIBRATED_NAMES:
        hit = load_calibration(name, mesh_shape, backend)
        if hit is not None:
            found[name] = float(hit["value"])
            try:
                ts = max(ts, float(hit.get("timestamp", 0.0)))
            except (TypeError, ValueError):
                pass
    if not found:
        return DEFAULT_CONSTANTS
    return dataclasses.replace(DEFAULT_CONSTANTS, source="calibrated",
                               backend=_backend_key(backend),
                               mesh=_mesh_key(mesh_shape),
                               timestamp=ts, **found)


def calibration_report(mesh_shape=None, backend: Optional[str] = None,
                       tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU) -> dict:
    """Per-constant measured-vs-assumed rollup (the calibration half of
    the observability gate): for every probe target, the measured value
    (None when never calibrated), the hand-set assumed value, the drift
    ratio measured/assumed (0.0 sentinel when unmeasured), and the probe
    metadata the entry carried (n_trials, spread, timestamp)."""
    resolved = resolve_constants(mesh_shape, backend)
    assumed = assumed_constants(tpu)
    rows = {}
    for name in CALIBRATED_NAMES:
        hit = load_calibration(name, mesh_shape, backend)
        measured = float(hit["value"]) if hit is not None else None
        rows[name] = {
            "assumed": assumed[name],
            "measured": measured,
            "drift_ratio": drift_ratio(measured, assumed[name])
            if measured is not None else 0.0,
            "n_trials": hit.get("n_trials") if hit else None,
            "spread": hit.get("spread") if hit else None,
            "timestamp": hit.get("timestamp") if hit else None,
        }
    return {"schema_version": CALIBRATION_SCHEMA_VERSION,
            "source": resolved.source,
            "backend": _backend_key(backend),
            "mesh": _mesh_key(mesh_shape),
            "timestamp": resolved.timestamp,
            "constants": rows}


@dataclasses.dataclass(frozen=True)
class TPServe:
    """Tensor-parallel serving geometry for the analytical cost models.

    ``n_devices`` shards the weight stream, the dense FLOPs, and (when the
    relevant head count divides) the attention work; each transformer
    layer pays two activation all-reduces (attn out-proj + MLP down-proj,
    the classic Megatron row-parallel cut) and the forward ends with one
    all-gather assembling the unembed ring's sharded logits GEMM.
    """
    n_devices: int
    d_model: int
    n_layers: int


def _tp_collective_s(tokens: float, tp: Optional["TPServe"],
                     in_bytes: int,
                     tpu: hwmodel.TPUSpec) -> float:
    """Per-forward collective seconds at ``tokens`` total query tokens
    under ``tp``; 0 when unsharded (the single-device models stay exact)."""
    if tp is None or tp.n_devices <= 1:
        return 0.0
    from repro.core import interconnect
    payload = float(tokens) * tp.d_model * in_bytes
    ar = interconnect.collective_time("all_reduce", payload,
                                      tp.n_devices, tpu).time_s
    ag = interconnect.collective_time("all_gather", payload,
                                      tp.n_devices, tpu).time_s
    return 2.0 * tp.n_layers * ar + ag


def _tp_shard(tp: Optional["TPServe"], heads: int) -> Tuple[int, int]:
    """(dense shard factor, attention shard factor) under ``tp`` — the
    attention factor falls back to 1 when ``heads`` doesn't divide, the
    same divisibility rule the runtime sharding ruleset applies."""
    if tp is None or tp.n_devices <= 1:
        return 1, 1
    d = tp.n_devices
    return d, (d if heads % d == 0 else 1)


def paged_decode_model(max_len: int, lengths: Iterable[int], n_heads: int,
                       n_kv_heads: int, head_dim: int, page_size: int,
                       in_bytes: int = 2,
                       page_lookup_s: Optional[float] = None,
                       tp: Optional[TPServe] = None,
                       constants: Optional[ServeConstants] = None,
                       tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU) -> dict:
    """Paged vs contiguous decode for one engine tick: same FLOPs, a
    page-table-lookup overhead term per visited K/V block, and an HBM
    *reservation* that drops from ``slots * max_len`` rows to the pages
    the live contexts actually touch (plus the null page).

    This is the trade the paper's paging chapter prices for the hardware:
    finer pages waste less capacity (internal fragmentation shrinks) but
    pay more translation work; the engine's ``page_size`` knob sits on the
    same curve.

    Under ``tp`` the attention work shards over kv heads (when they
    divide the mesh) and both variants pay the per-tick activation
    collectives — paging and tensor parallelism compose, they don't
    interact, so the contig-vs-paged delta is unchanged.

    ``constants`` (a ``ServeConstants``) supplies the lookup cost and —
    when calibrated — the measured HBM stream rate; None is the
    hand-set default set. An explicit ``page_lookup_s`` overrides.
    """
    # Deferred: keeps core free of a module-level serve/kernels dependency
    # (kernels.ops imports this module at its top level).
    from repro.kernels.flash_attention import _largest_divisor
    from repro.serve.paged import reservation

    const = constants if constants is not None else DEFAULT_CONSTANTS
    tpu = const.apply_tpu(tpu)
    if page_lookup_s is None:
        page_lookup_s = const.page_lookup_s

    group = max(1, n_heads // n_kv_heads)
    lengths = [int(l) for l in lengths]
    slots = len(lengths)
    _, attn_shard = _tp_shard(tp, n_kv_heads)
    collective_s = _tp_collective_s(slots, tp, in_bytes, tpu)

    contig_s, paged_s, visited_total = 0.0, 0.0, 0
    for length in lengths:
        p = AttnProblem(sq=group, skv=max(length, 1), n_heads=n_kv_heads,
                        head_dim=head_dim, causal=False, in_bytes=in_bytes)
        c, _ = choose_attn_block(p, tpu, use_cache=False)
        block_k = _largest_divisor(page_size, c.block_k)
        t, terms = attn_cost(p, AttnBlock(c.block_q, block_k), tpu)
        contig_s += t / attn_shard
        visited = terms["visited_blocks"]
        visited_total += visited
        paged_s += (t + visited * page_lookup_s) / attn_shard
    contig_s += collective_s
    paged_s += collective_s

    out = reservation(lengths, max_len, page_size)   # the one accounting
    bytes_per_row = 2 * n_kv_heads * head_dim * in_bytes     # K + V
    out.update({
        "collective_s": collective_s,
        "contig_s": contig_s,
        "paged_s": paged_s,
        "lookup_overhead_frac": (paged_s - contig_s) / contig_s
        if contig_s else 0.0,
        "visited_blocks": visited_total,
        "tokens_per_s_contig": slots / contig_s if contig_s else 0.0,
        "tokens_per_s_paged": slots / paged_s if paged_s else 0.0,
        "hbm_paged_bytes_per_layer": out["rows_resident"] * bytes_per_row,
        "hbm_contig_bytes_per_layer":
            out["rows_reserved_contig"] * bytes_per_row,
    })
    return out


def prefill_chunk_model(prompt_len: int, chunk: int, n_heads: int,
                        n_kv_heads: int, head_dim: int, page_size: int,
                        in_bytes: int = 2,
                        page_lookup_s: Optional[float] = None,
                        cached_rows: int = 0,
                        tp: Optional[TPServe] = None,
                        constants: Optional[ServeConstants] = None,
                        tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU) -> dict:
    """Price chunked paged prefill of one ``prompt_len`` prompt at one
    chunk size: per-chunk causal attention over the previously-written
    pages plus the chunk itself, a page-table-lookup term per visited K/V
    block (the software-TLB walk), and a per-chunk dispatch cost.

    The chunk-size trade this exposes is the paper's TLB-reach argument at
    serving granularity: big chunks amortize dispatch and run the MXU at
    full tiles but stall interleaved decode ticks for the whole chunk
    (``interleave_latency_s`` = the longest single chunk); small chunks
    keep decode latency tight but pay the fixed costs per chunk and pad
    the q tile below the MXU edge.

    ``cached_rows`` prices a prefix-cache hit (``ServeConfig.
    prefix_cache``): prefill starts at the cached cursor — chunks below
    it never run — while every remaining chunk still attends the full
    cached prefix (its K/V pages are resident, mapped by refcount), and
    a per-level hash-probe term charges the index walk. The shared-
    prefix TTFT collapse this models is the headline win: suffix-only
    compute, zero data movement for the hit.

    ``n_kv_heads`` is accepted for signature symmetry with
    ``paged_decode_model`` but does not change the traffic: the prefill
    grid (``flash_attention_paged``) is flattened over *q* heads, so K/V
    blocks re-stream once per q head even under GQA — pricing per q head
    is faithful to the kernel's actual DMA (the decode kernel's
    b*kvh-flattened layout is what lets ``paged_decode_model`` price per
    kv head instead). Under ``tp`` the attention shards over q heads when
    they divide the mesh and every chunk pays the activation collectives
    (a per-chunk fixed cost — one more term small chunks amortize badly).
    """
    const = constants if constants is not None else DEFAULT_CONSTANTS
    tpu = const.apply_tpu(tpu)
    if page_lookup_s is None:
        page_lookup_s = const.page_lookup_s
    dispatch_s = const.chunk_dispatch_s
    _, attn_shard = _tp_shard(tp, n_heads)
    del n_kv_heads
    coll_per_chunk = _tp_collective_s(chunk, tp, in_bytes, tpu)
    # A full-coverage hit still re-prefills the last row (the first
    # token's logit must be sampled) — same clamp the engine applies.
    cached_rows = max(0, min(int(cached_rows), prompt_len - 1))
    probe_s = _ceil_div(cached_rows, page_size) * const.prefix_hash_s
    n_chunks = _ceil_div(prompt_len - cached_rows, chunk)
    attn_s, lookup_s, visited_total, worst_chunk_s = 0.0, 0.0, 0, 0.0
    for i in range(n_chunks):
        # live rows after chunk i (cached prefix included: its pages are
        # resident and every suffix chunk attends them)
        skv = min(cached_rows + (i + 1) * chunk, prompt_len)
        p = AttnProblem(sq=chunk, skv=max(skv, chunk), n_heads=n_heads,
                        head_dim=head_dim, causal=True, in_bytes=in_bytes)
        c, _ = choose_attn_block(p, tpu, use_cache=False)
        from repro.kernels.flash_attention import _largest_divisor
        blk = AttnBlock(min(c.block_q, chunk),
                        _largest_divisor(page_size, c.block_k))
        t, terms = attn_cost(p, blk, tpu)
        t /= attn_shard
        visited = terms["visited_blocks"]
        chunk_s = t + visited * page_lookup_s + dispatch_s \
            + coll_per_chunk
        attn_s += t
        lookup_s += visited * page_lookup_s
        visited_total += visited
        worst_chunk_s = max(worst_chunk_s, chunk_s)
    collective_s = n_chunks * coll_per_chunk
    total_s = attn_s + lookup_s + n_chunks * dispatch_s \
        + collective_s + probe_s
    return {
        "chunk": chunk,
        "n_chunks": n_chunks,
        "cached_rows": cached_rows,
        "probe_s": probe_s,
        "prefill_s": total_s,
        "attn_s": attn_s,
        "lookup_s": lookup_s,
        "dispatch_s": n_chunks * dispatch_s,
        "collective_s": collective_s,
        "visited_blocks": visited_total,
        "interleave_latency_s": worst_chunk_s,
        "lookup_overhead_frac": lookup_s / attn_s if attn_s else 0.0,
    }


def choose_prefill_chunk(max_len: int, n_heads: int, n_kv_heads: int,
                         head_dim: int, page_size: int,
                         latency_weight: float = 4.0,
                         in_bytes: int = 2,
                         constants: Optional[ServeConstants] = None,
                         tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU
                         ) -> Tuple[int, dict]:
    """Pick the chunk size the serving engine prefills with.

    Candidates are page-aligned powers-of-two multiples of ``page_size``
    up to ``max_len``; the score charges the full-prompt prefill time plus
    ``latency_weight`` times the interleave latency (every decode slot
    waits out one chunk between its tokens while a prompt streams — the
    weight is roughly how many stalled slots a chunk delay costs). The
    engine consults this when ``ServeConfig.chunk_size`` is None.
    """
    assert 0 < page_size <= max_len, \
        ("chunked prefill needs at least one page per chunk",
         page_size, max_len)
    cands = []
    c = page_size
    while c <= max_len:
        cands.append(c)
        c *= 2
    if cands[-1] != max_len and max_len % page_size == 0:
        cands.append(max_len)
    best, best_score, best_terms = None, float("inf"), None
    for cand in cands:
        terms = prefill_chunk_model(max_len, cand, n_heads, n_kv_heads,
                                    head_dim, page_size, in_bytes=in_bytes,
                                    constants=constants, tpu=tpu)
        score = terms["prefill_s"] \
            + latency_weight * terms["interleave_latency_s"]
        if score < best_score:
            best, best_score, best_terms = cand, score, terms
    return best, dict(best_terms, score_s=best_score,
                      candidates=len(cands))


def choose_prefix_cache(prompt_len: int, prefix_rows: int, hit_rate: float,
                        n_heads: int, n_kv_heads: int, head_dim: int,
                        page_size: int, chunk: Optional[int] = None,
                        in_bytes: int = 2,
                        constants: Optional[ServeConstants] = None,
                        tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU
                        ) -> Tuple[bool, dict]:
    """On/off policy for ``ServeConfig.prefix_cache``, priced by hit rate.

    Expected per-request prefill cost with the cache on is a mixture:
    ``hit_rate`` of admissions prefill only the suffix past
    ``prefix_rows`` (plus the hash-probe walk and one copy-on-write page
    split amortized per hit — the full-coverage clamp's eager split is
    the worst case, so charging it on every hit is conservative);
    misses pay the full prefill *plus* the probe that found nothing.
    The cache wins when the mixture beats the uncached cost — at
    ``hit_rate`` 0 the probe tax makes "off" the choice, which is the
    policy's real content: everything else is monotone in the hit rate.
    """
    assert 0.0 <= hit_rate <= 1.0, hit_rate
    const = constants if constants is not None else DEFAULT_CONSTANTS
    tpu = const.apply_tpu(tpu)
    prefix_rows = max(0, min(int(prefix_rows), int(prompt_len)))
    if chunk is None:
        chunk, _ = choose_prefill_chunk(prompt_len, n_heads, n_kv_heads,
                                        head_dim, page_size,
                                        in_bytes=in_bytes,
                                        constants=const, tpu=tpu)
    full = prefill_chunk_model(prompt_len, chunk, n_heads, n_kv_heads,
                               head_dim, page_size, in_bytes=in_bytes,
                               constants=const, tpu=tpu)
    hit = prefill_chunk_model(prompt_len, chunk, n_heads, n_kv_heads,
                              head_dim, page_size, in_bytes=in_bytes,
                              cached_rows=prefix_rows, constants=const,
                              tpu=tpu)
    # One COW page split: read + write one page of K and V rows.
    cow_s = 4 * page_size * n_kv_heads * head_dim * in_bytes \
        / tpu.hbm_bandwidth
    probe_s = _ceil_div(prompt_len, page_size) * const.prefix_hash_s
    on_s = hit_rate * (hit["prefill_s"] + cow_s) \
        + (1.0 - hit_rate) * (full["prefill_s"] + probe_s)
    off_s = full["prefill_s"]
    return on_s < off_s, {
        "hit_rate": hit_rate,
        "prefix_rows": prefix_rows,
        "chunk": chunk,
        "prefill_s_off": off_s,
        "prefill_s_on": on_s,
        "prefill_s_hit": hit["prefill_s"],
        "cow_s": cow_s,
        "probe_s": probe_s,
        "speedup": off_s / on_s if on_s else float("inf"),
        "ttft_frac_hit": hit["prefill_s"] / off_s if off_s else 0.0,
    }


def expected_spec_tokens(k: int, accept_rate: float) -> float:
    """E[tokens emitted per verify tick] with per-draft accept probability
    ``accept_rate``: the accepted prefix length plus the always-emitted
    bonus/correction token, sum_{i=0..k} a^i. k=0 gives 1 (plain decode)."""
    return sum(accept_rate ** i for i in range(k + 1))


def spec_decode_model(lengths: Iterable[int], n_heads: int,
                      n_kv_heads: int, head_dim: int, page_size: int,
                      k: int, accept_rate: float, param_bytes: float,
                      draft_bytes: float = 0.0,
                      draft_token_s: Optional[float] = None,
                      in_bytes: int = 2,
                      page_lookup_s: Optional[float] = None,
                      plain_tick_s: Optional[float] = None,
                      tp: Optional[TPServe] = None,
                      constants: Optional[ServeConstants] = None,
                      tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU) -> dict:
    """Price one speculative verify tick against ``k + 1`` plain decode
    ticks — the serving-side instance of the paper's latency-hiding
    pricing: how much parallel work (k drafted tokens scored in one pass)
    amortizes the fixed-cost serial step (per-tick dispatch + streaming
    every weight byte from HBM once, which dominates small-batch decode).

    Per-tick terms, batch-wide:

    * ``weight_stream_s`` — ``param_bytes / hbm_bw``, paid once per tick
      no matter the verify width: the cost speculation amortizes.
    * paged attention per slot at query width ``group * (k+1)`` over the
      slot's live context (+ the drafted rows), with the page-walk term
      per visited block — the part that *grows* with width.
    * dense FLOPs for ``slots * (k+1)`` tokens — wasted on rejected rows.
    * draft cost: ``slots * k`` draft-model weight streams per tick
      (``draft_bytes``; 0 for the n-gram drafter) — the engine's
      ``ModelDraft`` rolls out per slot, serially; a batched draft would
      amortize to ``k`` streams (divide ``draft_bytes`` by the batch) —
      plus ``slots * k`` host lookups (``draft_token_s``).

    Emitted tokens per tick follow ``expected_spec_tokens(k,
    accept_rate)``; the headline is ``speedup`` = spec tokens/s over plain
    tokens/s. ``verify_overhead_frac`` is the widened tick's extra cost —
    the overhead an accept rate must beat.
    """
    from repro.kernels.flash_attention import _largest_divisor

    const = constants if constants is not None else DEFAULT_CONSTANTS
    tpu = const.apply_tpu(tpu)
    if page_lookup_s is None:
        page_lookup_s = const.page_lookup_s
    if draft_token_s is None:
        draft_token_s = const.draft_token_s
    group = max(1, n_heads // n_kv_heads)
    lengths = [int(l) for l in lengths]
    slots = len(lengths)
    dense_shard, attn_shard = _tp_shard(tp, n_kv_heads)
    # TP shards the weight stream too — each device streams its slice of
    # every matrix; the price is the per-tick activation collectives.
    weight_stream_s = param_bytes / tpu.hbm_bandwidth / dense_shard
    n_params = param_bytes / in_bytes

    def tick_s(width: int) -> float:
        attn = 0.0
        for length in lengths:
            p = AttnProblem(sq=group * width,
                            skv=max(length + width - 1, 1),
                            n_heads=n_kv_heads, head_dim=head_dim,
                            causal=False, in_bytes=in_bytes)
            c, _ = choose_attn_block(p, tpu, use_cache=False)
            blk = AttnBlock(c.block_q, _largest_divisor(page_size,
                                                        c.block_k))
            t, terms = attn_cost(p, blk, tpu)
            attn += (t + terms["visited_blocks"] * page_lookup_s) \
                / attn_shard
        dense = 2.0 * n_params * slots * width \
            / (dense_shard * tpu.peak_bf16_flops)
        return weight_stream_s + attn + dense + const.chunk_dispatch_s \
            + _tp_collective_s(slots * width, tp, in_bytes, tpu)

    # The width-1 tick is k-independent; choose_spec_k precomputes it
    # once and threads it through its candidate loop.
    plain_tick = plain_tick_s if plain_tick_s is not None else tick_s(1)
    spec_tick = tick_s(k + 1) if k else plain_tick
    draft_s = slots * k * (draft_bytes / tpu.hbm_bandwidth
                           + draft_token_s)
    spec_tick += draft_s
    e_tokens = expected_spec_tokens(k, accept_rate)
    tok_plain = slots / plain_tick
    tok_spec = slots * e_tokens / spec_tick
    return {
        "k": k,
        "accept_rate": accept_rate,
        "expected_tokens_per_tick": e_tokens,
        "weight_stream_s": weight_stream_s,
        "plain_tick_s": plain_tick,
        "spec_tick_s": spec_tick,
        "draft_s": draft_s,
        "verify_overhead_frac": spec_tick / plain_tick - 1.0,
        "tokens_per_s_plain": tok_plain,
        "tokens_per_s_spec": tok_spec,
        "speedup": tok_spec / tok_plain,
    }


def choose_spec_k(lengths: Iterable[int], n_heads: int,
                  n_kv_heads: int, head_dim: int, page_size: int,
                  accept_rate: float, param_bytes: float,
                  draft_bytes: float = 0.0,
                  draft_token_s: Optional[float] = None,
                  ks: Tuple[int, ...] = (1, 2, 3, 4, 6, 8),
                  in_bytes: int = 2,
                  tp: Optional[TPServe] = None,
                  constants: Optional[ServeConstants] = None,
                  tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU
                  ) -> Tuple[int, dict]:
    """Pick the verify width the serving engine speculates with.

    Maximizes modeled tokens/sec over candidate ``k``; returns ``k = 0``
    (speculation disabled — run plain decode ticks) when no candidate
    beats the plain engine, which happens exactly when the accept rate is
    too low to pay the verify-width + draft overhead (e.g. a model draft
    whose serial weight streams cost more than the tokens they land).
    The returned terms are the best candidate's either way, so the caller
    can see how close the call was.
    """
    lengths = list(lengths)
    best_k, best_terms, plain_tick_s = 0, None, None
    for k in ks:
        terms = spec_decode_model(lengths, n_heads, n_kv_heads,
                                  head_dim, page_size, k, accept_rate,
                                  param_bytes, draft_bytes=draft_bytes,
                                  draft_token_s=draft_token_s,
                                  in_bytes=in_bytes,
                                  plain_tick_s=plain_tick_s, tp=tp,
                                  constants=constants, tpu=tpu)
        plain_tick_s = terms["plain_tick_s"]
        if best_terms is None or \
                terms["tokens_per_s_spec"] > best_terms["tokens_per_s_spec"]:
            best_k, best_terms = k, terms
    if best_terms["speedup"] <= 1.0:
        best_k = 0
    return best_k, dict(best_terms, chosen_k=best_k,
                        candidates=len(list(ks)))


# -- serving overload pressure -------------------------------------------------

DEGRADE_HIGH = 0.85   # default enter-degraded threshold (ServeConfig)
DEGRADE_LOW = 0.60    # default leave-degraded threshold (hysteresis)


def serve_pressure(pool_occupancy: float, queue_depth: int,
                   batch: int) -> float:
    """Scalar load-pressure signal in [0, 1] for the serving engine's
    degradation ladder.

    Two independent saturation signals, take the worse: the KV page
    pool's occupancy fraction (pages in use / capacity — HBM pressure:
    near 1.0 the next decode page comes from a preemption), and the
    queue depth normalized by the decode batch (admission pressure: a
    queue deeper than the batch means arrivals outrun service even if
    every slot turned over each tick). ``max`` rather than a weighted
    sum — either resource saturating alone is an overload, and a bounded
    signal composes with fixed thresholds."""
    q = min(1.0, float(queue_depth) / max(1.0, float(batch)))
    return max(min(1.0, float(pool_occupancy)), q)


def choose_degradation(pressure: float, degraded: bool,
                       high: float = DEGRADE_HIGH,
                       low: float = DEGRADE_LOW) -> bool:
    """Hysteresis band for the load-shedding latch: enter degraded mode
    at/above ``high``, leave at/below ``low``. The dead band between
    them is what prevents flapping — a downshift frees resources (spec
    width, prefill budget), which *reduces* pressure; a single threshold
    would re-upshift immediately and oscillate every tick."""
    assert 0.0 <= low <= high <= 1.0, (low, high)
    if degraded:
        return pressure > low
    return pressure >= high


def tp_decode_model(lengths: Iterable[int], n_heads: int,
                    n_kv_heads: int, head_dim: int, page_size: int,
                    param_bytes: float, d_model: int, n_layers: int,
                    n_devices: int, in_bytes: int = 2,
                    page_lookup_s: Optional[float] = None,
                    constants: Optional[ServeConstants] = None,
                    tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU) -> dict:
    """Price one paged decode tick single-device vs tensor-parallel over
    ``n_devices`` — the serving-side instance of the paper's NVLink-era
    scaling question: decode is weight-stream bound, so sharding every
    matrix cuts the dominant HBM term by the mesh degree, and what's left
    to beat is the per-layer activation all-reduces plus the unembed
    ring's gather (``collective_s``), tiny at decode widths because the
    payload is activations (slots x d_model) rather than weights.

    The other headline is capacity, not speed: the KV page pool is
    device-sharded with pages as the shard unit, so the same per-device
    HBM budget holds ``n_devices`` times the pages globally
    (``pool_capacity_ratio``) — a slot's context can span devices.
    """
    lengths = [int(l) for l in lengths]
    slots = len(lengths)
    tp = TPServe(n_devices=n_devices, d_model=d_model, n_layers=n_layers)
    common = dict(n_heads=n_heads, n_kv_heads=n_kv_heads,
                  head_dim=head_dim, page_size=page_size,
                  k=0, accept_rate=0.0, param_bytes=param_bytes,
                  in_bytes=in_bytes, page_lookup_s=page_lookup_s,
                  constants=constants, tpu=tpu)
    base = spec_decode_model(lengths, **common)
    shard = spec_decode_model(lengths, tp=tp, **common)
    tick_1, tick_tp = base["plain_tick_s"], shard["plain_tick_s"]
    collective_s = _tp_collective_s(slots, tp, in_bytes, tpu)
    return {
        "n_devices": n_devices,
        "slots": slots,
        "tick_1dev_s": tick_1,
        "tick_tp_s": tick_tp,
        "weight_stream_1dev_s": base["weight_stream_s"],
        "weight_stream_tp_s": shard["weight_stream_s"],
        "collective_s": collective_s,
        "collective_frac": collective_s / tick_tp if tick_tp else 0.0,
        "attn_sharded": n_kv_heads % max(1, n_devices) == 0,
        "tokens_per_s_1dev": slots / tick_1 if tick_1 else 0.0,
        "tokens_per_s_tp": slots / tick_tp if tick_tp else 0.0,
        "speedup": tick_1 / tick_tp if tick_tp else float("inf"),
        "pool_capacity_ratio": float(n_devices),
    }


# ----------------------------------------------------------------------------
# Sharding selection for one weight-stationary matmul layer.
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardingChoice:
    name: str                   # "dp", "tp_col", "tp_row", "dp+tp"
    time_s: float
    compute_s: float
    collective_s: float


def choose_layer_sharding(batch_tokens: int, d_in: int, d_out: int,
                          data_axis: int, model_axis: int,
                          in_bytes: int = 2,
                          tpu: hwmodel.TPUSpec = hwmodel.DEFAULT_TPU
                          ) -> List[ShardingChoice]:
    """Rank standard layouts for out = x @ W by modeled step time.

    dp: batch sharded, W replicated (grad all-reduce amortized elsewhere).
    tp_col: W column-sharded -> output sharded, no comm until next layer.
    tp_row: W row-sharded -> partial sums all-reduced.
    """
    from repro.core import interconnect

    chips = data_axis * model_axis
    flops = 2.0 * batch_tokens * d_in * d_out
    out: List[ShardingChoice] = []

    def add(name, shard_factor, coll_kind, coll_payload, axis):
        comp = flops / (chips * tpu.peak_bf16_flops) \
            if shard_factor == chips else flops / (shard_factor * tpu.peak_bf16_flops)
        coll = interconnect.collective_time(coll_kind, coll_payload, axis,
                                            tpu).time_s if coll_payload else 0.0
        out.append(ShardingChoice(name, comp + coll, comp, coll))

    tokens_local = batch_tokens / data_axis
    # dp only: compute split over data axis, none over model.
    add("dp", data_axis, None, 0, 1)
    # tp_col: activations all-gathered next layer; charge the gather here.
    add("tp_col", chips, "all_gather",
        tokens_local * d_out * in_bytes, model_axis)
    # tp_row: partial-sum all-reduce of the output activations.
    add("tp_row", chips, "all_reduce",
        tokens_local * d_out * in_bytes, model_axis)
    out.sort(key=lambda s: s.time_s)
    return out

"""Serving: prefill + decode steps and a batched continuous-batching engine.

``make_serve_step`` builds the jitted one-token decode step the dry-run
lowers for the ``decode_32k`` / ``long_500k`` cells: one new token against a
KV/SSM cache of the cell's sequence length, caches donated in-place.

``ServingEngine`` is the decode fast path around it (see README.md here):

  * **Bucketed, jitted prefill** — prompts pad right to power-of-two
    buckets, so each bucket traces and compiles exactly once instead of
    once per distinct prompt length. The padded K/V rows are never
    attended (per-slot write positions are reset to the true length) and
    are overwritten as decode advances.
  * **Fused slot install** — the row caches produced by prefill scatter
    into the engine's batch caches inside the same jitted executable
    (one ``dynamic_update_slice`` per leaf, caches donated), not as a
    per-leaf host loop.
  * **Donated decode** — ``tick`` threads the engine caches through the
    decode step with buffer donation, so the cache never exists twice.
  * **Per-slot lengths** — caches carry one write position per slot;
    with ``use_flash`` the flash-decode kernel scalar-prefetches them and
    streams only each slot's live K/V blocks (O(context), not O(max_len)).
  * **Paged KV** (``ServeConfig.paged``) — slots stop reserving ``max_len``
    rows each: K/V rows live in a shared page pool (``serve.paged``) and
    each slot owns a page table. Admission allocates the first prompt
    chunk's pages (rejecting cleanly when the pool is short — the request
    stays queued), decode allocates lazily one page at a time as contexts
    grow, and freeing a slot returns its pages for immediate reuse.
  * **Chunked paged prefill** — prompts are written *in place* through the
    page table in fixed-size chunks (``ServeConfig.chunk_size``, default
    from the autotune chunk cost model): one jitted chunk executable total
    — not one per bucket — runs one chunk per mid-prefill slot per tick,
    so decode ticks keep making progress while a long prompt streams in.
    There is no contiguous row cache and no install scatter: the chunk's
    K/V rows land in their pages as they are computed, VMEM stays bounded
    at one chunk, and pages are pre-allocated per chunk right before the
    chunk that writes them.
  * **Preemption** — pool exhaustion mid-decode (or mid-prefill) preempts
    the youngest slot instead of raising: its pages return to the pool and
    its request re-queues at the head with generated tokens preserved
    (re-prefilled as prompt context on re-admission). Counted in
    ``engine.preemptions``; only a pool with nothing left to preempt still
    raises ``PagePoolExhausted``.
  * **Speculative decoding** (``ServeConfig.spec_k``, paged only) — each
    tick drafts ``k`` tokens per decode-active slot (``serve.spec`` draft
    sources: n-gram prompt lookup or a small draft model) and scores them
    together with the pending token in ONE batched verify executable over
    the paged ``s > 1`` attention path (``layers._paged_apply``,
    write-then-attend). The longest accepted prefix plus the corrected
    bonus token is emitted (>= 1 token per slot per tick; zero accepts
    degrade to plain decode), write positions roll back over rejected
    rows, and the emitted stream is exactly the plain engine's.
  * **Per-position sampling keys** — every emitted token is sampled under
    a key derived from (request id, emitted index), never from the tick
    count: preempted streams replay bit-identically on re-admission and
    the speculative verify consumes exactly the keys sequential decode
    would, so spec == plain holds at temperature > 0 too.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import sharding as sharding_mod
from repro.models import transformer as T
from repro.serve import paged as paged_mod
from repro.serve import spec as spec_mod
from repro.serve import telemetry as telemetry_mod


@dataclasses.dataclass(frozen=True)
class SLOClass:
    """One multi-tenant request class and its service-level objectives.

    ``priority`` orders both admission (higher classes admit first) and
    preemption (lower classes are evicted first). The TTFT/TPOT targets
    are accounting, not scheduling inputs — ``serve.traffic.summarize``
    reports attainment against them. ``rate``/``burst`` parameterize the
    class's admission token bucket (tokens per engine tick / bucket cap):
    a class can never occupy more sustained token throughput than its
    refill rate, so one tenant's burst cannot starve the others. A class
    with ``rate=None`` admits unmetered (subject only to pool headroom).
    """

    name: str
    priority: int = 0            # higher = more important
    ttft_slo: Optional[int] = None     # target ticks to first token
    tpot_slo: Optional[float] = None   # target ticks per output token
    rate: Optional[float] = None       # admission bucket refill, tokens/tick
    burst: Optional[float] = None      # bucket cap; None -> 8 * rate

    @property
    def bucket_cap(self) -> float:
        if self.burst is not None:
            return float(self.burst)
        return 8.0 * float(self.rate or 0.0)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int
    batch: int
    temperature: float = 0.0     # 0 -> greedy
    eos_id: int = 1
    seed: int = 0                # sampling PRNG (temperature > 0)
    min_bucket: int = 8          # smallest prefill bucket (power of two)
    paged: bool = False          # KV rows from a shared page pool
    page_size: int = 16          # KV rows per page (paged=True)
    n_pages: Optional[int] = None  # pool size incl. null page; None ->
    # the contiguous equivalent (batch * max_len / page_size + 1), i.e.
    # no savings but no exhaustion risk; size it down to reclaim HBM.
    chunk_size: Optional[int] = None  # prefill chunk rows (paged=True);
    # must be a page_size multiple; None -> the autotune chunk cost
    # model's choice (``core.autotune.choose_prefill_chunk``).
    spec_k: int = 0              # drafted tokens per verify tick (paged
    # only); 0 disables speculation — ``core.autotune.choose_spec_k``
    # prices when that is the right call.
    draft: Any = None            # spec_k > 0: a serve.spec DraftSource,
    # or "ngram" (default) / "self" / a configs/ arch name.
    spec_adapt_every: Optional[int] = None  # re-choose the live draft
    # width from the measured accept rate every N verify ticks
    # (``serve.spec.rechoose_k`` -> ``core.autotune.choose_spec_k``);
    # None keeps k fixed at spec_k. The verify executable's width stays
    # spec_k + 1 (one trace); only how many drafts are requested adapts,
    # and a collapsed accept rate drives ``k_live`` to 0 — plain decode
    # ticks — until the next window re-opens speculation.
    prefill_chunks_per_tick: Optional[int] = None  # per-tick prefill
    # chunk budget; None runs one chunk for *every* mid-prefill slot.
    # With a budget, the shortest-remaining-first order decides who runs.
    prefix_cache: bool = False   # paged only: share full-page-aligned
    # prompt prefixes across requests through the page table (refcounted
    # pages + hash-keyed ``paged.PrefixIndex``). Admission maps cached
    # pages into the new slot (zero data movement) and chunk-prefills
    # only the uncached suffix; copy-on-write splits any shared page
    # before a write could land in it; unreferenced cached prefixes are
    # reclaimed LRU before preemption fires. Token streams stay
    # bit-identical to an uncached engine on every path.
    # ``core.autotune.choose_prefix_cache`` prices when to enable it.
    # -- overload robustness (all default-off: legacy behavior unchanged) --
    classes: Optional[Tuple[SLOClass, ...]] = None  # multi-tenant request
    # classes: admission runs highest-priority-first with per-class
    # token-bucket metering; requests name their class via
    # ``Request.rclass`` (unknown names fall back to priority 0,
    # unmetered).
    max_queue: Optional[int] = None  # bounded queue: beyond this depth
    # the lowest-priority newest queued request is *shed* (cleanly
    # rejected, counted in ``engine.shed_by_class``/``rejected``) instead
    # of queueing unboundedly.
    max_preemptions: Optional[int] = None  # per-request preemption cap:
    # a request evicted this many times is next force-completed (partial
    # stream kept) or cleanly rejected instead of re-queued — bounds
    # preemption livelock. Also switches lone-slot pool exhaustion from
    # raising PagePoolExhausted to self-preemption (graceful ladder).
    preempt_cooldown: int = 2    # storm guard: a re-admitted slot is not
    # chosen as a preemption victim again for this many ticks while any
    # other victim exists (prevents admit/evict livelock under churn).
    degrade: bool = False        # automatic load-shedding downshifts:
    # under pressure (pool occupancy / queue depth, hysteresis via
    # ``core.autotune.choose_degradation``) the engine disables
    # speculation and tightens the prefill chunk budget for the tick,
    # recovering when pressure clears. Emitted tokens are unchanged —
    # every downshifted mode is bit-identical on the tokens it emits.
    pressure_high: float = 0.85  # enter degraded mode at/above this
    pressure_low: float = 0.60   # leave degraded mode at/below this
    spec_probe_every: Optional[int] = None  # adaptive spec-k probing:
    # while ``k_live == 0`` (the disable regime), run a k=1 trial verify
    # tick every N plain ticks; trial accept stats feed the normal
    # adaptation window, so speculation *recovers* when a collapsed
    # accept rate clears (requires spec_adapt_every). None keeps the
    # disable regime terminal (legacy).
    # -- observability (``serve.telemetry``) -------------------------------
    telemetry: bool = True       # event ring + spans (profiler
    # annotations and wall-clock aggregates). Disabling drops the ring
    # buffers, the annotations and every perf_counter read; the decision
    # *aggregates* (admission_rejections, shed_by_class, ...) stay exact
    # either way, and token streams are bit-identical traced or not.
    trace_capacity: int = 4096   # ring-buffer entries per stream (events,
    # tick times); eviction never touches the aggregates.


def prefill(params, cfg: T.ModelConfig, tokens, caches,
            frontend_embeds=None):
    """Run the prompt through the model, filling the caches."""
    logits, caches, _ = T.forward(params, cfg, tokens, caches=caches,
                                  frontend_embeds=frontend_embeds)
    return logits[:, -1], caches


def decode_step(params, cfg: T.ModelConfig, last_tokens, caches,
                frontend_embeds=None, unembed_fn=None):
    """One decode step: (b,) token ids -> (b,) next ids + new caches."""
    logits, caches, _ = T.forward(params, cfg, last_tokens[:, None],
                                  caches=caches,
                                  frontend_embeds=frontend_embeds,
                                  unembed_fn=unembed_fn)
    return logits[:, -1], caches


def sampler(temperature: float) -> Callable:
    """logits (..., vocab) -> token ids; greedy at temperature 0."""
    if temperature == 0.0:
        return lambda logits, key: jnp.argmax(logits, -1).astype(jnp.int32)

    def sample(logits, key):
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / temperature, axis=-1
        ).astype(jnp.int32)

    return sample


def make_serve_step(cfg: T.ModelConfig, donate: bool = True,
                    temperature: float = 0.0) -> Callable:
    """Jitted decode step (the dry-run's serve_step), caches donated."""
    pick = sampler(temperature)

    def step(params, last_tokens, caches, frontend_embeds=None, key=None):
        logits, caches = decode_step(params, cfg, last_tokens, caches,
                                     frontend_embeds=frontend_embeds)
        return pick(logits, key), caches

    return jax.jit(step, donate_argnums=(2,) if donate else ())


def greedy_generate(params, cfg: T.ModelConfig, prompt, max_new: int,
                    max_len: Optional[int] = None, frontend_embeds=None):
    """Reference generation loop (tests compare engine output to this).

    The decode step donates its caches: each iteration rebinds ``caches``
    to the step's output, so the donated buffer is never read again.
    """
    b, s = prompt.shape
    max_len = max_len or (s + max_new)
    caches = T.init_caches(cfg, b, max_len)
    logits, caches = prefill(params, cfg, prompt, caches,
                             frontend_embeds=frontend_embeds)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [tok]
    step = make_serve_step(cfg, donate=True)
    for _ in range(max_new - 1):
        tok, caches = step(params, tok, caches,
                           frontend_embeds=frontend_embeds)
        out.append(tok)
    return jnp.stack(out, axis=1)


def _counter_view(key: str, doc: str) -> property:
    """A legacy engine counter as a view over ``telemetry.counters``.

    Readable and writable (benches zero counters at the warm-up
    boundary), but the stored value lives in the telemetry aggregates —
    the event trace and the counter can never disagree."""
    def get(self):
        return self.telemetry.counters.get(key, 0)

    def set_(self, v):
        self.telemetry.counters[key] = int(v)

    return property(get, set_, doc=doc)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new: int
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    rclass: str = "default"      # SLO class name (ServeConfig.classes)
    preempt_count: int = 0       # times evicted back to the queue
    readmitted_at: Optional[int] = None  # tick of last re-admission
    # (preemption-storm guard input; None until first preemption)
    submit_s: Optional[float] = None     # host clock at first submit
    # (``time.perf_counter``): the first admission's span carries the
    # queue wait counted from it


class ServingEngine:
    """Slot-based continuous batching over a fixed decode batch.

    Requests join free slots as they arrive; each engine tick decodes one
    token for every active slot. Finished slots free immediately and their
    ``last_tok`` entry resets to 0 so a stale token can never collide with
    ``eos_id`` on a later tick.
    """

    def __init__(self, params, cfg: T.ModelConfig, serve_cfg: ServeConfig,
                 mesh=None):
        from repro.kernels import ops as kernel_ops
        if kernel_ops.native() and mesh is None:
            # On the chip every executable attends through the Pallas
            # kernels; the gather + sdpa branch stays the reference that
            # tests and chip_smoke.py compare against. The mesh path
            # keeps that branch: XLA cannot partition a Mosaic kernel
            # over the mesh by itself (page-local attention per device
            # is ROADMAP A6).
            cfg = dataclasses.replace(cfg, use_flash=True)
        self.cfg = cfg
        self.scfg = serve_cfg
        self.mesh = mesh
        # The cost-constant set pricing every choose_* decision this
        # engine makes: calibrated (core.calibrate probes for this
        # backend+mesh, read from the tuning cache) when available,
        # the documented defaults otherwise. REPRO_DEFAULT_CONSTANTS=1
        # forces the defaults — the reproducibility escape hatch.
        from repro.core import autotune as _autotune
        self.constants = _autotune.resolve_constants(mesh_shape=mesh)
        # Distributed serving (``serve.dist``): weights tensor-parallel
        # under the serving ruleset, the page pool device-sharded over the
        # pool axis, the unembed GEMM routed through the overlapped
        # collective ring. All host-side scheduling below is mesh-blind —
        # it prices admission/preemption against the *global* pool, so the
        # sharded engine's token streams and scheduling decisions are
        # bit-identical to the single-device paged engine's.
        if mesh is not None:
            from repro.dist import collective_matmul
            from repro.serve import dist as serve_dist
            assert serve_cfg.paged, "mesh serving is paged-only"
            self._ruleset = serve_dist.serve_ruleset(mesh)
            axis = self._ruleset._rule(serve_dist.POOL_RULE)
            self._pool_axis = axis
            self._n_dev = int(dict(mesh.shape).get(axis, 1))
            self._unembed_fn = collective_matmul.serve_unembed(mesh, axis)
            self.params = self._shard_params(params, mesh)
        else:
            self._ruleset = None
            self._pool_axis = None
            self._n_dev = 1
            self._unembed_fn = None
            self.params = params
        # Bucketing pads the prompt on the right; that only composes with
        # attention layers (masked K/V). SSM/hybrid stacks carry recurrent
        # state through every position, so they prefill at exact length
        # (still jitted + fused — just one executable per distinct length).
        self._bucketed = all(k in ("attn", "cross") for k in cfg.pattern) \
            and cfg.encoder is None and not cfg.n_frontend_tokens
        if serve_cfg.paged:
            assert self._bucketed, \
                "paged KV serving requires an attention-only stack"
            assert serve_cfg.max_len % serve_cfg.page_size == 0, \
                (serve_cfg.max_len, serve_cfg.page_size)
            n_pages = serve_cfg.n_pages or (
                1 + serve_cfg.batch * serve_cfg.max_len
                // serve_cfg.page_size)
            if n_pages % self._n_dev:
                # Striping needs equal blocks; rounding up only ever adds
                # capacity. Explicit n_pages on a mesh should already
                # divide it (parity runs pass the same pool both ways).
                n_pages += self._n_dev - n_pages % self._n_dev
            self.pool: Optional[paged_mod.PageAllocator] = \
                paged_mod.PageAllocator(n_pages, serve_cfg.page_size,
                                        n_devices=self._n_dev)
            self.caches = T.init_paged_caches(
                cfg, serve_cfg.batch, serve_cfg.max_len,
                serve_cfg.page_size, n_pages, mesh=mesh,
                pool_axis=self._pool_axis or "model")
            chunk = serve_cfg.chunk_size
            if chunk is None:
                from repro.core import autotune
                chunk, _ = autotune.choose_prefill_chunk(
                    serve_cfg.max_len, cfg.n_heads, cfg.n_kv_heads,
                    cfg.dhead, serve_cfg.page_size,
                    constants=self.constants)
            assert chunk % serve_cfg.page_size == 0 \
                and 0 < chunk <= serve_cfg.max_len, \
                (chunk, serve_cfg.page_size, serve_cfg.max_len)
            self.chunk: Optional[int] = chunk
            self._chunk_fn = self._make_chunk_fn()
            # Prefix cache: hash-keyed index over the pool's pages.
            # Host-side only (refcounts + digests) — the device caches
            # and kernels are untouched; sharing is purely which page
            # ids appear in which slots' tables.
            self.prefix: Optional[paged_mod.PrefixIndex] = \
                paged_mod.PrefixIndex(self.pool) \
                if serve_cfg.prefix_cache else None
        else:
            assert not serve_cfg.prefix_cache, \
                "prefix_cache requires paged=True (it shares pages)"
            self.prefix = None
            self.pool = None
            self.chunk = None
            self.caches = T.init_caches(cfg, serve_cfg.batch,
                                        serve_cfg.max_len,
                                        per_slot_index=True)
        self.slots: List[Optional[Request]] = [None] * serve_cfg.batch
        self.queue: List[Request] = []
        self.last_tok = jnp.zeros((serve_cfg.batch,), jnp.int32)
        self.finished: Dict[int, List[int]] = {}
        self._base_key = jax.random.PRNGKey(serve_cfg.seed)
        self._rid_keys: Dict[int, Any] = {}
        self._zero_key = jnp.zeros((2,), jnp.uint32)
        self._zero_ids = jnp.zeros((serve_cfg.batch,), jnp.int32)
        self._prefill_fns: Dict[int, Callable] = {}
        self.prefill_traces: Dict[int, int] = {}
        self.decode_traces = 0
        self.verify_traces = 0            # spec verify executables traced
        # Observability (``serve.telemetry``): the event trace IS the
        # bookkeeping — the legacy counters below the class body
        # (admission_rejections, preemptions, spec stats, shed_by_class,
        # preemption_log, ...) are properties reading the telemetry
        # aggregates, so decision accounting has exactly one home.
        self.telemetry = telemetry_mod.Telemetry(
            enabled=serve_cfg.telemetry, capacity=serve_cfg.trace_capacity)
        self.ticks = 0
        self.first_token_tick: Dict[int, int] = {}   # rid -> TTFT (ticks)
        self._prefilling: Dict[int, int] = {}   # slot -> prompt rows written
        self._prefill_wait: Dict[int, int] = {} # slot -> ticks since served
        self._slot_seq: Dict[int, int] = {}     # slot -> admission sequence
        # Prefix-cache publish cursor per slot: (digest of the deepest
        # published/matched prefix, pages published so far). Seeded at
        # admission from the probe; advanced as prefill completes pages.
        self._chain: Dict[int, Tuple[bytes, int]] = {}
        self._admit_seq = 0
        # -- overload-robustness accounting -----------------------------------
        self.submit_tick: Dict[int, int] = {}   # rid -> tick of submit()
        self.finish_tick: Dict[int, int] = {}   # rid -> tick of last token
        self.rejected: Dict[int, str] = {}      # rid -> shed/reject reason
        self.outcome: Dict[int, str] = {}       # rid -> done|forced:*|rejected:*
        self._arrival_seq: Dict[int, int] = {}  # rid -> submit order
        self._n_arrivals = 0
        self._classes: Dict[str, SLOClass] = {
            c.name: c for c in (serve_cfg.classes or ())}
        assert len(self._classes) == len(serve_cfg.classes or ()), \
            "duplicate SLO class names"
        for c in self._classes.values():
            assert c.rate is None or c.rate > 0, (c.name, c.rate)
        self._buckets: Dict[str, float] = {
            c.name: c.bucket_cap for c in self._classes.values()
            if c.rate is not None}
        if serve_cfg.max_queue is not None:
            assert serve_cfg.max_queue >= 1, serve_cfg.max_queue
        if serve_cfg.max_preemptions is not None:
            assert serve_cfg.max_preemptions >= 0, serve_cfg.max_preemptions
        assert serve_cfg.preempt_cooldown >= 0
        self.degraded = False           # load-shedding downshift latch
        self.last_pressure = 0.0
        self._probe_wait = 0
        self.spec_k = serve_cfg.spec_k
        self.k_live = self.spec_k     # adaptive draft width (<= spec_k)
        self._adapt_ticks = 0         # verify ticks since last re-choice
        self._adapt_proposed = 0      # drafted tokens in the window
        self._adapt_accepted = 0      # ... of which accepted
        if self.spec_k:
            assert self.spec_k >= 1
            assert self.pool is not None, \
                "speculative decoding needs paged=True (verify runs the " \
                "paged s>1 attention path)"
            self.draft = spec_mod.resolve_draft(serve_cfg.draft, cfg, params)
            self._verify_fn = self._make_verify_fn()
        if serve_cfg.spec_adapt_every is not None:
            assert serve_cfg.spec_adapt_every >= 1 and self.spec_k
        if serve_cfg.spec_probe_every is not None:
            # Probing needs the adaptation clock: trial-tick accept stats
            # recover k_live through the same rechoose_k window.
            assert serve_cfg.spec_probe_every >= 1 and self.spec_k \
                and serve_cfg.spec_adapt_every is not None
        if serve_cfg.prefill_chunks_per_tick is not None:
            assert serve_cfg.prefill_chunks_per_tick >= 1, \
                serve_cfg.prefill_chunks_per_tick
        self._step = self._make_decode_step()

    # -- telemetry-backed counter views ---------------------------------------
    # One bookkeeping home: these are the same attributes callers always
    # read (and benches reset), backed by the event-trace aggregates.

    admission_rejections = _counter_view(
        "admit_hold", "pool-exhausted admission holds")
    preemptions = _counter_view(
        "preempt", "slots evicted back to the queue")
    spec_ticks = _counter_view(
        "spec_verify", "(slot, tick) verify events")
    spec_accepted = _counter_view(
        "spec_accepted", "drafted tokens accepted")
    spec_emitted = _counter_view(
        "spec_emitted", "tokens emitted by verify ticks")
    spec_probes = _counter_view(
        "probe_tick", "k=1 trial ticks while speculation is disabled")
    downshifts = _counter_view(
        "degrade_enter", "clean->degraded ladder transitions")
    degraded_ticks = _counter_view(
        "degraded_tick", "ticks spent in degraded mode")
    prefix_hits = _counter_view(
        "prefix_hit", "admissions that mapped cached prefix pages")
    prefix_misses = _counter_view(
        "prefix_miss", "admissions that probed the index and found none")
    prefix_hit_pages = _counter_view(
        "prefix_hit_pages", "cached pages mapped by admissions (sum)")
    cow_copies = _counter_view(
        "cow_copy", "copy-on-write splits of shared pages")
    prefix_evictions = _counter_view(
        "prefix_evict", "LRU reclaims of cached-idle prefix runs")

    @property
    def shed_by_class(self) -> Dict[str, int]:
        """Clean rejects per class (view over ``shed`` events)."""
        return self.telemetry.shed_by_class

    @property
    def preemption_log(self) -> List[Tuple[int, str, int]]:
        """(rid, class, tokens generated at eviction) per ``preempt``
        event — fairness accounting."""
        return self.telemetry.preemption_log

    # -- distributed placement ------------------------------------------------

    def _shard_params(self, params, mesh):
        """Tensor-parallel placement (``serve.dist.param_shardings``).
        device_put up front — the executables then see committed
        shardings and emit no surprise resharding on the hot path. Leaves
        made already sharded (``launch.serve``) stay where they are."""
        from repro.serve import dist as serve_dist
        return jax.device_put(
            params, serve_dist.param_shardings(params, mesh, self._ruleset))

    # -- jitted executables ---------------------------------------------------

    def _make_decode_step(self) -> Callable:
        temp = self.scfg.temperature
        pick = spec_mod.per_row_sampler(temp)
        cfg, base = self.cfg, self._base_key

        def step(params, last_tokens, caches, rids, ts):
            self.decode_traces += 1          # runs at trace time only
            with sharding_mod.use_ruleset(self._ruleset):
                logits, caches = decode_step(
                    params, cfg, last_tokens, caches,
                    unembed_fn=self._unembed_fn)
            # Keys fold inside the executable (no per-tick host fold_ins);
            # greedy never consumes them, so skip the fold entirely.
            keys = spec_mod.fold_row_keys(base, rids, ts) if temp else None
            return pick(logits, keys), caches

        return jax.jit(step, donate_argnums=(2,))

    def _make_verify_fn(self) -> Callable:
        """The ONE jitted draft-verify executable. Width is fixed at
        ``spec_k + 1`` (the pending token + k drafts), so it traces
        exactly once — ``verify_traces`` gates it like the prefill
        executables. One batched forward scores every slot's candidate
        row through the paged s>1 attention path (write-then-attend in
        ``layers._paged_apply``: the candidates' K/V rows scatter through
        the page table, each query attends the slot's live prefix plus
        its own candidate prefix) and picks a target token per position —
        position j's key belongs to emitted index ``len(generated) + j``,
        so sampling matches sequential decode token for token."""
        temp = self.scfg.temperature
        pick = spec_mod.per_row_sampler(temp)
        cfg, base, width = self.cfg, self._base_key, self.spec_k + 1

        def verify(params, tokens, caches, rids, t0s):
            self.verify_traces += 1          # runs at trace time only
            with sharding_mod.use_ruleset(self._ruleset):
                logits, caches, _ = T.forward(params, cfg, tokens,
                                              caches=caches,
                                              unembed_fn=self._unembed_fn)
            keys = spec_mod.fold_span_keys(base, rids, t0s, width) \
                if temp else None
            return pick(logits, keys), caches

        return jax.jit(verify, donate_argnums=(2,))

    # -- sampling keys --------------------------------------------------------

    def _slot_key(self, rid: int, t: int):
        """PRNG key for request ``rid``'s ``t``-th emitted token.

        Keyed by (request, emitted index) — never by engine tick — so a
        preempted and re-admitted stream replays bit-identically and a
        speculative verify scoring positions t..t+k consumes exactly the
        keys the plain engine would, one tick at a time."""
        base = self._rid_keys.get(rid)
        if base is None:
            # & 0xffffffff: negative rids (warm-up requests) fold as their
            # uint32 bit pattern — the same coercion the traced int32 path
            # (spec.fold_row_keys) applies, so host and device keys agree.
            base = self._rid_keys[rid] = jax.random.fold_in(
                self._base_key, rid & 0xffffffff)
        return jax.random.fold_in(base, t)

    def _emit_key(self, req: Request):
        """Key for the next token ``req`` will emit (greedy: unused)."""
        if self.scfg.temperature == 0.0:
            return self._zero_key
        return self._slot_key(req.rid, len(req.generated))

    def _rid_ts(self, active):
        """(batch,) request ids + (batch,) next emitted indices — the two
        int vectors the jitted decode/verify steps fold into sampling
        keys on-device (``spec.fold_row_keys``/``fold_span_keys``). Host
        cost is two tiny int arrays per tick; greedy reuses zeros (the
        executables never consume them)."""
        if self.scfg.temperature == 0.0:
            return self._zero_ids, self._zero_ids
        rids = np.zeros((self.scfg.batch,), np.int32)
        ts = np.zeros((self.scfg.batch,), np.int32)
        for i in active:
            req = self.slots[i]
            rids[i] = req.rid
            ts[i] = len(req.generated)
        return jnp.asarray(rids), jnp.asarray(ts)

    def bucket_for(self, prompt_len: int) -> int:
        if not self._bucketed:
            return prompt_len
        b = self.scfg.min_bucket
        while b < prompt_len:
            b *= 2
        return min(b, self.scfg.max_len)

    def _prefill_fn(self, bucket: int) -> Callable:
        """One jitted prefill-install-sample executable per bucket
        (contiguous caches only — the paged engine prefills in chunks)."""
        fn = self._prefill_fns.get(bucket)
        if fn is not None:
            return fn
        cfg, scfg = self.cfg, self.scfg
        pick = sampler(scfg.temperature)

        def prefill_into_slot(params, tokens, true_len, slot, caches, key):
            # tokens: (1, bucket) right-padded prompt.
            self.prefill_traces[bucket] = \
                self.prefill_traces.get(bucket, 0) + 1   # trace-time only
            row = T.init_caches(cfg, 1, scfg.max_len, per_slot_index=True)
            logits, row, _ = T.forward(params, cfg, tokens, caches=row)
            last = jax.lax.dynamic_index_in_dim(logits, true_len - 1,
                                                axis=1, keepdims=False)
            # Padded K/V rows sit at positions >= true_len: resetting the
            # per-slot write position masks them out of every future step
            # and decode overwrites them in place.
            row = T.set_cache_lengths(row, true_len)

            def install(f, r):
                return jax.lax.dynamic_update_slice_in_dim(
                    f, r.astype(f.dtype), slot, axis=1)

            caches = [jax.tree.map(install, f, r)
                      for f, r in zip(caches, row)]
            return pick(last[0], key), caches

        fn = jax.jit(prefill_into_slot, donate_argnums=(4,))
        self._prefill_fns[bucket] = fn
        return fn

    def _make_chunk_fn(self) -> Callable:
        """The one jitted chunked-prefill executable (chunk size is fixed,
        so this traces exactly once no matter the prompt-length mix).

        Runs one ``chunk``-token slice of one slot's prompt *in place*
        through the page table: the model forward sees a batch-1 view of
        the shared pools (this slot's table row, write position =
        ``start``), the chunk's K/V rows scatter into their pages as they
        are computed (``layers._paged_apply``), and the logit at
        ``last_in_chunk`` is sampled — the host uses it only on the final
        chunk. ``end`` (true prompt length on a padded final chunk)
        overwrites the slot's write position so padded rows are never
        attended. No row cache, no install scatter."""
        cfg, scfg = self.cfg, self.scfg
        pick = sampler(scfg.temperature)
        chunk = self.chunk

        def prefill_chunk(params, tokens, start, end, last_in_chunk, slot,
                          caches, key):
            # tokens: (1, chunk); start: rows already written; end: live
            # rows after this chunk.
            self.prefill_traces[chunk] = \
                self.prefill_traces.get(chunk, 0) + 1    # trace-time only
            view = []
            for c in caches:
                pages = jax.lax.dynamic_slice_in_dim(c["pages"], slot, 1,
                                                     axis=1)
                idx = jnp.full((c["index"].shape[0], 1), start,
                               c["index"].dtype)
                view.append(dict(c, pages=pages, index=idx))
            with sharding_mod.use_ruleset(self._ruleset):
                logits, view, _ = T.forward(params, cfg, tokens,
                                            caches=view,
                                            unembed_fn=self._unembed_fn)
            last = jax.lax.dynamic_index_in_dim(logits[0], last_in_chunk,
                                                axis=0, keepdims=False)
            new_caches = [
                dict(c, kp=v["kp"], vp=v["vp"],
                     index=c["index"].at[:, slot].set(end))
                for c, v in zip(caches, view)
            ]
            return pick(last, key), new_caches

        return jax.jit(prefill_chunk, donate_argnums=(6,))

    # -- page-table plumbing --------------------------------------------------

    def _append_pages(self, slot: int, pages: List[int],
                      fresh: bool = True) -> None:
        """Extend a slot's logical->physical map in every layer cache
        (entries [have, have+n) — chunked prefill and lazy decode growth
        both append, never overwrite live entries). ``fresh=False`` skips
        the ``page_alloc`` event: a prefix-cache hit maps *existing*
        pages (``pool.share``), traced by ``prefix_hit`` instead, so the
        page_alloc event sum stays reconciled with the allocator's
        ``pages_allocated``."""
        if not pages:
            return
        if fresh:
            self.telemetry.emit(self.ticks, "page_alloc", slot=slot,
                                n=len(pages))
        with self.telemetry.span("pages", self.ticks, slot=slot,
                                 n=len(pages)):
            have = len(self.pool.slot_pages[slot]) - len(pages)
            cols = jnp.arange(have, have + len(pages))
            vals = jnp.asarray(pages, jnp.int32)
            self.caches = [
                dict(c, pages=c["pages"].at[:, slot, cols].set(vals))
                for c in self.caches
            ]

    # -- prefix cache (``paged.PrefixIndex``) ---------------------------------

    def _cow_page(self, slot: int, pos: int) -> None:
        """Copy-on-write split of slot table position ``pos``: allocate a
        fresh page, copy the K/V rows on device, swap the table entry.
        The one data-movement cost of sharing — ``page_size`` rows per
        layer, paid only when a write would otherwise land in a page
        another holder (slot or index) still reads."""
        old, new = self.pool.cow(slot, pos)
        self.telemetry.emit(self.ticks, "cow_copy", slot=slot,
                            old=old, new=new, pos=pos)
        with self.telemetry.span("pages", self.ticks, slot=slot, n=1):
            self.caches = [
                dict(c, kp=c["kp"].at[:, new].set(c["kp"][:, old]),
                     vp=c["vp"].at[:, new].set(c["vp"][:, old]),
                     pages=c["pages"].at[:, slot, pos].set(new))
                for c in self.caches
            ]

    def _cow_range(self, slot: int, lo: int, hi: int) -> None:
        """Split any *shared* page backing rows [lo, hi) before a write
        lands there. In steady state this never fires — shared pages sit
        strictly below every write cursor (hits are full pages below the
        prefill cursor; published pages are full pages below the decode
        position) — except the one admission case ``_admit`` handles
        eagerly. Kept as the write-barrier invariant: *no* write path
        may touch a page with refcount >= 2."""
        if self.prefix is None:
            return
        held = self.pool.slot_pages.get(slot, ())
        ps = self.scfg.page_size
        for pos in range(lo // ps, min((max(hi, lo + 1) - 1) // ps,
                                       len(held) - 1) + 1):
            if self.pool.refcount(held[pos]) >= 2:
                self._cow_page(slot, pos)

    def _publish_rows(self, slot: int, req: Request, rows: int) -> None:
        """Advance ``slot``'s publish chain: register every *full* page
        of the effective prompt below ``rows`` (rows actually written)
        with the prefix index. Generated-token pages are never published
        (they sit at the live write cursor); a published page is always
        strictly below every later write position, so its content is
        frozen for the lifetime of the index's hold."""
        if self.prefix is None or slot not in self._chain:
            return
        ps = self.scfg.page_size
        digest, done = self._chain[slot]
        limit = min(int(rows), self._effective_len(req)) // ps
        if limit <= done:
            return
        prompt = self._effective_prompt(req)
        held = self.pool.slot_pages.get(slot, ())
        for j in range(done, min(limit, len(held))):
            nxt = self.prefix.publish(prompt[j * ps:(j + 1) * ps],
                                      held[j], digest, now=self.ticks)
            if nxt is None:      # digest collision: stop the chain here
                break
            digest, done = nxt, j + 1
        self._chain[slot] = (digest, done)

    def _evict_prefixes(self, need: int) -> bool:
        """Reclaim cached-idle prefix pages (LRU) until ``need`` pages
        are allocatable. Runs *before* any preemption: dropping an idle
        cache entry costs a future prefill at most, evicting a live slot
        costs re-prefilling work already paid for. Returns True when the
        pool can now satisfy ``need``."""
        if self.prefix is None:
            return self.pool.can_alloc(need)
        while not self.pool.can_alloc(need):
            short = need - self.pool.free_pages
            n = self.prefix.evict(short, now=self.ticks)
            if not n:
                break
            self.telemetry.emit(self.ticks, "prefix_evict", n=n)
        return self.pool.can_alloc(need)

    def _pages_through_tick(self, slot: Request) -> int:
        """Table entries ``slot`` must have for this tick's decode write.

        The slot's cache length, host-side (no device sync), is the prompt
        plus every decoded token except the freshly sampled one — which
        this tick writes at position ``length``. A speculative tick writes
        ``spec_k`` drafted rows after it (all backed *optimistically*: an
        accepted row must land in a real page; a rejected row in an owned
        page is dead weight the next write overwrites). Writes at/past
        ``max_len`` spill to the null page and need no backing. Both the
        admission headroom check and the lazy allocator below use this one
        number, so they can never disagree."""
        length = len(slot.prompt) + len(slot.generated) - 1 + self.spec_k
        max_pages = self.scfg.max_len // self.scfg.page_size
        return min(length // self.scfg.page_size + 1, max_pages)

    def _ensure_decode_pages(self) -> None:
        """Lazily grow each decode-active slot's table so the next decode
        token's write position is backed by a real page (admission only
        reserved the first chunk's pages). A short pool preempts another
        slot in ``_choose_victim`` order; a pool with nothing left to
        preempt raises ``PagePoolExhausted`` — unless
        ``ServeConfig.max_preemptions`` is set, in which case the lone
        slot *self-preempts* (graceful ladder: its partial stream
        requeues, or force-completes at the cap) instead of crashing the
        engine."""
        if self.pool is None:
            return
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            if i in self._prefilling:
                # Mid-prefill slots ride the batched decode step too —
                # their (reset) write cursor takes 1 + spec_k dead rows
                # this tick. Width-aware write barrier: split any shared
                # page those rows could touch (never fires in steady
                # state — the cursor sits at/above every shared page).
                cur = self._prefilling[i]
                self._cow_range(i, cur, cur + 1 + self.spec_k)
                continue
            # Decode write barrier: this tick writes rows
            # [eff_len - 1, eff_len + spec_k) (spec drafts included).
            eff = self._effective_len(slot)
            self._cow_range(i, max(0, eff - 1), eff + self.spec_k)
            target = self._pages_through_tick(slot)
            while len(self.pool.slot_pages.get(i, ())) < target:
                if not self._preempt_for(1, protect={i}):
                    if self.scfg.max_preemptions is not None:
                        self._preempt(i)
                        break
                    raise paged_mod.PagePoolExhausted(
                        f"slot {i} needs a decode page and no other slot "
                        f"is left to preempt; raise n_pages")
                self._append_pages(i, self.pool.alloc(i, 1))

    # -- preemption -----------------------------------------------------------

    def _class_priority(self, req: Request) -> int:
        cls = self._classes.get(req.rclass)
        return cls.priority if cls is not None else 0

    def _choose_victim(self, victims: List[int]) -> int:
        """Priority + cost preemption policy (replaces youngest-slot):

        * lowest-class-priority slots are evicted first (protect
          high-class tenants),
        * within a class, the slot with the least completion progress
          loses (protect near-done streams — their sunk prefill+decode
          work is the most expensive to re-pay),
        * ties break youngest-admitted (least total sunk work).

        Two guards rank *above* everything else in the victim score, so
        they always yield when no alternative exists (a preemption that
        must happen always can) and never force a worse class out to
        satisfy a softer guard:

        * **cap guard** (strongest) — a slot whose request already hit
          ``max_preemptions`` ranks last: preempting it again would
          force-terminate it, so any victim that can still requeue is
          preferred — across class lines.
        * **storm guard** — a slot re-admitted within the last
          ``preempt_cooldown`` ticks ranks behind its class peers, so an
          admit/evict/admit livelock can't spin on one request. Unlike
          the cap guard it yields to class protection: a cooling
          low-class slot is still evicted before a fresh high-class one
          (cooling costs a re-prefill; terminating a paying tenant's
          stream costs the SLO).
        """
        lim = self.scfg.max_preemptions
        cool = self.scfg.preempt_cooldown

        def score(i):
            req = self.slots[i]
            ra = req.readmitted_at
            cooling = ra is not None and self.ticks - ra < cool
            capped = lim is not None and req.preempt_count >= lim
            done = len(req.generated) / max(1, req.max_new)
            return (capped, self._class_priority(req), cooling, done,
                    -self._slot_seq[i])

        return min(victims, key=score)

    def _preempt_for(self, need: int, protect: set) -> bool:
        """Free pages until ``need`` are available by preempting slots
        outside ``protect`` in ``_choose_victim`` order. Returns False
        when no victim is left (the caller decides whether that is a
        stall, a self-preemption, or a crash)."""
        if self.pool is None:
            return False
        # Cached-idle prefix pages are the cheapest pages in the pool:
        # reclaim them (LRU) before any live stream is evicted.
        if self._evict_prefixes(need):
            return True
        while not self.pool.can_alloc(need):
            victims = [i for i, s in enumerate(self.slots)
                       if s is not None and i not in protect]
            if not victims:
                return False
            self._preempt(self._choose_victim(victims))
        return True

    def _finish_forced(self, req: Request, reason: str) -> None:
        """Terminal: keep the partial stream (a bit-identical *prefix* of
        the uncontended stream — per-(rid, position) sampling keys make
        every emitted token exact) and leave the system."""
        req.done = True
        self.finished[req.rid] = req.generated
        self.finish_tick[req.rid] = self.ticks
        self.outcome[req.rid] = f"forced:{reason}"
        self.telemetry.emit(self.ticks, "finish", rid=req.rid,
                            rclass=req.rclass, outcome=f"forced:{reason}",
                            n_tokens=len(req.generated))

    def _reject(self, req: Request, reason: str) -> None:
        """Terminal: clean reject with explicit accounting — the request
        emitted nothing and is reported shed, never silently dropped.
        The ``shed`` event is the record; ``shed_by_class`` is its
        aggregate view."""
        req.done = True
        self.rejected[req.rid] = reason
        self.outcome[req.rid] = f"rejected:{reason}"
        self.telemetry.emit(self.ticks, "shed", rid=req.rid,
                            rclass=req.rclass, reason=reason)

    def _preempt(self, i: int) -> None:
        """Evict slot ``i``: its pages return to the pool and its
        generated tokens are preserved — on re-admission they prefill as
        prompt context and generation continues where it stopped
        (requeued at the head). A request already at
        ``ServeConfig.max_preemptions`` is not preempted again: it
        force-completes with its partial stream (or cleanly rejects when
        it never emitted), so no request can livelock through the
        evict/re-admit cycle and ``preempt_count`` is bounded by the cap."""
        req = self.slots[i]
        self.free_slot(i)
        self.last_tok = self.last_tok.at[i].set(0)
        if len(req.prompt) + len(req.generated) >= self.scfg.max_len:
            # Context already at the cache boundary: nothing re-prefillable
            # remains (the contiguous engine would be spilling writes too),
            # so finish with what it generated instead of requeueing an
            # unservable request.
            self._finish_forced(req, "max_len")
            return
        lim = self.scfg.max_preemptions
        if lim is not None and req.preempt_count >= lim:
            if req.generated:
                self._finish_forced(req, "preempt_limit")
            else:
                self._reject(req, "preempt_limit")
            return
        self.telemetry.emit(self.ticks, "preempt", rid=req.rid,
                            rclass=req.rclass,
                            n_generated=len(req.generated))
        req.preempt_count += 1
        self.queue.insert(0, req)

    # -- request lifecycle ----------------------------------------------------

    def submit(self, req: Request):
        if req.submit_s is None:
            req.submit_s = time.perf_counter()
        self.submit_tick.setdefault(req.rid, self.ticks)
        self._arrival_seq.setdefault(req.rid, self._n_arrivals)
        self._n_arrivals += 1
        self.telemetry.emit(self.ticks, "submit", rid=req.rid,
                            rclass=req.rclass, prompt_rows=len(req.prompt),
                            max_new=req.max_new)
        self.queue.append(req)
        mq = self.scfg.max_queue
        if mq is None or len(self.queue) <= mq:
            return
        # Bounded queue: shed the lowest-priority *newest* fresh request
        # (never a preempted one — its generated tokens must survive to a
        # terminal outcome) with explicit accounting. The just-submitted
        # request is always a candidate, so the bound always holds.
        cands = [r for r in self.queue if not r.preempt_count]
        victim = min(cands, key=lambda r: (
            self._class_priority(r), -self._arrival_seq[r.rid]))
        self.queue.remove(victim)
        self._reject(victim, "queue_full")

    # -- SLO-aware admission --------------------------------------------------

    def _refill_buckets(self) -> None:
        """One tick's refill for every metered class (tokens/tick,
        capped at the class's burst)."""
        for name, cls in self._classes.items():
            if cls.rate is None:
                continue
            self._buckets[name] = min(cls.bucket_cap,
                                      self._buckets[name] + cls.rate)

    def _bucket_ok(self, req: Request) -> bool:
        """Debit-style token bucket: a class may admit whenever its
        bucket is non-negative; the admitted request's full token cost
        then debits it (possibly below zero), so an oversized request is
        admitted once and paid off by refills rather than blocked
        forever. Re-admissions after preemption were charged at first
        admission and pass free."""
        cls = self._classes.get(req.rclass)
        if cls is None or cls.rate is None or req.preempt_count:
            return True
        return self._buckets[req.rclass] >= 0.0

    def _charge_bucket(self, req: Request) -> None:
        cls = self._classes.get(req.rclass)
        if cls is None or cls.rate is None or req.preempt_count:
            return
        self._buckets[req.rclass] -= \
            self._effective_len(req) + req.max_new

    def _admission_order(self) -> List[int]:
        """Queue indices in admission order. Legacy (no classes): FIFO.
        With classes: preempted re-admissions first (their sunk
        prefill+decode work is the most expensive to lose, and the
        requeue-at-head contract bounds their re-admission latency),
        then class priority descending, then arrival order."""
        if not self._classes:
            return list(range(len(self.queue)))

        def key(qi):
            r = self.queue[qi]
            return (0 if r.preempt_count else 1,
                    -self._class_priority(r),
                    self._arrival_seq.get(r.rid, qi), qi)

        return sorted(range(len(self.queue)), key=key)

    def _next_admission(self) -> Optional[int]:
        """First queue index in admission order whose class bucket
        admits; None when every queued request is bucket-throttled
        (they wait for refills — a metered class never blocks another
        class's admission)."""
        for qi in self._admission_order():
            if self._bucket_ok(self.queue[qi]):
                return qi
        return None

    def _effective_prompt(self, req: Request) -> np.ndarray:
        """The rows a (re-)admission must prefill: the original prompt
        plus any tokens generated before a preemption."""
        prompt = np.asarray(req.prompt, np.int32)
        if req.generated:
            prompt = np.concatenate(
                [prompt, np.asarray(req.generated, np.int32)])
        return prompt

    @staticmethod
    def _effective_len(req: Request) -> int:
        """len(_effective_prompt(req)) without materializing it — the
        admission-headroom and chunk-accounting paths only need lengths."""
        return len(req.prompt) + len(req.generated)

    def _draft_history(self, req: Request) -> np.ndarray:
        """The history the draft source sees each tick. Drafters that
        declare a ``window`` (n-gram lookup, sliding-window model draft)
        get only the trailing window — O(window) host work per tick, the
        bound that lets ``autotune.NGRAM_DRAFT_S`` price a draft token as
        a context-length-independent constant. Windowless drafters (the
        scripted test oracle locates itself by absolute position) get the
        full history."""
        window = getattr(self.draft, "window", None)
        if window is None:
            return self._effective_prompt(req)
        gen = req.generated
        if len(gen) >= window:
            return np.asarray(gen[-window:], np.int32)
        head = req.prompt[max(0, len(req.prompt) - (window - len(gen))):]
        if not gen:
            return np.asarray(head, np.int32)
        return np.concatenate([np.asarray(head, np.int32),
                               np.asarray(gen, np.int32)])

    def context_lengths(self) -> np.ndarray:
        """Per-slot live KV length (prompt + generated so far), shape
        (batch,) — the vector the flash-decode kernel scalar-prefetches."""
        return np.asarray(T.cache_lengths(self.caches))

    def _record(self, i: int, req: Request, tok: int) -> bool:
        """Append ``tok``; finish + free the slot on EOS/max_new.

        ``last_tok`` needs no reset here: tick's rebuild parks finished and
        empty slots at 0, and a slot freed during admission already was 0
        (the invariant: free slots always read 0).
        """
        req.generated.append(tok)
        if len(req.generated) == 1 and req.rid not in self.first_token_tick:
            self.first_token_tick[req.rid] = self.ticks
        if tok == self.scfg.eos_id or len(req.generated) >= req.max_new:
            req.done = True
            self.finished[req.rid] = req.generated
            self.finish_tick[req.rid] = self.ticks
            self.outcome[req.rid] = "done"
            self.telemetry.emit(self.ticks, "finish", rid=req.rid,
                                rclass=req.rclass, outcome="done",
                                n_tokens=len(req.generated))
            self.free_slot(i)
            return True
        return False

    def free_slot(self, i: int) -> None:
        """Release slot ``i``: zero its per-slot write position (flash
        decode stops streaming the dead context) and, when paged, return
        its pages to the pool and null out its page table row — the freed
        slot's drifting writes land in the null page, never in a page the
        pool may immediately re-assign."""
        self.slots[i] = None
        self._prefilling.pop(i, None)
        self._prefill_wait.pop(i, None)
        self._slot_seq.pop(i, None)
        self._chain.pop(i, None)
        if self.pool is not None:
            # Refcounted: only pages whose last holder left are freed —
            # pages the prefix index (or a co-sharing slot) still holds
            # stay resident, so ``page_free`` sizes keep reconciling with
            # the allocator's ``pages_freed``.
            freed = self.pool.free_slot(i)
            if freed:
                self.telemetry.emit(self.ticks, "page_free", slot=i,
                                    n=len(freed))
            self.caches = [
                dict(c, index=c["index"].at[:, i].set(0),
                     pages=c["pages"].at[:, i].set(0))
                for c in self.caches
            ]
        else:
            self.caches = [
                dict(c, index=c["index"].at[:, i].set(0))
                for c in self.caches
            ]

    def _imminent_page_need(self) -> int:
        """Pages committed slots will take this tick: decode growth for
        decode-active slots, the *next chunk* for mid-prefill slots.
        Admission must leave this headroom: a new request that grabs the
        pool's last page and strands an already-admitted slot turns a
        clean hold into a preemption."""
        ps, max_len = self.scfg.page_size, self.scfg.max_len
        total = 0
        for i, slot in enumerate(self.slots):
            if slot is None:
                continue
            have = len(self.pool.slot_pages.get(i, ()))
            if i in self._prefilling:
                cursor = self._prefilling[i]
                true_len = self._effective_len(slot)
                total += paged_mod.chunk_page_need(
                    cursor, min(self.chunk, true_len - cursor), have, ps,
                    max_len)
            else:
                total += max(0, self._pages_through_tick(slot) - have)
        return total

    def _admit(self):
        self._refill_buckets()
        for i, slot in enumerate(self.slots):
            if slot is not None:
                continue
            while self.queue:
                qi = self._next_admission()
                if qi is None:
                    return            # all queued classes bucket-throttled
                req = self.queue[qi]
                if self.pool is not None:
                    # Chunked admission needs only the length (tokens are
                    # materialized chunk-by-chunk in _prefill_tick) and
                    # reserves only the *first chunk's* pages; a short
                    # pool rejects cleanly — the request stays queued
                    # (later requests wait too) and retries next tick,
                    # after finished slots return pages. The headroom
                    # check also covers the imminent growth of
                    # already-committed slots.
                    ps = self.scfg.page_size
                    plen = self._effective_len(req)
                    assert plen <= self.scfg.max_len, \
                        (plen, self.scfg.max_len)
                    # A request over the pool's *capacity* (whole prompt +
                    # its first decode write, speculative width included)
                    # can never finish even with every other slot
                    # preempted. Legacy: fail loudly instead of holding it
                    # forever. Graceful mode (max_preemptions set): give
                    # it a terminal outcome — force-complete a partial
                    # stream, cleanly reject a fresh one — and move on.
                    with_decode = paged_mod.pages_for(
                        min(plen + 1 + self.spec_k, self.scfg.max_len), ps)
                    if with_decode > self.pool.capacity:
                        if self.scfg.max_preemptions is not None:
                            self.queue.pop(qi)
                            if req.generated:
                                self._finish_forced(req, "capacity")
                            else:
                                self._reject(req, "capacity")
                            continue   # retry this slot with the next
                        raise paged_mod.PagePoolExhausted(
                            f"request {req.rid}: needs {with_decode} pages "
                            f"but the pool holds {self.pool.capacity}; "
                            f"raise n_pages or page_size")
                    # Prefix-cache probe: the longest cached full-page
                    # prefix of the effective prompt. A full-coverage
                    # hit (page-aligned prompt entirely cached) still
                    # re-prefills the *last* row — the sampled first
                    # token needs its logit — so the cursor is clamped
                    # to plen - 1 and the page that row lands in is
                    # split eagerly (copy-on-write) below: the batched
                    # decode step would otherwise scribble dead rows
                    # into a page other holders read.
                    hit_pages: List[int] = []
                    hit_digest = paged_mod.ROOT_DIGEST
                    n_hit = 0
                    if self.prefix is not None:
                        hit_pages, hit_digest, n_hit = self.prefix.probe(
                            self._effective_prompt(req), plen // ps,
                            now=self.ticks)
                    cursor = min(n_hit * ps, plen - 1)
                    cow_at = (n_hit - 1) if n_hit * ps > cursor else None
                    # Unified admission pricing (bugfix): reserve the
                    # *first uncached chunk* only — cursor starts at the
                    # cached rows and the hit pages count as held — so a
                    # mostly-cached long prompt is admittable on a
                    # nearly-full pool instead of being priced as if it
                    # prefilled from row 0. (+1 page when the clamped
                    # cursor forces the eager copy-on-write split.)
                    suffix_need = paged_mod.chunk_page_need(
                        cursor, min(self.chunk, plen - cursor), n_hit, ps,
                        self.scfg.max_len)
                    first = suffix_need + (1 if cow_at is not None else 0)
                    # Cached-idle prefixes are reclaimed (LRU) before
                    # this turns into a hold — an idle cache entry never
                    # blocks a live admission.
                    if not self._evict_prefixes(
                            first + self._imminent_page_need()):
                        self.telemetry.emit(
                            self.ticks, "admit_hold", rid=req.rid,
                            rclass=req.rclass, need=first,
                            free=self.pool.free_pages)
                        return        # hold: everyone waits for pages
                    with self._admit_span(req, i):
                        self.queue.pop(qi)
                        self._charge_bucket(req)
                        self.slots[i] = req
                        if req.preempt_count:
                            req.readmitted_at = self.ticks   # storm guard
                        self._prefilling[i] = cursor
                        self._slot_seq[i] = self._admit_seq
                        self._admit_seq += 1
                        self.telemetry.emit(
                            self.ticks, "admit", rid=req.rid, slot=i,
                            rclass=req.rclass, rows=plen,
                            readmit=req.preempt_count)
                        if self.prefix is not None:
                            if n_hit:
                                self.pool.share(i, hit_pages)
                                self._append_pages(i, hit_pages, fresh=False)
                                self.telemetry.emit(
                                    self.ticks, "prefix_hit", rid=req.rid,
                                    slot=i, pages=n_hit, rows=cursor)
                                self.telemetry.count("prefix_hit_pages",
                                                     n_hit)
                            else:
                                self.telemetry.emit(
                                    self.ticks, "prefix_miss", rid=req.rid,
                                    slot=i)
                            self._chain[i] = (hit_digest, n_hit)
                        if cow_at is not None:
                            self._cow_page(i, cow_at)
                        self._append_pages(i, self.pool.alloc(i, suffix_need))
                    break             # chunks run in _prefill_tick
                prompt = self._effective_prompt(req)
                bucket = self.bucket_for(len(prompt))
                assert len(prompt) <= bucket <= self.scfg.max_len, \
                    (len(prompt), bucket, self.scfg.max_len)
                with self._admit_span(req, i):
                    self.queue.pop(qi)
                    self._charge_bucket(req)
                    self.telemetry.emit(
                        self.ticks, "admit", rid=req.rid, slot=i,
                        rclass=req.rclass, rows=len(prompt),
                        readmit=req.preempt_count)
                    padded = np.zeros((1, bucket), np.int32)
                    padded[0, :len(prompt)] = prompt
                    with self.telemetry.span("prefill_bucket", self.ticks,
                                             slot=i) as sp:
                        n0 = self.prefill_traces.get(bucket, 0)
                        tok, self.caches = self._prefill_fn(bucket)(
                            self.params, jnp.asarray(padded),
                            jnp.int32(len(prompt)), jnp.int32(i), self.caches,
                            self._emit_key(req))
                        sp.compile = self.prefill_traces.get(bucket, 0) > n0
                    self.slots[i] = req
                    self._slot_seq[i] = self._admit_seq
                    self._admit_seq += 1
                    self._first_token(i, req, tok)
                break

    def _admit_span(self, req: Request, slot: int):
        """The ``admit.request`` span of one request's install into
        ``slot``. A first admission carries ``queue_ms``, the host time
        since ``submit``; a re-admission after preemption carries
        ``readmit`` (its preemption count) instead."""
        if req.preempt_count:
            return self.telemetry.span("admit.request", self.ticks, slot=slot,
                                       rid=req.rid, readmit=req.preempt_count)
        return self.telemetry.span(
            "admit.request", self.ticks, slot=slot, rid=req.rid,
            queue_ms=1e3 * (time.perf_counter() - req.submit_s))

    def _first_token(self, i: int, req: Request, tok) -> None:
        """Fetch the token a completed prompt sampled (this blocks until
        the prefill finishes on the device) and record it."""
        tel = self.telemetry
        with tel.span("prefill_fetch", self.ticks, rid=req.rid):
            tok = int(np.asarray(tok))
        with tel.span("record", self.ticks) as sp:
            done = self._record(i, req, tok)
            if not done:
                self.last_tok = self.last_tok.at[i].set(tok)
            sp.note(n_finished=int(done))

    def _prefill_order(self) -> List[int]:
        """Mid-prefill slots in shortest-remaining-first order with aging
        (admission sequence breaks ties). Finishing the nearest-done
        prompt first is classic SRPT: it minimizes mean time-to-first-
        token under mixed prompt lengths. Pure SRPT starves: under a
        ``prefill_chunks_per_tick`` budget a long prompt would wait out
        every shorter arrival forever, so each tick a slot spends waiting
        ages it by one chunk of effective remaining work — a prompt with
        R chunks left runs after at most ~R ticks of being outranked.
        The order decides who runs at all under a budget, and who gets
        pages first when the pool is short; with neither constraint every
        slot still advances one chunk per tick, so throughput is
        unchanged."""
        def key(i):
            remaining = -(-(self._effective_len(self.slots[i])
                            - self._prefilling[i]) // self.chunk)
            return (remaining - self._prefill_wait.get(i, 0),
                    self._slot_seq[i])

        return sorted(self._prefilling, key=key)

    def _prefill_tick(self) -> None:
        """Advance mid-prefill slots by one chunk each (the interleave
        unit: between chunks the decode step below keeps every active
        stream moving), shortest-remaining-first, up to the per-tick
        chunk budget (``prefill_chunks_per_tick``; None -> every slot).
        Each chunk's pages are pre-allocated right here, immediately
        before the chunk that writes them; a short pool preempts younger
        slots, or — with nothing to preempt — stalls this slot's prefill
        for the tick (decode ticks still run and eventually return
        pages)."""
        ps, max_len = self.scfg.page_size, self.scfg.max_len
        budget = self.scfg.prefill_chunks_per_tick
        if self.degraded:
            # Downshift: one chunk per tick keeps admission live while
            # decode (the SLO-bearing work) gets the tick back. Prompt
            # *content* is untouched — only when it finishes prefilling.
            budget = 1 if budget is None else min(1, budget)
        served = 0
        for i in self._prefill_order():
            if budget is not None and served >= budget:
                # Outranked this tick: age so a long prompt can't be
                # starved by a stream of shorter arrivals. Only slots a
                # *served* chunk outranked age — a stalled or preempted
                # top slot doesn't consume budget.
                if i in self._prefilling:
                    self._prefill_wait[i] = self._prefill_wait.get(i, 0) + 1
                continue
            if i not in self._prefilling:      # preempted by an earlier
                continue                       # slot's chunk this tick
            req = self.slots[i]
            cursor = self._prefilling[i]
            prompt = self._effective_prompt(req)
            true_len = len(prompt)
            n = min(self.chunk, true_len - cursor)
            need = paged_mod.chunk_page_need(
                cursor, n, len(self.pool.slot_pages.get(i, ())), ps,
                max_len)
            if need:
                if not self._preempt_for(need, protect={i}):
                    continue                   # stalled, retry next tick
                self._append_pages(i, self.pool.alloc(i, need))
            # Write barrier: the chunk executable writes its full padded
            # width [cursor, cursor + chunk) — split any shared page in
            # reach first (no-op in steady state; see _cow_range).
            self._cow_range(i, cursor, cursor + self.chunk)
            served += 1
            self._prefill_wait.pop(i, None)    # served: aging resets
            chunk_toks = np.zeros((1, self.chunk), np.int32)
            chunk_toks[0, :n] = prompt[cursor:cursor + n]
            end = cursor + n
            # Padded final-chunk rows sit at/past true_len: `end` resets
            # the write position so they are never attended, and the
            # sampled logit row is the prompt's true last token.
            last_in = (true_len - 1 - cursor) if end == true_len else n - 1
            tel = self.telemetry
            tel.emit(self.ticks, "prefill_chunk", rid=req.rid, slot=i,
                     start=cursor, rows=n)
            with tel.span("prefill_chunk", self.ticks, slot=i,
                          rid=req.rid) as sp:
                n0 = self.prefill_traces.get(self.chunk, 0)
                tok, self.caches = self._chunk_fn(
                    self.params, jnp.asarray(chunk_toks), jnp.int32(cursor),
                    jnp.int32(end), jnp.int32(last_in), jnp.int32(i),
                    self.caches, self._emit_key(req))
                sp.compile = self.prefill_traces.get(self.chunk, 0) > n0
            # Publish the prefix pages this chunk completed: every row
            # below ``end`` went through the (deterministic) chunk
            # executable, so equal token prefixes yield equal page
            # contents and a future admission can share them.
            self._publish_rows(i, req, end)
            if end < true_len:
                self._prefilling[i] = end
                continue
            del self._prefilling[i]            # prefill complete
            self._first_token(i, req, tok)

    def _update_pressure(self) -> None:
        """Load-shedding downshift latch (``ServeConfig.degrade``): the
        pressure signal (pool occupancy vs queue depth,
        ``core.autotune.serve_pressure``) drives a hysteresis band
        (``choose_degradation``) — at/above ``pressure_high`` the engine
        enters degraded mode (speculation off, prefill chunk budget
        tightened to 1), and it stays degraded until pressure falls
        to/below ``pressure_low``. Both downshifts are stream-transparent
        (spec == plain is bit-identical; the chunk budget only re-orders
        *when* prompts finish prefilling), so degraded ticks emit exactly
        the tokens clean ticks would."""
        if not self.scfg.degrade:
            return
        from repro.core import autotune
        occ = (self.pool.pages_in_use / max(1, self.pool.capacity)
               if self.pool is not None else
               sum(s is not None for s in self.slots) / self.scfg.batch)
        self.last_pressure = autotune.serve_pressure(
            occ, len(self.queue), self.scfg.batch)
        was = self.degraded
        self.degraded = autotune.choose_degradation(
            self.last_pressure, was,
            self.scfg.pressure_high, self.scfg.pressure_low)
        if self.degraded:
            # Aggregate-only (no ring event): one count per degraded tick
            # would flood the ring; the enter/exit *transitions* are the
            # events worth a timeline mark.
            self.telemetry.count("degraded_tick")
            if not was:
                self.telemetry.emit(self.ticks, "degrade_enter",
                                    pressure=self.last_pressure)
        elif was:
            self.telemetry.emit(self.ticks, "degrade_exit",
                                pressure=self.last_pressure)

    def _spec_width(self) -> int:
        """Draft width for this tick. ``k_live`` normally; 0 while the
        degradation ladder has speculation shed; and — the probe clock —
        a single k=1 trial every ``spec_probe_every`` plain ticks while
        the adaptive disable regime (``k_live == 0``) holds. The trial
        tick's accept stats feed the same ``_maybe_adapt_k`` window as
        normal verify ticks, so a recovered accept rate re-opens
        speculation instead of the disable regime being terminal."""
        if not self.spec_k:
            return 0
        if self.degraded:
            return 0
        if self.k_live:
            return self.k_live
        if self.scfg.spec_probe_every is None:
            return 0
        self._probe_wait += 1
        if self._probe_wait < self.scfg.spec_probe_every:
            return 0
        self._probe_wait = 0
        self.telemetry.emit(self.ticks, "probe_tick")
        return 1

    def tick(self) -> int:
        """Admit, advance prefill chunks, one decode step — or one
        speculative draft/verify step (``spec_k > 0``) — for all
        decode-active slots; returns #slots making progress.

        The whole tick runs under the ``tick`` span, with a span per
        host phase inside (``serve.telemetry``): purely host-observed
        timing — no device syncs or transfers are added, so the traced
        tick does exactly the work an untraced tick does."""
        self.ticks += 1
        with self.telemetry.span("tick", self.ticks):
            return self._tick()

    def _tick(self) -> int:
        tel = self.telemetry
        self._update_pressure()
        with tel.span("admit", self.ticks):
            self._admit()
        with tel.span("prefill", self.ticks):
            self._prefill_tick()
            self._ensure_decode_pages()
        active = [i for i, s in enumerate(self.slots)
                  if s is not None and i not in self._prefilling]
        if not active:
            return len(self._prefilling)
        n = len(active) + len(self._prefilling)
        k = self._spec_width()
        if k:
            self._spec_tick(active, k)
            self._maybe_adapt_k()
        else:
            self._decode_tick(active)
        self._reset_prefill_positions()
        return n

    def _maybe_adapt_k(self) -> None:
        """Runtime feedback into the spec cost model: every
        ``spec_adapt_every`` verify ticks, re-choose the live draft
        width from the window's measured accept rate
        (``serve.spec.rechoose_k`` -> ``core.autotune.choose_spec_k``).
        A collapsing accept rate prices speculation below plain decode
        and drives ``k_live`` to 0 — the disable regime: the workload
        has shown drafts don't land, so the verify width is pure
        overhead. Terminal by default; with ``spec_probe_every`` set,
        periodic k=1 trial ticks (``_spec_width``) keep feeding this
        window so a recovered accept rate re-opens speculation. The
        verify executable (width spec_k + 1) stays traced either way."""
        every = self.scfg.spec_adapt_every
        if every is None:
            return
        self._adapt_ticks += 1
        if self._adapt_ticks < every:
            return
        rate = (self._adapt_accepted / self._adapt_proposed
                if self._adapt_proposed else 0.0)
        self.k_live, _ = spec_mod.rechoose_k(
            self.cfg, self.scfg.page_size,
            [max(1, l) for l in self.context_lengths()], rate, self.spec_k,
            constants=self.constants)
        self._adapt_ticks = 0
        self._adapt_proposed = 0
        self._adapt_accepted = 0

    def _decode_tick(self, active: List[int]) -> None:
        """One plain batched decode step: one token per active slot."""
        tel = self.telemetry
        # Host-side context accounting for the drift gate (cheap ints —
        # context_lengths() would sync the device every tick).
        tel.count("decode_slot_ticks", len(active))
        tel.count("decode_context_rows",
                  sum(self._effective_len(self.slots[i]) for i in active))
        rids, ts = self._rid_ts(active)
        with tel.span("decode", self.ticks) as sp:
            n0 = self.decode_traces
            with tel.span("decode.dispatch", self.ticks) as dp:
                nxt, self.caches = self._step(self.params, self.last_tok,
                                              self.caches, rids, ts)
                dp.compile = self.decode_traces > n0
            # The fetch waits for the device to finish the step.
            with tel.span("decode.fetch", self.ticks):
                nxt_host = np.asarray(nxt).copy()
            sp.compile = dp.compile
        with tel.span("record", self.ticks) as sp:
            active_set = set(active)
            n_fin = 0
            for i in range(self.scfg.batch):
                if i in active_set:
                    if not self._record(i, self.slots[i], int(nxt_host[i])):
                        continue
                    n_fin += 1
                # Freed or empty slot: park the fed-back token at 0 so
                # stale output can't alias eos_id (and decodes stay
                # deterministic).
                nxt_host[i] = 0
            self.last_tok = jnp.asarray(nxt_host, jnp.int32)
            sp.note(n_finished=n_fin)

    def _spec_tick(self, active: List[int],
                   k: Optional[int] = None) -> None:
        """One draft/verify step (``serve.spec``): up to ``spec_k``
        drafted tokens per active slot are scored together with the
        pending token in the single verify executable, and the longest
        accepted prefix plus the corrected bonus token is recorded — at
        least one token per slot per tick, so a zero-accept tick is
        exactly a plain decode tick.

        Rollback invariant: the verify advanced *every* slot's write
        position by ``spec_k + 1`` and scattered that many K/V rows
        through each slot's table. The rows for [pending, accepted
        drafts] are precisely the rows a plain engine would have written;
        the host rolls each slot's write position back to its true live
        length, leaving rejected rows as dead weight in owned pages
        (overwritten by the next tick's write at the same positions) or
        in the null page (positions past the table's reach). Slot state
        after the tick is therefore bit-identical to a plain engine that
        emitted the same tokens."""
        k = self.k_live if k is None else k
        width = self.spec_k + 1
        tel = self.telemetry
        tel.count("verify_slot_ticks", len(active))
        tel.count("verify_context_rows",
                  sum(self._effective_len(self.slots[i]) for i in active))
        tokens = np.zeros((self.scfg.batch, width), np.int32)
        tokens[:, 0] = np.asarray(self.last_tok)
        base_len: Dict[int, int] = {}
        n_prop: Dict[int, int] = {}
        with tel.span("draft", self.ticks):
            for i in active:
                req = self.slots[i]
                # Write position before the tick (host, no device sync).
                base_len[i] = self._effective_len(req) - 1
                # Draft at the *live* width (adaptive: <= spec_k); the
                # verify executable keeps its fixed spec_k + 1 shape.
                prop = np.asarray(
                    self.draft.propose(self._draft_history(req), k),
                    np.int32).ravel()[:k]
                n_prop[i] = len(prop)
                tokens[i, 1:1 + len(prop)] = np.clip(prop, 0,
                                                     self.cfg.vocab - 1)
        rids, t0s = self._rid_ts(active)
        with tel.span("spec_verify", self.ticks) as sp:
            n0 = self.verify_traces
            with tel.span("spec_verify.dispatch", self.ticks) as dp:
                picks, self.caches = self._verify_fn(
                    self.params, jnp.asarray(tokens), self.caches, rids, t0s)
                dp.compile = self.verify_traces > n0
            with tel.span("spec_verify.fetch", self.ticks):
                picks = np.asarray(picks)
            sp.compile = dp.compile
        with tel.span("record", self.ticks) as sp:
            sp.note(n_finished=self._record_verified(active, tokens, picks,
                                                     base_len, n_prop))

    def _record_verified(self, active: List[int], tokens: np.ndarray,
                         picks: np.ndarray, base_len: Dict[int, int],
                         n_prop: Dict[int, int]) -> int:
        """Record each slot's accepted drafts and bonus token, roll the
        write positions back to the live lengths and feed the last
        tokens back; returns how many requests finished."""
        tel = self.telemetry
        last = np.zeros((self.scfg.batch,), np.int32)
        cols: List[int] = []
        vals: List[int] = []
        n_fin = 0
        for i in active:
            req = self.slots[i]
            # Score only what the drafter actually proposed: a zero-padded
            # undrafted position that happened to match the target would
            # otherwise inflate the accept stats (the gated accept-rate
            # cell and any measured-accept feedback into choose_spec_k).
            accepted, emitted = spec_mod.longest_accept(
                tokens[i, 1:1 + n_prop[i]], picks[i, :n_prop[i] + 1])
            self._adapt_proposed += n_prop[i]
            self._adapt_accepted += accepted
            done, n_rec = False, 0
            for tok in emitted:
                n_rec += 1
                if self._record(i, req, int(tok)):
                    done = True          # EOS or max_new: rest discarded
                    break
            # One spec_verify event per (slot, tick): its payload carries
            # the accept accounting (the spec_* counters are aggregates
            # over these events).
            tel.emit(self.ticks, "spec_verify", rid=req.rid, slot=i,
                     proposed=n_prop[i], accepted=accepted, emitted=n_rec)
            n_fin += done
            if not done:
                # Live rows gained: the pending token plus n_rec - 1
                # accepted drafts (the last emitted token is the unwritten
                # bonus/divergence token, fed back as last_tok).
                cols.append(i)
                vals.append(base_len[i] + n_rec)
                last[i] = emitted[n_rec - 1]
        if cols:
            cj = jnp.asarray(cols, jnp.int32)
            vj = jnp.asarray(vals, jnp.int32)
            self.caches = [dict(c, index=c["index"].at[:, cj].set(vj))
                           for c in self.caches]
        # Freed slots were zeroed by free_slot (after the verify, so its
        # donation-rebound caches are what got zeroed); mid-prefill slots
        # reset in _reset_prefill_positions; empty slots drift through
        # the null page exactly like a plain tick, just k+1 wide.
        self.last_tok = jnp.asarray(last, jnp.int32)
        return n_fin

    def _reset_prefill_positions(self) -> None:
        """The batched decode/verify step advanced every slot's write
        position and wrote garbage K/V rows for mid-prefill slots (from
        the cursor — the next chunks overwrite them, or the null page
        absorbed them). Reset their positions so the next chunk resumes
        correctly."""
        if not self._prefilling:
            return
        with self.telemetry.span("positions", self.ticks):
            items = sorted(self._prefilling.items())
            cols = jnp.asarray([i for i, _ in items], jnp.int32)
            vals = jnp.asarray([v for _, v in items], jnp.int32)
            self.caches = [dict(c, index=c["index"].at[:, cols].set(vals))
                           for c in self.caches]

    def run_until_drained(self, max_ticks: int = 10000) -> Dict[int, List[int]]:
        for _ in range(max_ticks):
            n = self.tick()
            if n == 0 and not self.queue:
                break
        return self.finished

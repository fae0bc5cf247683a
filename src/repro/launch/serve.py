"""Serving launcher: batched requests through the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \\
      --requests 8 --max-new 16

Distributed serving shards the same engine over a 1-D mesh (weights
tensor-parallel, KV page pool device-sharded — see serve/README.md):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \\
      --paged --tp 8

Open-loop traffic mode (--rate) replaces the batch submit with the
seeded arrival generator, SLO-aware admission, and the operator report
(TTFT/TPOT percentiles, goodput, shed rate); --faults adds the canonical
fault schedule on top:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \\
      --paged --rate 2.0 --process bursty --max-queue 8 \\
      --max-preemptions 3 --degrade --tenant \\
      "name=paid,priority=2,weight=1" --tenant \\
      "name=free,weight=3,rate=2,burst=16,ttft=32"

--profile-dir DIR runs the serving loop under the JAX profiler: one
timeline with the engine's ``serve.*`` spans on the host and the
device's ops, which opens in Perfetto or TensorBoard, and a per-phase
summary of it in the report.
"""

from __future__ import annotations

import argparse
import functools
import os
import time

import jax
import numpy as np

from repro import configs
from repro.core import autotune
from repro.launch import cache as cache_lib
from repro.launch import mesh as mesh_lib
from repro.models import transformer as T
from repro.serve import dist as serve_dist
from repro.serve import profile as profile_mod
from repro.serve import traffic
from repro.serve.engine import Request, ServeConfig, ServingEngine, SLOClass
from repro.serve.faults import FaultInjector, canonical_schedule


def _parse_tenant(spec: str):
    """``name=paid,priority=2,rate=1.5,burst=8,ttft=16,tpot=4,weight=1``
    -> (SLOClass, TrafficClass) with unset fields at their defaults."""
    kv = {}
    for part in spec.split(","):
        k, _, v = part.partition("=")
        if not _ or not k:
            raise SystemExit(f"--tenant wants k=v pairs, got {part!r}")
        kv[k.strip()] = v.strip()
    name = kv.pop("name", None)
    if not name:
        raise SystemExit(f"--tenant needs name=..., got {spec!r}")
    num = lambda k, d=None: float(kv[k]) if k in kv else d  # noqa: E731
    slo = SLOClass(name, priority=int(num("priority", 0)),
                   ttft_slo=num("ttft"), tpot_slo=num("tpot"),
                   rate=num("rate"), burst=num("burst"))
    tcls = traffic.TrafficClass(
        name, weight=num("weight", 1.0),
        prompt_lo=int(num("prompt-lo", 4)),
        prompt_hi=int(num("prompt-hi", 12)),
        out_lo=int(num("out-lo", 2)), out_hi=int(num("out-hi", 8)),
        ttft_ms=num("ttft-ms"), tpot_ms=num("tpot-ms"),
        sessions=int(num("sessions", 0)),
        prefix_len=int(num("prefix-len", 0)))
    known = {"priority", "ttft", "tpot", "rate", "burst", "weight",
             "prompt-lo", "prompt-hi", "out-lo", "out-hi",
             "ttft-ms", "tpot-ms", "sessions", "prefix-len"}
    if set(kv) - known:
        raise SystemExit(f"--tenant unknown keys {sorted(set(kv) - known)}")
    return slo, tcls


def _profile_report(run_dir: str) -> None:
    """Where the ticks of the profiled run went, by engine phase."""
    rep = profile_mod.summary(profile_mod.load(run_dir))
    waits = list(rep["queue_ms"].values())
    wait = (f", admission wait p90 {np.percentile(waits, 90):.2f} ms"
            if waits else "")
    print(f"  profile: {run_dir} (open in Perfetto or TensorBoard): "
          f"{rep['ticks']} ticks, host work {rep['tick_host_ms']:.2f} "
          f"ms/tick besides the fetches{wait}")
    if "idle_s" in rep:
        idle = sorted(rep["idle_s"].items(), key=lambda x: -x[1])
        print(f"    device idle {sum(rep['idle_s'].values()) * 1e3:.1f} ms"
              f" of {rep['window_s'] * 1e3:.1f} ms, by phase: "
              + ", ".join(f"{k} {v * 1e3:.1f}" for k, v in idle))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="KV rows from a shared page pool (serve/paged.py)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="prefill chunk rows (paged; page-size multiple); "
                         "default: the autotune chunk cost model's choice")
    ap.add_argument("--prefix-cache", default=False,
                    action=argparse.BooleanOptionalAction,
                    help="share full-page-aligned prompt prefixes across "
                         "requests through the page table (paged only; "
                         "refcounted pages + copy-on-write — admission "
                         "skips prefill for cached prefixes, streams stay "
                         "bit-identical)")
    ap.add_argument("--pool-frac", type=float, default=1.0,
                    help="pool size as a fraction of the contiguous "
                         "batch*max_len reservation (>= 1.0 keeps the "
                         "full, exhaustion-free equivalent)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: drafted tokens per verify "
                         "tick (paged only; 0 disables — see "
                         "core.autotune.choose_spec_k for when that wins)")
    ap.add_argument("--draft", default="ngram",
                    help="draft source for --spec-k: 'ngram' (prompt "
                         "lookup, no second model), 'self' (sliding-window "
                         "self-speculation), or a configs/ arch name")
    ap.add_argument("--tp", type=int, default=None,
                    help="shard the engine tensor-parallel over this many "
                         "devices (paged only; weights TP, KV page pool "
                         "device-sharded). 1 = unsharded")
    ap.add_argument("--mesh", default=None,
                    help="explicit serving mesh as AXIS=N (e.g. model=8); "
                         "alternative spelling of --tp")
    traf = ap.add_argument_group(
        "open-loop traffic / SLO admission",
        "--rate switches from the batch submit to the seeded open-loop "
        "generator (serve/traffic.py): requests arrive on a Poisson or "
        "bursty (MMPP) clock, admission is SLO-aware, and the run ends "
        "with the operator report.")
    traf.add_argument("--rate", type=float, default=None,
                      help="offered load in requests per engine tick "
                           "(enables traffic mode)")
    traf.add_argument("--process", choices=("poisson", "bursty"),
                      default="poisson",
                      help="arrival process; 'bursty' modulates the rate "
                           "by --burst-factor in burst state")
    traf.add_argument("--burst-factor", type=float, default=8.0,
                      help="bursty-state rate multiplier (MMPP)")
    traf.add_argument("--tenant", action="append", default=[],
                      help="repeatable tenant class: 'name=paid,priority=2,"
                           "rate=1.5,burst=8,ttft=16,tpot=4,weight=1,"
                           "prompt-lo=4,prompt-hi=12,out-lo=2,out-hi=8'. "
                           "priority orders admission and shedding; "
                           "rate/burst meter a token bucket; ttft/tpot set "
                           "the SLO targets the report scores (ticks); "
                           "ttft-ms/tpot-ms score the same wall-clock "
                           "against the measured tick time")
    traf.add_argument("--max-queue", type=int, default=None,
                      help="bounded admission queue: overflow sheds the "
                           "lowest-priority newest request (explicit "
                           "rejected: outcome, never a silent drop)")
    traf.add_argument("--max-preemptions", type=int, default=None,
                      help="fairness cap: a request preempted this many "
                           "times is force-completed or cleanly rejected "
                           "instead of being evicted again")
    traf.add_argument("--degrade", action="store_true",
                      help="automatic load-shedding downshifts under "
                           "pressure (spec off, prefill budget 1); "
                           "stream-transparent, recovers on its own")
    traf.add_argument("--spec-probe-every", type=int, default=None,
                      help="after an accept-rate collapse disables "
                           "speculation, run a k=1 trial tick this often "
                           "so it can re-open (needs --spec-k and the "
                           "adaptation clock)")
    traf.add_argument("--faults", action="store_true",
                      help="run the canonical seeded fault schedule (pool "
                           "squeeze -> accept collapse -> churn storm) "
                           "against the traffic")
    obs = ap.add_argument_group(
        "observability (serve/telemetry.py)",
        "Structured tick traces and wall-clock spans are on by default "
        "(ring-buffered, overhead-bounded, stream-transparent). Each span "
        "is also a profiler annotation named serve.<phase>: serve.tick "
        "around a tick, and inside it serve.admit (serve.admit.request per "
        "request, with rid and queue_ms), serve.prefill "
        "(serve.prefill_chunk, serve.prefill_fetch), serve.pages, "
        "serve.decode (serve.decode.dispatch, serve.decode.fetch), "
        "serve.record and serve.positions; serve.draft and "
        "serve.spec_verify on the speculative path.")
    obs.add_argument("--profile-dir", default=None, metavar="DIR",
                     help="run the serving loop under jax.profiler and "
                          "write its trace (.xplane.pb) here: the serve.* "
                          "spans and the device's ops on one clock; open "
                          "it in Perfetto or TensorBoard")
    obs.add_argument("--no-telemetry", action="store_true",
                     help="disable the event ring and wall-clock spans "
                          "(decision counters stay exact either way)")
    obs.add_argument("--default-constants", action="store_true",
                     help="price choose_* decisions from the hand-set "
                          "default constants, skipping any calibrated: "
                          "cache entries (reproducibility escape hatch; "
                          "see repro.launch.calibrate)")
    args = ap.parse_args(argv)
    cache_lib.use_compile_cache()

    if args.default_constants:
        os.environ[autotune.DEFAULT_CONSTANTS_ENV] = "1"

    if args.spec_k and not args.paged:
        raise SystemExit("--spec-k needs --paged (verify runs the paged "
                         "s>1 attention path)")
    if args.tp is not None and args.mesh is not None:
        raise SystemExit("--tp and --mesh are alternative spellings; "
                         "pass one")
    mesh = None
    if args.mesh is not None:
        axis, _, size = args.mesh.partition("=")
        if axis != "model" or not size.isdigit():
            raise SystemExit(f"--mesh wants model=N, got {args.mesh!r}")
        mesh = mesh_lib.make_serving_mesh(int(size))
    elif args.tp is not None:
        mesh = mesh_lib.make_serving_mesh(args.tp)
    if mesh is not None and not args.paged:
        raise SystemExit("--tp/--mesh need --paged (the shard unit of the "
                         "distributed engine is the KV page)")
    if args.rate is None and (args.tenant or args.faults):
        raise SystemExit("--tenant/--faults need --rate (traffic mode)")
    if args.prefix_cache and not args.paged:
        raise SystemExit("--prefix-cache needs --paged (sharing happens "
                         "through the page table)")
    if args.spec_probe_every is not None and not args.spec_k:
        raise SystemExit("--spec-probe-every needs --spec-k")

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get_config(args.arch)
    if cfg.encoder is not None or cfg.n_frontend_tokens:
        raise SystemExit("serve launcher demo supports decoder-only archs")
    n_pages = None
    if args.paged and args.pool_frac < 1.0:
        # At least 2 (null page + one real page): a tiny fraction should
        # degrade to a tiny-but-usable pool, not an assert.
        n_pages = max(2, 1 + int(args.batch * args.max_len
                                 // args.page_size * args.pool_frac))
    tenants = [_parse_tenant(s) for s in args.tenant]
    scfg = ServeConfig(
        max_len=args.max_len, batch=args.batch, paged=args.paged,
        page_size=args.page_size, n_pages=n_pages,
        chunk_size=args.chunk_size, prefix_cache=args.prefix_cache,
        spec_k=args.spec_k, draft=args.draft,
        classes=tuple(slo for slo, _ in tenants) or None,
        max_queue=args.max_queue, max_preemptions=args.max_preemptions,
        degrade=args.degrade,
        spec_adapt_every=(args.spec_probe_every
                          if args.spec_probe_every else None),
        spec_probe_every=args.spec_probe_every,
        telemetry=not args.no_telemetry)
    if args.profile_dir and args.no_telemetry:
        raise SystemExit("--profile-dir needs telemetry (drop "
                         "--no-telemetry)")
    # Weights are made in the compute dtype. On a mesh each device makes
    # only its own shards, so no device ever holds the whole tree.
    key = jax.random.PRNGKey(args.seed)
    init = functools.partial(T.init_params, cfg=cfg, dtype=cfg.dtype)
    if mesh is not None:
        init = jax.jit(init, out_shardings=serve_dist.param_shardings(
            jax.eval_shape(init, key), mesh))
    engine = ServingEngine(init(key), cfg, scfg, mesh=mesh)
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    t0 = time.time()
    if args.rate is not None:
        tcfg = traffic.TrafficConfig(
            rate=args.rate, n_requests=args.requests, seed=args.seed,
            process=args.process, burst_factor=args.burst_factor,
            vocab=cfg.vocab, max_prompt=args.max_len - args.max_new,
            classes=tuple(t for _, t in tenants) or
            (traffic.TrafficClass("default", out_lo=2,
                                  out_hi=max(2, args.max_new)),))
        arrivals = traffic.TrafficGenerator(tcfg).arrivals()
        inj = FaultInjector(canonical_schedule()) if args.faults else None
        res = traffic.run_open_loop(engine, arrivals, injector=inj)
        if inj is not None:
            inj.finish(engine)
        dt = time.time() - t0
        s = traffic.summarize(engine, arrivals, classes=tcfg.classes)
        print(f"offered {s['offered']} requests at rate {args.rate} "
              f"({args.process}): {s['done']} done, {s['forced']} forced, "
              f"{s['rejected']} rejected, {len(res['unresolved'])} "
              f"unresolved in {s['ticks']} ticks / {dt:.2f}s")
        print(f"  ttft p50/p99 {s['ttft_p50']:.0f}/{s['ttft_p99']:.0f} "
              f"ticks, tpot p50/p99 {s['tpot_p50']:.2f}/{s['tpot_p99']:.2f}"
              f", goodput {s['goodput_tokens_per_tick']:.2f} tok/tick, "
              f"shed {s['shed_rate']:.2f}")
        print(f"  preemptions {s['preemptions']}, admission holds "
              f"{s['admission_holds']}, downshifts {s['downshifts']} "
              f"({s['degraded_ticks']} degraded ticks), spec probes "
              f"{engine.spec_probes}")
        if "tick_wall_s_mean" in s:
            print(f"  wall-clock: tick mean/p99 "
                  f"{s['tick_wall_s_mean'] * 1e3:.2f}/"
                  f"{s['tick_wall_s_p99'] * 1e3:.2f} ms, ttft p50 "
                  f"{s['ttft_ms_p50']:.0f} ms, tpot p50 "
                  f"{s['tpot_ms_p50']:.1f} ms/token")
        if inj is not None:
            print(f"  faults: {inj.injected} injected, {inj.cleared} "
                  f"cleared, {engine.pool.pages_in_use if engine.pool else 0}"
                  f" pages leaked")
        for name, c in sorted(s["by_class"].items()):
            slo = (f", ttft-slo {c['ttft_slo_attainment']:.0%}"
                   if "ttft_slo_attainment" in c else "")
            slo += (f", ttft-ms-slo {c['ttft_ms_slo_attainment']:.0%}"
                    if "ttft_ms_slo_attainment" in c else "")
            print(f"  class {name}: {c['done']}/{c['offered']} done, "
                  f"shed {engine.shed_by_class.get(name, 0)}{slo}")
        finished = engine.finished
    else:
        rng = np.random.RandomState(args.seed)
        for rid in range(args.requests):
            prompt = rng.randint(2, cfg.vocab, size=rng.randint(4, 12))
            engine.submit(Request(rid=rid, prompt=prompt.astype(np.int32),
                                  max_new=args.max_new))
        finished = engine.run_until_drained()
        dt = time.time() - t0
        toks = sum(len(v) for v in finished.values())
        print(f"served {len(finished)} requests, {toks} tokens "
              f"in {dt:.2f}s ({toks/dt:.1f} tok/s)")
    if args.profile_dir:
        jax.profiler.stop_trace()
    # Which constant set priced this session's choose_* decisions —
    # operators need to tell a stale calibration from a fresh one.
    const = engine.constants
    if const.source == "calibrated":
        age_min = max(0.0, (time.time() - const.timestamp) / 60.0)
        print(f"  constants: calibrated [{const.backend}:{const.mesh}] "
              f"priced choose_* (measured {age_min:.0f} min ago, "
              f"ts={const.timestamp:.0f}; --default-constants forces "
              f"the hand-set defaults)")
    else:
        print("  constants: hand-set defaults priced choose_* (run "
              "python -m repro.launch.calibrate to measure this backend)")
    if engine.pool is not None:
        occ = engine.pool.occupancy()
        mesh_note = (f" over {occ['n_devices']} devices"
                     if occ["n_devices"] > 1 else "")
        print(f"  paged: {occ['high_water']}/{occ['capacity']} pages "
              f"high-water ({args.page_size} rows each){mesh_note}, "
              f"{occ['pages_allocated']} alloc / {occ['pages_freed']} "
              f"freed, chunk={engine.chunk}, "
              f"{engine.admission_rejections} admission holds, "
              f"{engine.preemptions} preemptions")
        if engine.prefix is not None:
            # Prefix-cache operator report: sharing state of the live
            # pool + cumulative hit/COW/eviction traffic. hit rate is
            # over admissions that probed (hits + misses).
            probes = engine.prefix_hits + engine.prefix_misses
            hit_rate = engine.prefix_hits / probes if probes else 0.0
            print(f"  prefix cache: {occ['pages_shared']} shared / "
                  f"{occ['pages_exclusive']} exclusive / "
                  f"{occ['pages_cached_idle']} cached-idle pages, "
                  f"index {len(engine.prefix)} entries, "
                  f"hit rate {hit_rate:.0%} ({engine.prefix_hits}/"
                  f"{probes} admissions, {engine.prefix_hit_pages} pages "
                  f"mapped), {occ['cow_count']} cow copies, "
                  f"{engine.prefix.evicted_pages} evicted")
    if engine.spec_k:
        ticks = max(1, engine.spec_ticks)
        print(f"  spec: k={engine.spec_k} draft={args.draft} "
              f"accepted/tick={engine.spec_accepted / ticks:.2f} "
              f"emitted/tick={engine.spec_emitted / ticks:.2f} "
              f"({engine.verify_traces} verify executable)")
    tel = engine.telemetry
    tstats = tel.tick_stats()
    if tstats["n"]:
        print(f"  telemetry: tick p50/p99 {tstats['p50_s'] * 1e3:.2f}/"
              f"{tstats['p99_s'] * 1e3:.2f} ms over {tstats['n']} ticks, "
              f"{len(tel.events)} events in ring "
              f"({tel.dropped_events} evicted)")
        for name, st in sorted(tel.span_stats().items()):
            print(f"    span {name}: n={st['n']} "
                  f"exec-mean={st['execute_mean_s'] * 1e3:.2f} ms "
                  f"(compile {st['compile_n']}x "
                  f"{st['compile_s'] * 1e3:.1f} ms)")
    if args.profile_dir:
        _profile_report(args.profile_dir)
    for rid in sorted(finished):
        print(f"  req {rid}: {finished[rid][:10]}...")
    return engine


if __name__ == "__main__":
    main()

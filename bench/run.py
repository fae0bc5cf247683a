#!/usr/bin/env python3
"""Serving benchmark: one cell of ``BENCHMARK.json``, on a wall-clock open loop.

  python3 bench/run.py --workload qwen3-4b.chat --seed 7 --seconds 45 --trace 0

Runs from the root of a checkout on a machine with the TPU chips the cell
asks for, and exits nonzero without a result when there are none. It
makes the weights on the device from the seed, builds the program's
``ServingEngine`` from the cell's configuration file, warms every shape
the traffic uses, then drives ``submit``/``tick`` with the mix's requests
at their due times and measures for ``--seconds``. After the window it
compares what the engine served with the plain float32 reference.

The configuration's ``"arch"`` names its architecture module,
``bench/arch/<arch>.py``: weights, reference and work counts. A cell on
several chips serves on the program's tensor-parallel mesh over the
first ``chips`` devices, with every weight made where the engine keeps
it; its device numbers are per chip over those devices.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of
the window), ``device`` and, last, ``checks``: each compared number with
its limit. The checks are also the last lines of standard error.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# The compile cache lives at one fixed path inside the checkout, so only
# the first run of a cell there compiles and two checkouts share nothing.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, "bench", ".trace")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import arch, gen, readers  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TAIL_S = 60.0         # how long past the window a due request may take


class Compiles:
    """Counts executables compiled, or read from the persistent cache,
    while it is open."""

    def __init__(self):
        self.names = []

    @property
    def n(self) -> int:
        return len(self.names)

    def _on(self, event, duration, fun_name="?", **_):
        if event == COMPILE_EVENT:
            self.names.append(fun_name)

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# -- layout: everything is found by the names in BENCHMARK.json -------------

def layout(root: str, cell_name: str) -> dict:
    """The cell, its configuration, mix, metrics and their readers."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    (cell,) = [w for w in bm["workloads"] if w["name"] == cell_name]
    (centry,) = [c for c in bm["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(root, centry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    window = config.get("sliding_window")
    if window and mix["max_tokens"] > window:
        # The program attends over the whole context: only requests that
        # fit in the published window are served as the model states.
        raise SystemExit(f"{cell_name}: requests of up to {mix['max_tokens']}"
                         f" tokens pass the sliding window of {window}")
    e2e = [m for m in bm["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (cell_name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return {"cell": cell, "config": config, "arch": arch.load(config),
            "mix": mix, "end_to_end": e2e, "per_layer": per_layer,
            "readers": {m["name"]: reader(root, m["name"])
                        for m in e2e + per_layer}}


def reader_path(root: str, name: str) -> str:
    """``bench/metrics/<name>.py``, or else that of the name with its last
    dotted parts taken off one by one: ``decode_step_ms.chat`` is read by
    ``decode_step_ms.py`` unless the cell has a reader of its own."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = os.path.join(root, "bench", "metrics",
                            ".".join(parts[:n]) + ".py")
        if os.path.isfile(path):
            return path
    raise SystemExit(f"no reader for metric {name!r} in bench/metrics")


def reader(root: str, name: str):
    path = reader_path(root, name)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak_for(dev) -> dict:
    """One chip's peaks from ``peaks.json``; none off a TPU, where no
    reading is a device's."""
    if dev.platform != "tpu":
        return {}
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    if dev.device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {dev.device_kind!r} "
                         f"in peaks.json")
    return peaks[dev.device_kind]


def chips_peak(devs) -> dict:
    """The peaks of the cell's chips together: a share of it divides the
    whole model's work by every chip's peak over one chip's time."""
    return {k: v * len(devs) if isinstance(v, (int, float)) else v
            for k, v in peak_for(devs[0]).items()}


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))


# -- the program under test --------------------------------------------------

def build(a, c: dict, seed: int, chips: int):
    """Weights from the seed, and the engine that serves them. On several
    chips the engine serves on the program's tensor-parallel mesh, and
    each weight is made in the engine's own layout, so no chip ever
    holds the whole tree."""
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer as T
    from repro.serve import dist as serve_dist
    from repro.serve.engine import ServeConfig, ServingEngine
    mcfg = a.program_config(T, c)
    key = jax.random.PRNGKey(int(gen.rng_for(seed, 0).integers(2**31)))
    want = jax.eval_shape(lambda k: T.init_params(k, mcfg, dtype=mcfg.dtype),
                          key)
    got = jax.eval_shape(lambda k: a.make(c, k), key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (x.shape, x.dtype) != (y.shape, y.dtype) for x, y in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit("the benchmark's weight tree does not match the "
                         "program's parameter layout")
    mesh = make_serving_mesh(tp=chips)
    shardings = None if mesh is None else \
        serve_dist.param_shardings(got, mesh)
    t = time.time()
    params = a.make(c, key, shardings)
    jax.block_until_ready(params)
    t_w = time.time() - t
    sv = c["serving"]
    scfg = ServeConfig(max_len=sv["max_len"], batch=sv["batch"], paged=True,
                       page_size=sv["page_size"], n_pages=sv["n_pages"],
                       chunk_size=sv["chunk"], eos_id=-1)
    t = time.time()
    engine = ServingEngine(params, mcfg, scfg, mesh=mesh)
    jax.block_until_ready(engine.caches)
    return params, engine, t_w, time.time() - t


def warm(engine, sv: dict) -> None:
    """Compile every executable and host-side update the traffic reaches.

    One wave passes through every slot with prompts whose first and whose
    second chunk take each of 1 to chunk/page_size pages (the page table
    grows by a different update at its start and past it); the next keeps
    1 to k slots in mid-prefill at once while another decodes (k up to
    batch - 1, as many chunks per prompt as max_len allows)."""
    from repro.serve.engine import Request
    ps, chunk, batch = sv["page_size"], sv["chunk"], sv["batch"]
    rid = -1
    per = chunk // ps
    lengths = [1 + m * ps + j * chunk for j in (0, 1) for m in range(per)]
    wave = []
    for i in range(max(batch, len(lengths))):
        wave.append(Request(rid=rid, prompt=np.zeros(
            lengths[i % len(lengths)], np.int32), max_new=3))
        rid -= 1
    for r in wave:
        engine.submit(r)
    engine.run_until_drained()
    k = min(batch - 1, sv["max_len"] // chunk - 1)
    wave = [Request(rid=rid, prompt=np.zeros(1, np.int32), max_new=k + 4)]
    rid -= 1
    for j in range(1, k + 1):
        wave.append(Request(rid=rid, prompt=np.zeros(chunk * j + 1, np.int32),
                            max_new=2))
        rid -= 1
    engine.submit(wave[0])
    engine.tick()
    for r in wave[1:]:
        engine.submit(r)
    engine.run_until_drained()
    engine.finished.clear()


# -- the open loop -------------------------------------------------------------

def serve(engine, reqs, mix, seconds, tracing, compiles):
    """Drive the engine; returns the run's records."""
    from repro.serve.engine import Request
    clock = time.perf_counter
    TA = jax.profiler.TraceAnnotation
    recs = {r.rid: readers.Rec(rid=r.rid, due=r.due_s, plen=len(r.prompt),
                               max_new=r.max_new) for r in reqs}
    live = {}                       # rid -> Request not yet complete
    ticks, late = [], []
    state, nxt = "ramp", 0
    t_open = t_close = None
    tick_open = tick_close = compiles_open = 0
    window_ann = None
    first_done = False
    t0 = clock()
    while True:
        now = clock() - t0
        with TA("bench.submit"):
            while nxt < len(reqs) and reqs[nxt].due_s <= now:
                r = reqs[nxt]
                req = Request(rid=r.rid, prompt=r.prompt, max_new=r.max_new)
                engine.submit(req)
                live[r.rid] = req
                late.append(now - r.due_s)
                nxt += 1
        if state == "ramp" and (first_done if mix["window"] == "first_finish"
                                else now >= mix["ramp_s"]):
            if tracing:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                jax.profiler.start_trace(TRACE_DIR)
                window_ann = TA("bench.window")
                window_ann.__enter__()
            t_open, tick_open, compiles_open = (clock() - t0, engine.ticks,
                                                compiles.n)
            state = "window"
        elif state == "window" and now >= t_open + seconds:
            t_close, tick_close = now, engine.ticks
            # Requests due in the window: by the schedule where the window
            # opens at a set time, so every seed's window holds the same.
            due_lo, due_hi = ((mix["ramp_s"], mix["ramp_s"] + seconds)
                              if mix["window"] == "after_ramp"
                              else (t_open, t_close))
            compiles_in = compiles.names[compiles_open:]
            backlog = len(engine.queue) + len(reqs) - nxt
            if tracing:
                # The trace is stopped once the loop ends: stopping takes
                # tens of seconds, and no request may wait behind it.
                window_ann.__exit__(None, None, None)
            state = "tail"
        if state == "tail":
            due = [x for x in recs.values() if due_lo <= x.due < due_hi]
            if all(x.times for x in due) or now > t_close + TAIL_S:
                break
        if not live and not engine.queue:
            with TA("bench.idle"):
                gap = reqs[nxt].due_s - now if nxt < len(reqs) else 0.01
                time.sleep(min(max(gap, 0.0), 0.01))
            continue
        t_a = clock() - t0
        with TA("bench.tick"):
            engine.tick()
        with TA("bench.record"):
            t_b = clock() - t0
            held = {id(s) for s in engine.slots if s is not None}
            n_dec = 0
            for rid, req in list(live.items()):
                rec = recs[rid]
                n_new = len(req.generated) - len(rec.times)
                if n_new:
                    n_dec += n_new - (not rec.times)
                    rec.times.extend([t_b] * n_new)
                if rec.slot_t is None and (id(req) in held or rec.times):
                    rec.slot_a, rec.slot_t = t_a, t_b
                if len(rec.times) >= rec.max_new:
                    del live[rid]
                    first_done = True
            if state == "window":
                ticks.append((t_a, t_b, n_dec))
    stop = None
    if tracing:
        t_a = clock() - t0
        jax.profiler.stop_trace()
        stop = (t_a - t_close, clock() - t0 - t_a)
    return {"recs": [recs[r.rid] for r in reqs],
            "t_open": t_open, "t_close": t_close, "due_lo": due_lo,
            "due_hi": due_hi, "ticks": ticks,
            "late": late, "compiles_in": compiles_in,
            "backlog": backlog, "tick_open": tick_open,
            "tick_close": tick_close, "stop": stop}


# -- correctness ----------------------------------------------------------------

def sample(done, seed, mix):
    """The finished request with most tokens, then others drawn from the
    seed until ``check_tokens`` tokens or ``check_requests`` requests."""
    order = sorted(done, key=lambda x: (-len(x[2]), x[0]))
    if not order:
        return []
    pick, rest = [order[0]], order[1:]
    rng = gen.rng_for(seed, 2)
    rest = [rest[i] for i in rng.permutation(len(rest))]
    while rest and len(pick) < mix["check_requests"] and \
            sum(len(x[2]) for x in pick) < mix["check_tokens"]:
        pick.append(rest.pop())
    return pick


def logit_gaps(a, params, c, picked, mode="float32"):
    """Per request, the widest gap by which a served token's reference
    logit lies below the reference's best at its position. With
    ``mode="fp8"`` the tokens judged are the control's own first
    choices at each of those positions."""
    out = []
    for rid, prompt, served in picked:
        toks = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        ref = a.logits(params, c, toks, rows, "float32")
        pick = np.asarray(served) if mode == "float32" else np.asarray(
            jax.numpy.argmax(a.logits(params, c, toks, rows, mode),
                             axis=-1))
        best = jax.numpy.max(ref, axis=-1)
        got = jax.numpy.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        out.append(float(jax.numpy.max(best - got)))
    return out


def _passes(value, limit, kind):
    return value <= limit if kind == "max" else value >= limit


def judge(gaps, limits, compiles_in, failed, backlog):
    """Whether a run is correct, and each number compared with its limit
    as (name, value, limit, "max" or "min"). ``backlog`` is None where the
    mix keeps none."""
    checks = [
        ("served_logit_gap", max(gaps) if gaps else float("inf"),
         limits["served_logit_gap"], "max"),
        ("compiles_in_window", compiles_in, 0, "max"),
        ("failed_requests", failed, 0, "max"),
    ]
    if backlog is not None:
        checks.append(("backlog_left", backlog, 1, "min"))
    return all(_passes(*x[1:]) for x in checks), checks


def check_lines(checks, prefix="check"):
    return [f"{prefix} {n} {v} {'<=' if k == 'max' else '>='} {l} "
            f"{'ok' if _passes(v, l, k) else 'FAIL'}"
            for n, v, l, k in checks]


# -- one run --------------------------------------------------------------------

def execute(root, cell_name, seed, seconds, tracing, mutate=None,
            control=False):
    """One run of a cell; returns (result dict, check lines).

    ``mutate(engine)`` may break the engine before warm-up (the fault
    tests). ``control`` also judges the fp8 reference's first choices on
    the same requests by the same checks, under ``control`` (its
    ``correct``, ``checks`` and gaps) and in lines that start
    ``control check``."""
    lay = layout(root, cell_name)
    c, mix, cell, a = lay["config"], lay["mix"], lay["cell"], lay["arch"]
    sv = c["serving"]
    devs = jax.devices()[:cell["chips"]]
    peak = chips_peak(devs)
    t = time.time()
    with Compiles() as compiles:
        params, engine, t_w, t_e = build(a, c, seed, len(devs))
        if mutate is not None:
            mutate(engine)
        warm(engine, sv)
        t_warm = time.time() - t - t_w - t_e
        reqs = gen.requests(mix, seed, c["vocab_size"],
                            horizon_s=mix.get("ramp_s", 0) + seconds + TAIL_S)
        setup_s = time.time() - T_START
        log(f"setup {setup_s:.3f} s: imports {t - T_START:.3f}, weights "
            f"{t_w:.3f}, engine {t_e:.3f}, warm-up {t_warm:.3f}; "
            f"{compiles.n} executables compiled or read from the cache")
        out = serve(engine, reqs, mix, seconds, tracing, compiles)
    mem = max(peak_bytes(d) for d in devs)
    red = None
    if tracing:
        t = time.time()
        red = trace_mod.reduce(trace_mod.load(TRACE_DIR),
                               {d.id for d in devs})
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        after, took = out["stop"]
        log(f"trace stopped {after:.3f} s after the window closed, in "
            f"{took:.3f} s; read in {time.time() - t:.3f} s")
    run = readers.Run(
        cell=cell_name, config=c, mix=mix, peak=peak, batch=sv["batch"],
        setup_s=setup_s, t_open=out["t_open"], t_close=out["t_close"],
        due_lo=out["due_lo"], due_hi=out["due_hi"], requests=out["recs"],
        ticks=out["ticks"], trace=red)
    if out["compiles_in"]:
        log(f"compiled in the window: {out['compiles_in']}")
    late = np.asarray(out["late"])
    log(f"window {run.window_s:.3f} s over ticks {out['tick_open']}.."
        f"{out['tick_close']}; generator late mean "
        f"{1e3 * late.mean():.3f} ms, max {1e3 * late.max():.3f} ms; "
        f"preemptions {engine.preemptions}, admission holds "
        f"{engine.admission_rejections}")
    if mix["window"] == "first_finish":
        attempted = [r for r in run.requests
                     if r.slot_t is not None and r.slot_t <= run.t_close]
        failed = [r for r in attempted if r.rid in engine.rejected]
    else:
        attempted = readers.due_in_window(run)
        failed = [r for r in attempted
                  if not r.times or r.rid in engine.rejected]
    metrics = {}
    for m in (lay["per_layer"] if tracing else lay["end_to_end"]):
        v = lay["readers"][m["name"]](run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # Correctness: what the engine served, against the reference, once the
    # window is over, the peak is read and the engine is gone.
    done = [(r.rid, r.prompt, list(engine.finished[r.rid])) for r in reqs
            if r.rid in engine.finished]
    picked = sample(done, seed, mix)
    engine = None
    gc.collect()
    t = time.time()
    gaps = logit_gaps(a, params, c, picked)
    log(f"reference over {len(picked)} requests, "
        f"{sum(len(x[2]) for x in picked)} served tokens, "
        f"{time.time() - t:.3f} s: gaps {gaps}")
    args = (c["limits"], len(out["compiles_in"]), len(failed),
            out["backlog"] if mix["window"] == "first_finish" else None)
    ok, checks = judge(gaps, *args)
    lines = check_lines(checks)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": bool(ok), "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = trace_mod.breakdown(red)
    if control:
        c_gaps = logit_gaps(a, params, c, picked, "fp8")
        c_ok, c_checks = judge(c_gaps, *args)
        result["control"] = {
            "correct": bool(c_ok), "gaps": c_gaps, "program_gaps": gaps,
            "checks": {n: {"value": v, "limit": l}
                       for n, v, l, _ in c_checks}}
        lines = check_lines(c_checks, "control check") + lines
    result["checks"] = {n: {"value": v, "limit": l} for n, v, l, _ in checks}
    return result, lines


def configure():
    """Cache every executable, small ones too, at the checkout's path."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    configure()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        chips = [w["chips"] for w in json.load(f)["workloads"]
                 if w["name"] == args.workload]
    if not chips:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips[0]:
        log(f"needs {chips[0]} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
        return 3
    result, lines = execute(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace))
    for line in lines:
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

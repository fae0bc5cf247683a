#!/usr/bin/env python3
"""Smoke test on the chip: serve full-width qwen3-4b through the engine.

  python3 chip_smoke.py             # one TPU v5e chip
  python3 chip_smoke.py --chips 4   # four chips: tensor-parallel phase only

Everything runs in this one process, which holds the chip. With no
arguments the phases are:

1. device: the platform must be ``tpu`` and the device a v5e (the block
   choices on the path are priced by ``core.hwmodel.TPU_V5E``); otherwise
   exit 1 naming what was found. There is no CPU fallback.
2. serve: ``repro.launch.serve.main`` with ``--arch qwen3-4b --paged`` (full
   widths and depth, random bf16 weights from ``--seed``): 16 requests of
   256-1024 prompt tokens and 16-32 new tokens on the open-loop traffic
   path, batch 8, pages of 128 rows, max_len 2048. Every request must end
   ``done``, with no rejection and no page left in use.
3. kernels: the engine's decode and prefill-chunk executables must hold
   ``tpu_custom_call`` (the Pallas kernels, compiled natively).
4. parity: one prompt's prefill chunk and a few decode steps through
   ``T.forward`` with paged caches, once with the kernels and once with
   the gather + sdpa reference, on the engine's bf16 weights. The logits
   must agree to ``PARITY_TOL`` (max |diff| over max |reference|).

``--chips 4`` runs only the tensor-parallel phase: the same requests
through ``serve.main --tp 4``, the page-table device span of a long
prompt, per-device memory, and one prompt's logits through the mesh
engine's sharded weights against the single-device path (``TP_TOL``:
TP reductions reorder sums, so no bit identity is asserted).

Any failed phase raises, so the exit code is nonzero and no result line is
printed. The last line of standard output is one JSON object naming the
device: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "qwen3-4b"
SEED = 0
PAGE = 128
MAX_LEN = 2048
BATCH = 8
CHUNK = 256
N_REQUESTS = 16
SERVE_ARGS = [
    "--arch", ARCH, "--paged", "--page-size", str(PAGE),
    "--max-len", str(MAX_LEN), "--batch", str(BATCH),
    "--chunk-size", str(CHUNK), "--requests", str(N_REQUESTS),
    "--rate", "1.0", "--max-new", "32", "--seed", str(SEED),
    "--tenant", "name=chat,prompt-lo=256,prompt-hi=1024,out-lo=16,out-hi=32",
]
# bf16 weights and activations: the kernels keep scores and the softmax
# state in float32 while the reference rounds scores and probabilities to
# bf16, so the logits differ by bf16 rounding compounded over depth. At
# these widths in interpret mode the difference is 0.006 at 2 layers and
# 0.013 at 8, growing about as the square root of depth (about 0.03 at
# 36). An attention pattern that is wrong moves logits by order 1.
PARITY_TOL = 1e-1
TP_TOL = 1e-1
DECODE_STEPS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, msg: str) -> None:
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check_device(chips: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {d.platform!r} "
                 f"({d.device_kind})")
    kind = d.device_kind.lower()
    if "v5 lite" not in kind and "v5e" not in kind:
        sys.exit(f"chip_smoke: needs a TPU v5e (core.hwmodel.TPU_V5E "
                 f"prices its block choices), found {d.device_kind!r}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"found {len(devs)}")
    log(f"device: {d.device_kind} x{len(devs)} ({d.platform})")
    return devs


class CacheCounter:
    """Counts persistent compilation-cache hits and misses."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def memory_line(devs) -> str:
    parts = []
    for d in devs:
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id} in_use {st.get('bytes_in_use', 0) / 1e9:.3f}"
                     f" GB peak {st.get('peak_bytes_in_use', 0) / 1e9:.3f} GB")
    return "; ".join(parts)


def serve(extra=()):
    """The normal entry point; returns the engine after its report."""
    from repro.launch import serve as serve_mod
    t0 = time.time()
    engine = serve_mod.main(SERVE_ARGS + list(extra))
    wall = time.time() - t0
    outcomes = engine.outcome
    bad = {r: o for r, o in outcomes.items() if o != "done"}
    require(len(outcomes) == N_REQUESTS and not bad,
            f"requests not all done: {len(outcomes)} outcomes, {bad}")
    require(not engine.rejected and not engine.queue,
            f"rejected {engine.rejected}, {len(engine.queue)} queued")
    require(engine.pool.pages_in_use == 0,
            f"{engine.pool.pages_in_use} pages leaked")
    tokens = sum(len(v) for v in engine.finished.values())
    spans = engine.telemetry.span_stats()
    compile_s = {k: round(spans[k]["compile_s"], 3)
                 for k in ("decode", "prefill_chunk") if k in spans}
    log(f"serve: {len(outcomes)}/{N_REQUESTS} requests done, {tokens} "
        f"tokens served, 0 rejected, 0 pages in use, {wall:.1f} s wall "
        f"(weights init and compiles included); executable compile s "
        f"{compile_s}")
    return engine


def check_kernels(engine) -> None:
    """Both executables the engine ran must hold the native kernels."""
    ids = jnp.zeros((engine.scfg.batch,), jnp.int32)
    i32 = jnp.int32(0)
    texts = {
        "decode": engine._step.lower(
            engine.params, engine.last_tok, engine.caches, ids, ids),
        "prefill_chunk": engine._chunk_fn.lower(
            engine.params, jnp.zeros((1, engine.chunk), jnp.int32), i32,
            i32, i32, i32, engine.caches, engine._zero_key),
    }
    for name, lowered in texts.items():
        n = lowered.compile().as_text().count("tpu_custom_call")
        require(n > 0, f"{name} executable holds no tpu_custom_call")
        log(f"kernels: {name} executable holds tpu_custom_call ({n} "
            f"mentions)")


def paged_logits(params, cfg, prompt, steps, mesh=None, ruleset=None,
                 unembed_fn=None):
    """Logits of one slot through ``T.forward`` on paged caches: the
    prompt as one chunk, then ``steps`` single-token decode steps fed
    fixed tokens. Returns (chunk logits, decode logits) as float32."""
    from repro.dist import sharding
    from repro.models import transformer as T
    n_dev = 1 if mesh is None else int(mesh.devices.size)
    max_pages = MAX_LEN // PAGE
    n_pages = 1 + max_pages
    n_pages += -n_pages % n_dev
    caches = T.init_paged_caches(cfg, 1, MAX_LEN, PAGE, n_pages)
    table = jnp.arange(1, max_pages + 1, dtype=jnp.int32)
    caches = [dict(c, pages=jnp.broadcast_to(table, c["pages"].shape))
              for c in caches]
    if mesh is not None:
        from repro.serve import dist as serve_dist
        caches = jax.device_put(caches,
                                serve_dist.cache_shardings(caches, mesh))

    @jax.jit
    def fwd(p, tokens, c):
        with sharding.use_ruleset(ruleset):
            logits, c, _ = T.forward(p, cfg, tokens, caches=c,
                                     unembed_fn=unembed_fn)
        return logits.astype(jnp.float32), c

    chunk, caches = fwd(params, jnp.asarray(prompt[None, :CHUNK]), caches)
    dec = []
    for t in steps:
        out, caches = fwd(params, jnp.full((1, 1), t, jnp.int32), caches)
        dec.append(out)
    return np.asarray(chunk), np.asarray(jnp.concatenate(dec, axis=1))


def rel_diff(got, ref) -> float:
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def parity_prompt(vocab: int):
    rng = np.random.RandomState(SEED + 1)
    return (rng.randint(2, vocab, CHUNK).astype(np.int32),
            rng.randint(2, vocab, DECODE_STEPS).tolist())


def check_parity(params, cfg) -> None:
    """Kernel path vs the gather + sdpa reference, same bf16 weights."""
    import dataclasses
    prompt, steps = parity_prompt(cfg.vocab)
    got = paged_logits(params, dataclasses.replace(cfg, use_flash=True),
                       prompt, steps)
    ref = paged_logits(params, dataclasses.replace(cfg, use_flash=False),
                       prompt, steps)
    for name, g, r in zip(("prefill chunk", "decode"), got, ref):
        require(np.all(np.isfinite(g)) and np.all(np.isfinite(r)),
                f"{name} logits not finite")
        d = rel_diff(g, r)
        log(f"parity: {name} logits {g.shape}, kernels vs reference max "
            f"|diff| / max |ref| = {d!r} (tolerance {PARITY_TOL})")
        require(d <= PARITY_TOL, f"{name} parity {d} > {PARITY_TOL}")


def one_chip(devs) -> None:
    engine = serve()
    check_kernels(engine)
    log(f"memory after serving: {memory_line(devs[:1])}")
    params, cfg = engine.params, engine.cfg
    del engine                      # frees the page pool, keeps weights
    gc.collect()
    check_parity(params, cfg)
    log(f"memory: {memory_line(devs[:1])}")


def four_chips(devs) -> None:
    from repro.models import transformer as T
    from repro.serve.engine import Request
    engine = serve(["--tp", "4"])
    tree = sum(a.nbytes for a in jax.tree.leaves(engine.params))
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devs[:4]]
    log(f"tp memory: weights {tree / 1e9:.3f} GB in all; "
        f"{memory_line(devs[:4])}")
    # Peak, not just in use: the weights are made sharded, so no device
    # has held the whole tree at any point while serving.
    require(max(peak) < tree,
            f"a device held the whole tree: peaks {peak} vs {tree} bytes")
    # How far one long prompt's page table spreads over the mesh.
    prompt, steps = parity_prompt(engine.cfg.vocab)
    long_prompt = np.tile(prompt, 4)
    engine.submit(Request(rid=N_REQUESTS, prompt=long_prompt, max_new=2))
    span = 0
    while engine.queue or any(s is not None for s in engine.slots):
        engine.tick()
        for pages in engine.pool.slot_pages.values():
            span = max(span, len({engine.pool.device_of(p)
                                  for p in pages}))
    log(f"tp page table: a {len(long_prompt)}-token prompt's pages span "
        f"{span} of {engine.pool.n_devices} devices")
    require(span >= 2, f"page table spans {span} device")
    got = paged_logits(engine.params, engine.cfg, prompt, steps,
                       mesh=engine.mesh, ruleset=engine._ruleset,
                       unembed_fn=engine._unembed_fn)
    cfg = engine.cfg
    single = T.init_params(jax.random.PRNGKey(SEED), cfg, dtype=cfg.dtype)
    ref = paged_logits(single, cfg, prompt, steps)
    for name, g, r in zip(("prefill chunk", "decode"), got, ref):
        require(np.all(np.isfinite(g)), f"tp {name} logits not finite")
        d = rel_diff(g, r)
        log(f"tp parity: {name} logits, mesh engine vs one device max "
            f"|diff| / max |ref| = {d!r} (tolerance {TP_TOL})")
        require(d <= TP_TOL, f"tp {name} parity {d} > {TP_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the tensor-parallel phase")
    args = ap.parse_args(argv)
    devs = check_device(args.chips)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.launch import cache as cache_lib
    cache_dir = cache_lib.use_compile_cache()
    cache = CacheCounter()
    t0 = time.time()
    (four_chips if args.chips == 4 else one_chip)(devs)
    log(f"compile cache {cache_dir}: {cache.hits} hits, {cache.misses} "
        f"misses; total {time.time() - t0:.1f} s")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark harness: one module per paper table/figure (+ TPU-side benches).

Prints ``name,us_per_call,derived`` CSV. Each module exposes
``run() -> str | list[(subname, str)]`` returning the derived metric(s).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only table_1_1 fig_3_8
  PYTHONPATH=src python -m benchmarks.run --fast     # skip the slow sweeps
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time

MODULES = [
    # paper reproductions (ch.1 - ch.5)
    "table_1_1",        # FFMA register remapping (+15.4%)
    "table_2_1",        # warp scheduler mapping
    "table_3_1",        # memory hierarchy dissection (all 5 GPUs)  [slow]
    "fig_3_2",          # global latency classes
    "table_3_2",        # L1 bandwidth
    "fig_3_3",          # instruction cache hierarchy
    "table_3_4",        # L2 bandwidth
    "fig_3_7",          # constant cache broadcast
    "fig_3_8",          # register bank conflicts
    "fig_3_9",          # shared memory latency/bandwidth
    "fig_3_11",         # global memory bandwidth
    "fig_3_12",         # TLB sweep
    "table_4_1",        # instruction latencies
    "table_4_2",        # atomics under contention
    "fig_4_3",          # tensor core HMMA fragment maps
    "fig_4_8",          # floating-point throughput
    "table_5_1",        # interconnect p2p
    # TPU-side (the framework's own microbenchmarks)
    "tpu_mxu",          # MXU alignment cliffs + autotuned GEMM blocks
    "tpu_vmem",         # VMEM working-set budget + host p-chase demo
    "tpu_collectives",  # ICI alpha-beta curves over a real mesh  [slow]
    "tpu_e2e",          # roofline summary of the dry-run cells
    "tpu_serving",      # engine tokens/sec + modeled flash-decode speedup
    "breaking_point",   # load sweep + faults + telemetry overhead/drift
]

SLOW = {"table_3_1", "tpu_collectives"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    from repro.launch import cache as cache_lib
    cache_lib.use_compile_cache()
    names = args.only if args.only else MODULES
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        if args.fast and name in SLOW:
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            t0 = time.perf_counter()
            out = mod.run()
            us = (time.perf_counter() - t0) * 1e6
            rows = out if isinstance(out, list) else [("", out)]
            for sub, derived in rows:
                full = f"{name}.{sub}" if sub else name
                print(f"{full},{us:.1f},{derived}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Calibration launcher: probe the serving-path cost constants on this
backend and persist them for the engine's ``choose_*`` decisions.

  PYTHONPATH=src python -m repro.launch.calibrate            # full pass
  PYTHONPATH=src python -m repro.launch.calibrate --fast     # CI smoke
  PYTHONPATH=src python -m repro.launch.calibrate --no-persist --json

Each probe prints its measured value next to the hand-set assumption it
replaces and the drift ratio between them; the final line says which
constant set ``resolve_constants`` now returns. Undo with
``REPRO_DEFAULT_CONSTANTS=1`` (or ``--default-constants`` on the serve
launcher) — the defaults stay the documented, reproducible fallback.
"""

from __future__ import annotations

import argparse
import json
import time

from repro.core import autotune, calibrate
from repro.launch import cache as cache_lib


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer trials / smaller sweeps (CI smoke)")
    ap.add_argument("--no-persist", action="store_true",
                    help="measure and report without writing the cache")
    ap.add_argument("--json", action="store_true",
                    help="emit the calibration report as JSON")
    args = ap.parse_args(argv)
    cache_lib.use_compile_cache()

    backend = autotune._backend_key()
    persist = not args.no_persist
    t0 = time.time()
    results = calibrate.run_calibration(fast=args.fast, persist=persist)
    elapsed = time.time() - t0
    assumed = autotune.assumed_constants()

    if args.json:
        report = autotune.calibration_report()
        report["probe_details"] = {
            n: dict(value=r.value, unit=r.unit, n_trials=r.n_trials,
                    spread=r.spread, **r.detail)
            for n, r in results.items()}
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        print(f"== calibration [{backend}:{autotune._mesh_key(None)}] "
              f"{elapsed:.1f}s ==")
        print(f"{'constant':18s} {'measured':>12s} {'assumed':>12s} "
              f"{'drift':>8s} {'unit':>10s} {'n':>4s} {'spread':>7s}")
        for name, r in results.items():
            drift = autotune.drift_ratio(r.value, assumed[name])
            print(f"{name:18s} {r.value:12.4e} {assumed[name]:12.4e} "
                  f"{drift:8.2f} {r.unit:>10s} {r.n_trials:4d} "
                  f"{r.spread:7.2f}")

    resolved = autotune.resolve_constants()
    if persist:
        assert resolved.source == "calibrated", resolved
        assert len(results) >= 5, sorted(results)
    if not args.json:
        verb = "persisted; engine decisions now price from" \
            if persist else "not persisted; engines keep"
        print(f"constants {verb} the "
              f"'{resolved.source}' set "
              f"(backend={resolved.backend or backend}, "
              f"ts={resolved.timestamp:.0f})")
    return results


if __name__ == "__main__":
    main()

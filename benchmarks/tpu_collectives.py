"""ICI collective microbenchmarks over a real (placeholder-device) mesh:
compiled wire bytes vs the alpha-beta model (ch.5 TPU analogue).

Runs in a subprocess so the harness keeps its single CPU device."""
import json
import os
import subprocess
import sys

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import sys
sys.path.insert(0, os.environ["REPRO_SRC"])
import json
from repro.core import collectives
from repro.launch import mesh as mesh_mod
mesh = mesh_mod.make_mesh((4, 4), ("data", "model"))
out = []
for kind in ("all_reduce", "all_gather", "reduce_scatter", "all_to_all"):
    b = collectives.bench_collective(mesh, kind, 1 << 22, "model")
    out.append(dict(kind=kind, hlo_bytes=b.hlo_bytes,
                    modeled_bytes=b.modeled_bytes,
                    time_ms=b.modeled_time_s * 1e3))
small = collectives.bench_collective(mesh, "all_reduce", 1 << 12, "model")
big = collectives.bench_collective(mesh, "all_reduce", 1 << 26, "model")
out.append(dict(kind="alpha_beta", small_ms=small.modeled_time_s*1e3,
                big_ms=big.modeled_time_s*1e3))
print("JSON:" + json.dumps(out))
'''

def run():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    # Pin the CPU: the child wants 16 virtual host devices, never the
    # chip a parent process may hold.
    env = dict(os.environ, REPRO_SRC=src, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    data = json.loads(proc.stdout.split("JSON:")[1])
    rows = []
    for d in data:
        if d["kind"] == "alpha_beta":
            rows.append(("alpha_beta", f"4KiB={d['small_ms']:.3f}ms;"
                         f"64MiB={d['big_ms']:.3f}ms"))
        else:
            rows.append((d["kind"], f"hlo_bytes={d['hlo_bytes']:.3e};"
                         f"model_bytes={d['modeled_bytes']:.3e};"
                         f"t={d['time_ms']:.3f}ms"))
    return rows

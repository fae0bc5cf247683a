"""A cell's chips reach the engine, the weights, the memory and the peaks;
and a traced run stops the profiler only after its tail.

The four-chip cell runs in a child process on four virtual CPU devices,
through ``bench.run.execute`` with everything but the look for a chip."""

import json
import os
import subprocess
import sys

import jax
import pytest

import _bench_tiny as tb
from bench import gen, run

FOUR = r"""
import json, sys
import jax
sys.path[:0] = [ROOT_TESTS]
import _bench_tiny as tb
from bench import readers, run
from repro.serve import dist as serve_dist

root = tb.make_root(TMP)
bm = json.load(open(root + "/BENCHMARK.json"))
bm["workloads"].append({"name": "tiny.chat4", "config": "tiny",
                        "traffic": "tchat", "chips": 4, "why": "test"})
json.dump(bm, open(root + "/BENCHMARK.json", "w"))

FAKE = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "source": "test"}
run.peak_for = lambda dev: dict(FAKE)
run.peak_bytes = lambda dev: 1000 * (dev.id + 1)
seen = {}
build = run.build


def spy_build(a, c, seed, chips):
    params, engine, t_w, t_e = build(a, c, seed, chips)
    want = serve_dist.param_shardings(params, engine.mesh)
    seen["mesh"] = sorted(d.id for d in engine.mesh.devices.flat)
    seen["placed"] = all(
        x.sharding == s and x is y for x, s, y in
        zip(jax.tree.leaves(params), jax.tree.leaves(want),
            jax.tree.leaves(engine.params)))
    seen["sharded"] = any(len(x.sharding.device_set) == 4
                          and x.addressable_shards[0].data.size < x.size
                          for x in jax.tree.leaves(params))
    return params, engine, t_w, t_e


class SpyRun(readers.Run):
    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        seen["run"] = self


run.build = spy_build
readers.Run = SpyRun
res, lines = run.execute(root, "tiny.chat4", 2**31 + 3, 2.0, False)
r = seen["run"]
r.trace = {"window_s": 2.0, "busy_s": 1.0, "devices": []}
mfu = run.reader(root, "mfu.chat4")(r)
want = 100.0 * readers.window_flops(r) / (4 * FAKE["flops_per_s"]) / 2.0
print(json.dumps({"res": res, "lines": lines, "mesh": seen["mesh"],
                  "placed": seen["placed"], "sharded": seen["sharded"],
                  "peak": r.peak, "mfu": mfu, "mfu_want": want}))
"""


def test_a_four_chip_cell_runs_on_the_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(tb.REPO, "src"),
                                           tb.REPO]))
    code = FOUR.replace("ROOT_TESTS", repr(os.path.dirname(__file__))) \
        .replace("TMP", repr(str(tmp_path)))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=tb.REPO,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    res = out["res"]
    assert res["correct"], out["lines"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == 4
    assert res["device"]["memory_peak_bytes"] == 4000     # the fullest
    assert out["mesh"] == [0, 1, 2, 3]
    assert out["placed"] and out["sharded"]
    assert out["peak"] == {"flops_per_s": 4e12, "hbm_bytes_per_s": 4e11,
                           "source": "test"}
    assert out["mfu"] == pytest.approx(out["mfu_want"], rel=1e-12)


def test_the_traced_loop_stops_the_profiler_after_its_tail(tmp_path,
                                                           monkeypatch):
    """The window's annotation closes at the window's end; the profiler
    stops only once every request due in the window has its first token,
    after the loop's last tick."""
    root = tb.make_root(tmp_path)
    lay = run.layout(root, "tiny.chat")
    c, mix = lay["config"], lay["mix"]
    _, engine, _, _ = run.build(lay["arch"], c, 9, 1)
    run.warm(engine, c["serving"])
    calls = []
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", engine.ticks)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", engine.ticks)))
    seconds = 2.0
    reqs = gen.requests(mix, 9, c["vocab_size"],
                        horizon_s=mix["ramp_s"] + seconds + run.TAIL_S)
    with run.Compiles() as compiles:
        out = run.serve(engine, reqs, mix, seconds, True, compiles)
    assert calls == [("start", out["tick_open"]), ("stop", engine.ticks)]
    assert engine.ticks >= out["tick_close"]
    due = [r for r in out["recs"] if out["due_lo"] <= r.due < out["due_hi"]]
    assert due and all(r.times for r in due)
    after, took = out["stop"]
    assert after >= 0 and took >= 0

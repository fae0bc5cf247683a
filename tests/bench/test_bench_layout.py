"""The benchmark is driven by data: ``BENCHMARK.json`` names every
configuration, mix and metric, and each is a file of its own. Also the
generator's promises (same seed, same requests; every seed the same
sizes) and the contract's shape of ``BENCHMARK.json``."""

import json
import os
import re

import jax
import numpy as np
import pytest

import _bench_tiny as tb
from bench import gen, run
from bench.arch import dense

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

with open(os.path.join(tb.REPO, "BENCHMARK.json")) as _f:
    BM = json.load(_f)


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tb.make_root(tmp_path)
    cfg = dict(tb.CONFIG, name="other")
    with open(os.path.join(root, "bench", "configs", "other.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "bench", "traffic", "burst.json"), "w") as f:
        json.dump(dict(tb.CHAT, block_s=99.0), f)
    with open(os.path.join(root, "bench", "metrics", "answer.burst.py"),
              "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    bm = dict(tb.BENCHMARK)
    bm["configs"] = bm["configs"] + [dict(bm["configs"][0], name="other",
                                          file="bench/configs/other.json")]
    bm["workloads"] = bm["workloads"] + [
        {"name": "other.burst", "config": "other", "traffic": "burst",
         "chips": 1, "why": "test"}]
    bm["per_layer"] = bm["per_layer"] + [
        {"name": "answer.burst", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "scheduler",
         "moves": "out_tok_s", "workloads": ["other.burst"]}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    lay = run.layout(root, "other.burst")
    assert lay["config"]["name"] == "other"
    assert lay["mix"]["block_s"] == 99.0
    assert [m["name"] for m in lay["per_layer"]] == ["answer.burst"]
    assert lay["readers"]["answer.burst"](None) == 42.0
    # A per-layer metric without `workloads` goes to every cell that
    # reports the end-to-end metric it moves.
    assert [m["name"] for m in run.layout(root, "tiny.chat")["per_layer"]] \
        == ["queue_wait_p90_ms.chat"]


def test_benchmark_json_has_the_contract_shape():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert isinstance(BM["run_seconds"], int) and \
        1 <= BM["run_seconds"] <= 51
    for p in BM["paths"]:
        assert os.path.isdir(os.path.join(tb.REPO, p))
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in BM["paths"]))
        with open(os.path.join(tb.REPO, c["file"])) as f:
            body = json.load(f)
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert set(body["published"]) == set(c["reduced"])
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert os.path.isfile(run.reader_path(tb.REPO, m["name"]))
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in BM["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(tb.REPO, "bench", "traffic",
                                           w["traffic"] + ".json"))
        lay = run.layout(tb.REPO, w["name"])
        got = {m["name"] for m in lay["end_to_end"]}
        assert "setup_s" in got and len(got) >= 2 and lay["per_layer"]
        for m in lay["per_layer"]:
            assert m["moves"] in got, (w["name"], m["name"])


def test_generator_same_seed_same_requests():
    seed = 2**31 + 12345
    a = gen.requests(tb.CHAT, seed, 256, horizon_s=5.0)
    b = gen.requests(tb.CHAT, seed, 256, horizon_s=5.0)
    assert [(r.due_s, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.due_s, r.max_new, r.prompt.tolist()) for r in b]
    assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))


def test_a_metric_of_a_new_cell_reuses_the_reader_of_its_stem(tmp_path):
    """``decode_step_ms.<cell>`` is read by ``decode_step_ms.py``; a file
    named for the whole metric, where there is one, is read instead."""
    root = tb.make_root(tmp_path)
    metrics = os.path.join(root, "bench", "metrics")
    assert run.reader_path(root, "decode_step_ms.other") == \
        os.path.join(metrics, "decode_step_ms.py")
    assert run.reader_path(root, "mfu.prefill.other") == \
        os.path.join(metrics, "mfu.prefill.py")
    with open(os.path.join(metrics, "decode_step_ms.other.py"), "w") as f:
        f.write("def read(run):\n    return 1.0\n")
    assert run.reader(root, "decode_step_ms.other")(None) == 1.0
    with pytest.raises(SystemExit):
        run.reader_path(root, "no_such_metric.chat")


def test_a_mix_that_passes_the_sliding_window_is_refused(tmp_path):
    root = tb.make_root(tmp_path)
    path = os.path.join(root, "bench", "configs", "tiny.json")
    with open(path, "w") as f:
        json.dump(dict(tb.CONFIG, sliding_window=255), f)
    with pytest.raises(SystemExit, match="sliding window"):
        run.layout(root, "tiny.chat")
    with open(path, "w") as f:
        json.dump(dict(tb.CONFIG, sliding_window=256), f)
    assert run.layout(root, "tiny.chat")["config"]["sliding_window"] == 256


def _repo_mix(name):
    with open(os.path.join(tb.REPO, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix,seconds", [(tb.CHAT, 2.0),
                                         (_repo_mix("chat"),
                                          BM["run_seconds"])],
                         ids=["tiny", "chat"])
def test_every_seed_offers_the_same_work_in_the_window(mix, seconds):
    """The window holds whole blocks: the same number of requests, sizes
    and gaps whatever the seed, in another order."""
    lo, hi = mix["ramp_s"], mix["ramp_s"] + seconds

    def window(seed):
        rs = [r for r in gen.requests(mix, seed, 256, horizon_s=hi)
              if lo <= r.due_s < hi]
        dues = [r.due_s for r in rs] + [hi]
        assert dues[0] == lo
        return (sorted(len(r.prompt) for r in rs),
                sorted(r.max_new for r in rs),
                sorted(np.round(np.diff(dues), 9)), [r.rid for r in rs])

    a, b = window(2**31 + 11), window(7)
    assert a[:3] == b[:3]
    assert len(a[0]) == round(seconds / mix["block_s"]) * mix["block"]
    assert a[3] == b[3]


@pytest.mark.parametrize("mix", [tb.CHAT, tb.DOC], ids=["poisson",
                                                         "backlog"])
def test_every_seed_offers_the_same_sizes_per_block(mix):
    n = mix["block"]

    def block_sizes(seed):
        rs = gen.requests(mix, seed, 256, horizon_s=5.0)[:n + 1]
        return (sorted(len(r.prompt) for r in rs[:n]),
                sorted(r.max_new for r in rs[:n]), rs[n].due_s)

    a, b = block_sizes(1), block_sizes(2)
    assert a[:2] == b[:2]
    assert a[2] == pytest.approx(b[2])
    for r in gen.requests(mix, 3, 256, horizon_s=5.0):
        assert mix["prompt_tokens"]["lo"] <= len(r.prompt) \
            <= mix["prompt_tokens"]["hi"]
        assert len(r.prompt) + r.max_new <= mix["max_tokens"]


@pytest.mark.parametrize("name", ["qwen3-4b", "phi3-mini-3.8b"])
def test_weights_match_the_programs_parameter_layout(name):
    from repro.models import transformer as T
    with open(os.path.join(tb.REPO, "bench", "configs", name + ".json")) as f:
        c = json.load(f)
    mcfg = dense.program_config(T, c)
    key = jax.random.PRNGKey(0)
    want = jax.eval_shape(lambda k: T.init_params(k, mcfg, dtype=mcfg.dtype),
                          key)
    got = jax.eval_shape(lambda k: dense.make(c, k), key)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(want)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(got)]
    assert mcfg.dhead == c["head_dim"]


def test_reference_agrees_with_the_programs_float32_forward():
    """The reference is written apart from the program; at float32 and a
    tiny size the two compute the same logits."""
    from repro.models import transformer as T
    c = dict(tb.CONFIG, torch_dtype="float32")
    params = dense.make(c, jax.random.PRNGKey(3))
    toks = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    want, _, _ = T.forward(params, dense.program_config(T, c), toks[None])
    rows = np.arange(40)
    got = dense.logits(params, c, toks, rows)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               atol=2e-4, rtol=2e-4)

"""Architecture modules: each configuration names one by its ``"arch"``,
``bench/arch/<arch>.py``, and a new one is found by that name alone. The
dense module makes the same weights and reference logits, bit for bit,
as the benchmark's weights and reference did before they moved into it
(checksums recorded there)."""

import hashlib
import json
import os
import sys

import jax
import numpy as np
import pytest

import _bench_tiny as tb
import bench.arch
from bench import arch, run
from bench.arch import dense

TOY = "from bench.arch.dense import *  # noqa: F401,F403\n"


def _digest(tree) -> str:
    m = hashlib.sha256()
    for x in jax.tree.leaves(tree):
        m.update(np.asarray(x).tobytes())
    return m.hexdigest()[:16]


@pytest.mark.parametrize("name", ["qwen3-4b", "phi3-mini-3.8b"])
def test_each_config_names_its_arch_module(name):
    with open(os.path.join(tb.REPO, "bench", "configs", name + ".json")) as f:
        c = json.load(f)
    assert arch.load(c) is dense
    assert all(callable(getattr(dense, f)) for f in arch.EXPORTS)


@pytest.fixture
def extra_arch_dir(tmp_path, monkeypatch):
    """A second directory of ``bench.arch``, as a new file there would be."""
    d = tmp_path / "arch"
    d.mkdir()
    monkeypatch.setattr(bench.arch, "__path__",
                        [*bench.arch.__path__, str(d)])
    yield d
    for name in ("toy", "half"):
        sys.modules.pop(f"bench.arch.{name}", None)


def test_a_new_arch_is_found_by_name(tmp_path, extra_arch_dir):
    (extra_arch_dir / "toy.py").write_text(TOY)
    root = tb.make_root(tmp_path / "root")
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(dict(tb.CONFIG, arch="toy"), f)
    mod = run.layout(root, "tiny.chat")["arch"]
    assert mod.__name__ == "bench.arch.toy"
    assert mod.layer_matmul_params(tb.CONFIG) == \
        dense.layer_matmul_params(tb.CONFIG)


@pytest.mark.parametrize("value,match", [
    (None, "names no"), ("nosuch", "no bench/arch/nosuch.py"),
    ("../dense", "not the name"), ("half", "lacks make, program_config")],
    ids=["missing", "unknown", "path", "incomplete"])
def test_a_missing_or_unknown_arch_is_refused(tmp_path, extra_arch_dir,
                                              value, match):
    (extra_arch_dir / "half.py").write_text(
        "def shapes(c):\n    return {}\n")
    cfg = {k: v for k, v in tb.CONFIG.items() if k != "arch"}
    if value is not None:
        cfg["arch"] = value
    root = tb.make_root(tmp_path / "root")
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    with pytest.raises(SystemExit, match=match):
        run.layout(root, "tiny.chat")


def test_dense_weights_and_reference_are_unchanged_bit_for_bit():
    """Checksums of ``bench/weights.make`` and ``bench/reference.logits``
    taken at the commit before they moved into ``bench/arch/dense.py``,
    at the tiny configuration on the CPU."""
    params = dense.make(tb.CONFIG, jax.random.PRNGKey(2**31 - 5))
    assert _digest(params) == "980f7da5c37335bf"
    toks = np.random.default_rng(0).integers(0, 256, 70).astype(np.int32)
    rows = np.arange(30, 70)
    assert _digest(dense.logits(params, tb.CONFIG, toks, rows, "float32")) \
        == "41a044f96957a433"
    assert _digest(dense.logits(params, tb.CONFIG, toks, rows, "fp8")) \
        == "36d0ef6fc2f6a92f"

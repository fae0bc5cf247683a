"""Sharding rules (divisibility fallback), compression, and multi-device
shard_map paths (collective matmul, pipeline, elastic checkpoints) — the
multi-device parts run in one subprocess with 8 host devices."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import compression, sharding as shd
from jax.sharding import PartitionSpec as P


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_divisibility_fallback():
    rs = shd.Ruleset(mesh=FakeMesh({"data": 16, "model": 16}))
    # 14 heads don't divide 16 -> replicated; 32 heads do -> sharded.
    assert rs.spec(["heads"], [14]) == P(None)
    assert rs.spec(["heads"], [32]) == P("model")
    assert rs.spec(["batch", None], [256, 4096]) == P(("pod", "data"), None) \
        or rs.spec(["batch", None], [256, 4096]) == P("data", None)


def test_batch_composes_pod_and_data():
    rs = shd.Ruleset(mesh=FakeMesh({"pod": 2, "data": 16, "model": 16}))
    assert rs.spec(["batch"], [256]) == P(("pod", "data"))
    # batch=1 cannot shard.
    assert rs.spec(["batch"], [1]) == P(None)


def test_param_specs_by_leaf_name():
    rs = shd.Ruleset(mesh=FakeMesh({"data": 16, "model": 16}))
    assert shd.param_spec(("blocks", "attn", "wq"), (24, 896, 32, 64), rs) \
        == P(None, None, "model", None)
    # qwen2: 14 heads replicate.
    assert shd.param_spec(("blocks", "attn", "wq"), (24, 896, 14, 64), rs) \
        == P(None, None, None, None)
    assert shd.param_spec(("mlp", "w_gate"), (4096, 12800), rs) \
        == P(None, "model")
    assert shd.param_spec(("moe", "expert_gate"), (16, 4096, 10752), rs) \
        == P("model", None, None)


def test_fsdp_shards_largest_free_dim():
    rs = shd.Ruleset(mesh=FakeMesh({"data": 16, "model": 16}), fsdp=True)
    spec = shd.param_spec(("mlp", "w_gate"), (4096, 12800), rs)
    assert spec == P("data", "model")


def test_shard_noop_without_mesh():
    x = jnp.ones((4, 4))
    assert shd.shard(x, "batch", None) is x


def test_int8_compression_error_bound():
    rng = np.random.RandomState(0)
    g = {"w": jnp.asarray(rng.randn(1000), jnp.float32)}
    out = compression.int8_roundtrip(g)
    err = np.abs(np.asarray(out["w"]) - np.asarray(g["w"]))
    scale = np.abs(np.asarray(g["w"])).max() / 127
    assert err.max() <= scale * 1.01


def test_error_feedback_accumulates():
    g = {"w": jnp.full((256,), 0.004, jnp.float32) +
         jnp.linspace(0, 1e-4, 256)}
    res = compression.ErrorFeedback.init(g)
    comp, res = compression.ErrorFeedback.compress(g, res)
    # Residual is exactly the quantization error.
    np.testing.assert_allclose(
        np.asarray(res["w"]),
        np.asarray(g["w"]) - np.asarray(comp["w"]), atol=1e-7)


def test_error_feedback_threads_through_train_step():
    """EF-SGD end to end: the residual lives in TrainState, the jitted
    step consumes and refreshes it, and plain states keep the old pytree
    (no ``ef`` leaf — checkpoints and sharding derivations unchanged)."""
    from repro import configs
    from repro.data import DataConfig, SyntheticLMData
    from repro.train import steps as train_steps

    cfg = configs.get_smoke("qwen3-4b")
    plain = train_steps.init_state(jax.random.PRNGKey(0), cfg)
    assert "ef" not in plain.tree()
    state = train_steps.init_state(jax.random.PRNGKey(0), cfg,
                                   error_feedback=True).tree()
    assert "ef" in state
    assert all(float(jnp.abs(l).max()) == 0.0
               for l in jax.tree.leaves(state["ef"]))

    data = SyntheticLMData(DataConfig(vocab=cfg.vocab, seq_len=16,
                                      global_batch=2, seed=0))
    step = jax.jit(train_steps.make_train_step(
        cfg, compress_grads=True, error_feedback=True), donate_argnums=(0,))
    for i in range(2):
        tokens, labels = data.batch_at(i)
        state, metrics = step(state, {"tokens": jnp.asarray(tokens),
                                      "labels": jnp.asarray(labels)})
    assert np.isfinite(float(metrics["loss"]))
    # The residual is the quantization error — nonzero for real gradients.
    assert any(float(jnp.abs(l).max()) > 0.0
               for l in jax.tree.leaves(state["ef"]))
    # Round-trips through TrainState (checkpoint restore path).
    rt = train_steps.TrainState.from_tree(state)
    assert rt.ef is not None and int(np.asarray(rt.step)) == 2


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from repro.launch import mesh as mesh_mod
from repro.dist import collective_matmul, pipeline, sharding as shd
from repro.checkpoint import CheckpointManager

results = {}

# 1. Collective (overlapped all-gather) matmul == dense matmul.
mesh = mesh_mod.make_mesh((2, 4), ("data", "model"))
rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(16, 32), jnp.float32)
w = jnp.asarray(rng.randn(32, 24), jnp.float32)
out = collective_matmul.ag_matmul(x, w, mesh, axis="model")
np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w), rtol=1e-4,
                           atol=1e-4)
hlo = jax.jit(lambda x, w: collective_matmul.ag_matmul(x, w, mesh,
              "model")).lower(x, w).compile().as_text()
assert "collective-permute" in hlo and "all-gather" not in hlo.split(
    "ENTRY")[-1], "overlap should replace the big all-gather"
results["collective_matmul"] = "ok"

# 2. GPipe pipeline == sequential stack.
pmesh = mesh_mod.make_mesh((4,), ("stage",))
def layer(wb, x):
    return jnp.tanh(x @ wb["w"] + wb["b"])
ws = {"w": jnp.asarray(rng.randn(4, 8, 8) * 0.5, jnp.float32),
      "b": jnp.asarray(rng.randn(4, 8) * 0.1, jnp.float32)}
micro = jnp.asarray(rng.randn(6, 5, 8), jnp.float32)
piped = pipeline.gpipe(layer, pmesh, axis="stage")(ws, micro)
seq = micro
for i in range(4):
    seq = layer({"w": ws["w"][i], "b": ws["b"][i]}, seq)
np.testing.assert_allclose(np.asarray(piped), np.asarray(seq), rtol=1e-4,
                           atol=1e-4)
assert abs(pipeline.bubble_fraction(4, 6) - 3/9) < 1e-9
results["pipeline"] = "ok"

# 3. Elastic checkpoint: save unsharded, restore sharded onto a mesh, then
#    back onto a differently-shaped mesh.
import tempfile
d = tempfile.mkdtemp()
tree = {"mlp": {"w_gate": jnp.asarray(rng.randn(32, 64), jnp.float32)}}
mgr = CheckpointManager(d)
mgr.save(3, tree)
mgr.wait()
for shape, axes in (((2, 4), ("data", "model")), ((4, 2), ("data", "model"))):
    m = mesh_mod.make_mesh(shape, axes)
    rs = shd.Ruleset(mesh=m, fsdp=True)
    got, _ = mgr.restore(jax.tree.map(jnp.zeros_like, tree), ruleset=rs)
    np.testing.assert_allclose(np.asarray(got["mlp"]["w_gate"]),
                               np.asarray(tree["mlp"]["w_gate"]))
    assert len(got["mlp"]["w_gate"].sharding.device_set) > 1
results["elastic"] = "ok"

print("MULTIDEV_RESULTS:" + ",".join(f"{k}={v}" for k, v in results.items()))
"""


@pytest.mark.slow
def test_multidevice_shard_map_paths(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "multidev.py"
    script.write_text(MULTIDEV_SCRIPT)
    proc = subprocess.run([sys.executable, str(script), src],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "collective_matmul=ok" in proc.stdout
    assert "pipeline=ok" in proc.stdout
    assert "elastic=ok" in proc.stdout

"""Random weights from the seed, made on the device in one jitted call.

The tree is the layout the serving engine loads (a checkpoint format):
per-layer leaves stacked over the layers, attention weights kept 3-D.
It is built here from the configuration file, not by the program, so the
plain reference (``bench/reference.py``) can read the same weights
without taking anything the program made. Scales: norms are ones, the
embedding is N(0, 1), and every projection is N(0, 1/fan_in).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def shapes(c: dict) -> dict:
    """Leaf shapes by path, from the HF-style keys of a config file."""
    L, d, f, V = (c["num_hidden_layers"], c["hidden_size"],
                  c["intermediate_size"], c["vocab_size"])
    h, kvh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    attn = {"wq": (L, d, h, hd), "wk": (L, d, kvh, hd),
            "wv": (L, d, kvh, hd), "wo": (L, h, hd, d)}
    if c.get("qk_norm"):
        attn["q_norm"] = {"scale": (L, hd)}
        attn["k_norm"] = {"scale": (L, hd)}
    return {
        "embed": {"embedding": (V, d)},
        "blocks": [{
            "ln1": {"scale": (L, d)},
            "attn": attn,
            "ln2": {"scale": (L, d)},
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                    "w_down": (L, f, d)},
        }],
        "ln_f": {"scale": (d,)},
        "unembed": {"lm_head": (d, V)},
    }


def _fan_in(name: str, shape) -> float:
    if name == "embedding":
        return 1.0
    if name == "wo":                      # (L, h, hd, d)
        return float(shape[1] * shape[2])
    if name == "lm_head":                 # (d, V)
        return float(shape[0])
    return float(shape[1])                # (L, in, ...)


def make(c: dict, key, dtype=None):
    """Every leaf in ``dtype`` (default: the file's ``torch_dtype``)."""
    dtype = jnp.dtype(dtype or c["torch_dtype"])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(c), is_leaf=lambda x: isinstance(x, tuple))

    def build(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            name = path[-1].key
            if name == "scale":
                out.append(jnp.ones(shape, dtype))
                continue
            k = jax.random.fold_in(key, i)
            out.append((jax.random.normal(k, shape, jnp.float32)
                        * _fan_in(name, shape) ** -0.5).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)(key)

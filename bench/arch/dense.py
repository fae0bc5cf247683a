"""The dense decoder: pre-norm RMSNorm blocks, GQA attention with optional
per-head q/k RMSNorm, rotary positions with half-split rotation, SwiGLU
MLP, final norm and an untied output head.

Weights: random, from the seed, made on the device in one jitted call.
The tree is the layout the serving engine loads (a checkpoint format):
per-layer leaves stacked over the layers, attention weights kept 3-D.
It is built here from the configuration file, not by the program, so the
plain reference below reads the same weights without taking anything
the program made. Scales: norms are ones, the embedding is N(0, 1), and
every projection is N(0, 1/fan_in).

The plain reference: the forward pass in float32, written from the
published architecture in straightforward ``jax.numpy``. It imports
nothing of the program. Every matmul runs at ``Precision.HIGHEST``: on a
TPU a float32 matmul otherwise runs in bfloat16 passes.

``mode="fp8"`` is the control: the same forward with every matmul's
operands rounded to float8 e4m3 (weights scaled per tensor, activations
per row), the lower precision a later change would be tempted by. It
has to fail the comparison that the program passes.

The forward runs layer by layer (``lax.scan`` over the stacked layers,
each layer's weights cast to float32 inside the step) and attends in
blocks of queries, so it fits beside the weights on one chip. On a mesh
it runs under ``jit`` on the weights as they are sharded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512        # queries per attention block
PAD = 1024           # sequence lengths are padded to this multiple
ROWS = 512           # logit rows are padded to this multiple
FP8_MAX = 448.0      # largest finite float8 e4m3 value


def program_config(T, c: dict):
    """The program's ``ModelConfig`` from the HF-style keys of the file."""
    return T.ModelConfig(
        name=c["name"], n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], head_dim=c["head_dim"],
        qk_norm=bool(c.get("qk_norm")), rope_theta=float(c["rope_theta"]),
        compute_dtype=c["torch_dtype"])


# -- weights ---------------------------------------------------------------

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


def make(c: dict, key, shardings=None):
    """Every leaf in the file's ``torch_dtype``; with ``shardings`` (a
    tree of shardings like ``shapes(c)``'s) each leaf is made in its
    place, so no device ever holds the whole tree."""
    dtype = jnp.dtype(c["torch_dtype"])
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

    if shardings is None:
        return jax.jit(build)(key)
    return jax.jit(build, out_shardings=shardings)(key)


# -- the reference ---------------------------------------------------------

def _q8(x, axes):
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(eq, a, b, fp8, a_rows=True):
    """einsum of an activation ``a`` (token axis first, scaled per token
    under fp8) and ``b`` (a weight scaled per tensor, or with
    ``a_rows=False`` another activation scaled per leading row)."""
    if fp8:
        a = _q8(a, tuple(range(1, a.ndim)))
        b = _q8(b, None if a_rows else tuple(range(1, b.ndim)))
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (n, heads, hd) at positions 0..n-1."""
    n, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, fp8):
    """Causal GQA: q (n, h, hd), k/v (n, kvh, hd) -> (n, h, hd)."""
    n, h, hd = q.shape
    kvh = k.shape[1]
    q = q.reshape(n, kvh, h // kvh, hd)
    outs = []
    for lo in range(0, n, Q_BLOCK):
        hi = min(n, lo + Q_BLOCK)
        s = _ein("qkgd,tkd->kgqt", q[lo:hi], k[:hi], fp8, a_rows=False)
        s = s / np.sqrt(hd)
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _q8(p, -1)
            vv = _q8(v[:hi], (1, 2))
        else:
            vv = v[:hi]
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p, vv, precision=HI))
    return jnp.concatenate(outs, 0).reshape(n, h, hd)


@functools.partial(jax.jit, static_argnames=("statics", "fp8"))
def _forward(params, tokens, rows, statics, fp8):
    eps, theta, qk_norm = statics
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0
                 ).astype(jnp.float32)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def layer(x, p):
        a = p["attn"]
        hn = _rms(x, p["ln1"]["scale"], eps)
        q = _ein("nd,dhk->nhk", hn, f32(a["wq"]), fp8)
        k = _ein("nd,dhk->nhk", hn, f32(a["wk"]), fp8)
        v = _ein("nd,dhk->nhk", hn, f32(a["wv"]), fp8)
        if qk_norm:
            q = _rms(q, a["q_norm"]["scale"], eps)
            k = _rms(k, a["k_norm"]["scale"], eps)
        o = _attend(_rope(q, theta), _rope(k, theta), v, fp8)
        x = x + _ein("nhk,hkd->nd", o, f32(a["wo"]), fp8)
        m = p["mlp"]
        hn = _rms(x, p["ln2"]["scale"], eps)
        g = _ein("nd,df->nf", hn, f32(m["w_gate"]), fp8)
        u = _ein("nd,df->nf", hn, f32(m["w_up"]), fp8)
        x = x + _ein("nf,fd->nd", jax.nn.silu(g) * u, f32(m["w_down"]), fp8)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"][0])
    x = _rms(jnp.take(x, rows, axis=0), params["ln_f"]["scale"], eps)
    return _ein("nd,dv->nv", x, f32(params["unembed"]["lm_head"]), fp8)


def logits(params, c: dict, tokens: np.ndarray, rows: np.ndarray,
           mode: str = "float32") -> jax.Array:
    """float32 logits (len(rows), vocab) of the causal forward over
    ``tokens`` at positions ``rows``."""
    assert mode in ("float32", "fp8"), mode
    n = len(tokens)
    n_pad = -(-n // PAD) * PAD
    r_pad = -(-len(rows) // ROWS) * ROWS
    toks = np.zeros(n_pad, np.int32)
    toks[:n] = tokens
    idx = np.full(r_pad, rows[-1], np.int32)
    idx[:len(rows)] = rows
    statics = (float(c["rms_norm_eps"]), float(c["rope_theta"]),
               bool(c.get("qk_norm")))
    out = _forward(params, jnp.asarray(toks), jnp.asarray(idx),
                   statics=statics, fp8=(mode == "fp8"))
    return out[:len(rows)]


# -- counts for bench/work.py ------------------------------------------------

def _itemsize(c: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[c["torch_dtype"]]


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, h, kvh, hd, f = (c["hidden_size"], c["num_attention_heads"],
                        c["num_key_value_heads"], c["head_dim"],
                        c["intermediate_size"])
    return d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * f


def kv_bytes(c: dict, rows: int) -> int:
    """K and V of ``rows`` positions in all layers."""
    L, kvh, hd = (c["num_hidden_layers"], c["num_key_value_heads"],
                  c["head_dim"])
    return 2 * L * rows * kvh * hd * _itemsize(c)

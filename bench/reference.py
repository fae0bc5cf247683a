"""The plain reference: a dense decoder forward pass in float32.

Written from the published architecture (pre-norm RMSNorm blocks, GQA
attention with optional per-head q/k RMSNorm, rotary positions with
half-split rotation, SwiGLU MLP, final norm and output head) in
straightforward ``jax.numpy``. It imports nothing of the program and
reads the weights the benchmark made (``bench/weights.py``). Every matmul
runs at ``Precision.HIGHEST``: on a TPU a float32 matmul otherwise runs
in bfloat16 passes.

``mode="fp8"`` is the control: the same forward with every matmul's
operands rounded to float8 e4m3 (weights scaled per tensor, activations
per row), the lower precision a later change would be tempted by. It
has to fail the comparison that the program passes.

The forward runs layer by layer (``lax.scan`` over the stacked layers,
each layer's weights cast to float32 inside the step) and attends in
blocks of queries, so it fits beside the weights on one chip.
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

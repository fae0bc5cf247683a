"""Transformer stacks: decoder LMs, pattern-interleaved hybrids (Jamba,
llama4, llama-vision) and encoder-decoder (Whisper) — one implementation.

Layer stacks are expressed as a repeating *pattern* of layer kinds
(("attn",), ("mamba",)*4+("attn",)+..., ("cross","attn","attn","attn","attn")).
Parameters are stacked per pattern position with a leading period dim and the
stack runs under ``lax.scan`` — 100-layer models lower as one period body, so
the 512-device dry-run compiles in seconds instead of minutes. Decode caches
are pytrees stacked the same way and threaded through the scan.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import sharding
from repro.models import layers, mamba as mamba_mod, moe as moe_mod

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    n_layers: int
    n_ctx: int                   # frontend tokens (whisper: 1500 frames)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 -> d_model // n_heads
    norm: str = "rms"            # "rms" | "layer"
    activation: str = "swiglu"   # "swiglu" | "gelu"
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: Optional[float] = 10000.0
    pattern: Tuple[str, ...] = ("attn",)
    moe_positions: Tuple[int, ...] = ()      # pattern positions with MoE MLP
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_impl: str = "capacity"
    moe_capacity_factor: float = 1.25
    mamba_d_state: int = 128
    mamba_head_dim: int = 64
    mamba_expand: int = 2
    encoder: Optional[EncoderConfig] = None  # enc-dec (whisper)
    n_frontend_tokens: int = 0               # vision/audio stub tokens
    scan_layers: bool = True
    compute_dtype: str = "float32"
    use_flash: bool = False
    use_ssd_kernel: bool = False
    expand_kv: bool = False      # GQA KV broadcast for model-axis sharding
    attn_probs_fp32: bool = True # bf16 probs = beyond-paper memory opt
    remat: bool = False
    remat_policy: str = "full"   # "full" | "dots" (save matmul outputs)

    def __post_init__(self):
        assert self.n_layers % len(self.pattern) == 0, \
            (self.name, self.n_layers, self.pattern)

    @property
    def dhead(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def periods(self) -> int:
        return self.n_layers // len(self.pattern)

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)

    def attn_cfg(self, causal=True) -> layers.AttnConfig:
        return layers.AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.dhead,
            qk_norm=self.qk_norm, qkv_bias=self.qkv_bias,
            rope_theta=self.rope_theta, causal=causal,
            expand_kv=self.expand_kv, probs_fp32=self.attn_probs_fp32)

    def mamba_cfg(self) -> mamba_mod.MambaConfig:
        return mamba_mod.MambaConfig(
            d_model=self.d_model, d_state=self.mamba_d_state,
            head_dim=self.mamba_head_dim, expand=self.mamba_expand)

    def moe_cfg(self) -> moe_mod.MoEConfig:
        return moe_mod.MoEConfig(
            d_model=self.d_model, d_ff=self.d_ff, n_experts=self.n_experts,
            top_k=self.top_k, n_shared=self.n_shared_experts,
            impl=self.moe_impl, capacity_factor=self.moe_capacity_factor)

    def mlp_cfg(self) -> layers.MLPConfig:
        return layers.MLPConfig(self.d_model, self.d_ff, self.activation)


# ----------------------------------------------------------------------------
# Per-layer init/apply
# ----------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, kind: str, pos: int) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": layers.norm_init(cfg.norm, cfg.d_model)}
    if kind == "attn":
        p["attn"] = layers.attention_init(ks[0], cfg.attn_cfg())
    elif kind == "mamba":
        p["mamba"] = mamba_mod.mamba_init(ks[0], cfg.mamba_cfg())
    elif kind == "cross":
        p["attn"] = layers.attention_init(ks[0], cfg.attn_cfg())
        p["ln_x"] = layers.norm_init(cfg.norm, cfg.d_model)
        p["xattn"] = layers.cross_attention_init(
            ks[1], cfg.attn_cfg(causal=False))
    else:
        raise ValueError(kind)
    if cfg.d_ff > 0:
        p["ln2"] = layers.norm_init(cfg.norm, cfg.d_model)
        if pos in cfg.moe_positions and cfg.n_experts:
            p["moe"] = moe_mod.moe_init(ks[2], cfg.moe_cfg())
        else:
            p["mlp"] = layers.mlp_init(ks[2], cfg.mlp_cfg())
    return p


def _layer_apply(params: Params, cfg: ModelConfig, kind: str, x,
                 cross_kv=None, cache=None):
    """One block: mixer + (dense|MoE) MLP, pre-norm residual."""
    aux = jnp.zeros((), jnp.float32)
    h = layers.norm(cfg.norm, params["ln1"], x)
    if kind == "mamba":
        mix, new_cache = mamba_mod.mamba_apply(
            params["mamba"], cfg.mamba_cfg(), h, cache=cache,
            use_kernel=cfg.use_ssd_kernel)
    else:
        mix, new_cache = layers.attention_apply(
            params["attn"], cfg.attn_cfg(), h, cache=cache,
            use_flash=cfg.use_flash)
    x = x + mix
    if kind == "cross":
        hx = layers.norm(cfg.norm, params["ln_x"], x)
        x = x + layers.cross_attention_apply(
            params["xattn"], cfg.attn_cfg(causal=False), hx,
            cross_kv.astype(x.dtype))
    if "moe" in params:
        h2 = layers.norm(cfg.norm, params["ln2"], x)
        y, aux = moe_mod.moe_apply(params["moe"], cfg.moe_cfg(), h2)
        x = x + y
    elif "mlp" in params:
        h2 = layers.norm(cfg.norm, params["ln2"], x)
        x = x + layers.mlp_apply(params["mlp"], cfg.mlp_cfg(), h2)
    return x, new_cache, aux


# ----------------------------------------------------------------------------
# Stacks (pattern scan)
# ----------------------------------------------------------------------------

def _stack_init(key, cfg: ModelConfig) -> List[Params]:
    """Per pattern position: params stacked over periods (leading dim).
    vmap over the period keys gives the same values as one init per key,
    stacked, but traces one layer instead of ``periods`` of them."""
    blocks = []
    for pos, kind in enumerate(cfg.pattern):
        keys = jax.random.split(jax.random.fold_in(key, pos), cfg.periods)
        blocks.append(jax.vmap(
            lambda k, kind=kind, pos=pos: _layer_init(k, cfg, kind, pos))(keys))
    return blocks


def _stack_apply(blocks: List[Params], cfg: ModelConfig, x, cross_kv=None,
                 caches: Optional[List[Any]] = None):
    """Run the full stack; scan over periods."""

    def period_body(carry, xs):
        x, aux = carry
        block_slices, cache_slices = xs
        new_caches = []
        for pos, kind in enumerate(cfg.pattern):
            cache = cache_slices[pos] if cache_slices is not None else None
            x, nc, a = _layer_apply(block_slices[pos], cfg, kind, x,
                                    cross_kv=cross_kv, cache=cache)
            new_caches.append(nc)
            aux = aux + a
        ys = tuple(new_caches) if cache_slices is not None else None
        return (x, aux), ys

    if cfg.remat:
        policy = (jax.checkpoint_policies.dots_saveable
                  if cfg.remat_policy == "dots" else None)
        period_body = jax.checkpoint(period_body, prevent_cse=False,
                                     policy=policy)

    aux0 = jnp.zeros((), jnp.float32)
    xs = (tuple(blocks), tuple(caches) if caches is not None else None)
    if cfg.scan_layers:
        if caches is None:
            (x, aux), _ = jax.lax.scan(
                lambda c, b: period_body(c, (b, None)), (x, aux0),
                tuple(blocks))
            return x, None, aux
        (x, aux), new_caches = jax.lax.scan(period_body, (x, aux0), xs)
        return x, list(new_caches), aux
    # Unrolled path (small configs / debugging).
    aux = aux0
    new_caches: List[Any] = []
    for period in range(cfg.periods):
        block_slices = [jax.tree.map(lambda a: a[period], b) for b in blocks]
        cache_slices = ([jax.tree.map(lambda a: a[period], c)
                         for c in caches] if caches is not None else None)
        (x, aux), ys = period_body(
            (x, aux), (tuple(block_slices),
                       tuple(cache_slices) if cache_slices else None))
        if ys is not None:
            new_caches.append(ys)
    if caches is None:
        return x, None, aux
    stacked = [jax.tree.map(lambda *zs: jnp.stack(zs),
                            *[nc[pos] for nc in new_caches])
               for pos in range(len(cfg.pattern))]
    return x, stacked, aux


# ----------------------------------------------------------------------------
# Whole models
# ----------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """Random weights, every leaf stored in ``dtype``.

    float32 (the default) keeps full-precision master weights for
    training. Serving passes ``cfg.dtype``: the leaves are made and cast
    inside one jitted program, so a bf16 model never holds a float32 copy
    of its tree on the device, and each use's ``.astype(x.dtype)`` in
    ``layers`` is a no-op."""
    if jnp.dtype(dtype) == jnp.float32:
        return _init_params(key, cfg)
    return jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(dtype), _init_params(k, cfg)))(key)


def _init_params(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {
        "embed": layers.embedding_init(ks[0], cfg.vocab, cfg.d_model),
        "blocks": _stack_init(ks[1], cfg),
        "ln_f": layers.norm_init(cfg.norm, cfg.d_model),
        "unembed": layers.unembed_init(ks[2], cfg.d_model, cfg.vocab),
    }
    if cfg.encoder is not None:
        enc_cfg = dataclasses.replace(
            cfg, n_layers=cfg.encoder.n_layers, pattern=("attn",),
            moe_positions=(), rope_theta=None, name=cfg.name + "-encoder")
        p["encoder"] = {
            "blocks": _enc_stack_init(ks[3], enc_cfg),
            "ln_f": layers.norm_init(cfg.norm, cfg.d_model),
        }
    return p


def _enc_stack_init(key, enc_cfg: ModelConfig) -> List[Params]:
    # Encoder layers are non-causal attention blocks.
    return _stack_init(key, enc_cfg)


def encode(params: Params, cfg: ModelConfig, frontend_embeds):
    """Run the (whisper) encoder over precomputed frontend embeddings."""
    enc_cfg = dataclasses.replace(
        cfg, n_layers=cfg.encoder.n_layers, pattern=("attn",),
        moe_positions=(), rope_theta=None, name=cfg.name + "-encoder")
    x = frontend_embeds.astype(cfg.dtype)
    pos = layers.sinusoidal_positions(x.shape[1], cfg.d_model)
    x = x + pos[None].astype(x.dtype)
    enc_cfg_nc = dataclasses.replace(enc_cfg)
    # Non-causal: patch the attention config through a causal=False pattern.
    x, _, _ = _stack_apply_noncausal(params["encoder"]["blocks"], enc_cfg_nc, x)
    return layers.norm(cfg.norm, params["encoder"]["ln_f"], x)


def _stack_apply_noncausal(blocks, cfg: ModelConfig, x):
    noncausal = dataclasses.replace(cfg, rope_theta=None)

    def body(carry, block):
        x, _ = carry
        h = layers.norm(noncausal.norm, block["ln1"], x)
        mix, _ = layers.attention_apply(
            block["attn"], noncausal.attn_cfg(causal=False), h)
        x = x + mix
        h2 = layers.norm(noncausal.norm, block["ln2"], x)
        x = x + layers.mlp_apply(block["mlp"], noncausal.mlp_cfg(), h2)
        return (x, carry[1]), None

    (x, _), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                             blocks[0])
    return x, None, jnp.zeros((), jnp.float32)


def forward(params: Params, cfg: ModelConfig, tokens,
            frontend_embeds=None, caches=None, positions=None,
            cross_kv=None,
            unembed_fn=None) -> Tuple[Any, Optional[List[Any]], Any]:
    """Forward pass -> (logits, new_caches, aux_loss).

    ``frontend_embeds``: encoder input (whisper) or cross-attention source
    (vision); stubbed modality frontends provide it precomputed.
    ``cross_kv``: precomputed encoder output — serving passes it so decode
    steps do not re-run the encoder.
    ``unembed_fn``: override for the final logit matmul — the sharded
    serving engine routes it through the overlapped collective ring
    (``dist.collective_matmul.serve_unembed``); ``None`` keeps the plain
    ``layers.unembed``.
    """
    x = layers.embed(params["embed"], tokens, cfg.dtype)
    if cross_kv is not None:
        cross_kv = cross_kv.astype(cfg.dtype)
    elif cfg.encoder is not None:
        cross_kv = encode(params, cfg, frontend_embeds)
    elif cfg.n_frontend_tokens:
        cross_kv = frontend_embeds.astype(cfg.dtype)
    if cfg.rope_theta is None:
        # Sinusoidal absolute positions (whisper decoder), computed on the
        # fly so long-context decode does not embed a giant constant table.
        start = caches_index(caches) if caches is not None else 0
        idx = start + jnp.arange(tokens.shape[1])
        d = cfg.d_model
        dim = jnp.arange(d // 2, dtype=jnp.float32)
        angle = idx[:, None].astype(jnp.float32) / jnp.power(
            10000.0, 2 * dim / d)[None, :]
        pos = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
        x = x + pos[None].astype(x.dtype)
    x, new_caches, aux = _stack_apply(params["blocks"], cfg, x,
                                      cross_kv=cross_kv, caches=caches)
    x = layers.norm(cfg.norm, params["ln_f"], x)
    logits = (unembed_fn or layers.unembed)(params["unembed"], x)
    return logits, new_caches, aux


def caches_index(caches) -> Any:
    """Current decode position from any layer cache."""
    leaf = caches[0]
    if isinstance(leaf, dict) and "index" in leaf:
        idx = leaf["index"]
    else:
        idx = leaf["index"] if "index" in leaf else 0
    return idx.reshape(-1)[0] if hasattr(idx, "reshape") else idx


def cache_lengths(caches) -> Any:
    """Per-slot valid KV lengths, shape (batch,).

    With ``per_slot_index=True`` caches the index leaf is (periods, batch)
    and every period carries the same value; scalar-index caches
    ((periods,)-shaped leaf) broadcast their position over the batch read
    off a data leaf. This is the lengths vector the flash-decode kernel
    scalar-prefetches.
    """
    c0 = caches[0]
    idx = c0["index"]
    if idx.ndim == 2:
        return idx[0]
    batch = next(v for k, v in c0.items() if k != "index").shape[1]
    return jnp.full((batch,), idx[0], idx.dtype)


def set_cache_lengths(caches, lengths) -> List[Any]:
    """Overwrite every layer's write position (e.g. after a padded bucketed
    prefill, where the true prompt length is shorter than the bucket)."""
    out = []
    for c in caches:
        c = dict(c)
        c["index"] = jnp.broadcast_to(
            jnp.asarray(lengths, c["index"].dtype), c["index"].shape)
        out.append(c)
    return out


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=None, per_slot_index: bool = False) -> List[Any]:
    """Stacked decode caches aligned with pattern positions.

    ``per_slot_index=True`` gives each batch slot its own write position
    (continuous batching in ``serve.engine``)."""
    dtype = dtype or cfg.dtype
    idx0 = (jnp.zeros((batch,), jnp.int32) if per_slot_index
            else jnp.zeros((), jnp.int32))
    caches = []
    for pos, kind in enumerate(cfg.pattern):
        if kind in ("attn", "cross"):
            c = {
                "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.dhead),
                               dtype),
                "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.dhead),
                               dtype),
                "index": idx0,
            }
        elif kind == "mamba":
            c = mamba_mod.init_cache(cfg.mamba_cfg(), batch, dtype)
            c["index"] = idx0
        else:
            raise ValueError(kind)
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.periods,) + a.shape),
            c)
        caches.append(stacked)
    return caches


def init_paged_caches(cfg: ModelConfig, batch: int, max_len: int,
                      page_size: int, n_pages: int, dtype=None,
                      mesh=None, pool_axis: str = "model") -> List[Any]:
    """Paged decode caches: per pattern position a shared KV page pool
    instead of per-slot ``max_len`` reservations (``serve.paged``).

    Leaves per attention position (stacked over periods like
    ``init_caches``):

    * ``kp``/``vp``: (n_pages, page_size, kvh, dhead) physical pool; page
      0 is the null page (absorbs writes from freed/idle slots).
    * ``pages``: (batch, max_pages) int32 per-slot page table, 0-filled —
      one *logical* table shared by every layer; each layer keeps its own
      physical pool under the same page ids.
    * ``index``: (batch,) per-slot write position, identical to the
      ``per_slot_index=True`` contiguous cache (``cache_lengths`` and the
      engine's length plumbing work unchanged).

    Only attention patterns page (SSM state is O(1) per slot — nothing to
    page); hybrid stacks must serve contiguous.

    With ``mesh`` the pools are placed page-sharded over ``pool_axis``
    (page tables and write indices replicated) — the device-sharded pool
    ``serve.dist`` walks; ``n_pages`` must divide the axis.
    """
    assert all(k in ("attn", "cross") for k in cfg.pattern), \
        ("paged KV caches require an attention-only pattern", cfg.pattern)
    assert n_pages >= 2, n_pages
    dtype = dtype or cfg.dtype
    max_pages = -(-max_len // page_size)

    def make():
        caches = []
        for _ in cfg.pattern:
            c = {
                "kp": jnp.zeros(
                    (n_pages, page_size, cfg.n_kv_heads, cfg.dhead), dtype),
                "vp": jnp.zeros(
                    (n_pages, page_size, cfg.n_kv_heads, cfg.dhead), dtype),
                "pages": jnp.zeros((batch, max_pages), jnp.int32),
                "index": jnp.zeros((batch,), jnp.int32),
            }
            caches.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (cfg.periods,) + a.shape),
                c))
        return caches

    if mesh is None:
        return make()
    # Each device makes only its own pages: none ever holds the whole pool.
    from repro.serve import dist as serve_dist
    return jax.jit(make, out_shardings=serve_dist.cache_shardings(
        jax.eval_shape(make), mesh, pool_axis))()


def cache_hbm_rows(caches) -> int:
    """KV rows of HBM the caches hold: ``batch * max_len`` per contiguous
    layer, ``n_pages * page_size`` per paged pool (the reservation the
    paged layout shrinks)."""
    total = 0
    for c in caches:
        if "kp" in c:       # (periods, n_pages, page_size, kvh, d)
            total += int(np.prod(c["kp"].shape[:3]))
        elif "k" in c:      # (periods, batch, max_len, kvh, d)
            total += int(np.prod(c["k"].shape[:3]))
    return total


# ----------------------------------------------------------------------------
# Accounting (param counts, MODEL_FLOPS)
# ----------------------------------------------------------------------------

def param_count(cfg: ModelConfig) -> int:
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return sum(int(np.prod(l.shape))
               for l in jax.tree.leaves(shapes))


def active_param_count(cfg: ModelConfig) -> int:
    """Per-token active parameters (MoE: only top-k + shared experts)."""
    total = param_count(cfg)
    if not cfg.n_experts:
        return total
    d, f = cfg.d_model, cfg.d_ff
    per_expert = 3 * d * f
    n_moe_layers = cfg.periods * len(cfg.moe_positions)
    inactive = n_moe_layers * (cfg.n_experts - cfg.top_k) * per_expert
    return total - inactive


def model_flops(cfg: ModelConfig, batch: int, seq: int,
                mode: str = "train", cache_len: int = 0) -> float:
    """MODEL_FLOPS: 6*N_active*D for training, 2*N_active*D for inference,
    plus the attention O(s*ctx) term (ctx = cache length when decoding,
    half the sequence for causal prefill/train)."""
    n_active = active_param_count(cfg)
    tokens = batch * seq
    fwd_bwd = 3.0 if mode == "train" else 1.0
    total = 2.0 * fwd_bwd * n_active * tokens
    n_attn_layers = cfg.periods * sum(
        1 for k in cfg.pattern if k in ("attn", "cross"))
    ctx_eff = cache_len if cache_len else seq / 2.0
    attn = fwd_bwd * 4.0 * tokens * ctx_eff * cfg.n_heads * cfg.dhead \
        * n_attn_layers
    return total + attn

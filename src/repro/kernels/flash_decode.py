"""GQA flash-decode Pallas kernel: single-token queries vs ragged KV caches.

Decode attention in the serving engine is one (group, head_dim) query row per
(slot, kv head) against that slot's KV cache prefix. The seed path attended
over the full ``max_len`` cache every step; here the per-slot lengths ride in
as scalar-prefetch arguments so the K/V BlockSpec index maps can clamp the
streamed block to each slot's last valid block — grid steps past a slot's
length re-map to the block already resident in VMEM, so on TPU no fresh DMA
is issued and ``pl.when`` skips the compute. Decode attention cost becomes
O(actual context) instead of O(max_len).

Layout: the (slot, kv head) pair is flattened into grid dim 0, exactly like
``flash_attention``'s (batch, head) flattening; GQA needs no materialized
head repeat because the q rows for one kv head are contiguous.

``flash_decode_paged`` serves a *paged* cache (``serve.paged``): K/V live
in a shared (n_pages, page_size, kvh, d) pool and each slot owns a page
table instead of a contiguous row range. Its grid walks the batch's live
pages and nothing else: one step per (slot, page) pair, slot after slot,
with the step count a dynamic grid bound (``_walk_plan``). The K/V index
maps read the scalar-prefetched table — a software TLB: step g resolves
(slot, page) -> physical page before the DMA is issued, and the pipeline
fetches page g + 1 while page g is attended. Each step reads one page as
the pool stores it, a (page_size * kvh, d) block holding every kv head, so
the pool is never transposed; all query heads score the whole block at
once and each keeps its own kv head's columns. A slot stops at its length
or its first null table entry, so a freed slot (null row, drifting index)
and the null page are never fetched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import _largest_divisor

NEG_INF = -1e30


def _decode_body(length, ki, q_ref, read_kv, o_ref, m_scr, l_scr, acc_scr,
                 *, scale: float, block_k: int):
    """Online-softmax accumulator of the contiguous decode kernel over one
    (block_k, d) K/V block, read by ``read_kv``."""

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Blocks at/after the slot's length are load-skipped by the index map;
    # skip their compute too.
    @pl.when(ki * block_k < length)
    def _step():
        q = q_ref[0].astype(jnp.float32)                  # (group, d)
        k, v = read_kv()                                  # (bk, d) each
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _done():
        # Zero-length slots (freed engine slots) produce zeros, not NaN.
        denom = jnp.where(l_scr[...] > 0.0, l_scr[...], 1.0)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, block_k: int, kvh: int):
    bh, ki = pl.program_id(0), pl.program_id(1)
    _decode_body(lens_ref[bh // kvh], ki, q_ref,
                 lambda: (k_ref[0].astype(jnp.float32),
                          v_ref[0].astype(jnp.float32)),
                 o_ref, m_scr, l_scr, acc_scr, scale=scale, block_k=block_k)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q, k, v, lengths, block_k=None,
                 interpret: bool = False):
    """q: (b, h, d); k/v: (b, max_len, kvh, d); lengths: (b,) -> (b, h, d).

    ``lengths[i]`` is the number of valid KV rows for slot i (0 allowed:
    the output row is zeros). Only ``ceil(lengths[i] / block_k)`` K/V
    blocks are streamed for slot i. ``block_k=None`` asks the attention
    cost model (``core.autotune.choose_attn_block``), snapped to a
    dividing size.
    """
    b, h, d = q.shape
    _, max_len, kvh, _ = k.shape
    group = h // kvh
    assert group * kvh == h, (h, kvh)
    if block_k is None:
        from repro.core import autotune
        prob = autotune.AttnProblem(sq=group, skv=max_len, n_heads=kvh,
                                    head_dim=d, batch=b, causal=False,
                                    in_bytes=q.dtype.itemsize)
        chosen, _ = autotune.choose_attn_block(prob)
        block_k = _largest_divisor(max_len, chosen.block_k)
    block_k = min(block_k, max_len)
    assert max_len % block_k == 0, (max_len, block_k)
    nk = max_len // block_k

    qf = q.reshape(b * kvh, group, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, max_len, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, max_len, d)
    lengths = lengths.astype(jnp.int32)

    def kv_index(bh, ki, lens):
        # Clamp to the slot's last valid block: out-of-range grid steps
        # re-visit it, so the pipeline issues no new copy.
        last = jnp.maximum(lens[bh // kvh] - 1, 0) // block_k
        return (bh, jnp.minimum(ki, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * kvh, nk),
        in_specs=[
            pl.BlockSpec((1, group, d), lambda bh, ki, lens: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, group, d),
                               lambda bh, ki, lens: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=1.0 / np.sqrt(d),
                          block_k=block_k, kvh=kvh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kvh, group, d), q.dtype),
        interpret=interpret,
    )(lengths, qf, kf, vf)
    return out.reshape(b, h, d)


def _walk_plan(page_table, lengths, page_size: int):
    """The flattened page walk, slot by slot, each slot's live pages in
    order: returns (live (b,), end (b,), steps), where slot i walks
    ``live[i]`` pages as grid steps ``end[i] - live[i]`` to ``end[i] - 1``.

    Slot i walks ``ceil(lengths[i] / page_size)`` pages, stopping at its
    first null entry, so a freed slot (a null row, a drifting length)
    walks none.
    """
    max_pages = page_table.shape[1]
    col = jnp.arange(max_pages, dtype=jnp.int32)
    held = jnp.min(jnp.where(page_table == 0, col, max_pages), axis=1)
    live = jnp.minimum(-(-lengths // page_size), held).astype(jnp.int32)
    end = jnp.cumsum(live)
    return live, end, end[-1]


def _slot_of(g, end_ref):
    """The slot that grid step ``g`` walks: how many slots end at or
    before it, by binary search over the nondecreasing ``end``."""
    n = end_ref.shape[0]
    slot, step = 0, 1 << (n.bit_length() - 1)
    while step:
        probe = jnp.minimum(slot + step, n)
        slot = jnp.where(end_ref[probe - 1] <= g, probe, slot)
        step //= 2
    return jnp.minimum(slot, n - 1)


def _paged_decode_kernel(live_ref, end_ref, pages_ref, lens_ref,
                         q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                         other_scr, *, scale: float, page_size: int,
                         kvh: int):
    del pages_ref                       # consumed by the K/V index maps
    g = pl.program_id(0)
    slot = _slot_of(g, end_ref)
    j = g - (end_ref[slot] - live_ref[slot])    # the slot's page number
    n_slots, h, _ = q_ref.shape
    real = g < end_ref[n_slots - 1]
    shape = other_scr.shape             # (h, page_size * kvh)

    @pl.when(g == 0)
    def _first():
        # Slots that walk no page (zero length, freed) stay zeros.
        o_ref[...] = jnp.zeros_like(o_ref)
        # K/V rows are the page as stored: row r holds position r // kvh
        # of kv head r % kvh. Every head of the page is scored at once;
        # query head i keeps only the columns of kv head i // group, and
        # the others sink to NEG_INF through this bias.
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        other_scr[...] = jnp.where(col % kvh == row // (h // kvh), 0.0,
                                   NEG_INF)

    @pl.when(real & (j == 0))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(real)
    def _attend():
        s = jnp.dot(q_ref[slot], k_ref[0].T,
                    preferred_element_type=jnp.float32)
        s = s * scale + other_scr[...]
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        s = jnp.where(col < (lens_ref[slot] - j * page_size) * kvh, s,
                      NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # A slot's first page holds a live column of every head, so m_new
        # is finite and the masked columns give exactly 0.
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(real & (j == live_ref[slot] - 1))
    def _done():
        o_ref[slot] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _stored(pool, interpret: bool):
    """The pool as stored, ``(n_pages, page_size * kvh, d)`` (merged dims
    only), kept in HBM on the chip: the kernel streams its live pages from
    there, and XLA stages no whole layer's pool in VMEM for it."""
    if interpret:                       # the interpreter has no HBM
        return pool
    return pltpu.with_memory_space_constraint(pool, pltpu.HBM)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_paged(q, k_pages, v_pages, page_table, lengths,
                       interpret: bool = False):
    """Paged flash decode: q (b, h, d) vs a shared KV page pool.

    k_pages/v_pages: (n_pages, page_size, kvh, d) — page 0 is the null
    page. ``page_table``: (b, max_pages) int32 logical->physical map, 0 in
    unallocated entries. ``lengths``: (b,) live rows per slot (0 allowed:
    the output row is zeros). One grid step per live page of the batch
    (``_walk_plan``); null entries are neither fetched nor attended.
    """
    b, h, d = q.shape
    n_pages, page_size, kvh, _ = k_pages.shape
    assert h % kvh == 0, (h, kvh)
    rows = page_size * kvh
    page_table = page_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    live, end, steps = _walk_plan(page_table, lengths, page_size)

    def page(g, live, end, pages, lens):
        slot = _slot_of(g, end)
        return pages[slot, g - (end[slot] - live[slot])], 0, 0

    resident = pl.BlockSpec((b, h, d), lambda g, *_: (0, 0, 0))
    kv = pl.BlockSpec((1, rows, d), page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        # A batch with no live page still takes one step, which zeroes it.
        grid=(jnp.maximum(steps, 1),),
        in_specs=[resident, kv, kv],
        out_specs=resident,
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, rows), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=1.0 / np.sqrt(d),
                          page_size=page_size, kvh=kvh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(live, end, page_table, lengths, q,
      *(_stored(p.reshape(n_pages, rows, d), interpret)
        for p in (k_pages, v_pages)))

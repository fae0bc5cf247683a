"""Flash attention (online softmax) Pallas kernel, causal + GQA, with a
scalar-prefetch grid that skips out-of-diagonal K-block *loads*.

HBM->VMEM tiling: the (block_q, head_dim) query tile stays resident while
K/V tiles stream; running max/denominator/accumulator live in VMEM scratch
and persist across the sequential K steps. GQA is handled in the K/V
BlockSpec index maps (no materialized head repeat).

Causality is a *grid* property here, not a ``pl.when`` guard: the grid's
second dimension enumerates only the (q-block, k-block) pairs at or below
the diagonal, with the pair decoded from scalar-prefetched ``qmap``/``kmap``
arrays inside the index maps. Blocks past the diagonal are never part of
the grid, so their K/V tiles are never streamed from HBM — the skipped-load
optimization the seed kernel documented as out of scope. Block sizes default
to the microbench-priced attention cost model
(``core.autotune.choose_attn_block``).

``flash_attention_paged`` is the chunked-prefill variant of the same grid:
the queries are one fixed-size chunk of a prompt being written *in place*
through a KV page table (``serve.paged``), so K/V stream from a shared
(n_pages, page_size, kvh, d) pool instead of a contiguous row range. The
page table rides in as an extra scalar-prefetch argument next to
qmap/kmap/last and the K/V index maps first clamp the key block to the
slot's live span (``starts[slot] + chunk`` — the chunk's own rows included,
write-then-attend) and then translate logical→physical before the DMA — a
software-TLB walk, as in ``flash_decode_paged``, at prefill width. The
qmap/kmap/last enumeration is built once for the worst-case chunk position
(the chunk ending at the pool's last row), so one executable serves every
chunk of every prompt; blocks past a particular chunk's live span re-map to
the resident block (no fresh DMA) and skip their compute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _largest_divisor(dim: int, upper: int) -> int:
    for c in range(min(upper, dim), 0, -1):
        if dim % c == 0:
            return c
    return dim


def _lower_tri_maps(sq: int, skv: int, block_q: int, block_k: int,
                    causal: bool):
    """Enumerate visited (q-block, k-block) pairs, q-major.

    Causal: for query block qi only the K blocks whose first column is
    <= the block's last row (+ the skv-sq diagonal offset) are visited.
    Returns int32 (qmap, kmap, last) where last flags each q row's final
    K step (the online-softmax write-out point).
    """
    nq, nk = sq // block_q, skv // block_k
    off = skv - sq                 # query i attends keys <= i + off
    qmap, kmap, last = [], [], []
    for qi in range(nq):
        if causal:
            last_row = qi * block_q + block_q - 1
            kmax = min(max((last_row + off) // block_k + 1, 1), nk)
        else:
            kmax = nk
        for ki in range(kmax):
            qmap.append(qi)
            kmap.append(ki)
            last.append(1 if ki == kmax - 1 else 0)
    return (np.asarray(qmap, np.int32), np.asarray(kmap, np.int32),
            np.asarray(last, np.int32))


def _flash_kernel(qmap_ref, kmap_ref, last_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  offset: int):
    t = pl.program_id(1)
    qi, ki = qmap_ref[t], kmap_ref[t]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Every grid step is a visited block (off-diagonal blocks never made it
    # into the maps) — only the diagonal straddlers still need masking.
    q = q_ref[0].astype(jnp.float32)                  # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(cols <= rows + offset, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(last_ref[t] == 1)
    def _done():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, block_q=None,
                    block_k=None, interpret: bool = False):
    """q: (b, sq, h, d); k/v: (b, skv, kvh, d) -> (b, sq, h, d).

    ``block_q``/``block_k`` default to the attention cost model's choice
    (``core.autotune.choose_attn_block``), snapped to dividing sizes.
    """
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    group = h // kvh
    # Causal with sq > skv would leave early query rows with zero visitable
    # keys (undefined softmax); no call site produces that shape.
    assert not causal or skv >= sq, (sq, skv)
    if block_q is None or block_k is None:
        from repro.core import autotune
        prob = autotune.AttnProblem(sq=sq, skv=skv, n_heads=h, head_dim=d,
                                    batch=b, causal=causal,
                                    in_bytes=q.dtype.itemsize)
        chosen, _ = autotune.choose_attn_block(prob)
        # Cost-model choices are 128-aligned; snap to dividing sizes so
        # ragged sequence lengths stay launchable.
        block_q = block_q or _largest_divisor(sq, chosen.block_q)
        block_k = block_k or _largest_divisor(skv, chosen.block_k)
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, d)

    qmap, kmap, last = _lower_tri_maps(sq, skv, block_q, block_k, causal)

    def q_index(bh, t, qm, km, lf):
        return (bh, qm[t], 0)

    def kv_index(bh, t, qm, km, lf):
        # flattened q index bh = batch*h + head -> kv row batch*kvh + head//g
        return ((bh // h) * kvh + (bh % h) // group, km[t], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b * h, len(qmap)),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=1.0 / np.sqrt(d),
                          causal=causal, block_q=block_q, block_k=block_k,
                          offset=skv - sq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(qmap), jnp.asarray(kmap), jnp.asarray(last), qf, kf, vf)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def _paged_prefill_kernel(qmap_ref, kmap_ref, last_ref, starts_ref, pages_ref,
                          q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                          *, scale: float, block_q: int, block_k: int,
                          sq: int, h: int, max_rows: int):
    del pages_ref                    # consumed by the index maps (the TLB)
    t = pl.program_id(1)
    qi, ki = qmap_ref[t], kmap_ref[t]
    start = starts_ref[pl.program_id(0) // h]
    kv_end = jnp.minimum(start + sq, max_rows)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Key blocks at/past the live span were never DMA'd (the index map
    # re-visits the resident block); skip their compute too.
    @pl.when(ki * block_k < kv_end)
    def _step():
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        # Global positions: query row r of this chunk sits at start + r.
        rows = start + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(last_ref[t] == 1)
    def _done():
        # `last` flags the statically-last K step per q block (worst-case
        # chunk position); skipped steps left acc/l untouched, so the
        # accumulator already holds this chunk's final values here.
        denom = jnp.where(l_scr[...] > 0.0, l_scr[...], 1.0)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret"))
def flash_attention_paged(q, k_pages, v_pages, page_table, starts,
                          block_q=None, block_k=None,
                          interpret: bool = False):
    """Causal chunk attention against a paged KV pool (chunked prefill).

    q: (b, sq, h, d) — one chunk of queries per slot, slot i's rows sitting
    at global positions ``starts[i] + [0, sq)``. k_pages/v_pages:
    (n_pages, page_size, kvh, d) shared pool, page 0 the null page;
    ``page_table``: (b, max_pages) logical→physical map. The chunk's own
    K/V rows must already be written through the table (write-then-attend);
    each query attends causally over every previously-written position plus
    its own prefix of the chunk. Returns (b, sq, h, d).

    ``block_k`` must divide ``page_size`` (None -> cost-model choice
    snapped to a dividing size); one executable serves every chunk
    position — ``starts`` is data, not shape.
    """
    b, sq, h, d = q.shape
    n_pages, page_size, kvh, _ = k_pages.shape
    max_pages = page_table.shape[1]
    max_rows = max_pages * page_size
    group = h // kvh
    assert group * kvh == h, (h, kvh)
    if block_q is None or block_k is None:
        from repro.core import autotune
        prob = autotune.AttnProblem(sq=sq, skv=max_rows, n_heads=h,
                                    head_dim=d, batch=b, causal=True,
                                    in_bytes=q.dtype.itemsize)
        chosen, _ = autotune.choose_attn_block(prob)
        block_q = block_q or _largest_divisor(sq, chosen.block_q)
        block_k = block_k or _largest_divisor(page_size, chosen.block_k)
    block_q = min(block_q, sq)
    block_k = min(block_k, page_size)
    assert sq % block_q == 0, (sq, block_q)
    assert page_size % block_k == 0, (page_size, block_k)
    bpp = page_size // block_k          # blocks per page

    # Worst-case enumeration: the chunk ending at the pool's last row
    # (offset = max_rows - sq) visits the most K blocks; real chunks clamp
    # at runtime. One (qmap, kmap, last) set -> one executable for every
    # chunk of every prompt.
    qmap, kmap, last = _lower_tri_maps(sq, max_rows, block_q, block_k,
                                       causal=True)

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k_pages.transpose(2, 0, 1, 3)  # (kvh, n_pages, page_size, d)
    vf = v_pages.transpose(2, 0, 1, 3)
    starts = starts.astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)

    def q_index(bh, t, qm, km, lf, st, pages):
        return (bh, qm[t], 0)

    def kv_index(bh, t, qm, km, lf, st, pages):
        # Clamp to the slot's last live block (chunk rows included — they
        # are already written), then walk the page table: logical block ->
        # (physical page, in-page block) before the DMA issues.
        slot = bh // h
        kv_end = jnp.minimum(st[slot] + sq, max_rows)
        last_blk = jnp.maximum(kv_end - 1, 0) // block_k
        kic = jnp.minimum(km[t], last_blk)
        return ((bh % h) // group, pages[slot, kic // bpp], kic % bpp, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b * h, len(qmap)),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_prefill_kernel, scale=1.0 / np.sqrt(d),
                          block_q=block_q, block_k=block_k, sq=sq, h=h,
                          max_rows=max_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(qmap), jnp.asarray(kmap), jnp.asarray(last), starts,
      page_table, qf, kf, vf)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)

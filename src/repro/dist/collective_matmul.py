"""Overlapped all-gather matmul (collective matmul).

``x @ w`` where ``x`` is sharded along its contracting dim over one mesh
axis.  The naive SPMD lowering is ``all_gather(x) @ w`` — the full gather
must land before the first MAC issues.  Instead we run a shard_map ring:
each device multiplies the x-block it currently holds against the matching
row-block of ``w`` while collective-permuting the block to its neighbour,
so communication for step s+1 hides under the GEMM of step s (the
communication/computation-overlap structure the microbenchmark papers
measure on NVLink rings).  The compiled HLO therefore contains
``collective-permute`` ops and no entry-computation ``all-gather`` — which
``tests/test_sharding_dist.py`` asserts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def ag_matmul(x, w, mesh, axis: str = "model"):
    """Compute ``x @ w`` with the all-gather of ``x`` replaced by an
    overlapped collective-permute ring over mesh ``axis``.

    x: (m, k) sharded (k over ``axis``); w: (k, n) replicated; out: (m, n)
    replicated.  Falls back to a plain matmul when the axis is trivial or
    k doesn't divide it (the same divisibility fallback the sharding rules
    apply).
    """
    n_shards = int(dict(mesh.shape)[axis])
    k = x.shape[-1]
    if n_shards == 1 or k % n_shards:
        return x @ w
    k_block = k // n_shards
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def ring(x_block, w_full):
        # x_block: (m, k_block) — this device's current block of x columns.
        # Device i starts with block i; after s permutes it holds block
        # (i - s) mod n, which contracts against w rows [(i-s)*kb, ...).
        i = jax.lax.axis_index(axis)
        acc = jnp.zeros((x_block.shape[0], w_full.shape[-1]),
                        jnp.promote_types(x_block.dtype, w_full.dtype))
        block = x_block
        for s in range(n_shards):
            src = (i - s) % n_shards
            # Issue the permute before the GEMM so XLA can overlap them.
            nxt = (jax.lax.ppermute(block, axis, perm)
                   if s + 1 < n_shards else None)
            w_block = jax.lax.dynamic_slice_in_dim(
                w_full, src * k_block, k_block, axis=0)
            acc = acc + block @ w_block
            if nxt is not None:
                block = nxt
        return acc

    fn = shard_map(ring, mesh=mesh,
                   in_specs=(P(None, axis), P(None, None)),
                   out_specs=P(None, None), check_vma=False)
    return fn(x, w)


def rs_matmul(x, w, mesh, axis: str = "model"):
    """``x @ w`` as a psum-scatter ring: the reduce–scatter dual of
    ``ag_matmul``.

    x: (m, k) sharded (k over ``axis``); w: (k, n) replicated; out:
    (m, n) sharded (n over ``axis``). Where ``ag_matmul`` circulates the
    *inputs* so every device ends with the full product, this ring
    circulates the *partial sums*: device i contributes its
    ``x_block @ w_block`` slice into the accumulator destined for each
    output column block as it passes by, so after n-1 hops device i
    holds output block i, fully reduced. Same overlap structure
    (permute hides under the GEMM), half the resident output — the
    variant MoE dispatch wants, where the next op consumes the output
    already sharded. Falls back to a plain matmul (replicated out) when
    the axis is trivial or k or n doesn't divide it.
    """
    n_shards = int(dict(mesh.shape)[axis])
    m, k = x.shape
    n = w.shape[-1]
    if n_shards == 1 or k % n_shards or n % n_shards:
        return x @ w
    k_block = k // n_shards
    n_block = n // n_shards
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def ring(x_block, w_full):
        # The accumulator at device i at step s is destined for output
        # block (i - 1 - s) mod n: each device folds in its contribution
        # for that block, permutes the partial forward, and after the
        # final (unpermuted) step holds its own block, fully reduced.
        i = jax.lax.axis_index(axis)
        acc = jnp.zeros((m, n_block),
                        jnp.promote_types(x_block.dtype, w_full.dtype))
        for s in range(n_shards):
            dest = (i - 1 - s) % n_shards
            w_block = jax.lax.dynamic_slice(
                w_full, (i * k_block, dest * n_block), (k_block, n_block))
            acc = acc + x_block @ w_block
            if s + 1 < n_shards:
                acc = jax.lax.ppermute(acc, axis, perm)
        return acc

    fn = shard_map(ring, mesh=mesh,
                   in_specs=(P(None, axis), P(None, None)),
                   out_specs=P(None, axis), check_vma=False)
    return fn(x, w)


def serve_unembed(mesh, axis: str = "model"):
    """Serving entry point: an ``unembed_fn`` for ``models.transformer.
    forward`` that routes the decode/verify logit matmul — the single
    biggest GEMM on the serving path, (slots·width, d_model) x
    (d_model, vocab) — through the overlapped ``ag_matmul`` ring instead
    of the naive all-gather lowering. Output logits stay replicated, so
    the engine's sampling and stream bookkeeping are unchanged."""

    def unembed_fn(unembed_params, x):
        w = unembed_params["lm_head"].astype(x.dtype)
        b, s, d = x.shape
        out = ag_matmul(x.reshape(b * s, d), w, mesh, axis=axis)
        return out.reshape(b, s, w.shape[-1])

    return unembed_fn

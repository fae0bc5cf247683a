"""GPipe pipeline parallelism over one mesh axis.

``gpipe(layer, mesh, axis)`` turns a per-stage ``layer(weights, x)`` into
a pipelined function over stage-stacked weights and a leading microbatch
dim: stage i (one device along ``axis``) holds its own weights, processes
microbatch t-i at tick t, and hands its activation to stage i+1 via
``collective_permute`` — the classic GPipe schedule with
(stages-1)/(microbatches+stages-1) bubble overhead (``bubble_fraction``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def bubble_fraction(stages: int, microbatches: int) -> float:
    """Fraction of stage-ticks idle in one GPipe forward sweep."""
    return (stages - 1) / (microbatches + stages - 1)


def gpipe(layer, mesh, axis: str = "stage"):
    """Pipeline ``layer`` over mesh ``axis``.

    Returns ``fn(weights, micro)`` where every ``weights`` leaf has a
    leading stage dim equal to the axis size and ``micro`` is
    (microbatches, *sample_shape).  Output == applying the stages
    sequentially to every microbatch; the schedule runs
    microbatches + stages - 1 ticks with activations ring-permuted between
    stages each tick.
    """
    n_stages = int(dict(mesh.shape)[axis])
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

    def transform(weights, micro):
        for leaf in jax.tree.leaves(weights):
            if leaf.shape[0] != n_stages:
                raise ValueError(f"stage dim {leaf.shape[0]} != mesh "
                                 f"axis {axis}={n_stages}")
        n_micro = micro.shape[0]

        def run(w_block, mb):
            i = jax.lax.axis_index(axis)
            w = jax.tree.map(lambda a: a[0], w_block)   # this stage's slice
            state = jnp.zeros(mb.shape[1:], mb.dtype)   # input from stage i-1
            out = jnp.zeros_like(mb)
            for t in range(n_micro + n_stages - 1):
                # Stage 0 feeds fresh microbatches; later stages consume the
                # permuted activation.  Ticks outside a stage's window do
                # masked-out throwaway work (the pipeline bubble).
                feed = mb[min(t, n_micro - 1)]
                y = layer(w, jnp.where(i == 0, feed, state))
                done = t - (n_stages - 1)               # microbatch leaving
                if 0 <= done < n_micro:
                    out = out.at[done].set(
                        jnp.where(i == n_stages - 1, y, out[done]))
                state = jax.lax.ppermute(y, axis, perm)
            # Only the last stage wrote; psum replicates its result.
            return jax.lax.psum(out, axis)

        w_specs = jax.tree.map(
            lambda a: P(axis, *([None] * (a.ndim - 1))), weights)
        fn = shard_map(run, mesh=mesh,
                       in_specs=(w_specs, P(*([None] * micro.ndim))),
                       out_specs=P(*([None] * micro.ndim)), check_vma=False)
        return fn(weights, micro)

    return transform

"""Device-sharded KV page pools: shard_map scatter/gather over a mesh.

The paged cache treats serving HBM as virtual memory; this module is its
NUMA layer. The physical pools (``models.transformer.init_paged_caches``)
are sharded over one mesh axis with **pages as the shard unit** — global
page id ``p`` lives on device ``p // block`` at local slot ``p % block``,
the (device, local_page) pair ``serve.paged.PageAllocator`` hands out.
Slots are *not* the shard unit on purpose: a slot's page table can then
span devices, so one context can grow past any single chip's pool (the
ROADMAP's ``long_500k`` cell) and admission stays priced against the
global pool, exactly like the paper's NVLink remote-access chapter where
a GPU reaches pages resident on a peer instead of faulting.

Two shard_map primitives do all the cross-device work:

* ``scatter_pages`` — write the s new KV rows through the page table.
  Each device resolves the global page ids against its own block and
  drops writes it does not own (``mode="drop"``) — no communication at
  all: ownership is a partition, so every row lands exactly once.
* ``gather_pages`` — the page-table walk. Each device gathers the rows
  it owns into the slot-contiguous layout (zeros elsewhere) and one
  ``psum`` over the pool axis assembles the replicated contiguous view —
  the "remote page access" collective. Payload is the gathered view, not
  the pool, so it scales with live context, and because exactly one
  device contributes each row the sum is exact (no float reordering:
  the oracle's bit-identical streams survive).

The engine never sees any of this: it keeps one flat allocator and one
logical page table, and ``models.layers._paged_apply`` routes through
these helpers only when the ambient ruleset (``dist.sharding``) carries a
real mesh whose ``kv_pages`` axis is non-trivial.

Prefix caching composes unchanged: refcounts and the prefix index are
host-side state on the flat allocator, and a cache hit only installs
already-resident page ids into another slot's table. A shared page lives
on its owning device like any other; scatter/gather address pages by id,
blind to how many tables map them. Copy-on-write allocates the fresh page
wherever the allocator's least-loaded placement puts it — the copy is a
device-local pool-to-pool row move expressed through the same donated
cache update the engine already uses.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import sharding

# Logical name of the pool's page axis (rule target: the mesh axis the
# pool shards over — "model" by default, alongside the TP weights).
POOL_RULE = "kv_pages"


def serve_ruleset(mesh, rules: Optional[dict] = None) -> sharding.Ruleset:
    """The serving engine's ruleset: TP params/activations (no FSDP — no
    per-token gather on the decode path) + the sharded page pool."""
    return sharding.Ruleset(mesh=mesh, rules=dict(rules or {}), fsdp=False)


def param_shardings(params, mesh, ruleset=None):
    """Tensor-parallel NamedSharding per weight leaf: each leaf gets the
    spec its name resolves to under the serving ruleset (heads/mlp/vocab
    over "model"; norms and non-divisible leaves replicate). ``params``
    may hold arrays or shapes (``jax.eval_shape``), so weights can be made
    already sharded."""
    rs = ruleset or serve_ruleset(mesh)

    def spec(path, leaf):
        names = tuple(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path)
        return NamedSharding(mesh, sharding.param_spec(names, leaf.shape, rs))

    return jax.tree_util.tree_map_with_path(spec, params)


def active_pool_mesh() -> Optional[Tuple[Any, str]]:
    """(mesh, axis) when the ambient ruleset shards the page pool.

    Requires a *real* jax Mesh (rule stubs used by the sharding unit
    tests don't run shard_map) with a non-trivial ``kv_pages`` axis;
    returns None otherwise, which keeps every single-device path — and
    therefore every existing test — byte-identical.
    """
    rs = sharding.current_ruleset()
    if rs is None or not isinstance(rs.mesh, jax.sharding.Mesh):
        return None
    target = rs._rule(POOL_RULE)
    if target is None:
        return None
    axis = target if isinstance(target, str) else tuple(target)[0]
    if int(dict(rs.mesh.shape).get(axis, 1)) <= 1:
        return None
    return rs.mesh, axis


def pool_sharding(mesh, axis: str, ndim: int, page_dim: int):
    """NamedSharding for a pool array sharded over its page dimension."""
    spec = [None] * ndim
    spec[page_dim] = axis
    return NamedSharding(mesh, P(*spec))


def cache_shardings(caches, mesh, axis: str = "model"):
    """NamedSharding per paged-cache leaf: kp/vp page-sharded (dim 1 —
    dim 0 is the period stack), page tables and write indices
    replicated. ``caches`` may hold arrays or shapes, so the pools can be
    made already sharded."""
    repl = NamedSharding(mesh, P())
    out = []
    for c in caches:
        if "kp" in c:
            n_pages = c["kp"].shape[1]
            assert n_pages % int(dict(mesh.shape)[axis]) == 0, \
                (n_pages, dict(mesh.shape))
            sh = pool_sharding(mesh, axis, c["kp"].ndim, page_dim=1)
            out.append({k: sh if k in ("kp", "vp") else repl for k in c})
        else:
            out.append({k: repl for k in c})
    return out


def scatter_pages(kp, vp, k, v, page, row, mesh, axis: str = "model"):
    """Write rows (b, s) through the global page table into the sharded
    pool: each device keeps the writes whose pages it owns, drops the
    rest. kp/vp: (n_pages, page_size, kvh, hd) page-sharded; k/v:
    (b, s, kvh, hd); page/row: (b, s) global page id / in-page row."""
    n_dev = int(dict(mesh.shape)[axis])
    block = kp.shape[0] // n_dev

    def body(kp_l, vp_l, k, v, page, row):
        d = jax.lax.axis_index(axis)
        local = page - d * block
        owned = (local >= 0) & (local < block)
        # Not-owned writes get an out-of-range local id and are dropped
        # by the scatter itself — ownership is a partition, so every row
        # is written by exactly one device and none twice.
        lp = jnp.where(owned, local, block)
        kp_l = kp_l.at[lp, row].set(k.astype(kp_l.dtype), mode="drop")
        vp_l = vp_l.at[lp, row].set(v.astype(vp_l.dtype), mode="drop")
        return kp_l, vp_l

    pool = P(axis, None, None, None)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(pool, pool, P(None, None, None, None),
                             P(None, None, None, None), P(None, None),
                             P(None, None)),
                   out_specs=(pool, pool), check_vma=False)
    return fn(kp, vp, k, v, page, row)


def gather_pages(kp, vp, pages, mesh, axis: str = "model"):
    """Page-table walk over the sharded pool: materialize the replicated
    contiguous (b, max_pages*page_size, kvh, hd) view.

    Each device resolves the global table against its block — rows it
    owns in place, zeros elsewhere — and a single psum over ``axis``
    assembles the view (exact: one contributor per row). Rows mapped
    through the null page are garbage, masked by the caller's lengths
    exactly as in the single-device walk (``serve.paged.gather_kv``).
    """
    n_dev = int(dict(mesh.shape)[axis])
    block = kp.shape[0] // n_dev
    b, max_pages = pages.shape
    ps = kp.shape[1]

    def body(kp_l, vp_l, pages):
        d = jax.lax.axis_index(axis)
        local = pages - d * block
        owned = (local >= 0) & (local < block)
        lp = jnp.where(owned, local, 0)
        m = owned[..., None, None, None]
        kc = jnp.where(m, jnp.take(kp_l, lp, axis=0), 0)
        vc = jnp.where(m, jnp.take(vp_l, lp, axis=0), 0)
        kc = jax.lax.psum(kc, axis)
        vc = jax.lax.psum(vc, axis)
        return (kc.reshape(b, max_pages * ps, *kp_l.shape[2:]),
                vc.reshape(b, max_pages * ps, *vp_l.shape[2:]))

    pool = P(axis, None, None, None)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(pool, pool, P(None, None)),
                   out_specs=(P(None, None, None, None),
                              P(None, None, None, None)),
                   check_vma=False)
    return fn(kp, vp, pages)

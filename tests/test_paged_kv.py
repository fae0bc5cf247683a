"""Paged KV subsystem: allocator invariants (property-tested), the paged
flash-decode kernel vs the contiguous oracle, and the page-table gather."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import autotune
from repro.kernels import ref
from repro.kernels import flash_decode as fd
from repro.kernels.flash_decode import flash_decode_paged
from repro.serve import paged


# ----------------------------------------------------------------------------
# Allocator
# ----------------------------------------------------------------------------

def test_alloc_free_roundtrip():
    al = paged.PageAllocator(n_pages=8, page_size=4)
    a = al.alloc(0, 3)
    b = al.alloc(1, 2)
    assert len(set(a) | set(b)) == 5          # all distinct
    assert paged.NULL_PAGE not in a + b
    assert al.pages_in_use == 5 and al.free_pages == 2
    freed = al.free_slot(0)
    assert sorted(freed) == sorted(a)
    assert al.pages_in_use == 2 and al.free_pages == 5
    al.reset()
    assert al.pages_in_use == 0 and al.free_pages == 7


def test_freed_pages_are_reused_first():
    """LIFO free list: a freed slot's pages are the next ones handed out
    (warm-page reuse on re-admission)."""
    al = paged.PageAllocator(n_pages=16, page_size=4)
    a = al.alloc(0, 4)
    al.alloc(1, 4)
    al.free_slot(0)
    assert al.alloc(2, 4) == a


def test_exhaustion_raises_and_allocates_nothing():
    al = paged.PageAllocator(n_pages=4, page_size=4)
    al.alloc(0, 2)
    with pytest.raises(paged.PagePoolExhausted):
        al.alloc(1, 2)
    assert al.pages_in_use == 2               # failed alloc took nothing
    assert 1 not in al.slot_pages


def test_occupancy_and_fragmentation_accounting():
    al = paged.PageAllocator(n_pages=9, page_size=8)
    al.alloc(0, 2)                            # 16 rows allocated
    al.alloc(1, 1)                            # 8 rows allocated
    occ = al.occupancy({0: 9, 1: 8})
    assert occ["pages_in_use"] == 3
    assert occ["rows_resident"] == 4 * 8      # + null page
    assert occ["fragmentation_rows"] == 24 - 17
    assert occ["high_water"] == 3
    assert occ["utilization"] == pytest.approx(3 / 8)


@given(seed=st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_allocator_never_double_assigns_live_pages(seed):
    """Random alloc/free interleavings: every live page is owned by exactly
    one slot and the null page is never handed out."""
    rng = np.random.RandomState(seed)
    al = paged.PageAllocator(n_pages=int(rng.randint(3, 20)),
                             page_size=int(rng.randint(1, 9)))
    for _ in range(50):
        slot = int(rng.randint(0, 6))
        if rng.rand() < 0.6:
            n = int(rng.randint(1, 4))
            try:
                al.alloc(slot, n)
            except paged.PagePoolExhausted:
                pass
        else:
            al.free_slot(slot)
        owned = [p for ps in al.slot_pages.values() for p in ps]
        assert len(owned) == len(set(owned)), "page owned twice"
        assert paged.NULL_PAGE not in owned
        assert len(owned) + al.free_pages == al.n_pages - 1


@given(seed=st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_refcount_churn_never_double_frees_or_leaks(seed):
    """Property: random share/retain/COW/release churn layered on
    alloc/free keeps every reference accounted for — a live page's
    refcount equals its slot-table occurrences plus its index hold, live
    and free pages partition the pool, and the conservation counters
    (allocated - freed == in-use) balance after every operation."""
    rng = np.random.RandomState(seed)
    al = paged.PageAllocator(n_pages=int(rng.randint(6, 24)),
                             page_size=int(rng.randint(1, 5)))
    held = set()                              # mirror of the index hold
    for _ in range(80):
        op = rng.rand()
        slots = sorted(s for s, ps in al.slot_pages.items() if ps)
        if op < 0.35:
            try:
                al.alloc(int(rng.randint(0, 6)), int(rng.randint(1, 4)))
            except paged.PagePoolExhausted:
                pass
        elif op < 0.50 and slots:             # prefix-hit path
            src = slots[rng.randint(len(slots))]
            k = int(rng.randint(1, len(al.slot_pages[src]) + 1))
            al.share(int(rng.randint(0, 6)), al.slot_pages[src][:k])
        elif op < 0.60 and slots:             # publish path
            run = al.slot_pages[slots[rng.randint(len(slots))]]
            p = run[rng.randint(len(run))]
            if p not in held:
                al.retain(p)
                held.add(p)
        elif op < 0.70 and held:              # evict path
            p = sorted(held)[rng.randint(len(held))]
            held.discard(p)
            al.release(p)
        elif op < 0.85 and slots:             # COW a shared page
            src = slots[rng.randint(len(slots))]
            pos = int(rng.randint(len(al.slot_pages[src])))
            if al.refcount(al.slot_pages[src][pos]) >= 2:
                try:
                    al.cow(src, pos)
                except paged.PagePoolExhausted:
                    pass
        else:
            al.free_slot(int(rng.randint(0, 6)))
        counts = {}
        for ps in al.slot_pages.values():
            for p in ps:
                counts[p] = counts.get(p, 0) + 1
        for p in held:
            counts[p] = counts.get(p, 0) + 1
        assert paged.NULL_PAGE not in counts
        assert counts == {p: al.refcount(p) for p in counts}, "ref drift"
        assert len(counts) == al.pages_in_use
        assert al.pages_in_use + al.free_pages == al.n_pages - 1
        assert al.pages_allocated - al.pages_freed == al.pages_in_use
        cls = al.page_classes()
        assert sum(cls.values()) == al.pages_in_use


def test_pages_for():
    assert [paged.pages_for(n, 8) for n in (0, 1, 8, 9, 16)] == \
        [0, 1, 1, 2, 2]


# ----------------------------------------------------------------------------
# Paged kernel vs contiguous oracle
# ----------------------------------------------------------------------------

def _paginate(k, v, lengths, page_size, n_pages, rng):
    """Scatter contiguous (b, max_len, kvh, d) K/V into a shuffled page
    pool + per-slot tables (live entries drawn from pages 1..n_pages-1)."""
    b, max_len, kvh, d = k.shape
    max_pages = max_len // page_size
    ids = rng.permutation(np.arange(1, n_pages))
    kp = np.zeros((n_pages, page_size, kvh, d), np.asarray(k).dtype)
    vp = np.zeros_like(kp)
    table = np.zeros((b, max_pages), np.int32)
    nxt = 0
    for i in range(b):
        for j in range(paged.pages_for(int(lengths[i]), page_size)):
            pid = ids[nxt]
            nxt += 1
            table[i, j] = pid
            kp[pid] = np.asarray(k[i, j * page_size:(j + 1) * page_size])
            vp[pid] = np.asarray(v[i, j * page_size:(j + 1) * page_size])
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table)


def _case(rng, b, h, kvh, d, max_len):
    q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, max_len, kvh, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, max_len, kvh, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("h,kvh", [(4, 4), (8, 2), (8, 1)])
def test_paged_decode_matches_contiguous_oracle(h, kvh):
    rng = np.random.RandomState(0)
    b, d, max_len, ps = 4, 16, 64, 16
    q, k, v = _case(rng, b, h, kvh, d, max_len)
    lengths = jnp.asarray([1, 17, 64, 33], jnp.int32)
    kp, vp, table = _paginate(k, v, lengths, ps, 24, rng)
    out = flash_decode_paged(q, kp, vp, table, lengths, interpret=True)
    expect = ref.flash_decode(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_paged_decode_zero_length_slot_is_zeros_not_nan():
    """A freed slot (length 0, null table row) gives zeros — and reading
    through the null page never touches a live page."""
    rng = np.random.RandomState(1)
    q, k, v = _case(rng, 3, 4, 2, 8, 32)
    lengths = jnp.asarray([0, 5, 32], jnp.int32)
    kp, vp, table = _paginate(k, v, lengths, 8, 16, rng)
    assert int(table[0].sum()) == 0           # freed slot: all-null row
    out = np.asarray(flash_decode_paged(q, kp, vp, table, lengths,
                                        interpret=True))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[0], np.zeros_like(out[0]))
    expect = np.asarray(ref.flash_decode(q, k, v, lengths))
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-4)


@given(seed=st.integers(0, 50), page_size=st.sampled_from([4, 8, 16]),
       kvh=st.sampled_from([1, 2, 4]))
@settings(max_examples=10, deadline=None)
def test_paged_decode_block_and_length_invariance(seed, page_size, kvh):
    """Property: any page size (the kernel's block), GQA group, ragged
    length vector and page shuffle reproduces the contiguous oracle
    (within fp tolerance)."""
    rng = np.random.RandomState(seed)
    b, d, max_len = 3, 8, 64
    h = kvh * int(rng.randint(1, 4))
    q, k, v = _case(rng, b, h, kvh, d, max_len)
    lengths = jnp.asarray(rng.randint(0, max_len + 1, size=b), jnp.int32)
    n_pages = 1 + b * max_len // page_size
    kp, vp, table = _paginate(k, v, lengths, page_size, n_pages, rng)
    out = flash_decode_paged(q, kp, vp, table, lengths, interpret=True)
    expect = ref.flash_decode(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


# The benchmark cells' decode heads: qwen3-4b (group 4, kvh 8, head_dim
# 128) and phi3-mini (MHA, kvh 32, head_dim 96), bf16 as served, with
# lengths of 0, a page, a page plus one and the whole table.
@pytest.mark.parametrize("h,kvh,d", [(32, 8, 128), (32, 32, 96)],
                         ids=["qwen3-4b", "phi3-mini"])
def test_paged_decode_cell_heads_bf16(h, kvh, d):
    rng = np.random.RandomState(3)
    ps, max_pages = 16, 3
    q, k, v = (a.astype(jnp.bfloat16) for a in
               _case(rng, 4, h, kvh, d, ps * max_pages))
    lengths = jnp.asarray([0, ps, ps + 1, ps * max_pages], jnp.int32)
    kp, vp, table = _paginate(k, v, lengths, ps, 9, rng)
    out = flash_decode_paged(q, kp.astype(jnp.bfloat16),
                             vp.astype(jnp.bfloat16), table, lengths,
                             interpret=True)
    assert out.dtype == jnp.bfloat16
    expect = ref.flash_decode(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), lengths)
    out = np.asarray(out.astype(jnp.float32))
    np.testing.assert_array_equal(out[0], np.zeros_like(out[0]))
    # Only the output's rounding to bf16 separates it from the f32 oracle.
    np.testing.assert_allclose(out, np.asarray(expect), rtol=1e-2,
                               atol=1e-2)


def test_paged_decode_shared_page_serves_both_slots():
    """Prefix caching maps one physical page into two slots' tables; each
    slot attends it under its own length."""
    rng = np.random.RandomState(4)
    ps = 8
    q, k, v = _case(rng, 2, 4, 2, 8, 3 * ps)
    k = k.at[1, :ps].set(k[0, :ps])
    v = v.at[1, :ps].set(v[0, :ps])
    lengths = jnp.asarray([2 * ps + 3, ps + 5], jnp.int32)
    kp, vp, table = _paginate(k, v, lengths, ps, 8, rng)
    table = np.asarray(table).copy()
    table[1, 0] = table[0, 0]                 # the shared prefix page
    out = flash_decode_paged(q, kp, vp, jnp.asarray(table), lengths,
                             interpret=True)
    expect = ref.flash_decode(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_paged_decode_freed_slot_walks_no_page():
    """A freed slot keeps a null table row while its write index drifts one
    position per step: it gives zeros, and the walk never visits it or
    the null page (poisoned here, so attending it would show)."""
    rng = np.random.RandomState(5)
    ps = 8
    q, k, v = _case(rng, 3, 4, 2, 8, 4 * ps)
    lengths = jnp.asarray([9, 3 * ps + 2, 21], jnp.int32)
    kp, vp, table = _paginate(k, v, lengths, ps, 12, rng)
    table = table.at[1].set(0)                # freed: null row, index drifts
    kp, vp = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
    out = np.asarray(flash_decode_paged(q, kp, vp, table, lengths,
                                        interpret=True))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))
    live = jnp.asarray([9, 0, 21], jnp.int32)
    np.testing.assert_allclose(out, np.asarray(ref.flash_decode(q, k, v,
                                                                live)),
                               rtol=2e-4, atol=2e-4)
    live, end, steps = fd._walk_plan(table, lengths, ps)
    assert np.asarray(live).tolist() == [2, 0, 3] and int(steps) == 5
    slots = [int(fd._slot_of(g, end)) for g in range(5)]
    walked = [(i, g - int(end[i] - live[i])) for g, i in enumerate(slots)]
    assert walked == [(0, 0), (0, 1), (2, 0), (2, 1), (2, 2)]
    assert all(int(table[i, j]) != 0 for i, j in walked)


@pytest.mark.parametrize("b", [1, 3, 5, 32])
def test_walk_slot_of_every_step_matches_searchsorted(b):
    """Grid step g walks the slot whose page range holds it; slots with
    no page to walk (zeros in ``live``) are stepped over."""
    rng = np.random.RandomState(b)
    live = rng.randint(0, 4, size=b) * (rng.rand(b) < 0.7)
    end = jnp.asarray(np.cumsum(live), jnp.int32)
    for g in range(int(end[-1])):
        want = int(np.searchsorted(np.asarray(end), g, side="right"))
        assert int(fd._slot_of(g, end)) == want


def test_gather_kv_reconstructs_contiguous_view():
    rng = np.random.RandomState(2)
    _, k, v = _case(rng, 2, 4, 2, 8, 32)
    lengths = jnp.asarray([32, 9], jnp.int32)
    kp, vp, table = _paginate(k, v, lengths, 8, 12, rng)
    kc, vc = paged.gather_kv(kp, vp, table)
    assert kc.shape == (2, 32, 2, 8)
    np.testing.assert_array_equal(np.asarray(kc[0]), np.asarray(k[0]))
    np.testing.assert_array_equal(np.asarray(vc[1][:8]), np.asarray(v[1][:8]))


def test_reservation_model():
    out = paged.reservation([100, 200, 0], max_len=1024, page_size=64)
    assert out["rows_resident"] == (2 + 4 + 0 + 1) * 64
    assert out["rows_reserved_contig"] == 3 * 1024
    assert 0 < out["reservation_ratio"] < 0.5


def test_paged_decode_model_prices_lookup_and_reservation():
    lengths = [512, 4096, 16384, 32768]
    out = autotune.paged_decode_model(32768, lengths, n_heads=32,
                                      n_kv_heads=8, head_dim=128,
                                      page_size=256)
    assert out["paged_s"] > out["contig_s"]           # lookups aren't free
    assert out["lookup_overhead_frac"] < 0.5          # but nearly so
    assert out["tokens_per_s_paged"] < out["tokens_per_s_contig"]
    assert out["reservation_ratio"] < 0.5             # the HBM win
    # Zero overhead -> identical time (same FLOPs, same blocks).
    free = autotune.paged_decode_model(32768, lengths, n_heads=32,
                                       n_kv_heads=8, head_dim=128,
                                       page_size=256, page_lookup_s=0.0)
    assert free["paged_s"] == pytest.approx(free["contig_s"])

"""Distributed paged serving: the engine sharded over a mesh must be a
*bit-identical* re-plumbing of the single-device engine — same token
streams across greedy, sampled, preemption and spec-decode paths, with
weights tensor-parallel, the KV page pool device-sharded (pages as the
shard unit, so one slot's context spans devices), and still exactly one
decode/verify executable per mesh. The 8-device checks run in one
subprocess (``--xla_force_host_platform_device_count=8``); the allocator
property tests, the adaptive spec-k regression, and the mesh-keyed tuning
cache tests are host-side and fast. check.sh gates this file in the
serving subset."""

import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.core import autotune, roofline
from repro.serve import paged, spec


# ----------------------------------------------------------------------------
# Device-sharded PageAllocator (host-side: no jax, no mesh needed)
# ----------------------------------------------------------------------------

def test_single_device_allocation_order_unchanged():
    """D=1 must allocate 1, 2, 3, ... exactly as the pre-mesh allocator:
    the device-sharded pool is a superset, not a behavior change."""
    pool = paged.PageAllocator(n_pages=8, page_size=4)
    got = pool.alloc(0, 7)
    assert got == [1, 2, 3, 4, 5, 6, 7]
    assert pool.capacity == 7
    assert pool.device_occupancy() == [7]


def test_capacity_is_mesh_invariant():
    """Same n_pages -> same capacity on any device count (one global null
    page, not one per device) — the 1-vs-8 parity the bench cell pins."""
    for d in (1, 2, 4, 8):
        pool = paged.PageAllocator(n_pages=16, page_size=4, n_devices=d)
        assert pool.capacity == 15, d


@given(d=st.sampled_from([2, 4, 8]), seed=st.integers(0, 1000))
@settings(max_examples=40, deadline=None)
def test_sharded_allocator_churn_invariants(d, seed):
    """Property: under admit/free churn, (a) no (device, local_page) pair
    is ever live twice, (b) per-device occupancy sums to the global count,
    (c) the null page is never handed out, (d) freed pages return to their
    home device's free list (devices never leak capacity)."""
    rng = np.random.RandomState(seed)
    pool = paged.PageAllocator(n_pages=8 * d, page_size=4, n_devices=d)
    live = {}
    for step in range(120):
        rid = int(rng.randint(0, 6))
        if rng.rand() < 0.6 and pool.free_pages:
            n = int(rng.randint(1, min(4, pool.free_pages) + 1))
            for p in pool.alloc(rid, n):
                assert p != paged.NULL_PAGE
                key = (pool.device_of(p), pool.local_of(p))
                assert key not in live, "double allocation of " + str(key)
                assert 0 <= key[1] < pool.block
                live[key] = rid
        elif rid in pool.slot_pages:
            for p in pool.slot_pages[rid]:
                del live[(pool.device_of(p), pool.local_of(p))]
            pool.free_slot(rid)
        occ = pool.device_occupancy()
        assert sum(occ) == len(live) == \
            sum(len(v) for v in pool.slot_pages.values())
        for dev in range(d):
            assert occ[dev] == sum(1 for (pd, _) in live if pd == dev)
    assert pool.free_pages == pool.capacity - len(live)


def test_occupancy_reports_per_device_counts():
    pool = paged.PageAllocator(n_pages=8, page_size=2, n_devices=4)
    pool.alloc(0, 5)
    occ = pool.occupancy()
    assert occ["capacity"] == 7 and occ["n_devices"] == 4
    assert sum(occ["pages_in_use_by_device"]) == occ["pages_in_use"] == 5
    # Least-loaded placement spreads pages across every device.
    assert all(c >= 1 for c in occ["pages_in_use_by_device"])


# ----------------------------------------------------------------------------
# Tuning cache keyed by backend AND mesh shape (satellite: two writes,
# two entries — single- and multi-device runs must not clobber each other)
# ----------------------------------------------------------------------------

def test_tuning_cache_keyed_by_mesh_shape(tmp_path, monkeypatch):
    monkeypatch.setattr(autotune, "TUNING_CACHE_PATH",
                        str(tmp_path / "cache.json"))
    # The in-memory memo outlives earlier tests in the same process;
    # reset it so this test sees only its own two writes (monkeypatch
    # restores the shared memo afterwards).
    monkeypatch.setattr(autotune, "_tuning_cache", None)
    p = autotune.AttnProblem(sq=128, skv=512, n_heads=4, head_dim=64,
                             causal=True, in_bytes=2)
    b1, _ = autotune.choose_attn_block(p, mesh_shape="dev1")
    b8, _ = autotune.choose_attn_block(p, mesh_shape={"model": 8})
    cache = autotune._load_tuning_cache()
    assert len(cache) == 2, list(cache)
    keys = sorted(cache)
    assert any(":dev1:" in k for k in keys), keys
    assert any(":mesh(model=8):" in k for k in keys), keys
    # Same problem, same backend: only the mesh component differs.
    assert {k.split(":", 3)[3] for k in keys} == \
        {keys[0].split(":", 3)[3]}
    # Both entries hit on re-lookup (no clobbering).
    assert autotune.choose_attn_block(p, mesh_shape="dev1")[0] == b1
    assert autotune.choose_attn_block(p, mesh_shape={"model": 8})[0] == b8


def test_default_mesh_key_is_device_count():
    import jax
    # check.sh runs this file with 8 forced host devices; bare pytest
    # sees 1 — either way the default key is the visible device count.
    assert autotune._mesh_key() == f"dev{jax.device_count()}"
    assert autotune._mesh_key("dev8") == "dev8"
    assert autotune._mesh_key((2, 4)) == "mesh(2,4)"


# ----------------------------------------------------------------------------
# TP cost models (collective terms in decode/chunk/spec models)
# ----------------------------------------------------------------------------

def test_tp_decode_model_shards_weight_stream():
    terms = autotune.tp_decode_model(
        [4096] * 8, n_heads=32, n_kv_heads=8, head_dim=128, page_size=64,
        param_bytes=8e9, d_model=4096, n_layers=36, n_devices=8)
    assert terms["weight_stream_tp_s"] * 8 == \
        pytest.approx(terms["weight_stream_1dev_s"])
    assert terms["speedup"] > 1.0          # decode is weight-stream bound
    assert terms["collective_s"] > 0.0
    assert terms["pool_capacity_ratio"] == 8.0
    assert terms["attn_sharded"]


def test_tp_collective_terms_price_in_models():
    """The chunk/spec/decode models all surface a nonzero collective term
    under tp and reduce to their exact single-device selves without it."""
    tp = autotune.TPServe(n_devices=8, d_model=4096, n_layers=36)
    c0 = autotune.prefill_chunk_model(2048, 256, 32, 8, 128, 64)
    c8 = autotune.prefill_chunk_model(2048, 256, 32, 8, 128, 64, tp=tp)
    assert c0["collective_s"] == 0.0 and c8["collective_s"] > 0.0
    d0 = autotune.paged_decode_model(4096, [1000, 2000], 32, 8, 128, 64)
    d8 = autotune.paged_decode_model(4096, [1000, 2000], 32, 8, 128, 64,
                                     tp=tp)
    assert d0["collective_s"] == 0.0 and d8["collective_s"] > 0.0
    s8 = autotune.spec_decode_model([2048] * 4, 32, 8, 128, 64, k=4,
                                    accept_rate=0.8, param_bytes=8e9,
                                    tp=tp)
    s0 = autotune.spec_decode_model([2048] * 4, 32, 8, 128, 64, k=4,
                                    accept_rate=0.8, param_bytes=8e9)
    assert s8["weight_stream_s"] * 8 == pytest.approx(s0["weight_stream_s"])


def test_collective_matmul_roofline_prices_rs_vs_ar():
    """rs_matmul's ring moves half the all-reduce baseline's wire bytes
    and the ag variants differ only in overlap, not bytes."""
    t = roofline.collective_matmul_terms(256, 4096, 8192, 8)
    assert t["rs_ring"].collective_bytes * 2 == \
        pytest.approx(t["all_reduce"].collective_bytes)
    assert t["ag_ring"].collective_bytes == t["all_gather"].collective_bytes
    for v in t.values():
        assert v.step_time_overlapped_s <= v.step_time_s


# ----------------------------------------------------------------------------
# Adaptive spec-k: measured accept rate feeds back into choose_spec_k
# ----------------------------------------------------------------------------

def _spec_engine(cfg, params, prompt, ref, pattern, adapt_every):
    from repro.serve.engine import Request, ServeConfig, ServingEngine
    draft = spec.ScriptedDraft(len(prompt), ref, pattern, cfg.vocab)
    eng = ServingEngine(params, cfg,
                        ServeConfig(max_len=64, batch=2, eos_id=-1,
                                    paged=True, page_size=8, chunk_size=8,
                                    spec_k=2, draft=draft,
                                    spec_adapt_every=adapt_every))
    eng.submit(Request(rid=0, prompt=np.asarray(prompt, np.int32),
                       max_new=len(ref)))
    return eng


@pytest.fixture(scope="module")
def model():
    import jax
    from repro.models import transformer as T
    cfg = configs.get_smoke("qwen3-4b")
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def test_collapsing_accept_rate_disables_speculation(model):
    """Regression (satellite): an always-rejected draft drives the
    measured accept rate to zero, and the runtime re-choice pushes
    ``k_live`` into the disable regime (0 = plain decode ticks) — while
    the emitted stream stays exactly the reference."""
    import jax.numpy as jnp
    from repro.serve.engine import greedy_generate
    cfg, params = model
    prompt = list(range(3, 11))
    ref = np.asarray(greedy_generate(
        params, cfg, jnp.asarray(prompt)[None], 12, max_len=64)[0]).tolist()
    eng = _spec_engine(cfg, params, prompt, ref, [0], adapt_every=2)
    out = eng.run_until_drained()
    assert out[0] == ref
    assert eng.k_live == 0, "zero accept rate must disable speculation"
    assert eng.spec_ticks < 12, "later ticks must be plain decode"


def test_healthy_accept_rate_keeps_speculation_live(model):
    import jax.numpy as jnp
    from repro.serve.engine import greedy_generate
    cfg, params = model
    prompt = list(range(5, 12))
    ref = np.asarray(greedy_generate(
        params, cfg, jnp.asarray(prompt)[None], 12, max_len=64)[0]).tolist()
    eng = _spec_engine(cfg, params, prompt, ref, [1], adapt_every=3)
    out = eng.run_until_drained()
    assert out[0] == ref
    assert eng.k_live >= 1, "perfect drafts must keep speculation on"


def test_rechoose_k_tracks_accept_rate():
    cfg = configs.get_smoke("qwen3-4b")
    k_lo, _ = spec.rechoose_k(cfg, 4, [16, 20], 0.0, 2)
    k_hi, _ = spec.rechoose_k(cfg, 4, [16, 20], 1.0, 2)
    assert k_lo == 0 and 1 <= k_hi <= 2


# ----------------------------------------------------------------------------
# 8-device subprocess: parity oracle + rs_matmul + sharded-pool engine
# ----------------------------------------------------------------------------

MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.launch import mesh as mesh_lib
from repro.dist import collective_matmul as cm
from repro.models import transformer as T
from repro.serve.engine import Request, ServeConfig, ServingEngine
from jax import shard_map
from jax.sharding import PartitionSpec as P

assert jax.device_count() == 8
results = {}

# 1. rs_matmul == ag_matmul == x @ w == explicit all-reduce, and the ring
#    compiles to collective-permutes (no entry-computation all-reduce).
mesh = mesh_lib.make_mesh((8,), ("model",))
rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(16, 64), jnp.float32)
w = jnp.asarray(rng.randn(64, 128), jnp.float32)
ref = np.asarray(x @ w)

def ar_matmul(x, w):   # the naive row-parallel baseline rs_matmul halves
    kb = x.shape[1] // 8
    def body(xb, wf):
        i = jax.lax.axis_index("model")
        wb = jax.lax.dynamic_slice_in_dim(wf, i * kb, kb, axis=0)
        return jax.lax.psum(xb @ wb, "model")
    return shard_map(body, mesh=mesh, in_specs=(P(None, "model"),
                     P(None, None)), out_specs=P(None, None),
                     check_vma=False)(x, w)

for name, fn in (("rs", lambda: cm.rs_matmul(x, w, mesh, "model")),
                 ("ag", lambda: cm.ag_matmul(x, w, mesh, "model")),
                 ("ar", lambda: ar_matmul(x, w))):
    np.testing.assert_allclose(np.asarray(fn()), ref, rtol=1e-4, atol=1e-4)
hlo = jax.jit(lambda x, w: cm.rs_matmul(x, w, mesh, "model")).lower(
    x, w).compile().as_text()
assert "collective-permute" in hlo
assert "all-reduce" not in hlo.split("ENTRY")[-1], \
    "psum-scatter ring should replace the big all-reduce"
# Non-divisible n falls back to the plain matmul.
w_odd = jnp.asarray(rng.randn(64, 130), jnp.float32)
np.testing.assert_allclose(np.asarray(cm.rs_matmul(x, w_odd, mesh,
                           "model")), np.asarray(x @ w_odd), rtol=1e-4)
results["rs_matmul"] = "ok"

# 2. Engine parity oracle: greedy / sampled / preemption / spec streams on
#    the 8-device engine must be bit-identical to the single-device paged
#    engine, with >= one slot's page table spanning >= 2 devices and
#    exactly one decode (and verify) executable per mesh.
cfg = configs.get_smoke("qwen3-4b")
params = T.init_params(jax.random.PRNGKey(0), cfg)
prng = np.random.RandomState(1)
prompts = [prng.randint(2, cfg.vocab, n).astype(np.int32)
           for n in (9, 13, 6, 11)]

def run(scfg_kw, n_req, max_new, mesh=None, watch_span=False):
    eng = ServingEngine(params, cfg, ServeConfig(**scfg_kw), mesh=mesh)
    for i in range(n_req):
        eng.submit(Request(rid=i, prompt=prompts[i].copy(),
                           max_new=max_new))
    spans = {}
    if watch_span:
        orig = eng.tick
        def tick():
            n = orig()
            for rid, pages in eng.pool.slot_pages.items():
                devs = {eng.pool.device_of(p) for p in pages}
                spans[rid] = spans.get(rid, set()) | devs
            return n
        eng.tick = tick
    out = {k: list(v) for k, v in eng.run_until_drained().items()}
    return out, eng, spans

greedy = dict(max_len=64, batch=3, eos_id=-1, paged=True, page_size=4,
              chunk_size=8, n_pages=56)
g1, _, _ = run(greedy, 3, 12)
g8, e8, spans = run(greedy, 3, 12, mesh=mesh, watch_span=True)
assert g1 == g8, (g1, g8)
assert any(len(v) >= 2 for v in spans.values()), spans
assert e8.decode_traces == 1, e8.decode_traces
assert e8.pool.n_devices == 8
# Same n_pages -> same capacity as the 1-device pool (global null page).
assert e8.pool.capacity == ServingEngine(
    params, cfg, ServeConfig(**greedy)).pool.capacity
results["greedy"] = "ok"

sampled = dict(greedy, temperature=0.9, seed=5)
s1, _, _ = run(sampled, 3, 8)
s8, _, _ = run(sampled, 3, 8, mesh=mesh)
assert s1 == s8, (s1, s8)
results["sampled"] = "ok"

tiny = dict(max_len=64, batch=4, eos_id=-1, paged=True, page_size=4,
            chunk_size=8, n_pages=16)
p1, ep1, _ = run(tiny, 4, 10)
p8, ep8, _ = run(tiny, 4, 10, mesh=mesh)
assert p1 == p8, (p1, p8)
assert ep8.preemptions > 0 and ep1.preemptions == ep8.preemptions
results["preempt"] = "ok"

spec_kw = dict(max_len=64, batch=3, eos_id=-1, paged=True, page_size=4,
               chunk_size=8, spec_k=2, draft="ngram")
k1, _, _ = run(spec_kw, 3, 10)
k8, ev8, _ = run(spec_kw, 3, 10, mesh=mesh)
assert k1 == k8, (k1, k8)
assert ev8.verify_traces == 1, ev8.verify_traces
results["spec"] = "ok"

# 3. Weights made already sharded (the mesh launcher's init) match the
#    one-device bf16 init bit for bit, each leaf carries the sharding the
#    engine places it with, and the big leaves really are split. The
#    engine's page pools are made sharded too, and start zeroed.
import functools
from repro.serve import dist as serve_dist
key = jax.random.PRNGKey(0)
init = functools.partial(T.init_params, cfg=cfg, dtype=jnp.bfloat16)
shardings = serve_dist.param_shardings(jax.eval_shape(init, key), mesh)
made = jax.jit(init, out_shardings=shardings)(key)
eng = ServingEngine(made, cfg, ServeConfig(**greedy), mesh=mesh)
for a, b, p, sh in zip(jax.tree.leaves(made), jax.tree.leaves(init(key)),
                       jax.tree.leaves(eng.params), jax.tree.leaves(shardings)):
    assert a.dtype == jnp.bfloat16 and a.sharding == sh == p.sharding
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
assert any(not s.is_fully_replicated for s in jax.tree.leaves(shardings))
for c, sh in zip(jax.tree.leaves(eng.caches), jax.tree.leaves(
        serve_dist.cache_shardings(eng.caches, mesh))):
    assert c.sharding == sh and not np.asarray(c).any()
assert not eng.caches[0]["kp"].sharding.is_fully_replicated
results["sharded_init"] = "ok"

print("MULTIDEV_RESULTS:" + ",".join(f"{k}={v}"
                                     for k, v in results.items()))
"""


@pytest.mark.slow
def test_multidevice_serving_parity(tmp_path):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = tmp_path / "serve_dist.py"
    script.write_text(MULTIDEV_SCRIPT)
    proc = subprocess.run([sys.executable, str(script), src],
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for part in ("rs_matmul", "greedy", "sampled", "preempt", "spec",
                 "sharded_init"):
        assert f"{part}=ok" in proc.stdout, proc.stdout

"""Attention cost model: monotonicity + feasibility properties, and the
persistent tuning cache round-trip."""

import dataclasses
import json
import os

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import autotune, hwmodel


def _problem(sq, skv, causal=True, heads=8, d=128, batch=1):
    return autotune.AttnProblem(sq=sq, skv=skv, n_heads=heads, head_dim=d,
                                batch=batch, causal=causal)


@given(skv=st.sampled_from([512, 1024, 2048, 4096, 8192]),
       causal=st.sampled_from([True, False]))
@settings(max_examples=10, deadline=None)
def test_attn_cost_monotone_in_kv_length(skv, causal):
    c = autotune.AttnBlock(128, 128)
    t1, _ = autotune.attn_cost(_problem(512, skv, causal), c)
    t2, _ = autotune.attn_cost(_problem(512, 2 * skv, causal), c)
    assert t2 > t1


@given(sq=st.sampled_from([256, 512, 1024, 2048]))
@settings(max_examples=8, deadline=None)
def test_attn_cost_monotone_in_query_length(sq):
    c = autotune.AttnBlock(128, 128)
    t1, _ = autotune.attn_cost(_problem(sq, 4096), c)
    t2, _ = autotune.attn_cost(_problem(2 * sq, 4096), c)
    assert t2 > t1


@given(batch=st.integers(1, 8), heads=st.sampled_from([4, 8, 16]))
@settings(max_examples=8, deadline=None)
def test_attn_cost_monotone_in_rows(batch, heads):
    c = autotune.AttnBlock(128, 128)
    p = _problem(512, 2048, heads=heads, batch=batch)
    t1, _ = autotune.attn_cost(p, c)
    t2, _ = autotune.attn_cost(dataclasses.replace(p, batch=2 * batch), c)
    assert t2 > t1


@given(sq=st.sampled_from([1024, 2048, 4096]),
       bk=st.sampled_from([128, 256, 512]))
@settings(max_examples=8, deadline=None)
def test_causal_skips_work_and_traffic(sq, bk):
    """The skipped-load grid visits ~half the blocks of the full grid."""
    c = autotune.AttnBlock(128, bk)
    _, terms_c = autotune.attn_cost(_problem(sq, sq, causal=True), c)
    _, terms_f = autotune.attn_cost(_problem(sq, sq, causal=False), c)
    assert terms_c["visited_blocks"] < terms_f["visited_blocks"]
    assert terms_c["traffic_bytes"] < terms_f["traffic_bytes"]
    # Block-granular triangle: between half and half-plus-one-diagonal.
    frac = terms_c["visited_blocks"] / terms_f["visited_blocks"]
    assert 0.5 <= frac <= 0.5 + bk / sq + 1e-9


@given(sq=st.sampled_from([256, 1024, 4096, 16384]),
       causal=st.sampled_from([True, False]))
@settings(max_examples=10, deadline=None)
def test_choose_attn_block_beats_or_ties_naive(sq, causal):
    p = _problem(sq, sq, causal)
    cfg, terms = autotune.choose_attn_block(p, use_cache=False)
    t_naive, _ = autotune.attn_cost(p, autotune.NAIVE_ATTN_BLOCK)
    assert terms["time_s"] <= t_naive + 1e-12
    budget = hwmodel.DEFAULT_TPU.vmem_bytes * 0.5
    assert cfg.vmem_bytes(p) <= budget


def test_candidates_respect_vmem_budget():
    p = _problem(8192, 8192)
    for c in autotune.candidate_attn_blocks(p):
        assert c.vmem_bytes(p) <= hwmodel.DEFAULT_TPU.vmem_bytes * 0.5


def test_decode_speedup_gt_one_for_ragged_contexts():
    out = autotune.decode_attn_speedup(
        32768, [512, 4096, 16384, 32768], n_heads=32, n_kv_heads=8,
        head_dim=128)
    assert out["speedup"] > 1.0
    full = autotune.decode_attn_speedup(
        32768, [32768, 32768], n_heads=32, n_kv_heads=8, head_dim=128)
    assert full["speedup"] == pytest.approx(1.0)


@pytest.mark.parametrize("garbage", [
    '{"tpu_v5e:sq=1024', " ", "\x00\x01binary", "null", "[1, 2, 3]", '"str"',
])
def test_tuning_cache_recovers_from_corrupt_file(tmp_path, monkeypatch,
                                                 garbage):
    """A torn concurrent write (truncated / binary / non-object JSON)
    must not crash the cache: the bad file is discarded and the next
    write-through rebuilds it."""
    path = tmp_path / "cache.json"
    path.write_text(garbage)
    monkeypatch.setattr(autotune, "TUNING_CACHE_PATH", str(path))
    monkeypatch.setattr(autotune, "_tuning_cache", None)
    p = _problem(1024, 1024)
    cfg, terms = autotune.choose_attn_block(p)
    assert "cached" not in terms              # recovered, not served stale
    analytic, _ = autotune.choose_attn_block(p, use_cache=False)
    assert cfg == analytic
    rebuilt = json.load(open(path))           # rebuilt clean by the store
    assert isinstance(rebuilt, dict) and len(rebuilt) == 1


def test_tuning_cache_tolerates_malformed_entry(tmp_path, monkeypatch):
    """A structurally-broken entry (file parses, entry torn) is a miss and
    gets overwritten with a good one."""
    path = tmp_path / "cache.json"
    monkeypatch.setattr(autotune, "TUNING_CACHE_PATH", str(path))
    monkeypatch.setattr(autotune, "_tuning_cache", None)
    p = _problem(1024, 1024)
    key = autotune._cache_key(p, hwmodel.DEFAULT_TPU)
    for bad in ({"block_q": 128}, "torn", {"block_q": "x", "block_k": 1,
                                           "terms": {}, "time_s": 0.0}):
        path.write_text(json.dumps({key: bad}))
        monkeypatch.setattr(autotune, "_tuning_cache", None)
        cfg, terms = autotune.choose_attn_block(p)
        assert "cached" not in terms, bad
        assert cfg == autotune.choose_attn_block(p, use_cache=False)[0]
        stored = json.load(open(path))[key]
        assert stored["block_q"] == cfg.block_q   # overwritten in place


def test_tuning_cache_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "cache.json"
    monkeypatch.setattr(autotune, "TUNING_CACHE_PATH", str(path))
    monkeypatch.setattr(autotune, "_tuning_cache", None)
    p = _problem(1024, 1024)
    cfg, terms = autotune.choose_attn_block(p)
    assert "cached" not in terms
    assert os.path.exists(path)
    stored = json.load(open(path))
    assert len(stored) == 1
    # Second call (fresh in-memory cache) serves the persisted entry.
    monkeypatch.setattr(autotune, "_tuning_cache", None)
    cfg2, terms2 = autotune.choose_attn_block(p)
    assert cfg2 == cfg
    assert terms2["cached"] is True
    assert terms2["time_s"] == pytest.approx(terms["time_s"])


def test_tuning_cache_cpu_entry_not_read_on_tpu(tmp_path, monkeypatch):
    """Entries are keyed by the live backend: a block a CPU run stored
    (the cache file is untracked, so it travels with a copied tree) is a
    miss for a run on the chip, which derives its own choice."""
    import jax
    path = tmp_path / "cache.json"
    monkeypatch.setattr(autotune, "TUNING_CACHE_PATH", str(path))
    monkeypatch.setattr(autotune, "_tuning_cache", None)
    p = _problem(1024, 1024)
    analytic, _ = autotune.choose_attn_block(p, use_cache=False)
    planted = next(c for c in autotune.candidate_attn_blocks(p)
                   if c != analytic)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    cpu_key = autotune._cache_key(p, hwmodel.DEFAULT_TPU)
    path.write_text(json.dumps({cpu_key: {
        "block_q": planted.block_q, "block_k": planted.block_k,
        "time_s": 1.0, "terms": {}}}))
    blk, terms = autotune.choose_attn_block(p)
    assert blk == planted and terms["cached"] is True
    monkeypatch.setattr(autotune, "_tuning_cache", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert autotune._cache_key(p, hwmodel.DEFAULT_TPU) != cpu_key
    blk, terms = autotune.choose_attn_block(p)
    assert "cached" not in terms and blk == analytic


# ----------------------------------------------------------------------------
# Chunked-prefill cost model
# ----------------------------------------------------------------------------

def test_prefill_chunk_model_terms():
    """The chunk-size trade's two ends: the whole-prompt 'chunk' has the
    worst interleave latency (one chunk = the whole prefill), small chunks
    pay more dispatches; the lookup term scales with visited blocks."""
    dims = dict(n_heads=32, n_kv_heads=8, head_dim=128, page_size=256)
    small = autotune.prefill_chunk_model(8192, 256, **dims)
    whole = autotune.prefill_chunk_model(8192, 8192, **dims)
    assert small["n_chunks"] == 32 and whole["n_chunks"] == 1
    assert small["interleave_latency_s"] < whole["interleave_latency_s"]
    assert small["dispatch_s"] > whole["dispatch_s"]
    assert whole["interleave_latency_s"] == pytest.approx(
        whole["prefill_s"])
    for terms in (small, whole):
        assert terms["prefill_s"] == pytest.approx(
            terms["attn_s"] + terms["lookup_s"] + terms["dispatch_s"])
        assert terms["lookup_s"] > 0


def test_choose_prefill_chunk_is_page_aligned_and_bounded():
    chunk, terms = autotune.choose_prefill_chunk(
        32768, n_heads=32, n_kv_heads=8, head_dim=128, page_size=256)
    assert chunk % 256 == 0 and 256 <= chunk <= 32768
    assert terms["score_s"] >= terms["prefill_s"]
    # A chunk far below max_len must win once latency is priced at all:
    # whole-prompt prefill stalls every decode slot for the full prompt.
    assert chunk < 32768


# ----------------------------------------------------------------------------
# Speculative-decode cost model
# ----------------------------------------------------------------------------

SPEC_DIMS = dict(n_heads=32, n_kv_heads=8, head_dim=128, page_size=256,
                 param_bytes=8e9)
SPEC_LENS = [512, 2048, 8192, 32768]


def test_expected_spec_tokens_bounds():
    """E[tokens/tick] = sum a^i: 1 at k=0 or a=0, k+1 at a=1, monotone in
    both arguments."""
    assert autotune.expected_spec_tokens(0, 0.9) == 1.0
    assert autotune.expected_spec_tokens(4, 0.0) == 1.0
    assert autotune.expected_spec_tokens(4, 1.0) == pytest.approx(5.0)
    e2 = autotune.expected_spec_tokens(2, 0.6)
    e4 = autotune.expected_spec_tokens(4, 0.6)
    assert 1.0 < e2 < e4 < 5.0
    assert autotune.expected_spec_tokens(2, 0.8) > e2


def test_spec_decode_model_terms():
    """The verify-width trade: a wider tick costs more than a plain tick
    (the overhead an accept rate must beat) but amortizes the fixed
    weight stream — at a healthy accept rate the tokens/sec win."""
    out = autotune.spec_decode_model(SPEC_LENS, k=4,
                                     accept_rate=0.8, **SPEC_DIMS)
    assert out["spec_tick_s"] > out["plain_tick_s"]
    assert out["verify_overhead_frac"] > 0
    assert out["weight_stream_s"] > 0
    assert out["expected_tokens_per_tick"] == pytest.approx(
        autotune.expected_spec_tokens(4, 0.8))
    assert out["speedup"] == pytest.approx(
        out["tokens_per_s_spec"] / out["tokens_per_s_plain"])
    assert out["speedup"] > 1.0
    # Zero accepts: pure overhead, strictly worse than plain decode.
    zero = autotune.spec_decode_model(SPEC_LENS, k=4,
                                      accept_rate=0.0, **SPEC_DIMS)
    assert zero["speedup"] < 1.0


def test_spec_speedup_monotone_in_accept_rate():
    prev = 0.0
    for a in (0.1, 0.4, 0.7, 0.95):
        out = autotune.spec_decode_model(SPEC_LENS, k=4,
                                         accept_rate=a, **SPEC_DIMS)
        assert out["speedup"] > prev
        prev = out["speedup"]


def test_choose_spec_k_disables_when_speculation_loses():
    """k=0 is a real answer: a low accept rate plus an expensive serial
    model draft must disable speculation, while the free n-gram drafter
    at a healthy accept rate picks k >= 1 with a real speedup."""
    k, terms = autotune.choose_spec_k(SPEC_LENS, accept_rate=0.05,
                                      draft_bytes=1e9, **SPEC_DIMS)
    assert k == 0 and terms["speedup"] <= 1.0
    k2, terms2 = autotune.choose_spec_k(SPEC_LENS, accept_rate=0.7,
                                        **SPEC_DIMS)
    assert k2 >= 1 and terms2["speedup"] > 1.0 and terms2["chosen_k"] == k2


def test_choose_spec_k_grows_with_accept_rate():
    klo, _ = autotune.choose_spec_k(SPEC_LENS, accept_rate=0.3,
                                    **SPEC_DIMS)
    khi, _ = autotune.choose_spec_k(SPEC_LENS, accept_rate=0.95,
                                    **SPEC_DIMS)
    assert khi >= klo

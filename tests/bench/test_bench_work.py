"""Operation and byte counts of ``bench/work.py`` against hand counts."""

import json
import os

import pytest

import _bench_tiny as tb
from bench import readers, work
from bench.arch import dense

C = tb.CONFIG            # L=2, d=64, h=4, kvh=2, hd=16, f=128, V=256, bf16


def _published(name):
    with open(os.path.join(tb.REPO, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_layer_params():
    # q, k, v, o: 64*4*16 + 2*(64*2*16) + 4*16*64; MLP: 3*64*128.
    assert work.layer_matmul_params(C) == 4096 + 4096 + 4096 + 24576


def test_token_and_chunk_flops():
    # Dense 2*L*36864, head 2*64*256, attention 4*L*h*hd*ctx.
    assert work.token_flops(C, 10) == 2 * 2 * 36864 + 2 * 64 * 256 \
        + 4 * 2 * 4 * 16 * 10
    # Rows at 3, 4, 5, 6 attend 4 + 5 + 6 + 7 keys.
    assert work.chunk_pairs(3, 4) == 22
    assert work.chunk_flops(C, 3, 4, head=False) == \
        2 * 2 * 36864 * 4 + 4 * 2 * 4 * 16 * 22
    assert work.chunk_flops(C, 3, 4, head=True) - \
        work.chunk_flops(C, 3, 4, head=False) == 2 * 64 * 256


def test_kernel_counts():
    # Decode, contexts 5 and 7: K+V rows 2*L*n*kvh*hd*2 B, q+o 2*L*h*hd*2 B.
    flops, nbytes = work.decode_kernel(C, [5, 7])
    assert flops == 4 * 2 * 4 * 16 * 12
    assert nbytes == 2 * 2 * 12 * 2 * 16 * 2 + 2 * (2 * 2 * 4 * 16 * 2)
    flops, nbytes = work.prefill_kernel(C, [(0, 4)])
    assert flops == 4 * 2 * 4 * 16 * 10
    assert nbytes == 2 * 2 * 4 * 2 * 16 * 2 + 2 * 2 * 4 * 4 * 16 * 2


def _run(recs, t_open=10.0, t_close=20.0):
    return readers.Run(cell="t", config=C, mix={}, peak={}, batch=4,
                       setup_s=0.0, t_open=t_open, t_close=t_close,
                       due_lo=t_open, due_hi=t_close, requests=recs,
                       ticks=[])


def test_prefill_work_is_counted_in_the_windows_share():
    """Each prompt's whole prefill, spread from the start of the tick that
    gave it a slot to its first token, in the share inside the window."""
    rec = readers.Rec
    recs = [rec(0, 0.0, 40, 2, slot_a=8.0, slot_t=8.1, times=[12.0, 13.0]),
            rec(1, 0.0, 30, 2, slot_a=11.0, slot_t=11.1, times=[12.0]),
            rec(2, 0.0, 20, 2, slot_a=19.0, slot_t=19.1, times=[23.0]),
            rec(3, 0.0, 10, 2, slot_a=2.0, slot_t=2.1, times=[5.0]),
            rec(4, 0.0, 10, 2)]
    run = _run(recs)
    assert readers.prefills(run) == [(40, 0.5), (30, 1.0), (20, 0.25)]
    assert readers.prefill_flops(run) == pytest.approx(
        0.5 * work.chunk_flops(C, 0, 40, True)
        + work.chunk_flops(C, 0, 30, True)
        + 0.25 * work.chunk_flops(C, 0, 20, True))
    # Token 1 of request 0 is decoded at 13.0 from position 40, seeing 41.
    assert readers.decode_ctxs(run) == [41]


def test_roofline_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(1000.0, 50.0, peak) == (10.0, "compute")
    assert work.roofline_s(100.0, 50.0, peak) == (5.0, "memory")


@pytest.mark.parametrize("cfg", [C, _published("qwen3-4b"),
                                 _published("phi3-mini-3.8b")],
                         ids=["tiny", "qwen3-4b", "phi3-mini-3.8b"])
def test_token_flops_match_the_weights_a_token_multiplies(cfg):
    """Twice the matmul weights of every layer and the head, whatever
    the layout that ``bench/arch/dense.py`` makes."""
    shapes = dense.shapes(cfg)
    n = 0
    for blk in shapes["blocks"]:
        for name in ("wq", "wk", "wv", "wo"):
            s = blk["attn"][name]
            n += s[0] * s[1] * s[2] * s[3]
        for s in blk["mlp"].values():
            n += s[0] * s[1] * s[2]
    d, v = shapes["unembed"]["lm_head"]
    assert work.token_flops(cfg, 0) == 2.0 * (n + d * v)


@pytest.mark.parametrize("name,params", [("qwen3-4b", 4.41e9),
                                         ("phi3-mini-3.8b", 3.82e9)])
def test_published_sizes(name, params):
    """4.41 B parameters for qwen3-4b with its untied head, 3.82 B for
    phi3-mini."""
    cfg = _published(name)
    total = 0
    for leaf in (dense.shapes(cfg)["embed"]["embedding"],
                 dense.shapes(cfg)["unembed"]["lm_head"]):
        total += leaf[0] * leaf[1]
    total += cfg["num_hidden_layers"] * work.layer_matmul_params(cfg)
    assert abs(total / params - 1) < 0.005, total


def test_kernel_time_is_the_mean_over_the_cells_chips():
    """A kernel's device time is the mean over the cell's planes; an
    executable's launches are those of the first plane, since every chip
    of a mesh runs each launch."""
    run = _run([])
    run.trace = {"window_s": 1.0, "busy_s": 0.5, "devices": [
        {"ops_s": {"flash_decode_paged.1": 0.3, "copy.2": 0.1},
         "modules_s": {"jit_step": [0.1, 0.2]}},
        {"ops_s": {"flash_decode_paged.1": 0.5},
         "modules_s": {"jit_step": [0.3, 0.4]}}]}
    assert readers.op_s(run, "flash_decode_paged") == pytest.approx(0.4)
    assert readers.op_s(run, "copy") == pytest.approx(0.05)
    assert readers.module_s(run, "jit_step") == [0.1, 0.2]

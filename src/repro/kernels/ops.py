"""Jit'd public wrappers for the Pallas kernels.

On the CPU the kernels execute in interpret mode (the kernel body runs in
Python for correctness validation); on a TPU backend they compile
natively. ``native()`` is the one platform test: it also decides whether
the serving engine routes attention through these kernels
(``serve.engine.ServingEngine``). Block shapes default to the
microbench-informed autotuner's choices (``core/autotune``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.kernels import flash_attention as _flash
from repro.kernels import flash_decode as _flash_decode
from repro.kernels import gemm as _gemm
from repro.kernels import pchase_probe as _pchase
from repro.kernels import ssd_scan as _ssd


def native() -> bool:
    """True when the kernels compile for the chip (the TPU backend)."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not native()


def gemm(x, y, block=None):
    if block is None:
        p = autotune.GemmProblem(m=x.shape[0], k=x.shape[1], n=y.shape[1],
                                 in_bytes=x.dtype.itemsize)
        cfg, _ = autotune.choose_gemm_block(p)
        bm = min(cfg.bm, x.shape[0])
        bk = min(cfg.bk, x.shape[1])
        bn = min(cfg.bn, y.shape[1])
    else:
        bm, bk, bn = block
    # Fall back to aligned divisors when shapes don't tile.
    bm = _largest_divisor(x.shape[0], bm)
    bk = _largest_divisor(x.shape[1], bk)
    bn = _largest_divisor(y.shape[1], bn)
    return _gemm.gemm(x, y, bm=bm, bk=bk, bn=bn, interpret=_interpret())


_largest_divisor = _flash._largest_divisor


def flash_attention(q, k, v, causal: bool = True, block_q=None,
                    block_k=None):
    # block defaults (None) resolve inside the kernel via the attention
    # cost model; explicit blocks just snap to dividing sizes here.
    if block_q is not None:
        block_q = _largest_divisor(q.shape[1], block_q)
    if block_k is not None:
        block_k = _largest_divisor(k.shape[1], block_k)
    return _flash.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=_interpret())


def flash_attention_paged(q, k_pages, v_pages, page_table, starts,
                          block_q=None, block_k=None):
    """Chunked-prefill causal attention against a paged KV pool: q
    (b, sq, h, d) at global positions ``starts[i] + [0, sq)`` vs a
    (n_pages, page_size, kvh, d) pool walked through ``page_table``.
    The chunk's rows must already be written through the table."""
    if block_q is not None:
        block_q = _largest_divisor(q.shape[1], block_q)
    if block_k is not None:
        block_k = _largest_divisor(k_pages.shape[1], block_k)
    return _flash.flash_attention_paged(
        q, k_pages, v_pages, page_table, starts, block_q=block_q,
        block_k=block_k, interpret=_interpret())


def flash_decode(q, k, v, lengths, block_k=None):
    """Single-token GQA decode: q (b, h, d) vs ragged (b, max_len, kvh, d).

    ``block_k=None`` resolves through the attention cost model inside the
    kernel wrapper."""
    if block_k is not None:
        block_k = _largest_divisor(k.shape[1], block_k)
    return _flash_decode.flash_decode(q, k, v, lengths, block_k=block_k,
                                      interpret=_interpret())


def flash_decode_paged(q, k_pages, v_pages, page_table, lengths):
    """Paged GQA decode: q (b, h, d) vs a (n_pages, page_size, kvh, d)
    pool walked through ``page_table`` (b, max_pages), one page (every kv
    head) per step as the pool stores it."""
    return _flash_decode.flash_decode_paged(
        q, k_pages, v_pages, page_table, lengths, interpret=_interpret())


def ssd_scan(x, a_log, b, c, chunk: int = 128):
    chunk = _largest_divisor(x.shape[1], chunk)
    return _ssd.ssd_scan(x, a_log, b, c, chunk=chunk,
                         interpret=_interpret())


def pchase(chain, steps: int):
    return _pchase.pchase(chain, steps, interpret=_interpret())

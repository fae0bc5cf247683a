"""Operations and bytes that the algorithm needs, computed from shapes.

These count what serving requires, whatever kernel or layout runs it:
attention over the live context only (never ``max_len`` or the pool),
one output-head row per sampled token, weights read once per step. So a
change that removes a copy or a kernel can raise a share but never push
it past the chip's peak. A multiply-add counts as two operations.

The counts that depend on the layers' shapes (weights a token multiplies
through, K and V bytes per position) come from the configuration's
architecture module, ``bench/arch/<arch>.py``.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from bench import arch


def _dims(c: dict):
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["head_dim"], c["vocab_size"])


def _itemsize(c: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[c["torch_dtype"]]


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies through in one layer."""
    return arch.load(c).layer_matmul_params(c)


def attention_flops(c: dict, queries_keys: int) -> float:
    """QK^T and PV over ``queries_keys`` (query, key) pairs, all layers."""
    L, _, h, hd, _ = _dims(c)
    return 4.0 * L * h * hd * queries_keys


def token_flops(c: dict, ctx: int, head: bool = True) -> float:
    """One token through the model, attending ``ctx`` keys."""
    L, d, _, _, V = _dims(c)
    dense = 2.0 * L * layer_matmul_params(c) + (2.0 * d * V if head else 0)
    return dense + attention_flops(c, ctx)


def chunk_pairs(start: int, rows: int) -> int:
    """Causal (query, key) pairs of ``rows`` queries at ``start``."""
    return rows * start + rows * (rows + 1) // 2


def chunk_flops(c: dict, start: int, rows: int, head: bool) -> float:
    """A prefill chunk; ``head`` when its last row's logits are sampled."""
    L, d, _, _, V = _dims(c)
    return (2.0 * L * layer_matmul_params(c) * rows
            + attention_flops(c, chunk_pairs(start, rows))
            + (2.0 * d * V if head else 0.0))


def kv_bytes(c: dict, rows: int) -> int:
    """K and V of ``rows`` positions in all layers."""
    return arch.load(c).kv_bytes(c, rows)


def qo_bytes(c: dict, rows: int) -> int:
    """Query in and attention out of ``rows`` positions, all layers."""
    L, _, h, hd, _ = _dims(c)
    return 2 * L * rows * h * hd * _itemsize(c)


def decode_kernel(c: dict, ctxs: Iterable[int]) -> Tuple[float, float]:
    """(flops, bytes) of paged decode attention for one token per slot,
    slot i attending ``ctxs[i]`` keys, all layers."""
    ctxs = list(ctxs)
    return (attention_flops(c, sum(ctxs)),
            float(sum(kv_bytes(c, n) + qo_bytes(c, 1) for n in ctxs)))


def prefill_kernel(c: dict, chunks: Iterable[Tuple[int, int]]
                   ) -> Tuple[float, float]:
    """(flops, bytes) of paged causal chunk attention for chunks of
    (start, rows), all layers."""
    chunks = list(chunks)
    return (attention_flops(c, sum(chunk_pairs(s, n) for s, n in chunks)),
            float(sum(kv_bytes(c, s + n) + qo_bytes(c, n)
                      for s, n in chunks)))


def roofline_s(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

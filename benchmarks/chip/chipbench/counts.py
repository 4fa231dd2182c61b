"""Operations and bytes that the work needs, from shapes alone.

Counted from live tokens only: a decode row attends to the tokens cached
for it plus its own, a chunk token to the tokens before it. Page padding,
idle rows and padded chunk tokens are left out, so no implementation can
read above its roofline. Per-chip counts divide the heads by the number of
chips the model axis splits them over.
"""
from __future__ import annotations

from chipbench.weights import Dims

BF16 = 2


def matmul_params(m: Dims) -> int:
    """Weights a token multiplies by in one layer."""
    return m.d * (m.heads + 2 * m.kv_heads) * m.head_dim + m.heads * m.head_dim * m.d + 3 * m.d * m.ff


def token_flops(m: Dims, ctx: int) -> float:
    """Model FLOPs of one token through every layer, attending to ``ctx``
    tokens (itself included); the output head is counted apart."""
    return m.layers * (2.0 * matmul_params(m) + 4.0 * m.heads * m.head_dim * ctx)


def head_flops(m: Dims) -> float:
    return 2.0 * m.d * m.vocab


def decode_flops(m: Dims, ctxs) -> float:
    """One decode step: every row through the stack and the head."""
    return sum(token_flops(m, c) + head_flops(m) for c in ctxs)


def chunk_flops(m: Dims, start: int, n_real: int) -> float:
    """One prefill chunk: its real tokens through the stack, one head row."""
    return sum(token_flops(m, start + i + 1) for i in range(n_real)) + head_flops(m)


def _least_s(flops: float, nbytes: float, peaks) -> float:
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes)


def paged_decode_least_s(m: Dims, ctxs, shards: int, peaks) -> float:
    """Least time of the paged decode kernel over one layer on one chip."""
    h, kv, hd = m.heads // shards, m.kv_heads // shards, m.head_dim
    flops = sum(4.0 * h * hd * c for c in ctxs)
    nbytes = sum(2.0 * kv * hd * BF16 * c for c in ctxs) + 2.0 * len(ctxs) * h * hd * BF16
    return _least_s(flops, nbytes, peaks)


def paged_chunk_least_s(m: Dims, start: int, n_real: int, shards: int, peaks) -> float:
    """Least time of the paged chunk kernel over one layer on one chip."""
    h, kv, hd = m.heads // shards, m.kv_heads // shards, m.head_dim
    flops = sum(4.0 * h * hd * (start + i + 1) for i in range(n_real))
    nbytes = 2.0 * kv * hd * BF16 * (start + n_real) + 2.0 * n_real * h * hd * BF16
    return _least_s(flops, nbytes, peaks)

"""Operations and bytes the model needs, worked out from its shapes.

Counts are what the algorithm needs, not what the program happens to
compute: causal attention counts each query against the keys at or
before it, and the output head counts the rows whose logits are used.
One layer's matrix work per token is the configuration's family's
(``matmul_params_per_token``); attention, the head and the paged
kernel's traffic are the same for every family.
"""

from __future__ import annotations

import math
from typing import Iterable

from bench import config
from bench.config import Shapes


def head_flops(s: Shapes, rows: int) -> int:
    return 2 * rows * s.vocab * s.d_model


def attention_flops(s: Shapes, context: int) -> int:
    """One query token against ``context`` keys, over every layer:
    q.k and p.v, two multiply-adds each per head and position."""
    return 4 * context * s.heads * s.head_dim * s.layers


def prefill_flops(s: Shapes, start: int, n: int) -> int:
    """``n`` tokens at positions ``[start, start + n)`` with the rows
    before ``start`` already cached; logits for the last token only."""
    dense = 2 * n * s.layers * config.family(s).matmul_params_per_token(s)
    # sum of contexts start+1 .. start+n
    contexts = n * start + n * (n + 1) // 2
    return dense + attention_flops(s, 1) * contexts + head_flops(s, 1)


def decode_flops(s: Shapes, lens: Iterable[int]) -> int:
    """One decode step: one token per row, row i attending ``lens[i]``
    keys (its own included), with the head over every row."""
    lens = list(lens)
    per_row = 2 * s.layers * config.family(s).matmul_params_per_token(s)
    return (len(lens) * per_row + sum(attention_flops(s, n) for n in lens)
            + head_flops(s, len(lens)))


def paged_attn_bytes(s: Shapes, lens: Iterable[int]) -> int:
    """Least HBM traffic of the paged kernel for one decode step over
    every layer: each live page of K and V read whole, plus q read and
    the output written, in the served dtype (2 bytes)."""
    page_bytes = s.page_tokens * s.kv_heads * s.head_dim * 2
    per_layer = 0
    for n in lens:
        per_layer += 2 * math.ceil(n / s.page_tokens) * page_bytes
        per_layer += 2 * s.heads * s.head_dim * 2
    return per_layer * s.layers


def paged_attn_flops(s: Shapes, lens: Iterable[int]) -> int:
    return sum(attention_flops(s, n) for n in lens)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Roofline time: the larger of the compute and the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])

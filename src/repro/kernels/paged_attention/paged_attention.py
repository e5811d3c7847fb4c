"""Paged decode attention over SEE++ arena pages (Pallas TPU kernel).

One query token per sequence attends over a KV cache stored as
**non-contiguous pages** allocated by :class:`repro.core.arena.
PagedKVAllocator` — the TPU-native consequence of the paper's §IV.A memory
management: the page table (physical page index per logical page) is
scalar-prefetched so the index map can issue one HBM→VMEM DMA per page,
and *contiguity of the physical pages* (legacy vs modern allocator)
decides whether those DMAs coalesce into long strides.

Grid ``(B, max_pages)``: each step fetches one physical page and serves
**every** query head of that sequence from it — the earlier
``(B, K·G, max_pages)`` layout re-fetched the same page once per query
head, multiplying both the DMA traffic on TPU and the grid-iteration
overhead in interpret mode (CPU tests decode through this kernel in
interpret mode, so grid size is wall-clock there; a TPU runs it compiled).
Per-page online softmax lives in VMEM scratch shaped ``(K, G[, hd])``.
Invalid pages (table entry < 0, or beyond the sequence length) are
masked; their DMA reads page 0 (clamped index) and discards the result.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_pallas"]

NEG_INF = -2.0e38


def _kernel(
    table_ref,                 # (B, max_pages) int32 prefetched
    lens_ref,                  # (B,) int32 prefetched
    q_ref,                     # (1, KG, hd)  — every head of one sequence
    k_ref,                     # (1, page, K, hd)  — one physical page
    v_ref,
    o_ref,                     # (1, KG, hd)
    m_ref, l_ref, acc_ref,     # VMEM scratch: (K, G), (K, G), (K, G, hd)
    *,
    scale: float,
    page_size: int,
    max_pages: int,
    num_kv: int,
):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[b]
    page_id = table_ref[b, p]
    valid_page = jnp.logical_and(page_id >= 0, p * page_size < seq_len)

    @pl.when(valid_page)
    def _step():
        kg, hd = q_ref.shape[1], q_ref.shape[2]
        g = kg // num_kv
        q = q_ref[0].astype(jnp.float32).reshape(num_kv, g, hd) * scale
        k = k_ref[0].astype(jnp.float32)                      # (page, K, hd)
        # s[k, g, p'] = q[k, g, :] · k[p', k, :] — batched over kv heads
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )                                                     # (K, G, page)
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size,), 0
        )
        s = jnp.where((pos < seq_len)[None, None, :], s, NEG_INF)
        m_prev = m_ref[...]                                   # (K, G)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        pexp = jnp.exp(s - m_new[..., None])                  # (K, G, page)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = corr * l_ref[...] + jnp.sum(pexp, axis=-1)
        val = v_ref[0].astype(jnp.float32)                    # (page, K, hd)
        pv = jax.lax.dot_general(
            pexp, val, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
        )                                                     # (K, G, hd)
        acc_ref[...] = acc_ref[...] * corr[..., None] + pv
        m_ref[...] = m_new

    @pl.when(p == max_pages - 1)
    def _finish():
        kg, hd = o_ref.shape[1], o_ref.shape[2]
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        ).reshape(kg, hd).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret"),
)
def paged_attention_pallas(
    q: jnp.ndarray,            # (B, KG, hd)
    k_pages: jnp.ndarray,      # (num_pages, page_size, K, hd)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,   # (B, max_pages) int32, -1 padded
    lens: jnp.ndarray,         # (B,) int32
    *,
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    B, KG, hd = q.shape
    num_pages, page_size, K, _ = k_pages.shape
    max_pages = page_table.shape[1]

    kernel = functools.partial(
        _kernel, scale=scale, page_size=page_size, max_pages=max_pages,
        num_kv=K,
    )

    def _page_index(b, p, table, lens):
        return (jnp.maximum(table[b, p], 0), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, KG, hd), lambda b, p, t, l: (b, 0, 0)),
            pl.BlockSpec((1, page_size, K, hd), _page_index),
            pl.BlockSpec((1, page_size, K, hd), _page_index),
        ],
        out_specs=pl.BlockSpec((1, KG, hd), lambda b, p, t, l: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, KG // K), jnp.float32),
            pltpu.VMEM((K, KG // K), jnp.float32),
            pltpu.VMEM((K, KG // K, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KG, hd), q.dtype),
        interpret=interpret,
        # the HLO instruction's name, which trace reductions match on;
        # fixed here so that renaming this function cannot change it
        name="paged_attention",
    )(page_table, lens, q, k_pages, v_pages)
    return out

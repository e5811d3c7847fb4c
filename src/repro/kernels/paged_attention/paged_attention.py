"""Paged decode attention over SEE++ arena pages (Pallas TPU kernel).

One query token per sequence attends over a KV cache stored as
**non-contiguous pages** allocated by :class:`repro.core.arena.
PagedKVAllocator` — the TPU-native consequence of the paper's §IV.A memory
management.  The pool keeps the arena's layout ``(pages, page, K, hd)``,
which the prefill scatter and the copy-on-write page copy share, and stays
in HBM: the kernel gathers the pages it needs itself.

**Grid and blocks.**  The grid is one step, which walks every live block
of every row in order.  A block is ``ppb`` consecutive logical pages of one
row, ``ppb · page`` tokens.  One async copy per physical page (the table is
scalar-prefetched) gathers a block's K and V into VMEM buffers
``(2, ppb, page, K, hd)``.  The buffers are double-buffered: the copies of
the next block (the row's next one, or the first block of the next live
row) start before the current block is computed, so one block's DMA
overlaps the other's compute.

**Block size.**  :func:`pages_per_block` works ``ppb`` out from the shapes:
a power of two of pages near ``BLOCK_TOKENS`` tokens, smaller where a block
of K would pass ``BLOCK_BYTES``, capped at the table width rounded up to a
power of four.  Its docstring gives the measurements behind the rule.

**Dead work is skipped.**  A row ends at its length or at its first ``-1``
table entry, whichever comes first (the arena maps a sequence's pages to
logical pages ``0..n-1`` without gaps; empty and prefilling slots are
all-``-1`` rows).  A row has ``ceil(len / (ppb · page))`` live blocks.  The
blocks after them issue no copy and do no compute, and a row with none
only has its output zeroed.  Inside a row's last block only the pages that
hold live tokens are copied, and the block is computed over the smallest
power of four of pages that holds them (or the whole block).  That size
depends on the row alone, so a row's result does not change with the
table's width or with the other rows.  The V buffers are zeroed once per call, so a page slot
never copied holds zeros and cannot put ``0 · NaN`` into the output.

**Compute.**  Each KV head is computed on its own: its ``G`` query heads
against its tokens, an online softmax per head, so a head-sliced call
(``ops.paged_attention_sharded``) gives the same bits as the whole one.
For the served pools (16-bit, an even ``K``) a head's tokens are one
sublane-strided load away: the buffer's rows ``t·K + h`` (token ``t``,
head ``h``) read as 32-bit words each hold heads ``2j`` (low half) and
``2j + 1`` (high half) of a token, and two bit operations split them.
Other pools take the heads apart as values.

**Numerics.**  K and V are widened to f32 exactly; ``q·k``, its scaling,
the online softmax and ``p·v`` all run in f32, and ``p`` is never rounded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_pallas", "pages_per_block"]

NEG_INF = -2.0e38

#: tokens per block the block size aims at
BLOCK_TOKENS = 1024
#: most bytes of one block of K (or of V): the two buffers of each stay
#: within half of the chip's 16 MiB of scoped VMEM
BLOCK_BYTES = 2 << 20


def pages_per_block(page_size: int, num_kv: int, head_dim: int,
                    itemsize: int, max_pages: int) -> int:
    """Pages per block for a pool of these shapes and a table this wide.

    The largest power of two of pages within ``BLOCK_TOKENS`` tokens,
    halved while one block of K would pass ``BLOCK_BYTES``, and capped at
    the table width rounded up to a power of four.

    Measured alone on a v5e (32 rows of 192-2400 tokens on random pages,
    256-page tables; ``K·hd`` 512, 1024 and 256 for starcoder2-7b,
    qwen2.5-32b and its TP-4 slice), in µs per call at 16 / 32 / 64 / 128
    pages a block: 367 / 271 / 236 / 226, 607 / 412 / 357 / 368, and
    309 / 253 / 227 / 217, with every head unrolled (the loop over head
    pairs reads 252, 391 and 226 at 64).  A block pays fixed costs (a
    loop step, the next block's copies started, per head two products
    and a softmax update), so larger blocks win until about 1024 tokens;
    128 pages of qwen's heads needs more than the default scoped VMEM.
    """
    ppb = 1
    while 2 * ppb * page_size <= BLOCK_TOKENS:
        ppb *= 2
    while ppb > 1 and ppb * page_size * num_kv * head_dim * itemsize > BLOCK_BYTES:
        ppb //= 2
    cap = 1
    while cap < max_pages:
        cap *= 4
    return min(ppb, cap)


def _kernel(
    lens_ref,                  # (B,) int32, prefetched: live tokens per row
    table_ref,                 # (B, max_pages) int32, prefetched
    q_ref,                     # (B, KG, hd) in VMEM
    k_hbm, v_hbm,              # (num_pages, page, K, hd) in HBM
    o_ref,                     # (B, KG, hd) in VMEM
    k_buf, v_buf,              # (2, ppb, page, K, hd) VMEM
    sems,                      # DMA semaphores (2 slots, K and V)
    q_sc, m_ref, l_ref, acc_ref,  # VMEM f32 (K, G, hd), (K, G, 1) twice, (K, G, hd)
    *,
    scale: float,
    ppb: int,
):
    B, KG, hd = q_ref.shape
    _, page, K, _ = k_hbm.shape
    G = KG // K
    T = ppb * page
    # block sizes a block is computed at: powers of four below ppb, and ppb
    sizes = [4 ** e for e in range(ppb.bit_length()) if 4 ** e < ppb] + [ppb]

    def n_blocks(b):
        return (lens_ref[b] + T - 1) // T

    def next_live(b):
        """The first row after ``b`` with a live block, or ``B``."""
        return jax.lax.while_loop(
            lambda r: jnp.logical_and(r < B, lens_ref[jnp.minimum(r, B - 1)] == 0),
            lambda r: r + 1, b + 1)

    def live_pages(b, i):
        return jnp.minimum(ppb, (lens_ref[b] - i * T + page - 1) // page)

    def each_copy(b, i, slot, action):
        """Apply ``action`` to the K and V copy of every page of block
        ``i`` of row ``b`` that holds a live token; no other page moves."""
        def one(j, _):
            pid = table_ref[b, i * ppb + j]
            action(pltpu.make_async_copy(
                k_hbm.at[pid], k_buf.at[slot, j], sems.at[slot, 0]))
            action(pltpu.make_async_copy(
                v_hbm.at[pid], v_buf.at[slot, j], sems.at[slot, 1]))

        jax.lax.fori_loop(0, live_pages(b, i), one, None)

    def start(b, i, slot):
        each_copy(b, i, slot, lambda c: c.start())

    def wait(b, i, slot):
        each_copy(b, i, slot, lambda c: c.wait())

    def fold(h, q, k, v, live):
        """Fold one KV head's ``n`` tokens (f32 ``(n, hd)`` K and V) into
        the online softmax of its ``G`` query heads ``q``."""
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale           # (G, n)
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[h]
        m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m), 0.0)
        corr = jnp.exp(m_prev - m)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                   # (G, hd)
        m_ref[h] = m
        l_ref[h] = corr * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = corr * acc_ref[h] + pv

    def attend(b, i, slot, c):
        """Fold block ``i`` of row ``b`` into the row's online softmax,
        computed over the first ``c`` pages of the buffer (static)."""
        n = c * page
        live = i * T + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) < lens_ref[b]
        if k_buf.dtype.itemsize != 2 or K % 2:
            ks = k_buf[slot, :c].reshape(n, K, hd).astype(jnp.float32)
            vs = v_buf[slot, :c].reshape(n, K, hd).astype(jnp.float32)
            for h in range(K):
                fold(h, q_sc[h], ks[:, h, :], vs[:, h, :], live)
            return
        # Rows t·K + h (token t, head h) pack in pairs into 32-bit words,
        # head 2j in the low half and 2j + 1 in the high one: one
        # sublane-strided load of words yields two heads' tokens.
        kw, vw = (buf.at[slot].reshape(ppb * page * K, hd).bitcast(jnp.uint32)
                  for buf in (k_buf, v_buf))
        lo = lambda w: jax.lax.bitcast_convert_type(w << 16, jnp.float32)
        hi = lambda w: jax.lax.bitcast_convert_type(
            w & jnp.uint32(0xFFFF0000), jnp.float32)

        def pair(j, _):
            k = kw[pl.ds(j, n, stride=K // 2), :]
            v = vw[pl.ds(j, n, stride=K // 2), :]
            fold(2 * j, q_sc[2 * j], lo(k), lo(v), live)
            fold(2 * j + 1, q_sc[2 * j + 1], hi(k), hi(v), live)

        jax.lax.fori_loop(0, K // 2, pair, None)

    v_buf[...] = jnp.zeros_like(v_buf)
    first = next_live(-1)

    @pl.when(first < B)
    def _prime():
        start(first, 0, 0)

    def row(b, slot):
        n = n_blocks(b)
        nxt = next_live(b)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for h in range(K):
            q_sc[h] = q_ref[b, pl.ds(h * G, G), :].astype(jnp.float32)

        def block(i, slot):
            more = i + 1 < n
            nb = jnp.where(more, b, nxt)
            ni = jnp.where(more, i + 1, 0)

            @pl.when(nb < B)
            def _prefetch():
                start(nb, ni, 1 - slot)

            wait(b, i, slot)
            # the smallest size that holds the block's live pages, the
            # lesser of a power of four and the widest block: it depends
            # on the row alone (the width cap is a power of four too), so
            # a row's result does not change with the table width or with
            # the other rows
            size = ppb
            for c in sizes[-2::-1]:
                size = jnp.where(live_pages(b, i) <= c, c, size)
            for c in sizes:
                pl.when(size == c)(functools.partial(attend, b, i, slot, c))
            return 1 - slot

        slot = jax.lax.fori_loop(0, n, block, slot)
        for h in range(K):
            o_ref[b, pl.ds(h * G, G), :] = (
                acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).astype(o_ref.dtype)
        return slot

    jax.lax.fori_loop(0, B, row, 0)


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
    _, KG, _ = q.shape
    _, page_size, K, hd = k_pages.shape
    ppb = pages_per_block(page_size, K, hd, k_pages.dtype.itemsize,
                          page_table.shape[1])
    # a row ends at its length or at its first -1 page
    lead = jnp.sum(jnp.cumprod(page_table >= 0, axis=1), axis=1)
    lens = jnp.minimum(lens, lead * page_size).astype(jnp.int32)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[vmem, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page_size, K, hd), k_pages.dtype),
            pltpu.VMEM((2, ppb, page_size, K, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((K, KG // K, hd), jnp.float32),
            pltpu.VMEM((K, KG // K, 1), jnp.float32),
            pltpu.VMEM((K, KG // K, 1), jnp.float32),
            pltpu.VMEM((K, KG // K, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, ppb=ppb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        # the HLO instruction's name, which trace reductions match on;
        # fixed here so that renaming this function cannot change it
        name="paged_attention",
    )(lens, page_table, q, k_pages, v_pages)

"""Dispatching wrapper for paged decode attention.

Accepts the page table straight from
:meth:`repro.core.arena.PagedKVAllocator.page_table` (numpy int32) and the
sequence lengths from :meth:`seq_lens`, closing the loop between the
paper's memory manager and the serving hot path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .paged_attention import paged_attention_pallas

__all__ = ["paged_attention", "paged_attention_sharded"]


def paged_attention(q, k_pages, v_pages, page_table, lens, *, scale,
                    interpret: bool | None = None):
    return paged_attention_pallas(
        q, k_pages, v_pages,
        jnp.asarray(page_table, jnp.int32), jnp.asarray(lens, jnp.int32),
        scale=scale, interpret=resolve_interpret(interpret),
    )


def paged_attention_sharded(q, k_pages, v_pages, page_table, lens, *,
                            scale, mesh, axis_name: str = "model",
                            interpret: bool | None = None):
    """Head-sharded paged attention over a tensor-parallel mesh.

    Each mesh member runs the kernel grid over its KV-head slice of the
    page pool (q heads are KV-major, so the matching q slice is
    contiguous); outputs concatenate back over the head axis.  Per-KV-head
    online softmax is independent, so the sharded result is bit-identical
    to the unsharded kernel.  When the head counts don't divide the mesh
    — or there is no mesh — falls back to the unsharded kernel on
    replicated inputs rather than mis-slicing a head group.
    """
    num_kv = k_pages.shape[2]
    num_q = q.shape[1]
    n = int(mesh.devices.size) if mesh is not None else 1
    if mesh is None or n <= 1 or num_kv % n or num_q % n:
        return paged_attention(q, k_pages, v_pages, page_table, lens,
                               scale=scale, interpret=interpret)

    from jax.sharding import PartitionSpec as P

    def local(q_l, kp_l, vp_l, table, lens_):
        return paged_attention(q_l, kp_l, vp_l, table, lens_,
                               scale=scale, interpret=interpret)

    rep = P()
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, axis_name, None), P(None, None, axis_name, None),
                  P(None, None, axis_name, None), rep, rep),
        out_specs=P(None, axis_name, None),
        check_vma=False,
    )(q, k_pages, v_pages,
      jnp.asarray(page_table, jnp.int32), jnp.asarray(lens, jnp.int32))

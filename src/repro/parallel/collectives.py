"""Distributed-optimization tricks: compressed gradient reduction + overlap.

``compressed_psum`` — int8-quantized gradient all-reduce with per-block
scales, for the ``pod`` axis (cross-pod DCN is the bandwidth-starved hop
at 1000+ node scale): wire bytes drop ~3.5× vs bf16 (7× vs f32) at the
cost of ≤1/254 relative quantization error per block.  Built on
``shard_map`` + ``all_gather`` of the int8 payload so it lowers on any
mesh.  ``ErrorFeedback`` accumulates the quantization residual into the
next step's gradient (Seide et al.; keeps SGD unbiased over time).

``microbatch_overlap_note``: compute/comm overlap for FSDP gathers and
grad reductions is delegated to XLA's latency-hiding scheduler — the
dry-run HLO already emits ``all-gather-start``/``-done`` pairs that
overlap with the layer matmuls; what this module adds is the *semantic*
knob (what to compress, where the residual lives).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["quantize_int8", "dequantize_int8", "compressed_psum",
           "maybe_psum", "ErrorFeedback", "compressed_grad_tree"]

BLOCK = 256


def maybe_psum(x: jnp.ndarray, axis_name: str = "model") -> jnp.ndarray:
    """``psum(x, axis_name)`` when the axis is bound, identity otherwise.

    Model bodies call this after every row-sharded matmul so *one*
    definition serves both execution modes: inside ``shard_map`` the
    axis name resolves and partial products reduce across the mesh;
    under plain ``jit`` (single-device serving, training, tests) the
    unbound name raises ``NameError`` at trace time and the full-width
    product passes through untouched.  Integer operands reduce exactly
    (psum of int32 is order-independent), which is what lets the
    sharded-vs-single-device differential tests demand byte equality.
    """
    try:
        return jax.lax.psum(x, axis_name)
    except NameError:
        return x


def quantize_int8(x: jnp.ndarray, block: int = BLOCK):
    """Flat per-block symmetric int8 quantization → (q, scales, pad)."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block).astype(jnp.float32)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32), pad


def dequantize_int8(q, scale, pad, shape):
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape)


def compressed_psum(x: jnp.ndarray, axis_name: str):
    """int8 all-gather + local dequant-sum ≅ psum(x) with ~3.5x less wire.

    Call inside shard_map.  Exact psum wire (bf16 ring): 2·(n-1)/n·B;
    int8 gather wire: (n-1)/n·(B/2 + scales) — plus the result needs no
    second pass because every member reconstructs the sum locally.
    """
    q, scale, pad = quantize_int8(x)
    qs = jax.lax.all_gather(q, axis_name)          # (n, blocks, BLOCK) int8
    ss = jax.lax.all_gather(scale, axis_name)
    total = jnp.sum(qs.astype(jnp.float32) * ss, axis=0)
    flat = total.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(x.shape).astype(x.dtype)


class ErrorFeedback:
    """Residual accumulator for biased compressed reductions."""

    @staticmethod
    def init(tree):
        return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), tree)

    @staticmethod
    def apply(grads, residual):
        """Returns (corrected_grads, fn(compressed)->new_residual)."""
        corrected = jax.tree.map(
            lambda g, r: g.astype(jnp.float32) + r, grads, residual
        )

        def update(compressed):
            return jax.tree.map(
                lambda c, co: co - c.astype(jnp.float32), compressed, corrected
            )

        return corrected, update


def compressed_grad_tree(grads, mesh, axis_name: str = "pod"):
    """Compressed psum of a gradient pytree over one mesh axis.

    Gradients are assumed already sharded/reduced over the other axes
    (GSPMD handles those); this performs the cross-pod (DCN) hop with
    int8 payloads via shard_map.
    """
    if mesh is None or axis_name not in mesh.axis_names:
        return grads

    other = tuple(a for a in mesh.axis_names if a != axis_name)

    def one(g):
        spec_in = P()          # replicated view over the compressed axis

        def fn(gl):
            return compressed_psum(gl, axis_name)

        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=P(*([None] * g.ndim)),
            out_specs=P(*([None] * g.ndim)),
            axis_names={axis_name},
            check_vma=False,
        )(g)

    return jax.tree.map(one, grads)

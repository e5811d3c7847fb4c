"""Mixture-of-Experts with expert-parallel all-to-all dispatch.

Token path (``shard_map`` over the production mesh):

1. tokens are flattened and sharded over every mesh axis
   (``(pod, data, model)``) — each shard routes its local tokens;
2. **local dispatch**: top-k routing, slot assignment via one-hot cumsum
   (capacity-bounded, dropped tokens masked), scatter into a per-shard
   ``(E, C, D)`` buffer — no ``(T, E, C)`` dispatch tensor is ever built;
3. ``all_to_all`` over the ``model`` axis exchanges expert shards
   (EP within a data replica, exactly the NCCL a2a pattern of DeepSpeed-MoE
   mapped onto ``jax.lax.all_to_all``);
4. expert FFN as batched einsum over the local experts, with FSDP
   all-gather of the ``F``-sharded expert weights over ``data``;
5. reverse all-to-all, gather-combine with router weights.

Router variants: ``softmax_topk`` (qwen3: softmax over the top-k logits,
renormalized) and ``sigmoid_top1`` (llama4 scout).  A shared-expert branch
(llama4) runs densely on all tokens.  The load-balance auxiliary loss is
``E · Σ_e f_e · p_e`` (Switch-style), psum'd across shards.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ArchConfig, constrain, current_mesh, gated_mlp

__all__ = ["moe_params_shape", "init_moe_params", "moe_block"]


def moe_params_shape(cfg: ArchConfig) -> Dict[str, Any]:
    D, E, F = cfg.d_model, cfg.num_experts, cfg.expert_d_ff
    shapes = {
        "router": (D, E),
        "wg": (E, D, F),
        "wu": (E, D, F),
        "wd": (E, F, D),
    }
    if cfg.num_shared_experts:
        Fs = cfg.expert_d_ff * cfg.num_shared_experts
        shapes.update({"swg": (D, Fs), "swu": (D, Fs), "swd": (Fs, D)})
    return shapes


def init_moe_params(rng, cfg: ArchConfig, dtype) -> Dict[str, jnp.ndarray]:
    out = {}
    for name, shape in moe_params_shape(cfg).items():
        rng, sub = jax.random.split(rng)
        fan_in = shape[-2] if len(shape) > 1 else shape[0]
        out[name] = (jax.random.normal(sub, shape) / math.sqrt(fan_in)).astype(
            jnp.float32 if name == "router" else dtype
        )
    return out


# --------------------------------------------------------------------------
# per-shard computation
# --------------------------------------------------------------------------

def _dispatch_compute_combine(
    x: jnp.ndarray,            # (T, D) local tokens
    router_w: jnp.ndarray,     # (D, E)
    wg: jnp.ndarray,           # (E_loc, D, F)
    wu: jnp.ndarray,
    wd: jnp.ndarray,           # (E_loc, F, D)
    cfg: ArchConfig,
    *,
    model_axis: Optional[str],
    model_size: int,
    lossless: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    T, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token

    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))  # (T, E)
    if cfg.router_score == "sigmoid_top1":
        top_vals, top_idx = jax.lax.top_k(logits, k)
        weights = jax.nn.sigmoid(top_vals)
    else:
        top_vals, top_idx = jax.lax.top_k(logits, k)
        weights = jax.nn.softmax(top_vals, axis=-1)   # renormalized over top-k

    e_flat = top_idx.reshape(T * k)
    w_flat = weights.reshape(T * k).astype(x.dtype)
    token_idx = jnp.arange(T * k) // k

    # slot assignment: position of each copy within its expert's queue
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)          # (Tk, E)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(T * k), e_flat]
    if lossless:
        capacity = T * k       # decode: a dropped token is a wrong answer
    else:
        capacity = max(1, int(math.ceil(T * k / E * cfg.capacity_factor)))
    capacity = min(capacity, T * k)
    keep = pos < capacity
    dump = E * capacity
    slot = jnp.where(keep, e_flat * capacity + pos, dump)

    x_rep = x[token_idx]                                          # (Tk, D)
    buf = jnp.zeros((E * capacity + 1, D), x.dtype).at[slot].add(x_rep)
    buf = buf[: E * capacity].reshape(E, capacity, D)

    if model_axis is not None:
        # EP exchange: (E, C, D) -> (E/M, C*M, D)
        buf = jax.lax.all_to_all(
            buf, model_axis, split_axis=0, concat_axis=1, tiled=True
        )

    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg.activation]
    h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
        "ecd,edf->ecf", buf, wu
    )
    y = jnp.einsum("ecf,efd->ecd", h, wd)

    if model_axis is not None:
        y = jax.lax.all_to_all(
            y, model_axis, split_axis=1, concat_axis=0, tiled=True
        )

    y_flat = jnp.concatenate([y.reshape(E * capacity, D), jnp.zeros((1, D), y.dtype)])
    out_copies = y_flat[slot] * (w_flat * keep.astype(w_flat.dtype))[:, None]
    out = out_copies.reshape(T, k, D).sum(axis=1)

    # Switch-style load-balance aux loss (local estimate; psum'd by caller)
    probs = jax.nn.softmax(logits, axis=-1)                        # (T, E)
    f_e = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32), axis=1), axis=0
    )
    p_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e * p_e)
    return out, aux


# --------------------------------------------------------------------------
# public block
# --------------------------------------------------------------------------

def moe_block(
    x: jnp.ndarray,            # (B, S, D)
    p: Dict[str, jnp.ndarray],
    cfg: ArchConfig,
    *,
    token_axes: Tuple[str, ...] = ("pod", "data", "model"),
    lossless: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out (B,S,D), aux_loss scalar).

    ``token_axes``: mesh axes the flattened tokens shard over.  Train and
    prefill shard over all three; the decode step passes ``("pod",
    "data")`` because its token count equals the batch.  ``lossless``
    disables capacity-based token dropping (mandatory for decode).
    """
    B, S, D = x.shape
    mesh = current_mesh()

    if mesh is None or mesh.size == 1:
        out, aux = _dispatch_compute_combine(
            x.reshape(B * S, D), p["router"], p["wg"], p["wu"], p["wd"], cfg,
            model_axis=None, model_size=1, lossless=lossless,
        )
        out = out.reshape(B, S, D)
    else:
        axes = set(mesh.axis_names)
        # §Perf-B4: tokens enter shard_map on a 2-D (batch, seq) grid that
        # matches the residual stream's (data, model) sharding exactly and
        # flatten *locally* — flattening (B,S)→(B·S) across sharded dims in
        # GSPMD forces an involuntary full rematerialization (a global-
        # batch-sized f32 all-reduce appeared in the llama4 backward).
        b_axes = tuple(a for a in ("pod", "data") if a in axes)
        kept, prod = [], 1
        for a in b_axes:
            if B % (prod * mesh.shape[a]) == 0:
                kept.append(a)
                prod *= mesh.shape[a]
        b_axes = tuple(kept)
        s_axis = "model" if "model" in axes and S % mesh.shape["model"] == 0 \
            else None
        token_axes = b_axes + ((s_axis,) if s_axis else ())

        E, F = cfg.num_experts, cfg.expert_d_ff
        model_axis = "model" if "model" in axes else None
        data_axis = "data" if "data" in axes else None
        # EP needs E divisible by the model axis; FSDP gather needs F
        # divisible by the data axis.  Fall back to replication otherwise
        # (reduced smoke configs on big meshes).
        if model_axis and E % mesh.shape["model"] != 0:
            model_axis = None
        if data_axis and F % mesh.shape["data"] != 0:
            data_axis = None
        model_size = mesh.shape.get("model", 1) if model_axis else 1

        def shard_fn(xb, router_w, wg, wu, wd):
            if data_axis is not None:
                wg = jax.lax.all_gather(wg, data_axis, axis=2, tiled=True)
                wu = jax.lax.all_gather(wu, data_axis, axis=2, tiled=True)
                wd = jax.lax.all_gather(wd, data_axis, axis=1, tiled=True)
            bl, sl, _ = xb.shape
            out, aux = _dispatch_compute_combine(
                xb.reshape(bl * sl, D), router_w, wg, wu, wd, cfg,
                model_axis=model_axis, model_size=model_size, lossless=lossless,
            )
            aux = jax.lax.pmean(aux, token_axes)
            return out.reshape(bl, sl, D), aux

        xb = constrain(x, b_axes, s_axis, None)
        e_spec = P(model_axis, None, data_axis)
        d_spec = P(model_axis, data_axis, None)
        out, aux = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(b_axes or None, s_axis, None), P(None, None),
                      e_spec, e_spec, d_spec),
            out_specs=(P(b_axes or None, s_axis, None), P()),
            check_vma=False,
        )(xb, p["router"], p["wg"], p["wu"], p["wd"])

    if cfg.num_shared_experts:
        shared = gated_mlp(x, p["swu"], p["swg"], p["swd"], cfg.activation)
        out = out + shared
    out = constrain(out, "data", "model", None)
    return out, aux

"""Dispatching wrapper: compiled Pallas on TPU, interpret mode on CPU.

``flash_attention`` accepts the model-side layout (B, S, K, G, hd) used by
``repro.models.attention`` and returns the same layout.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .flash_attention import flash_attention_pallas

__all__ = ["flash_attention"]


def flash_attention(
    qg: jnp.ndarray,           # (B, Sq, K, G, hd)
    k: jnp.ndarray,            # (B, Sk, K, hd)
    v: jnp.ndarray,
    *,
    q_positions=None,
    k_positions=None,
    window,
    scale: float,
    logit_cap: float = 0.0,
    causal: bool = True,
    interpret: bool | None = None,
) -> jnp.ndarray:
    B, Sq, K, G, hd = qg.shape
    q = qg.reshape(B, Sq, K * G, hd)
    out = flash_attention_pallas(
        q, k, v, jnp.asarray(window, jnp.int32),
        scale=scale, logit_cap=logit_cap, causal=causal,
        interpret=resolve_interpret(interpret),
    )
    return out.reshape(B, Sq, K, G, hd)

"""Dispatching wrapper for the WKV6 kernel."""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import resolve_interpret

from .wkv6 import wkv6_pallas

__all__ = ["wkv6"]


def wkv6(r, k, v, w, u, state0, *, interpret: bool | None = None):
    return wkv6_pallas(r, k, v, w, u,
                       state0.astype(jnp.float32),
                       interpret=resolve_interpret(interpret))

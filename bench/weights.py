"""Random weights from ``--seed``, made on the device in one jitted call,
in the dtype they are served in.

The benchmark makes them, so the reference can make the same ones again
after the program's state is freed, without taking anything from the
program.  The tree has the layout the program serves from: stacked
layers under ``layers``, q/k/v as ``(D, heads, head_dim)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.config import Shapes


def seed_key(seed: int):
    """A key for any seed up to 2**63: the low 31 bits, with the rest
    folded in."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def layout(s: Shapes) -> dict:
    """Shape of every leaf, by path."""
    L, D, H, K, hd, F = (s.layers, s.d_model, s.heads, s.kv_heads,
                         s.head_dim, s.d_ff)
    attn = {"wq": (L, D, H, hd), "wk": (L, D, K, hd), "wv": (L, D, K, hd),
            "wo": (L, H * hd, D)}
    if s.qkv_bias:
        attn.update(bq=(L, H, hd), bk=(L, K, hd), bv=(L, K, hd))
    mlp = {"wu": (L, D, F), "wd": (L, F, D)}
    if s.gated:
        mlp["wg"] = (L, D, F)
    tree = {"embed": (s.vocab, D), "final_norm": (D,),
            "layers": {"ln1": (L, D), "ln2": (L, D), "attn": attn,
                       "mlp": mlp}}
    if not s.tie_embeddings:
        tree["unembed"] = (s.vocab, D)
    return tree


def _scale(path: str, shape, s: Shapes) -> tuple:
    """(mean, std) of each leaf: norms near 1, biases small, matrices
    with unit-variance outputs for unit-variance inputs."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("ln1", "ln2", "final_norm"):
        return 1.0, 0.1
    if leaf.startswith("b"):
        return 0.0, 0.1
    if leaf == "embed":
        return 0.0, 1.0
    fan_in = s.d_ff if leaf == "wd" else s.d_model
    return 0.0, 1.0 / math.sqrt(fan_in)


def make(s: Shapes, seed: int, sharding=None):
    """The weights for ``seed``, drawn leaf by leaf inside one jit."""
    shapes = layout(s)
    paths = [("/".join(str(getattr(k, "key", k)) for k in p), v)
             for p, v in jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]
    dtype = jnp.dtype(s.dtype)

    def draw(key):
        keys = jax.random.split(key, len(paths))
        leaves = []
        for k, (path, shape) in zip(keys, paths):
            mean, std = _scale(path, shape, s)
            leaves.append((mean + std * jax.random.normal(k, shape,
                                                          jnp.float32))
                          .astype(dtype))
        treedef = jax.tree_util.tree_structure(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw, out_shardings=sharding)(seed_key(seed))

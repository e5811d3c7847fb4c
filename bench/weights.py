"""Random weights from ``--seed``, made on the device in one jitted call,
in the dtype they are served in.

The benchmark makes them, so the reference can make the same ones again
after the program's state is freed, without taking anything from the
program.  The configuration's family gives the tree, in the layout the
program serves from, and each leaf's mean and spread.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import config
from bench.config import Shapes


def seed_key(seed: int):
    """A key for any seed up to 2**63: the low 31 bits, with the rest
    folded in."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make(s: Shapes, seed: int, sharding=None):
    """The weights for ``seed``, drawn leaf by leaf inside one jit."""
    fam = config.family(s)
    shapes = fam.layout(s)
    paths = [("/".join(str(getattr(k, "key", k)) for k in p), v)
             for p, v in jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]
    dtype = jnp.dtype(s.dtype)

    def draw(key):
        keys = jax.random.split(key, len(paths))
        leaves = []
        for k, (path, shape) in zip(keys, paths):
            mean, std = fam.scale(path, shape, s)
            leaves.append((mean + std * jax.random.normal(k, shape,
                                                          jnp.float32))
                          .astype(dtype))
        treedef = jax.tree_util.tree_structure(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(draw, out_shardings=sharding)(seed_key(seed))

"""Partition rules: every arch's params/state get LEGAL shardings.

``NamedSharding.shard_shape`` raises when a dim doesn't divide — so this
validates the full rule table against the production mesh without any
device allocation.
"""

import jax
import pytest

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.launch.steps import make_batch_stub
from repro.models import build_model
from repro.optim import adamw_init
from repro.parallel.sharding import (batch_shardings, decode_state_shardings,
                                     opt_state_shardings, param_shardings)

# NamedSharding.shard_shape only needs the mesh *shape*, not real devices:
# an AbstractMesh stands in for the 256-chip pod.
from jax.sharding import AbstractMesh  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


def _mesh():
    return AbstractMesh((16, 16), ("data", "model"))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_and_opt_shardings_legal(arch):
    mesh = _mesh()
    cfg = get_config(arch)
    model = build_model(cfg)
    p_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_shard = param_shardings(p_shapes, mesh)
    o_shapes = jax.eval_shape(adamw_init, p_shapes)
    o_shard = opt_state_shardings(o_shapes, mesh)
    n_sharded = 0
    for (path, leaf), sh in zip(
        jax.tree_util.tree_flatten_with_path(p_shapes)[0],
        jax.tree.leaves(p_shard),
    ):
        sh.shard_shape(leaf.shape)          # raises if illegal
        if sh.spec != P(*([None] * len(leaf.shape))):
            n_sharded += 1
    assert n_sharded > 3, f"{arch}: params basically unsharded"
    for leaf, sh in zip(jax.tree.leaves(o_shapes), jax.tree.leaves(o_shard)):
        sh.shard_shape(leaf.shape)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_state_shardings_legal(arch):
    mesh = _mesh()
    cfg = get_config(arch)
    model = build_model(cfg)
    s_shapes = jax.eval_shape(lambda: model.init_decode_state(128, 32768))
    s_shard = decode_state_shardings(s_shapes, mesh)
    cache_sharded = 0
    for (path, leaf), sh in zip(
        jax.tree_util.tree_flatten_with_path(s_shapes)[0],
        jax.tree.leaves(s_shard),
    ):
        sh.shard_shape(leaf.shape)
        if sh.spec != P(*([None] * len(leaf.shape))):
            cache_sharded += 1
    assert cache_sharded >= 1, f"{arch}: decode state unsharded"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_batch_shardings_legal(arch):
    mesh = _mesh()
    cfg = get_config(arch)
    for name, shape in SHAPES.items():
        if shape.kind == "decode":
            continue
        stub = make_batch_stub(cfg, batch=shape.global_batch,
                               seq=shape.seq_len, kind=shape.kind)
        for key, sh in batch_shardings(stub, mesh).items():
            sh.shard_shape(stub[key].shape)

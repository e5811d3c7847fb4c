"""The serving main path compiled for a described TPU v5e, at real widths.

Nothing here runs: each test compiles one program for chips that are
described, not attached, and reads the compiler's verdict.  That catches
what interpret mode cannot (tiling and VMEM refusals of a Pallas kernel,
a program that does not fit the chip's 16 GB) at no chip time.  The
model is qwen2.5-32b at its published widths cut to 4 layers
(``configs/qwen2_5_32b.py: CHIP``), with the 4096-page KV pool that
``chip_smoke.py`` serves from.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Tests that compile a whole model step steer the kernels' interpret
choice to the compiled kernel, since the process's backend is the CPU.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs.qwen2_5_32b import CHIP
from repro.kernels.paged_attention.ops import paged_attention_sharded
from repro.kernels.paged_attention.paged_attention import paged_attention_pallas
from repro.models import build_model

#: one v5e chip's HBM
HBM_BYTES = 16e9
BATCH, POOL_PAGES, PAGE, MAX_PAGES = 8, 4096, 16, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels called without an explicit ``interpret`` compile for the
    TPU, as they do on a TPU backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(sharding):
    K, hd = CHIP.num_kv_heads, CHIP.hd
    pages = (POOL_PAGES, PAGE, K, hd)
    return (_shape((BATCH, CHIP.num_heads, hd), jnp.bfloat16, sharding),
            _shape(pages, jnp.bfloat16, sharding),
            _shape(pages, jnp.bfloat16, sharding),
            _shape((BATCH, MAX_PAGES), jnp.int32, sharding),
            _shape((BATCH,), jnp.int32, sharding))


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _model_shapes(model, sharding):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = jax.eval_shape(
        functools.partial(model.init_paged_state, POOL_PAGES, PAGE))
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda s: _shape(s.shape, s.dtype, sharding), t)
    return place(params), place(pool)


def test_paged_kernel_compiles_at_qwen_widths(one_chip):
    compiled = paged_attention_pallas.lower(
        *_kernel_args(one_chip), scale=CHIP.hd ** -0.5, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_paged_kernel_compiles_at_starcoder_widths(one_chip):
    """starcoder2-7b's heads (4 KV heads of 9 queries each) over its
    7168-page pool, 32 rows of a 256-page table: the widest bucket the
    engine pads to, at the largest block the kernel chooses."""
    pages = _shape((7168, PAGE, 4, 128), jnp.bfloat16, one_chip)
    compiled = paged_attention_pallas.lower(
        _shape((32, 36, 128), jnp.bfloat16, one_chip), pages, pages,
        _shape((32, 256), jnp.int32, one_chip),
        _shape((32,), jnp.int32, one_chip),
        scale=128 ** -0.5, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_sharded_kernel_compiles_over_four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices), ("model",))
    rep = NamedSharding(mesh, PartitionSpec())
    fn = jax.jit(functools.partial(
        paged_attention_sharded, scale=CHIP.hd ** -0.5, mesh=mesh,
        interpret=False))
    compiled = fn.lower(*_kernel_args(rep)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _device_bytes(compiled) < HBM_BYTES


def test_init_never_holds_f32_weights(one_chip):
    """The jitted init draws and casts each weight in one pass: beside
    the 7 GB of bf16 output it needs far less than the f32 copies."""
    model = build_model(CHIP)
    compiled = jax.jit(model.init, out_shardings=one_chip).lower(
        _shape((2,), jnp.uint32, one_chip)).compile()
    m = compiled.memory_analysis()
    n_params = sum(s.size for s in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    # bf16 weights, plus the chip's alignment padding of the small leaves
    assert 2 * n_params <= m.output_size_in_bytes < 2.001 * n_params
    assert m.temp_size_in_bytes < 0.25 * m.output_size_in_bytes
    assert _device_bytes(compiled) < HBM_BYTES


def test_paged_decode_step_compiles_full_width(one_chip, compiled_kernels):
    model = build_model(CHIP)
    params, pool = _model_shapes(model, one_chip)
    vec = _shape((BATCH,), jnp.int32, one_chip)
    table = _shape((BATCH, MAX_PAGES), jnp.int32, one_chip)
    compiled = jax.jit(model.paged_decode_step, donate_argnums=(1,)).lower(
        params, pool, vec, table, vec).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_paged_prefill_at_compiles_full_width(one_chip, compiled_kernels):
    """Suffix prefill of a 264-token suffix behind a 256-token shared
    prefix.  Its attention is dense XLA (no Pallas kernel on this path)."""
    model = build_model(CHIP)
    params, pool = _model_shapes(model, one_chip)
    compiled = jax.jit(model.paged_prefill_at).lower(
        params, _shape((1, 264), jnp.int32, one_chip), pool,
        _shape((1, MAX_PAGES), jnp.int32, one_chip),
        _shape((), jnp.int32, one_chip),
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES

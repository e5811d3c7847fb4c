"""Gradient compression: int8 psum accuracy + error feedback."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.parallel.collectives import (
    ErrorFeedback,
    compressed_psum,
    dequantize_int8,
    quantize_int8,
)


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000) * 3.0, jnp.float32)
    q, s, pad = quantize_int8(x)
    back = dequantize_int8(q, s, pad, x.shape)
    err = np.abs(np.asarray(back - x))
    bound = np.abs(np.asarray(x)).max() / 127.0 + 1e-6
    assert err.max() <= bound


def test_compressed_psum_matches_exact():
    mesh = jax.make_mesh((1,), ("pod",))
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)

    def fn(v):
        return compressed_psum(v, "pod")

    out = jax.shard_map(fn, mesh=mesh, in_specs=P(None, None),
                        out_specs=P(None, None), check_vma=False)(x)
    # n=1: psum == identity up to quantization error
    np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                               atol=float(jnp.abs(x).max()) / 120)


def test_error_feedback_removes_bias():
    rng = np.random.default_rng(2)
    g_true = jnp.asarray(rng.standard_normal(512), jnp.float32) * 0.1
    residual = ErrorFeedback.init({"g": g_true})
    acc_plain, acc_ef = np.zeros(512), np.zeros(512)
    for step in range(50):
        grads = {"g": g_true}
        corrected, update = ErrorFeedback.apply(grads, residual)
        q, s, pad = quantize_int8(corrected["g"])
        compressed = {"g": dequantize_int8(q, s, pad, g_true.shape)}
        residual = update(compressed)
        acc_ef += np.asarray(compressed["g"])
        qp, sp, pp = quantize_int8(grads["g"])
        acc_plain += np.asarray(dequantize_int8(qp, sp, pp, g_true.shape))
    target = np.asarray(g_true) * 50
    # error feedback must track the true accumulated gradient more closely
    assert np.abs(acc_ef - target).max() <= np.abs(acc_plain - target).max() + 1e-5
    np.testing.assert_allclose(acc_ef, target, atol=0.02)

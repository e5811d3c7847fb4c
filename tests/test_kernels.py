"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.segment_zero.ops import segment_zero
from repro.kernels.segment_zero.ref import segment_zero_ref
from repro.kernels.wkv6.ops import wkv6
from repro.kernels.wkv6.ref import wkv6_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,K,G,hd", [
    (1, 256, 1, 1, 64),
    (2, 512, 2, 2, 64),
    (1, 256, 2, 4, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window,cap,causal", [
    (0, 0.0, True), (128, 50.0, True), (0, 0.0, False),
])
def test_flash_attention_sweep(B, S, K, G, hd, dtype, window, cap, causal):
    q = jnp.asarray(RNG.standard_normal((B, S, K, G, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, S, K, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, S, K, hd)), dtype)
    out = flash_attention(q, k, v, window=window, scale=hd ** -0.5,
                          logit_cap=cap, causal=causal, interpret=True)
    ref = flash_attention_ref(q.reshape(B, S, K * G, hd), k, v, window,
                              scale=hd ** -0.5, logit_cap=cap, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out.reshape(B, S, K * G, hd), np.float32),
        np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,K,G,hd,page,P,MP", [
    (2, 1, 2, 64, 16, 16, 4),
    (3, 2, 3, 128, 32, 24, 6),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, K, G, hd, page, P, MP, dtype):
    q = jnp.asarray(RNG.standard_normal((B, K * G, hd)), dtype)
    kp = jnp.asarray(RNG.standard_normal((P, page, K, hd)), dtype)
    vp = jnp.asarray(RNG.standard_normal((P, page, K, hd)), dtype)
    lens = RNG.integers(1, MP * page, (B,)).astype(np.int32)
    table = np.full((B, MP), -1, np.int32)
    pool = list(RNG.permutation(P))
    for b in range(B):
        for i in range(-(-int(lens[b]) // page)):
            table[b, i] = pool.pop()
    out = paged_attention(q, kp, vp, table, lens, scale=hd ** -0.5,
                          interpret=True)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(table),
                              jnp.asarray(lens), scale=hd ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def _paged_brute_force(q, kp, vp, table, lens, scale):
    """Token-at-a-time numpy oracle for paged attention (no paging math
    shared with ref.py: tokens are gathered one by one through the
    table, so a page-indexing bug in ref.py cannot cancel out here)."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, kp, vp))
    B, KG, hd = q.shape
    _, page, K, _ = kp.shape
    G = KG // K
    out = np.zeros((B, KG, hd))
    for b in range(B):
        n = int(lens[b])
        if n == 0:
            continue
        ks = np.stack([kp[table[b, t // page], t % page] for t in range(n)])
        vs = np.stack([vp[table[b, t // page], t % page] for t in range(n)])
        for h in range(KG):
            s = ks[:, h // G] @ (q[b, h] * scale)
            w = np.exp(s - s.max())
            w /= w.sum()
            out[b, h] = w @ vs[:, h // G]
    return out


def _paged_case(B, K, G, hd, page, P, lens):
    """Random q/pages + a permuted -1-padded table covering ``lens``."""
    lens = np.asarray(lens, np.int32)
    q = jnp.asarray(RNG.standard_normal((B, K * G, hd)), jnp.float32)
    kp = jnp.asarray(RNG.standard_normal((P, page, K, hd)), jnp.float32)
    vp = jnp.asarray(RNG.standard_normal((P, page, K, hd)), jnp.float32)
    MP = max(-(-int(n) // page) for n in lens)
    table = np.full((B, MP), -1, np.int32)
    pool = list(RNG.permutation(P))
    for b in range(B):
        for i in range(-(-int(lens[b]) // page)):
            table[b, i] = pool.pop()
    return q, kp, vp, table, lens


def test_paged_attention_ref_matches_brute_force():
    """ref.py itself against an independent token-at-a-time oracle —
    ragged lens, page_size not dividing seq_len, -1-padded rows."""
    q, kp, vp, table, lens = _paged_case(
        4, 2, 3, 32, page=8, P=32, lens=[1, 7, 24, 37])
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(table),
                              jnp.asarray(lens), scale=32 ** -0.5)
    brute = _paged_brute_force(q, kp, vp, table, lens, 32 ** -0.5)
    np.testing.assert_allclose(np.asarray(ref, np.float32), brute,
                               rtol=1e-4, atol=1e-4)


def test_paged_attention_page_not_dividing_seq_len():
    """Kernel vs ref vs brute force when sequences end mid-page (the
    tail page is partially valid) and when they end exactly on a page
    boundary."""
    q, kp, vp, table, lens = _paged_case(
        4, 1, 4, 16, page=16, P=16, lens=[1, 17, 48, 33])
    out = paged_attention(q, kp, vp, table, lens, scale=0.25,
                          interpret=True)
    ref = paged_attention_ref(q, kp, vp, jnp.asarray(table),
                              jnp.asarray(lens), scale=0.25)
    brute = _paged_brute_force(q, kp, vp, table, lens, 0.25)
    np.testing.assert_allclose(np.asarray(out, np.float32), brute,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ref, np.float32), brute,
                               rtol=1e-4, atol=1e-4)


def test_paged_attention_dead_rows_and_padded_tables():
    """An all--1 row (a dead decode slot, len 0) must come out exactly
    zero — not NaN — and live rows must be unaffected by how much -1
    padding trails their pages (the serving engine pads table width to
    power-of-two buckets)."""
    q, kp, vp, table, lens = _paged_case(
        3, 2, 2, 16, page=8, P=16, lens=[11, 5, 16])
    lens = lens.copy()
    lens[1] = 0
    table[1, :] = -1                       # dead slot: no pages at all
    wide = np.pad(table, ((0, 0), (0, 5)), constant_values=-1)
    out = paged_attention(q, kp, vp, wide, lens, scale=0.25,
                          interpret=True)
    out = np.asarray(out, np.float32)
    assert np.all(np.isfinite(out))
    assert np.all(out[1] == 0.0)
    brute = _paged_brute_force(q, kp, vp, table, lens, 0.25)
    np.testing.assert_allclose(out[[0, 2]], brute[[0, 2]],
                               rtol=1e-4, atol=1e-4)
    narrow = paged_attention(q, kp, vp, table, lens, scale=0.25,
                             interpret=True)
    np.testing.assert_allclose(out[[0, 2]],
                               np.asarray(narrow, np.float32)[[0, 2]],
                               rtol=0, atol=0)


@pytest.mark.parametrize("B,T,H,hd", [(1, 32, 1, 8), (2, 128, 3, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_sweep(B, T, H, hd, dtype):
    r = jnp.asarray(RNG.standard_normal((B, T, H, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, T, H, hd)), dtype) * 0.3
    v = jnp.asarray(RNG.standard_normal((B, T, H, hd)), dtype)
    w = jnp.asarray(
        jax.nn.sigmoid(jnp.asarray(RNG.standard_normal((B, T, H, hd)))) * 0.6
        + 0.35, jnp.float32)
    u = jnp.asarray(RNG.standard_normal((H, hd)), jnp.float32) * 0.2
    s0 = jnp.asarray(RNG.standard_normal((B, H, hd, hd)), jnp.float32) * 0.1
    S_k, y_k = wkv6(r, k, v, w, u, s0, interpret=True)
    S_r, y_r = wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(S_k), np.asarray(S_r),
                               rtol=1e-4, atol=1e-4)


def test_wkv6_matches_model_scan():
    from repro.models.rwkv import wkv6_scan

    B, T, H, hd = 2, 64, 2, 16
    r = jnp.asarray(RNG.standard_normal((B, T, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, T, H, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, T, H, hd)), jnp.float32)
    w = jnp.asarray(jax.nn.sigmoid(
        jnp.asarray(RNG.standard_normal((B, T, H, hd)))) * 0.5 + 0.4)
    u = jnp.asarray(RNG.standard_normal((H, hd)), jnp.float32)
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    S_m, y_m = wkv6_scan(r, k, v, w, u, s0, chunk=16)
    S_k, y_k = wkv6(r, k, v, w, u, s0, interpret=True)
    np.testing.assert_allclose(y_m, y_k, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(S_m, S_k, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n,lo,hi", [
    (1000, 100, 900), (1024, 0, 0), (4096, 4000, 4096), (777, 0, 777),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
def test_segment_zero_sweep(n, lo, hi, dtype):
    x = jnp.asarray(RNG.standard_normal(n), dtype)
    out = segment_zero(x, lo, hi, interpret=True)
    ref = segment_zero_ref(x, lo, hi)
    assert jnp.array_equal(out, ref)


# ------------------------------------------------------- interpret choice


@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True)])
def test_interpret_follows_backend(monkeypatch, backend, interpret):
    from repro.kernels import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_interpret(None) is interpret
    # an explicit choice wins over the backend
    assert resolve_interpret(not interpret) is (not interpret)


def test_interpret_refuses_unknown_backend(monkeypatch):
    """A backend that is neither the TPU nor the CPU raises, so a kernel
    never drops into the interpreter on a misconfigured machine."""
    from repro.kernels import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        resolve_interpret(None)
    with pytest.raises(RuntimeError, match="'gpu'"):
        paged_attention(jnp.zeros((1, 2, 16)), jnp.zeros((4, 8, 1, 16)),
                        jnp.zeros((4, 8, 1, 16)), jnp.zeros((1, 1), jnp.int32),
                        jnp.ones((1,), jnp.int32), scale=0.25)

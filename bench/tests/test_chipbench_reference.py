"""The plain reference against the program's own forward pass at a
small size on the CPU, and the consumed stream it compares over."""

import numpy as np
import pytest

from bench import config, reference, weights
from bench.config import Shapes


def smoke(dtype="float32", gated=True) -> Shapes:
    return Shapes(name="smoke", layers=2, d_model=64, heads=4, kv_heads=2,
                  head_dim=16, d_ff=128, vocab=256, rope_theta=1e4,
                  norm_eps=1e-5, gated=gated,
                  activation="silu" if gated else "gelu_tanh", qkv_bias=True,
                  tie_embeddings=False, dtype=dtype, max_batch=4,
                  max_seq=256, page_tokens=16, pool_pages=64,
                  family=str(config.family_file("dense")))


@pytest.mark.parametrize("gated", [True, False])
def test_reference_matches_program_forward_in_f32(gated):
    import jax.numpy as jnp
    from repro.models import build_model
    s = smoke(gated=gated)
    w = weights.make(s, 2**31 + 5)
    model = build_model(config.family(s).arch_config(s))
    toks = np.random.default_rng(0).integers(0, s.vocab, 40).astype(np.int32)
    logits, _ = model.forward(w, jnp.asarray(toks[None]))
    padded = np.zeros(reference.QUERY_BLOCK, np.int32)
    padded[:40] = toks
    h = reference.hidden(w, jnp.asarray(padded), s=s)[:40]
    ref = np.asarray(h) @ np.asarray(w["unembed"], np.float32).T
    np.testing.assert_allclose(np.asarray(logits[0]), ref, atol=2e-4,
                               rtol=2e-4)


def test_gap_is_zero_for_the_reference_greedy_tokens():
    import jax.numpy as jnp
    s = smoke()
    w = weights.make(s, 9)
    prompt = np.arange(20, dtype=np.int32) % s.vocab
    tokens = []
    for _ in range(6):                   # greedy decode by the reference
        stream = reference.consumed_stream(prompt, tokens + [0])
        padded = np.zeros(reference.QUERY_BLOCK, np.int32)
        padded[:len(stream)] = stream
        h = reference.hidden(w, jnp.asarray(padded), s=s)[len(stream) - 1]
        tokens.append(int(np.argmax(np.asarray(h) @ np.asarray(
            w["unembed"], np.float32).T)))
    served, ctl = reference.gaps(w, s, prompt, tokens, control=True)
    assert served.shape == (6,) and ctl.shape == (6,)
    assert np.max(served) == pytest.approx(0.0, abs=1e-5)
    wrong = [(t + 1) % s.vocab for t in tokens]
    bad, _ = reference.gaps(w, s, prompt, wrong)
    assert np.min(bad[:1]) > 0.0


def test_consumed_stream_repeats_the_last_prompt_token():
    out = reference.consumed_stream([5, 6, 7], [1, 2, 3])
    assert out.tolist() == [5, 6, 7, 7, 1, 2]

"""What the dense family gives, pinned to the values the harness gave
before model families were files (commit eae9160, read on the CPU): the
weights drawn from a seed, the reference's hidden states and gaps at the
CPU test size, and the FLOP counts and program configs of the two
configurations the cells serve.  Any change here moves a reading of a
cell."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import config, flops, reference, weights
from bench.config import Shapes

SEED_HI = 2**31 + 5


def smoke(dtype="float32", gated=True) -> Shapes:
    return Shapes(name="smoke", layers=2, d_model=64, heads=4, kv_heads=2,
                  head_dim=16, d_ff=128, vocab=256, rope_theta=1e4,
                  norm_eps=1e-5, gated=gated,
                  activation="silu" if gated else "gelu_tanh", qkv_bias=True,
                  tie_embeddings=False, dtype=dtype, max_batch=4,
                  max_seq=256, page_tokens=16, pool_pages=64,
                  family=str(config.family_file("dense")))


SHAPES = {"f32_gated": smoke(), "bf16_plain": smoke("bfloat16", gated=False)}


def digest(tree) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes."""
    h = hashlib.sha256()
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(a)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


WEIGHTS = {
    ("f32_gated", 9):
        "6d61148207f557174256c835d3321a1162ad0bdba22ee5160726486981a798f6",
    ("f32_gated", SEED_HI):
        "59ddfc97724befa1b9418c93dd0eefb3c2896304afd89a27e8bbf3bd3d1b8c07",
    ("bf16_plain", 9):
        "521a7107e6b2f483edd14d6dc0c483cb5e636e297e0177da42190e4190d844e5",
    ("bf16_plain", SEED_HI):
        "82ff5582373202ed7e24f1ba5b37efb70e5d6c6e1e9652c14f358deeb4ada425",
}


@pytest.mark.parametrize("label,seed", sorted(WEIGHTS))
def test_weights_are_the_parents(label, seed):
    assert digest(weights.make(SHAPES[label], seed)) == WEIGHTS[label, seed]


#: hidden states at positions 0, 17, 39 and 511 of one 512-token stream
#: (and, after it, the gaps over a fixed prompt and answer), reference
#: and fp8 control, weights of seed 2**31 + 5
HIDDEN = {
    ("f32_gated", False):
        "455eca0eeab13bfb1a48fca4bba69bdb82efdf5e960989963dabb02cc0128ba2",
    ("f32_gated", True):
        "78fa632aec4375d9c4fcf383f74fb797dd44e09a838aadf89341ac738efb0037",
    ("bf16_plain", False):
        "9453719a01a5d0f03f5457d590be1269b9507f813ac479009530acd893943991",
    ("bf16_plain", True):
        "2bf86964042b0416b438315f2a9b31079814d26316d59dd2e5dfec5512095daa",
}
GAPS = {
    "f32_gated":
        "5ed0eb9e080accb1b959fab1067fd3ab3ea871654b6ace8c2e37c19ff9e5973a",
    "bf16_plain":
        "fd16f58d234a2940525c25fcb770cb8abe99ec84e9e3b7b59d23f36f0d67984b",
}


@pytest.mark.parametrize("label", sorted(SHAPES))
def test_reference_hidden_and_gaps_are_the_parents(label):
    s = SHAPES[label]
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 256, reference.QUERY_BLOCK).astype(np.int32)
    w = weights.make(s, SEED_HI)
    with jax.default_matmul_precision("highest"):
        for lower in (False, True):
            h = np.asarray(reference.hidden(w, jnp.asarray(toks), s=s,
                                            lower=lower))
            assert digest(h[[0, 17, 39, 511]]) == HIDDEN[label, lower]
    prompt = (np.arange(40, dtype=np.int32) * 7) % s.vocab
    tokens = [int(x) for x in rng.integers(0, 256, 12)]
    served, ctl = reference.gaps(w, s, prompt, tokens, control=True)
    assert digest({"served": served, "control": ctl}) == GAPS[label]


LENS = [1, 17, 1536, 4096]
#: per configuration: matrix parameters a token multiplies in one layer,
#: decode_flops(LENS), prefill_flops(1000, 536), prefill_flops(0, 3072),
#: paged_attn_bytes(LENS), paged_attn_flops(LENS)
COUNTS = {
    "qwen2.5-32b-4l": (487587840, 22294200320, 2148032512000,
                       12371188776960, 93388800, 462848000),
    "starcoder2-7b-16l": (217055232, 31261261824, 3923899711488,
                          22729872900096, 187301888, 1666252800),
}
#: the program's config: every field that differs from ArchConfig's
#: defaults
ARCH = {
    "qwen2.5-32b-4l": dict(
        arch_id="qwen2.5-32b-4l", family="dense", num_layers=4, d_model=5120,
        num_heads=40, num_kv_heads=8, d_ff=27648, vocab_size=152064,
        head_dim=128, qkv_bias=True, rope_base=1000000.0,
        tie_embeddings=False, norm_eps=1e-05),
    "starcoder2-7b-16l": dict(
        arch_id="starcoder2-7b-16l", family="dense", num_layers=16,
        d_model=4608, num_heads=36, num_kv_heads=4, d_ff=18432,
        vocab_size=49152, head_dim=128, qkv_bias=True, rope_base=1000000.0,
        tie_embeddings=False, norm_eps=1e-05, activation="gelu_tanh",
        gated=False),
}


def _shapes(name: str) -> Shapes:
    raw = json.loads((config.ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    return config.shapes_from(raw)


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_flop_counts_are_the_parents(name):
    s = _shapes(name)
    got = (config.family(s).matmul_params_per_token(s),
           flops.decode_flops(s, LENS), flops.prefill_flops(s, 1000, 536),
           flops.prefill_flops(s, 0, 3072), flops.paged_attn_bytes(s, LENS),
           flops.paged_attn_flops(s, LENS))
    assert got == COUNTS[name]


@pytest.mark.parametrize("name", sorted(ARCH))
def test_program_config_is_the_parents(name):
    from repro.models.common import ArchConfig
    s = _shapes(name)
    assert config.family(s).arch_config(s) == ArchConfig(**ARCH[name])

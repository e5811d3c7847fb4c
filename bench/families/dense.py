"""The dense decoder: grouped-query attention with rotary embedding and
optional q/k/v biases, then an MLP (SwiGLU, or the plain two-matrix
GELU-tanh MLP), RMSNorm before each.

The configuration's ``arch`` block says what the published keys leave
to the model's code: ``mlp`` (``gated`` or ``plain``), ``activation``,
which projections carry a bias, and the norm.  A later family can reuse
``qkv``, ``attend`` and ``attention`` and change only its feed-forward.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.config import Shapes
from bench.reference import QUERY_BLOCK, _mm, _rms, _rope


def _first(raw: dict, *keys):
    for k in keys:
        if k in raw:
            return raw[k]
    raise KeyError(f"config has none of {keys}")


def shapes(raw: dict) -> Shapes:
    arch, serving = raw["arch"], raw["serving"]
    if arch["norm"] != "rms" or arch["out_bias"] or arch["mlp_bias"]:
        raise ValueError(f"{raw['name']}: the program serves RMSNorm with "
                         "biases on q, k and v only")
    heads = raw["num_attention_heads"]
    return Shapes(
        name=raw["name"],
        layers=raw["num_hidden_layers"],
        d_model=raw["hidden_size"],
        heads=heads,
        kv_heads=raw["num_key_value_heads"],
        head_dim=raw.get("head_dim") or raw["hidden_size"] // heads,
        d_ff=raw["intermediate_size"],
        vocab=raw["vocab_size"],
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(_first(raw, "rms_norm_eps", "norm_epsilon",
                              "layer_norm_eps")),
        gated=arch["mlp"] == "gated",
        activation=arch["activation"],
        qkv_bias=bool(arch["qkv_bias"]),
        tie_embeddings=bool(raw.get("tie_word_embeddings", False)),
        dtype=raw.get("torch_dtype", "bfloat16"),
        max_batch=serving["max_batch"],
        max_seq=serving["max_seq"],
        page_tokens=serving["page_tokens"],
        pool_pages=serving["pool_pages"],
        family=__file__,
    )


def arch_config(s: Shapes):
    """The program's config for these shapes."""
    from repro.models.common import ArchConfig
    return ArchConfig(
        arch_id=s.name, family="dense", num_layers=s.layers,
        d_model=s.d_model, num_heads=s.heads, num_kv_heads=s.kv_heads,
        head_dim=s.head_dim, d_ff=s.d_ff, vocab_size=s.vocab,
        qkv_bias=s.qkv_bias, rope_base=s.rope_theta, norm_eps=s.norm_eps,
        activation=s.activation, gated=s.gated,
        tie_embeddings=s.tie_embeddings, dtype=s.dtype)


# ------------------------------------------------------------- weights


def layout(s: Shapes) -> dict:
    """Shape of every leaf, by path: stacked layers under ``layers``,
    q/k/v as ``(D, heads, head_dim)``."""
    L, D, H, K, hd, F = (s.layers, s.d_model, s.heads, s.kv_heads,
                         s.head_dim, s.d_ff)
    attn = {"wq": (L, D, H, hd), "wk": (L, D, K, hd), "wv": (L, D, K, hd),
            "wo": (L, H * hd, D)}
    if s.qkv_bias:
        attn.update(bq=(L, H, hd), bk=(L, K, hd), bv=(L, K, hd))
    ffn = {"wu": (L, D, F), "wd": (L, F, D)}
    if s.gated:
        ffn["wg"] = (L, D, F)
    tree = {"embed": (s.vocab, D), "final_norm": (D,),
            "layers": {"ln1": (L, D), "ln2": (L, D), "attn": attn,
                       "mlp": ffn}}
    if not s.tie_embeddings:
        tree["unembed"] = (s.vocab, D)
    return tree


def scale(path: str, shape, s: Shapes) -> tuple:
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


# ----------------------------------------------------------- reference


def _act(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu_tanh":
        return functools.partial(jax.nn.gelu, approximate=True)
    raise ValueError(f"activation {name!r}")


def qkv(s: Shapes, lower: bool, a, p):
    """q, k and v of the normed input ``a``, with their biases."""
    q = _mm("sd,dhk->shk", a, p["attn"]["wq"], lower)
    k = _mm("sd,dhk->shk", a, p["attn"]["wk"], lower)
    v = _mm("sd,dhk->shk", a, p["attn"]["wv"], lower)
    if s.qkv_bias:
        q = q + p["attn"]["bq"].astype(jnp.float32)
        k = k + p["attn"]["bk"].astype(jnp.float32)
        v = v + p["attn"]["bv"].astype(jnp.float32)
    return q, k, v


def attend(s: Shapes, lower: bool, q, k, v):
    """Rotary embedding, then grouped-query causal attention in blocks
    of ``QUERY_BLOCK`` queries; ``(S, heads * head_dim)``."""
    S = q.shape[0]
    K, G, hd = s.kv_heads, s.heads // s.kv_heads, s.head_dim
    pos = jnp.arange(S)
    q = _rope(q, pos, s.rope_theta) / math.sqrt(hd)
    k = _rope(k, pos, s.rope_theta)
    qb = q.reshape(S // QUERY_BLOCK, QUERY_BLOCK, K, G, hd)

    def block(args):
        i, qi = args
        sc = _mm("qkgh,tkh->kgqt", qi, k, lower)
        rows = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        sc = jnp.where(pos[None, :] <= rows[:, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return _mm("kgqt,tkh->qkgh", w, v, lower)

    o = jax.lax.map(block, (jnp.arange(S // QUERY_BLOCK), qb))
    return o.reshape(S, s.heads * hd)


def attention(s: Shapes, lower: bool, x, p):
    """The attention half of a layer, residual included."""
    a = _rms(x, p["ln1"], s.norm_eps)
    q, k, v = qkv(s, lower, a, p)
    return x + _mm("sf,fd->sd", attend(s, lower, q, k, v), p["attn"]["wo"],
                   lower)


def mlp(s: Shapes, lower: bool, x, p):
    """The feed-forward half of a layer, residual included."""
    m = _rms(x, p["ln2"], s.norm_eps)
    up = _mm("sd,df->sf", m, p["mlp"]["wu"], lower)
    if s.gated:
        h = _act(s.activation)(_mm("sd,df->sf", m, p["mlp"]["wg"], lower)) * up
    else:
        h = _act(s.activation)(up)
    return x + _mm("sf,fd->sd", h, p["mlp"]["wd"], lower)


def layer(s: Shapes, lower: bool, x, p):
    return mlp(s, lower, attention(s, lower, x, p), p)


# --------------------------------------------------------------- flops


def matmul_params_per_token(s: Shapes) -> int:
    q = s.heads * s.head_dim
    kv = s.kv_heads * s.head_dim
    attn = s.d_model * (q + 2 * kv) + q * s.d_model
    ffn = (3 if s.gated else 2) * s.d_model * s.d_ff
    return attn + ffn

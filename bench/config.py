"""A configuration file under ``bench/configs/``, read into the shapes
that the harness, ``flops.py`` and ``reference.py`` use.

The file holds the published ``config.json`` keys with the cuts applied
(``reduced`` names them), an ``arch`` block that says in one vocabulary
what the published keys leave to the model's code (gated or plain MLP,
activation, which projections carry a bias, the norm), and a
``serving`` block with the engine's sizes.  A new configuration is a
new file; nothing here names one.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shapes:
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    gated: bool
    activation: str
    qkv_bias: bool
    tie_embeddings: bool
    dtype: str
    max_batch: int
    max_seq: int
    page_tokens: int
    pool_pages: int


def _first(raw: dict, *keys):
    for k in keys:
        if k in raw:
            return raw[k]
    raise KeyError(f"config has none of {keys}")


def shapes_from(raw: dict) -> Shapes:
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
    )

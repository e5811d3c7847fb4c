"""qwen2.5-32b [dense] — GQA with QKV bias.

[hf:Qwen/Qwen2.5-32B (scaled from 0.5B card); hf]  64L d_model=5120 40H
(kv=8) d_ff=27648 vocab=152064; RoPE base 1e6; untied embeddings.
"""

import dataclasses

from repro.models.common import ArchConfig

CONFIG = ArchConfig(
    arch_id="qwen2.5-32b", family="dense",
    num_layers=64, d_model=5120, num_heads=40, num_kv_heads=8,
    d_ff=27648, vocab_size=152064,
    qkv_bias=True, rope_base=1_000_000.0, tie_embeddings=False,
)

#: CPU smoke preset for the tests (not a cut of the published widths)
REDUCED = ArchConfig(
    arch_id="qwen2.5-32b-smoke", family="dense",
    num_layers=3, d_model=80, num_heads=5, num_kv_heads=1,
    d_ff=160, vocab_size=256,
    qkv_bias=True, rope_base=1_000_000.0, tie_embeddings=False,
)

#: One v5e chip's share of a pipeline deployment: every width as
#: published in ``CHIP_SOURCE``, depth cut as ``CHIP_REDUCED`` records.
#: The other 60 layers would lie on further chips as pipeline stages, so
#: this chip holds 4 whole layers plus the embedding and the output head:
#: about 3.5 B bf16 parameters (7.0 GB of its 16 GB), the rest for KV.
CHIP = dataclasses.replace(CONFIG, arch_id="qwen2.5-32b-4l", num_layers=4)
CHIP_SOURCE = "hf:Qwen/Qwen2.5-32B config.json"
CHIP_REDUCED = {"num_layers": (64, 4)}

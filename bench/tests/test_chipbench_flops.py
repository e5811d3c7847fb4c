"""bench/flops.py, through the dense family, against counts worked out
by hand."""

from bench import config, flops
from bench.config import Shapes


def tiny(**kw) -> Shapes:
    base = dict(name="t", layers=2, d_model=8, heads=4, kv_heads=2,
                head_dim=2, d_ff=16, vocab=10, rope_theta=1e4, norm_eps=1e-5,
                gated=True, activation="silu", qkv_bias=True,
                tie_embeddings=False, dtype="bfloat16", max_batch=4,
                max_seq=64, page_tokens=4, pool_pages=32,
                family=str(config.family_file("dense")))
    base.update(kw)
    return Shapes(**base)


def test_matmul_params_per_layer():
    # q 8x8, k 8x4, v 8x4, o 8x8, gated mlp 3 x 8x16
    per_token = config.family(tiny()).matmul_params_per_token
    assert per_token(tiny()) == 64 + 32 + 32 + 64 + 384
    assert per_token(tiny(gated=False)) == 192 + 256


def test_decode_flops_by_hand():
    s = tiny()
    # rows attending 3 and 5 keys: dense 2*2*576 each, attention
    # 4*n*4*2 per layer, head 2*10*8 each
    want = (2 * 2 * 2 * 576) + (4 * 3 * 8 * 2 + 4 * 5 * 8 * 2) + 2 * 160
    assert flops.decode_flops(s, [3, 5]) == want


def test_prefill_flops_by_hand():
    s = tiny()
    # 3 tokens after 2 cached: contexts 3, 4, 5
    want = 2 * 3 * 2 * 576 + (3 + 4 + 5) * 4 * 8 * 2 + 2 * 10 * 8
    assert flops.prefill_flops(s, 2, 3) == want


def test_paged_attn_bytes_by_hand():
    s = tiny()
    page_bytes = 4 * 2 * 2 * 2                  # 4 tokens, 2 kv heads, hd 2
    # 5 keys -> 2 pages, 4 keys -> 1 page; K and V; q and out 4*2*2 each
    per_layer = 2 * 2 * page_bytes + 2 * 16 + 2 * 1 * page_bytes + 2 * 16
    assert flops.paged_attn_bytes(s, [5, 4]) == 2 * per_layer


def test_least_seconds_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds(1000, 5, peak) == 10.0
    assert flops.least_seconds(100, 50, peak) == 5.0

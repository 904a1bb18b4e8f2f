import flops

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
M22B = dict(hidden_size=6144, intermediate_size=16384,
            num_attention_heads=48, num_key_value_heads=8, head_dim=128,
            num_hidden_layers=1, num_local_experts=8, num_experts_per_tok=2,
            vocab_size=32768)


def test_token_flops_by_hand():
    d, de, V = 6144, 16384, 32768
    attn = d * 48 * 128 * 2 + d * 8 * 128 * 2
    moe = d * 8 + 2 * 3 * d * de
    assert flops.token_flops(M22B, 0) == 2 * (attn + moe + d * V)
    # each position of context adds q.k and w.v over 48 heads of 128
    assert (flops.token_flops(M22B, 100) - flops.token_flops(M22B, 0)
            == 2 * 2 * 48 * 128 * 100)


def test_gmm_call_counts_weights_once_per_group():
    f, b = flops.gmm_call(rows=256, groups=16, d_in=6144, d_out=16384)
    assert f == 2 * 256 * 6144 * 16384
    assert b == 2 * (16 * 6144 * 16384 + 256 * (6144 + 16384))


def test_gmm_at_decode_sizes_is_bound_by_hbm():
    f, b = flops.gmm_call(256, 16, 6144, 16384)
    t, bound = flops.roofline_s(f, b, PEAK)
    assert bound == "hbm" and t == b / 819e9
    # 3.9 ms to stream the 16 slots' 3.2 GB
    assert 3.8e-3 < t < 4.0e-3


def test_large_square_matmul_is_bound_by_flops():
    f, b = flops.gmm_call(8192, 1, 8192, 8192)
    t, bound = flops.roofline_s(f, b, PEAK)
    assert bound == "flops" and t == f / 197e12

import pytest

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


#: DeepSeek-V3's catalog config (huggingface.co/deepseek-ai/DeepSeek-V3
#: config.json) cut as planned: 1 dense + 4 MoE layers, vocabulary / 8
DSV3 = {"first_k_dense_replace": 1, "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "n_shared_experts": 1, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 5,
        "num_key_value_heads": 128, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "vocab_size": 16160}
#: 16 of the 512 slots (256 ranks x 2) on this chip: 8 ranks' share
DSV3_DEP = {"ep_ranks": 256, "slots_per_rank": 2, "ranks_here": 8,
            "max_batch": 128}


def test_token_flops_deepseek_shaped_by_hand():
    d = 7168
    # absorbed MLA per token: q down 7168x1536, q up 1536 x 128 heads x
    # 192, kv down 7168 x 576, key absorption 128 x 128 x 512, value up
    # 128 x 512 x 128, output 128 x 128 x 7168
    attn = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
            + 128 * 128 * 512 + 128 * 512 * 128 + 128 * 128 * 7168)
    per_position = 139264               # 128 heads x (2 x 512 + 64)
    # router, 1 shared expert, 8 routed of which 16 / 512 are held here
    moe = 7168 * 256 + 3 * 7168 * 2048 + 8 * (16 / 512) * 3 * 7168 * 2048
    dense = 3 * 7168 * 18432
    head = 7168 * 16160
    for ctx in (0, 1, 4096):
        want = 2 * (5 * (attn + per_position * ctx) + 4 * moe + dense + head)
        assert flops.token_flops(DSV3, ctx, DSV3_DEP) == want
    # holding every slot, the chip does all 8 routed experts' work
    everything = dict(DSV3_DEP, ranks_here=256)
    assert (flops.token_flops(DSV3, 0, everything)
            - flops.token_flops(DSV3, 0, DSV3_DEP)
            == 2 * 4 * (8 - 8 * 16 / 512) * 3 * d * 2048)


def test_gmm_roofline_counts_the_slots_held():
    """The reader finds the calls on the 16 slots held here, [16, 7168,
    2048] or [16, 2048, 7168], and counts 128 x 8 x 16 / 512 = 32 rows
    over 16 groups."""
    import bench
    import xplane

    def call(shape):
        return (f"%gmm_resident.1 = bf16[32,2048]{{1,0}} custom-call(s32[17]"
                f"{{0}} %a, bf16[{shape}]{{2,1,0}} %w), custom_call_target="
                f"\"tpu_custom_call\"", 0.0, 200e3, "", 1)

    ops = [call("16,7168,2048"), call("16,2048,7168"),
           call("512,7168,2048")]          # every slot's: not this chip's
    run = bench.Run(cell={}, model=DSV3, deployment=DSV3_DEP, peak=PEAK,
                    seconds=1.0, t_process=0.0,
                    trace=xplane.Reduced(planes=1, ops=ops))
    nbytes = 2 * (16 * 7168 * 2048 + 32 * (7168 + 2048))
    assert flops.gmm_call(32, 16, 7168, 2048) == (
        2 * 32 * 7168 * 2048, nbytes)
    read = bench.load_reader(bench.CHIP_DIR, "gmm_roofline")(run)
    assert read == pytest.approx(100 * (nbytes / 819e9) / 200e-6, rel=1e-12)

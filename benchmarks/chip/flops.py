"""Operations and bytes of the served model and of the ``gmm`` kernel,
counted from shapes. Model FLOPs count the work the architecture needs for
a token (two per multiply-add), not what an implementation computes: the
routed experts are counted ``top_k`` times, never all of them.
"""
from __future__ import annotations

BF16 = 2


def token_flops(model: dict, context: int) -> float:
    """Forward FLOPs of one token at position ``context - 1`` through every
    layer and the LM head."""
    d, H = model["hidden_size"], model["num_attention_heads"]
    KV = model["num_key_value_heads"]
    hd = model.get("head_dim") or d // H
    de, k = model["intermediate_size"], model["num_experts_per_tok"]
    E, L, V = (model["num_local_experts"], model["num_hidden_layers"],
               model["vocab_size"])
    attn_proj = d * H * hd * 2 + d * KV * hd * 2      # wq, wo, wk, wv
    attn_ctx = 2 * H * hd * context                   # q.k and w.v
    moe = d * E + k * 3 * d * de                      # router, k experts
    return 2.0 * (L * (attn_proj + attn_ctx + moe) + d * V)


def gmm_call(rows: int, groups: int, d_in: int, d_out: int,
             itemsize: int = BF16) -> tuple[float, float]:
    """(FLOPs, bytes) of one grouped matmul over ``rows`` group-sorted rows
    and ``groups`` weight matrices [d_in, d_out]: every row multiplied
    once, each group's weights read once, the rows read and written once."""
    flops = 2.0 * rows * d_in * d_out
    nbytes = itemsize * (groups * d_in * d_out + rows * (d_in + d_out))
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict
               ) -> tuple[float, str]:
    """Least time on the chip and which bound sets it."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "hbm")

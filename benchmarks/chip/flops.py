"""Operations and bytes of the served model and of the ``gmm`` kernel,
counted from shapes. Model FLOPs count the work the architecture needs for
a token (two per multiply-add), not what an implementation computes: the
routed experts are counted ``top_k`` times, never all of them.

The model is read by the Hugging Face key names of its family: Mixtral's
``num_local_experts`` and ``intermediate_size`` as the expert width,
DeepSeek's ``n_routed_experts``, ``moe_intermediate_size``,
``n_shared_experts``, ``first_k_dense_replace`` and the MLA ranks.
"""
from __future__ import annotations

from typing import Optional

BF16 = 2


def expert_count_key(model: dict) -> Optional[str]:
    """The key that holds the routed expert count, or None for a model
    with no routed experts."""
    return next((k for k in ("num_local_experts", "n_routed_experts")
                 if k in model), None)


def expert_width_key(model: dict) -> str:
    """The key that holds a routed expert's FFN width."""
    return ("moe_intermediate_size" if "moe_intermediate_size" in model
            else "intermediate_size")


def slots_held(deployment: dict) -> tuple[int, float]:
    """(slots this chip holds, their share of all slots): ``ranks_here``
    ranks (default ``ep_ranks``) of ``slots_per_rank`` each. The replicas'
    hash split gives every slot, in expectation, the same share of the
    (token, choice) pairs, so the chip does that share of the routed work;
    holding every slot, it does all of it."""
    ranks = deployment["ep_ranks"]
    here = deployment.get("ranks_here", ranks)
    return here * deployment["slots_per_rank"], here / ranks


def attention_flops(model: dict) -> tuple[int, int]:
    """(multiply-adds per token, per position of context) of one attention
    layer. GQA: the q, k, v and o projections, then q.k and w.v over each
    head. With ``kv_lora_rank`` (MLA) the absorbed form: the cache holds
    only the compressed latent and the shared rope key, and absorbing the
    key and value up-projections into the query and the output is the
    least work that computes the layer from it; per token the query
    (through ``q_lora_rank`` where given), the latent and rope key, the
    key absorption, the value up-projection and the output; per position
    the scores against latent and rope key and the weighted latent."""
    d, H = model["hidden_size"], model["num_attention_heads"]
    if "kv_lora_rank" in model:
        r, q_r = model["kv_lora_rank"], model.get("q_lora_rank")
        nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
        v = model["v_head_dim"]
        query = (d * q_r + q_r * H * (nope + rope) if q_r
                 else d * H * (nope + rope))
        per_token = (query + d * (r + rope) + H * nope * r + H * r * v
                     + H * v * d)
        return per_token, H * (2 * r + rope)
    KV = model["num_key_value_heads"]
    hd = model.get("head_dim") or d // H
    return d * H * hd * 2 + d * KV * hd * 2, 2 * H * hd


def token_flops(model: dict, context: int,
                deployment: Optional[dict] = None) -> float:
    """Forward FLOPs of one token at position ``context - 1`` through every
    layer and the LM head, of the share of the routed experts that
    ``deployment`` holds on this chip (all of them where it is None). The
    first ``first_k_dense_replace`` layers are dense SwiGLU FFNs of
    ``intermediate_size``; each other layer routes through the router,
    ``n_shared_experts`` shared experts and ``top_k`` routed ones of the
    expert width."""
    d, L, V = (model["hidden_size"], model["num_hidden_layers"],
               model["vocab_size"])
    de, k = model[expert_width_key(model)], model["num_experts_per_tok"]
    E = model[expert_count_key(model)]
    dense = model.get("first_k_dense_replace", 0)
    share = 1.0 if deployment is None else slots_held(deployment)[1]
    attn_proj, attn_pos = attention_flops(model)
    moe = (d * E + model.get("n_shared_experts", 0) * 3 * d * de
           + k * share * 3 * d * de)
    ffn = 3 * d * model["intermediate_size"]
    return 2.0 * (L * (attn_proj + attn_pos * context)
                  + (L - dense) * moe + dense * ffn + d * V)


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

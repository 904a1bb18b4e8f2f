"""Attention variants: GQA (llama-style), sliding-window (mixtral), MLA
(deepseek compressed latent), cross-attention (whisper), and a
sequence-sharded distributed decode path for long contexts.

All math is plain jnp (GSPMD shards heads/batch via param/activation
shardings); the Pallas flash kernels in ``repro.kernels`` are the TPU
hot-path implementation and are validated against these references.
Scores/softmax accumulate in fp32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.layers import NEG_INF, apply_rope, causal_mask, rmsnorm

# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def gqa_init(key, cfg: ArchConfig, dtype):
    d, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = 1.0 / np.sqrt(d)
    p = {
        "wq": (jax.random.normal(ks[0], (d, H, hd), jnp.float32) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, KV, hd), jnp.float32) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, KV, hd), jnp.float32) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (H, hd, d), jnp.float32)
               * (1.0 / np.sqrt(H * hd))).astype(dtype),
    }
    if cfg.attn_head_pad:
        # SSPerf P3: zero-padded Q heads make H divide the TP axis. Exact
        # semantics: zero wq rows -> uniform-softmax garbage context, zeroed
        # out by the zero wo rows. The attention math never changes.
        pad = cfg.attn_head_pad
        p["wq"] = jnp.concatenate(
            [p["wq"], jnp.zeros((d, pad, hd), dtype)], axis=1)
        p["wo"] = jnp.concatenate(
            [p["wo"], jnp.zeros((pad, hd, d), dtype)], axis=0)
    return p


def mla_init(key, cfg: ArchConfig, dtype):
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 5)
    def mk(k, shape, fan):
        return (jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan)).astype(dtype)
    return {
        "wq_a": mk(ks[0], (d, m.q_lora_rank), d),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "wq_b": mk(ks[1], (m.q_lora_rank, H, m.qk_head_dim), m.q_lora_rank),
        "wkv_a": mk(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), d),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wkv_b": mk(ks[3], (m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim),
                    m.kv_lora_rank),
        "wo": mk(ks[4], (H, m.v_head_dim, d), H * m.v_head_dim),
    }


def cross_attn_init(key, cfg: ArchConfig, dtype):
    """MHA cross-attention (decoder queries over encoder states)."""
    return gqa_init(key, cfg, dtype)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def init_gqa_cache(cfg: ArchConfig, batch: int, max_len: int, dtype,
                   num_layers: int):
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.attention == "swa" and cfg.window > 0:
        max_len = min(max_len, cfg.window)
    return {
        "k": jnp.zeros((num_layers, batch, max_len, KV, hd), dtype),
        "v": jnp.zeros((num_layers, batch, max_len, KV, hd), dtype),
        "pos": jnp.full((num_layers, batch, max_len), -1, jnp.int32),
    }


def init_mla_cache(cfg: ArchConfig, batch: int, max_len: int, dtype,
                   num_layers: int):
    m = cfg.mla
    return {
        "latent": jnp.zeros((num_layers, batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((num_layers, batch, max_len, m.qk_rope_head_dim), dtype),
        "pos": jnp.full((num_layers, batch, max_len), -1, jnp.int32),
    }


# ---------------------------------------------------------------------------
# Core softmax attention (reference path)
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, mask, scale):
    """q: [B,Sq,H,hd] k/v: [B,Sk,KV,hd] mask: [B?,Sq,Sk] additive fp32.
    Operands stay in model dtype; accumulation is fp32 via
    preferred_element_type (MXU-native) — no fp32 copy of the KV cache."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = scores + mask[:, None, None, :, :]
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, hd)


QCHUNK = 1024   # query-block size for long-sequence train/prefill attention


def _sdpa_qchunked(q, k, v, q_pos, k_pos, scale, window: int = 0,
                   chunk: int = QCHUNK):
    """Causal attention with the query dim processed in blocks via lax.scan,
    bounding the live score tensor to [B,KV,G,chunk,Sk] (the XLA-path stand-in
    for the Pallas flash kernel at 32k+ prefill; the kernel is the TPU
    hot-path implementation)."""
    B, Sq, H, hd = q.shape
    if Sq <= chunk:
        mask = causal_mask(q_pos, k_pos, window)
        return _sdpa(q, k, v, mask, scale)
    pad = (-Sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)))
    n = (Sq + pad) // chunk
    qc = q.reshape(B, n, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    pc = q_pos.reshape(B, n, chunk).transpose(1, 0, 2)

    def body(_, inp):
        qb, pb = inp
        mask = causal_mask(pb, k_pos, window)
        return None, _sdpa(qb, k, v, mask, scale)

    _, outs = jax.lax.scan(body, None, (qc, pc))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq + pad, H, hd)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# GQA / SWA
# ---------------------------------------------------------------------------


def gqa_project_qkv(cfg: ArchConfig, p, x, positions):
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    return q, k, v


def gqa_full(cfg: ArchConfig, p, x, positions):
    """Training / prefill self-attention (causal, optional sliding window).
    x: [B,S,d]; positions: [B,S]."""
    q, k, v = gqa_project_qkv(cfg, p, x, positions)
    window = cfg.window if cfg.attention == "swa" else 0
    out = _sdpa_qchunked(q, k, v, positions, positions,
                         1.0 / np.sqrt(cfg.head_dim), window)
    return jnp.einsum("bshe,hed->bsd", out.astype(x.dtype), p["wo"])


def gqa_prefill_cache(cfg: ArchConfig, p, x, positions, cache, layer):
    """Run full attention AND write k/v into the (possibly ring) cache."""
    q, k, v = gqa_project_qkv(cfg, p, x, positions)
    window = cfg.window if cfg.attention == "swa" else 0
    out = _sdpa_qchunked(q, k, v, positions, positions,
                         1.0 / np.sqrt(cfg.head_dim), window)
    y = jnp.einsum("bshe,hed->bsd", out.astype(x.dtype), p["wo"])
    W = cache["k"].shape[1]     # per-period slice: [B, W, KV, hd]
    slots = positions % W
    bidx = jnp.arange(x.shape[0])[:, None]
    cache = dict(cache)
    cache["k"] = cache["k"].at[bidx, slots].set(k)
    cache["v"] = cache["v"].at[bidx, slots].set(v)
    cache["pos"] = cache["pos"].at[bidx, slots].set(positions)
    return y, cache


def gqa_decode(cfg: ArchConfig, p, x, lengths, cache):
    """One-token decode against the cache. x: [B,1,d]; lengths: [B] current
    context length (the new token's position). cache leaves: [B, W, ...]."""
    positions = lengths[:, None]
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)

    W = cache["k"].shape[1]
    slot = (lengths % W)[:, None]
    bidx = jnp.arange(x.shape[0])[:, None]
    ck = cache["k"].at[bidx, slot].set(k)
    cv = cache["v"].at[bidx, slot].set(v)
    cpos = cache["pos"].at[bidx, slot].set(positions)

    window = cfg.window if cfg.attention == "swa" else 0
    valid = cpos >= 0
    if window > 0:
        valid &= cpos > (positions - window)
    valid &= cpos <= positions
    mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[:, None, :]
    out = _sdpa(q, ck, cv, mask, 1.0 / np.sqrt(cfg.head_dim))
    y = jnp.einsum("bshe,hed->bsd", out.astype(x.dtype), p["wo"])
    return y, {"k": ck, "v": cv, "pos": cpos}


def gqa_decode_seqsharded(cfg: ArchConfig, p, x, lengths, cache,
                          axis: str = "data"):
    """Distributed long-context decode: the KV cache's sequence dim is sharded
    over ``axis`` (context parallelism); each shard computes partial attention
    and the shards merge with a numerically-stable log-sum-exp combine.
    Runs inside shard_map; cache leaves here are the LOCAL shard [B, W/n, ...].
    New k/v land on the shard owning slot ``pos % W``."""
    idx = jax.lax.axis_index(axis)
    n = jax.lax.axis_size(axis)
    positions = lengths[:, None]
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)

    Wl = cache["k"].shape[1]               # local slots per shard
    gslot = positions[:, 0] % (Wl * n)     # global slot
    owner = gslot // Wl
    lslot = (gslot % Wl)[:, None]
    mine = (owner == idx)[:, None]
    bidx = jnp.arange(x.shape[0])[:, None]
    upd_k = jnp.where(mine[..., None, None], k, cache["k"][bidx, lslot])
    upd_v = jnp.where(mine[..., None, None], v, cache["v"][bidx, lslot])
    upd_p = jnp.where(mine, positions, cache["pos"][bidx, lslot])
    ck = cache["k"].at[bidx, lslot].set(upd_k)
    cv = cache["v"].at[bidx, lslot].set(upd_v)
    cpos = cache["pos"].at[bidx, lslot].set(upd_p)

    valid = (cpos >= 0) & (cpos <= positions)
    maskv = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)[:, None, :]

    B, Sq, H, hd = q.shape
    KV = ck.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(hd)
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, ck,
                        preferred_element_type=jnp.float32) * scale
    scores = scores + maskv[:, None, None, :, :]
    m_local = jnp.max(scores, axis=-1, keepdims=True)
    m_global = jax.lax.pmax(m_local, axis)
    e = jnp.exp(scores - m_global)
    denom = jax.lax.psum(jnp.sum(e, axis=-1, keepdims=True), axis)
    part = jnp.einsum("bkgqs,bskd->bqkgd", e.astype(cv.dtype), cv,
                      preferred_element_type=jnp.float32)
    out = jax.lax.psum(part, axis) / jnp.maximum(
        denom.transpose(0, 3, 1, 2, 4), 1e-30)
    out = out.reshape(B, Sq, H, hd)
    y = jnp.einsum("bshe,hed->bsd", out.astype(x.dtype), p["wo"])
    return y, {"k": ck, "v": cv, "pos": cpos}


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------


def _mla_qkv_latent(cfg: ArchConfig, p, x, positions):
    m = cfg.mla
    q_lat = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"])
    q = jnp.einsum("bsr,rhe->bshe", q_lat, p["wq_b"])     # [B,S,H,qk_head]
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    latent = rmsnorm(kv[..., : m.kv_lora_rank], p["kv_norm"])
    k_rope = apply_rope(kv[..., None, m.kv_lora_rank:], positions,
                        cfg.rope_theta)[..., 0, :]        # shared across heads
    return q_nope, q_rope, latent, k_rope


def mla_full(cfg: ArchConfig, p, x, positions):
    """Train/prefill MLA: expand the latent to per-head k/v (compute-bound).
    Query dim is chunk-scanned at long S to bound score memory."""
    m = cfg.mla
    q_nope, q_rope, latent, k_rope = _mla_qkv_latent(cfg, p, x, positions)
    kvb = jnp.einsum("bsr,rhe->bshe", latent, p["wkv_b"])
    k_nope = kvb[..., : m.qk_nope_head_dim]
    v = kvb[..., m.qk_nope_head_dim:]
    scale = 1.0 / np.sqrt(m.qk_head_dim)

    def block(qn, qr, pb):
        scores = (jnp.einsum("bqhd,bkhd->bhqk", qn, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", qr, k_rope,
                               preferred_element_type=jnp.float32)) * scale
        mask = causal_mask(pb, positions)
        scores = scores + mask[:, None, :, :]
        w = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bkhe->bqhe", w.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    B, Sq = x.shape[0], x.shape[1]
    if Sq <= QCHUNK:
        out = block(q_nope, q_rope, positions)
    else:
        pad = (-Sq) % QCHUNK
        qn = jnp.pad(q_nope, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qr = jnp.pad(q_rope, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pp = jnp.pad(positions, ((0, 0), (0, pad)))
        n = (Sq + pad) // QCHUNK
        def body(_, inp):
            a, b, c = inp
            return None, block(a, b, c)
        _, outs = jax.lax.scan(
            body, None,
            (qn.reshape(B, n, QCHUNK, *qn.shape[2:]).transpose(1, 0, 2, 3, 4),
             qr.reshape(B, n, QCHUNK, *qr.shape[2:]).transpose(1, 0, 2, 3, 4),
             pp.reshape(B, n, QCHUNK).transpose(1, 0, 2)))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq + pad,
                                                    *outs.shape[3:])[:, :Sq]
    return jnp.einsum("bshe,hed->bsd", out.astype(x.dtype), p["wo"])


def mla_prefill_cache(cfg: ArchConfig, p, x, positions, cache):
    y = mla_full(cfg, p, x, positions)
    m = cfg.mla
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    latent = rmsnorm(kv[..., : m.kv_lora_rank], p["kv_norm"])
    k_rope = apply_rope(kv[..., None, m.kv_lora_rank:], positions,
                        cfg.rope_theta)[..., 0, :]
    bidx = jnp.arange(x.shape[0])[:, None]
    cache = dict(cache)
    cache["latent"] = cache["latent"].at[bidx, positions].set(latent)
    cache["k_rope"] = cache["k_rope"].at[bidx, positions].set(k_rope)
    cache["pos"] = cache["pos"].at[bidx, positions].set(positions)
    return y, cache


def mla_decode(cfg: ArchConfig, p, x, lengths, cache):
    """Absorbed-matrix MLA decode: attention runs against the compressed
    latent cache only (memory-bound on latent + rope-k), never materializing
    per-head K/V. cache leaves: latent [B,S,r], k_rope [B,S,rd], pos [B,S]."""
    m = cfg.mla
    positions = lengths[:, None]
    q_nope, q_rope, latent_new, k_rope_new = _mla_qkv_latent(cfg, p, x, positions)

    bidx = jnp.arange(x.shape[0])[:, None]
    slot = positions  # full (non-ring) cache for MLA
    cl = cache["latent"].at[bidx, slot].set(latent_new)
    cr = cache["k_rope"].at[bidx, slot].set(k_rope_new)
    cp = cache["pos"].at[bidx, slot].set(positions)

    wkv_b_k = p["wkv_b"][..., : m.qk_nope_head_dim]    # [r, H, nope]
    wkv_b_v = p["wkv_b"][..., m.qk_nope_head_dim:]     # [r, H, v]
    # absorb W_uk into q:  q_abs[b,q,h,r]
    q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope, wkv_b_k)
    scale = 1.0 / np.sqrt(m.qk_head_dim)
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_abs, cl,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bsd->bhqs", q_rope, cr,
                           preferred_element_type=jnp.float32)) * scale
    valid = (cp >= 0) & (cp <= positions)
    scores = scores + jnp.where(valid, 0.0, NEG_INF)[:, None, None, :]
    w = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqs,bsr->bqhr", w.astype(cl.dtype), cl,
                     preferred_element_type=jnp.float32)
    out = jnp.einsum("bqhr,rhe->bqhe", ctx.astype(x.dtype), wkv_b_v)
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    return y, {"latent": cl, "k_rope": cr, "pos": cp}


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder over encoder states)
# ---------------------------------------------------------------------------


def cross_attention(cfg: ArchConfig, p, x, enc_k, enc_v):
    """x: [B,Sq,d]; enc_k/enc_v: [B,Se,KV,hd] (precomputed from encoder)."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    mask = jnp.zeros((x.shape[0], x.shape[1], enc_k.shape[1]), jnp.float32)
    out = _sdpa(q, enc_k, enc_v, mask, 1.0 / np.sqrt(cfg.head_dim))
    return jnp.einsum("bshe,hed->bsd", out.astype(x.dtype), p["wo"])


def encode_cross_kv(cfg: ArchConfig, p, enc_out):
    k = jnp.einsum("bsd,dhe->bshe", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", enc_out, p["wv"])
    return k, v

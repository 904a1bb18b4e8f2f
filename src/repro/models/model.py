"""Arch-generic model: init / train forward / prefill / decode.

Every architecture is a list of ScanGroups (transformer.py). The group body
is one *period* of sublayers; ``lax.scan`` runs it over stacked parameters,
keeping HLO size independent of depth. MoE sublayers call the
membership-elastic dispatch from ``repro.core`` — the mutable
``MembershipState`` arrays are threaded through every step as arguments of
the compiled function (the paper's graph-stable/content-mutable contract).
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.membership import MembershipState
from repro.models import attention as attn
from repro.models.ffn import ffn_apply, ffn_init
from repro.models.layers import embed_init, norm_apply, norm_init
from repro.models.mamba import init_mamba_state, mamba_apply, mamba_init
from repro.models.moe import MoEDeployment, local_deployment, moe_apply, moe_layer_init
from repro.models.transformer import LayerSpec, ScanGroup, build_groups
from repro.models.xlstm import (
    init_mlstm_state,
    init_slstm_state,
    mlstm_apply,
    mlstm_init,
    slstm_apply,
    slstm_init,
)


@dataclass(frozen=True)
class Deployment:
    """Compile-time parallelism context threaded through the model."""

    moe: MoEDeployment
    mesh: object = None
    seq_shard_axis: Optional[str] = None   # context-parallel decode (long ctx)
    fixed_s2e: object = None               # np[E]: fixed-membership routing
                                           # (training / Fig-9 baseline)

    @staticmethod
    def local(cfg: ArchConfig) -> "Deployment":
        slots = (cfg.moe.num_experts if cfg.is_moe else 1)
        return Deployment(moe=local_deployment(max(slots, 1),
                                               cfg.capacity_factor))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(cfg: ArchConfig, spec: LayerSpec, key, dtype,
                slot_to_expert, num_slots, serving: bool = False):
    ks = jax.random.split(key, 8)
    lp = {"norm1": norm_init(cfg.norm, cfg.d_model, dtype)}
    if spec.mixer == "attn":
        lp["attn"] = (attn.mla_init(ks[0], cfg, dtype)
                      if cfg.attention == "mla"
                      else attn.gqa_init(ks[0], cfg, dtype))
    elif spec.mixer == "mamba":
        lp["mamba"] = mamba_init(ks[0], cfg, dtype)
    elif spec.mixer == "mlstm":
        lp["mlstm"] = mlstm_init(ks[0], cfg, dtype)
    elif spec.mixer == "slstm":
        lp["slstm"] = slstm_init(ks[0], cfg, dtype)
    if spec.cross_attn:
        lp["norm_cross"] = norm_init(cfg.norm, cfg.d_model, dtype)
        lp["cross"] = attn.cross_attn_init(ks[1], cfg, dtype)
    if spec.ffn == "dense":
        lp["norm2"] = norm_init(cfg.norm, cfg.d_model, dtype)
        lp["ffn"] = ffn_init(ks[2], cfg.d_model, cfg.d_ff, cfg.activation, dtype)
    elif spec.ffn == "moe":
        lp["norm2"] = norm_init(cfg.norm, cfg.d_model, dtype)
        lp["moe"] = moe_layer_init(
            ks[2], cfg, num_slots, slot_to_expert, dtype,
            expert_dtype=cfg.expert_serving_dtype if serving else "")
    return lp


def _init_period(cfg, group: ScanGroup, key, dtype, slot_to_expert,
                 num_slots, serving: bool = False):
    return {f"layer{i}": _init_layer(cfg, spec, jax.random.fold_in(key, i),
                                     dtype, slot_to_expert, num_slots,
                                     serving)
            for i, spec in enumerate(group.layout)}


def init_params(cfg: ArchConfig, key, dtype=jnp.float32,
                slot_to_expert: Optional[np.ndarray] = None,
                num_slots: Optional[int] = None, serving: bool = False):
    """Real initialization, as ONE jitted program: every weight is drawn
    straight into its final (period-stacked, slot-stacked) buffer, so no
    eager fp32 draw, per-slot gather or stack copy holds a second full set.
    The dry-run uses ``param_shapes`` (no allocation)."""
    if cfg.is_moe and slot_to_expert is None:
        num_slots = num_slots or cfg.moe.num_experts
        slot_to_expert = np.arange(num_slots) % cfg.moe.num_experts
    s2e = (None if slot_to_expert is None
           else tuple(int(e) for e in np.asarray(slot_to_expert)))
    return _init_params_jit(cfg, key, dtype=jnp.dtype(dtype), s2e=s2e,
                            num_slots=num_slots, serving=serving)


def _init_params_body(cfg: ArchConfig, key, *, dtype, s2e, num_slots,
                      serving):
    slot_to_expert = None if s2e is None else np.asarray(s2e)
    params = {
        "embed": embed_init(key, cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
        "groups": {},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(jax.random.fold_in(key, 1),
                                       cfg.vocab_size, cfg.d_model, dtype).T
    for g in build_groups(cfg):
        # crc32, not hash(): str hashes are salted per process, and the
        # same seed must give the same weights in every process
        gk = jax.random.fold_in(key, zlib.crc32(g.name.encode()) % (2**31))
        keys = jax.vmap(lambda p: jax.random.fold_in(gk, p))(
            jnp.arange(g.n_periods))
        params["groups"][g.name] = jax.vmap(
            lambda k: _init_period(cfg, g, k, dtype, slot_to_expert,
                                   num_slots, serving))(keys)
    if cfg.encoder is not None:
        ek = jax.random.fold_in(key, 2)
        enc_spec = LayerSpec("attn", "dense")
        keys = jax.vmap(lambda p: jax.random.fold_in(ek, p))(
            jnp.arange(cfg.encoder.num_layers))
        params["encoder"] = {
            "layers": jax.vmap(lambda k: {"layer0": _init_layer(
                cfg, enc_spec, k, dtype, slot_to_expert, num_slots)})(keys),
            "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
        }
    return params


_init_params_jit = jax.jit(
    _init_params_body, static_argnums=(0,),
    static_argnames=("dtype", "s2e", "num_slots", "serving"))


def param_shapes(cfg: ArchConfig, dtype=jnp.bfloat16,
                 slot_to_expert: Optional[np.ndarray] = None,
                 num_slots: Optional[int] = None, serving: bool = False):
    """Shape-only params (dry-run): eval_shape one period per group, then
    broadcast the period dim — no device allocation, O(1) periods traced."""
    if cfg.is_moe and slot_to_expert is None:
        num_slots = num_slots or cfg.moe.num_experts
        slot_to_expert = np.arange(num_slots) % cfg.moe.num_experts
    key = jax.random.key(0)
    out = {
        "embed": jax.ShapeDtypeStruct((cfg.vocab_size, cfg.d_model), dtype),
        "final_norm": jax.eval_shape(
            lambda: norm_init(cfg.norm, cfg.d_model, dtype)),
        "groups": {},
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab_size), dtype)
    for g in build_groups(cfg):
        period = jax.eval_shape(
            lambda: _init_period(cfg, g, key, dtype, slot_to_expert,
                                 num_slots, serving))
        out["groups"][g.name] = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((g.n_periods,) + s.shape, s.dtype),
            period)
    if cfg.encoder is not None:
        enc_spec = LayerSpec("attn", "dense")
        period = jax.eval_shape(
            lambda: {"layer0": _init_layer(cfg, enc_spec, key, dtype,
                                           slot_to_expert, num_slots)})
        out["encoder"] = {
            "layers": jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    (cfg.encoder.num_layers,) + s.shape, s.dtype), period),
            "final_norm": jax.eval_shape(
                lambda: norm_init(cfg.norm, cfg.d_model, dtype)),
        }
    return out


# ---------------------------------------------------------------------------
# Caches / recurrent state
# ---------------------------------------------------------------------------


def init_caches(cfg: ArchConfig, batch: int, max_len: int, dtype):
    """Per-group decode state. Attn groups get KV caches; SSM mixers get
    recurrent state. Leaves carry a leading [n_periods] dim for the scan."""
    caches = {}
    for g in build_groups(cfg):
        gc = {}
        for i, spec in enumerate(g.layout):
            if spec.mixer == "attn":
                if cfg.attention == "mla":
                    c = attn.init_mla_cache(cfg, batch, max_len, dtype,
                                            g.n_periods)
                else:
                    c = attn.init_gqa_cache(cfg, batch, max_len, dtype,
                                            g.n_periods)
            elif spec.mixer == "mamba":
                c = init_mamba_state(cfg, batch, g.n_periods, dtype)
            elif spec.mixer == "mlstm":
                c = init_mlstm_state(cfg, batch, g.n_periods, dtype)
            elif spec.mixer == "slstm":
                c = init_slstm_state(cfg, batch, g.n_periods, dtype)
            else:
                c = {}
            gc[f"layer{i}"] = c
        caches[g.name] = gc
    if cfg.encoder is not None:
        # cross-attention K/V per decoder layer, filled at prefill
        for g in build_groups(cfg):
            for i, spec in enumerate(g.layout):
                if spec.cross_attn:
                    caches[g.name][f"layer{i}"]["cross_k"] = jnp.zeros(
                        (g.n_periods, batch, cfg.encoder.source_len,
                         cfg.num_kv_heads, cfg.head_dim), dtype)
                    caches[g.name][f"layer{i}"]["cross_v"] = jnp.zeros(
                        (g.n_periods, batch, cfg.encoder.source_len,
                         cfg.num_kv_heads, cfg.head_dim), dtype)
    return caches


# ---------------------------------------------------------------------------
# Group execution
# ---------------------------------------------------------------------------


def _attn_cache_keys(cfg: ArchConfig) -> tuple[str, ...]:
    return (("latent", "k_rope", "pos") if cfg.attention == "mla"
            else ("k", "v", "pos"))


def _run_group(cfg: ArchConfig, group: ScanGroup, gparams, x, *, mode: str,
               membership, dpl: Deployment, caches=None, positions=None,
               lengths=None, enc_out=None):
    """Scan the group's period body over its stacked params.

    Caches travel in the scan CARRY (sliced/updated per period with dynamic
    index ops) rather than as xs/ys — this lets XLA alias the donated cache
    buffers in place (measured 12x lower temp memory than the xs/ys form on
    decode steps). Returns (x, new_caches, moe_load [E] or None)."""
    E = cfg.moe.num_experts if cfg.is_moe else 0

    def layer_body(xx, pslice, cslice):
        new_c = {} if cslice is not None else None
        load = jnp.zeros((E,), jnp.float32) if E else jnp.zeros((1,), jnp.float32)
        for i, spec in enumerate(group.layout):
            lp = pslice[f"layer{i}"]
            lc = cslice[f"layer{i}"] if cslice is not None else None
            h = norm_apply(cfg.norm, xx, lp["norm1"])
            # ---- mixer ----
            if spec.mixer == "attn":
                y, nc = _attention(cfg, spec, i, lp["attn"], h, mode=mode,
                                   positions=positions, lengths=lengths,
                                   lc=lc, dpl=dpl)
            elif spec.mixer == "mamba":
                st = None if mode == "train" else lc
                y, nc = mamba_apply(cfg, lp["mamba"], h, st,
                                    chunk=cfg.scan_chunk)
            elif spec.mixer == "mlstm":
                st = None if mode == "train" else lc
                y, nc = mlstm_apply(cfg, lp["mlstm"], h, st,
                                    chunk=cfg.scan_chunk)
            elif spec.mixer == "slstm":
                st = None if mode == "train" else lc
                y, nc = slstm_apply(cfg, lp["slstm"], h, st)
            else:
                raise ValueError(spec.mixer)
            xx = xx + y
            # ---- cross attention (enc-dec) ----
            if spec.cross_attn:
                hc = norm_apply(cfg.norm, xx, lp["norm_cross"])
                if mode == "train":
                    ck, cv = attn.encode_cross_kv(cfg, lp["cross"], enc_out)
                elif mode == "prefill":
                    ck, cv = attn.encode_cross_kv(cfg, lp["cross"], enc_out)
                    nc = dict(nc or {})
                    nc["cross_k"], nc["cross_v"] = ck, cv
                else:
                    ck, cv = lc["cross_k"], lc["cross_v"]
                    nc = dict(nc or {})
                    nc["cross_k"], nc["cross_v"] = ck, cv
                xx = xx + attn.cross_attention(cfg, lp["cross"], hc, ck, cv)
            elif mode != "train" and lc is not None and "cross_k" in lc:
                nc = dict(nc or {})
                nc["cross_k"], nc["cross_v"] = lc["cross_k"], lc["cross_v"]
            # ---- ffn ----
            if spec.ffn == "dense":
                h2 = norm_apply(cfg.norm, xx, lp["norm2"])
                xx = xx + ffn_apply(lp["ffn"], h2, cfg.activation)
            elif spec.ffn == "moe":
                h2 = norm_apply(cfg.norm, xx, lp["norm2"])
                B, S, d = h2.shape
                yt, aux = moe_apply(cfg, lp["moe"], h2.reshape(B * S, d),
                                    membership, dpl.moe,
                                    fixed_s2e=dpl.fixed_s2e)
                xx = xx + yt.reshape(B, S, d)
                if E:
                    load = load + aux["expert_load"]
            if new_c is not None:
                new_c[f"layer{i}"] = nc if nc else (lc if lc is not None else {})
        return xx, new_c, load

    # ---- train: no caches; params streamed as xs; remat on the body --------
    if mode == "train":
        def body(xc, pslice):
            xx, nc, load = layer_body(xc, pslice, None)
            return xx, load

        rb = cfg.remat_block
        if cfg.remat and rb > 1 and group.n_periods % rb == 0:
            # hierarchical remat: save only every rb-th period input;
            # recompute the inner scan during backward (activation mem / rb)
            gp2 = jax.tree_util.tree_map(
                lambda a: a.reshape((group.n_periods // rb, rb) + a.shape[1:]),
                gparams)

            @jax.checkpoint
            def outer(xc, pblk):
                xc, loads = jax.lax.scan(body, xc, pblk)
                return xc, loads.sum(0)

            x, loads = jax.lax.scan(outer, x, gp2)
            return x, None, (loads.sum(0) if E else None)

        if cfg.remat:
            body = jax.checkpoint(body)
        x, loads = jax.lax.scan(body, x, gparams)
        return x, None, (loads.sum(0) if E else None)

    # ---- prefill/decode: caches travel in the carry (in-place aliasing) ----
    def body(carry, per):
        xc, cg = carry
        pslice, i = per
        cslice = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            cg)
        xc, new_c, load = layer_body(xc, pslice, cslice)
        cg = jax.tree_util.tree_map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(
                a, u.astype(a.dtype), i, 0),
            cg, new_c)
        return (xc, cg), load

    idx = jnp.arange(group.n_periods, dtype=jnp.int32)
    (x, new_caches), loads = jax.lax.scan(body, (x, caches), (gparams, idx))
    load = loads.sum(0) if E else None
    return x, new_caches, load


@jax.named_scope("attention")
def _attention(cfg: ArchConfig, spec: LayerSpec, i: int, p, h, *, mode: str,
               positions, lengths, lc, dpl: Deployment):
    """One attention mixer (QKV projections, the cache read and write,
    attention, the output projection) under the device-trace scope
    ``attention``. Returns (y, new layer cache)."""
    if mode == "train":
        y = (attn.mla_full(cfg, p, h, positions) if cfg.attention == "mla"
             else attn.gqa_full(cfg, p, h, positions))
        return y, {}
    if mode == "prefill":
        if cfg.attention == "mla":
            return attn.mla_prefill_cache(cfg, p, h, positions, lc)
        return attn.gqa_prefill_cache(cfg, p, h, positions, lc, i)
    if cfg.attention == "mla":
        return attn.mla_decode(cfg, p, h, lengths, lc)
    if dpl.seq_shard_axis:
        return _seqsharded_decode(cfg, p, h, lengths, lc, dpl)
    return attn.gqa_decode(cfg, p, h, lengths, lc)


def _seqsharded_decode(cfg, p, h, lengths, lc, dpl: Deployment):
    """Context-parallel decode island: cache sequence dim sharded over
    dpl.seq_shard_axis; LSE-merged partial attention."""
    from jax.sharding import PartitionSpec as P
    ax = dpl.seq_shard_axis
    cache_specs = {"k": P(None, ax), "v": P(None, ax), "pos": P(None, ax)}
    fn = jax.shard_map(
        partial(attn.gqa_decode_seqsharded, cfg, axis=ax),
        mesh=dpl.mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(), p), P(), P(),
                  cache_specs),
        out_specs=(P(), cache_specs),
        check_vma=False,
    )
    return fn(p, h, lengths, {k: lc[k] for k in ("k", "v", "pos")})


# ---------------------------------------------------------------------------
# Embedding / heads
# ---------------------------------------------------------------------------


def _embed(cfg: ArchConfig, params, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


@jax.named_scope("lm_head")
def _logits(cfg: ArchConfig, params, x):
    """Final norm and LM head, under the device-trace scope ``lm_head``."""
    x = norm_apply(cfg.norm, x, params["final_norm"])
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.float32)


def _encoder_forward(cfg: ArchConfig, params, frames, dpl: Deployment):
    """Bidirectional encoder over stub frame embeddings [B, Se, d]."""
    x = frames
    enc = params["encoder"]

    def body(xc, pslice):
        lp = pslice["layer0"]
        h = norm_apply(cfg.norm, xc, lp["norm1"])
        q = jnp.einsum("bsd,dhe->bshe", h, lp["attn"]["wq"])
        k = jnp.einsum("bsd,dhe->bshe", h, lp["attn"]["wk"])
        v = jnp.einsum("bsd,dhe->bshe", h, lp["attn"]["wv"])
        mask = jnp.zeros((xc.shape[0], xc.shape[1], xc.shape[1]), jnp.float32)
        o = attn._sdpa(q, k, v, mask, 1.0 / np.sqrt(cfg.head_dim))
        xc = xc + jnp.einsum("bshe,hed->bsd", o.astype(xc.dtype),
                             lp["attn"]["wo"])
        h2 = norm_apply(cfg.norm, xc, lp["norm2"])
        xc = xc + ffn_apply(lp["ffn"], h2, cfg.activation)
        return xc, None

    x, _ = jax.lax.scan(body, x, enc["layers"])
    return norm_apply(cfg.norm, x, enc["final_norm"])


# ---------------------------------------------------------------------------
# Top-level entry points
# ---------------------------------------------------------------------------


def forward_train(cfg: ArchConfig, params, batch, membership: MembershipState,
                  dpl: Deployment):
    """Causal-LM loss. batch: tokens [B,S], labels [B,S] (-1 ignored),
    optional visual_embed [B,Nf,d] (vlm) / frames [B,Se,d] (audio)."""
    tokens = batch["tokens"]
    labels = batch["labels"]
    x = _embed(cfg, params, tokens)
    enc_out = None
    if cfg.frontend == "vision_stub" and "visual_embed" in batch:
        ve = batch["visual_embed"].astype(x.dtype)
        x = jnp.concatenate([ve, x], axis=1)
        labels = jnp.concatenate(
            [jnp.full(ve.shape[:2], -1, labels.dtype), labels], axis=1)
    if cfg.encoder is not None:
        enc_out = _encoder_forward(cfg, params, batch["frames"].astype(x.dtype),
                                   dpl)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    total_load = None
    for g in build_groups(cfg):
        x, _, load = _run_group(cfg, g, params["groups"][g.name], x,
                                mode="train", membership=membership, dpl=dpl,
                                positions=positions, enc_out=enc_out)
        if load is not None:
            total_load = load if total_load is None else total_load + load

    logits = _logits(cfg, params, x)
    # next-token prediction
    lg = logits[:, :-1]
    tg = labels[:, 1:]
    mask = (tg >= 0).astype(jnp.float32)
    tg_safe = jnp.maximum(tg, 0)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tg_safe[..., None], axis=-1)[..., 0]
    loss = jnp.sum((logz - gold) * mask) / jnp.maximum(mask.sum(), 1.0)
    metrics = {"loss": loss}
    if total_load is not None:
        metrics["expert_load"] = total_load
    return loss, metrics


def prefill(cfg: ArchConfig, params, batch, caches,
            membership: MembershipState, dpl: Deployment):
    """Prompt processing: full attention + cache write. batch: tokens [B,S]
    (+ visual_embed / frames). Returns (last-token logits [B,V], caches)."""
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens)
    enc_out = None
    if cfg.frontend == "vision_stub" and "visual_embed" in batch:
        x = jnp.concatenate([batch["visual_embed"].astype(x.dtype), x], axis=1)
    if cfg.encoder is not None:
        enc_out = _encoder_forward(cfg, params, batch["frames"].astype(x.dtype),
                                   dpl)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    new_caches = {}
    for g in build_groups(cfg):
        x, nc, _ = _run_group(cfg, g, params["groups"][g.name], x,
                              mode="prefill", membership=membership, dpl=dpl,
                              caches=caches[g.name], positions=positions,
                              enc_out=enc_out)
        new_caches[g.name] = nc
    logits = _logits(cfg, params, x[:, -1:])[:, 0]
    return logits, new_caches


def decode_step(cfg: ArchConfig, params, tokens, lengths, caches,
                membership: MembershipState, dpl: Deployment):
    """One decoding step. tokens [B,1], lengths [B] (current context length).
    Returns (logits [B,V], caches)."""
    x = _embed(cfg, params, tokens)
    new_caches = {}
    for g in build_groups(cfg):
        x, nc, _ = _run_group(cfg, g, params["groups"][g.name], x,
                              mode="decode", membership=membership, dpl=dpl,
                              caches=caches[g.name], lengths=lengths)
        new_caches[g.name] = nc
    logits = _logits(cfg, params, x)[:, 0]
    return logits, new_caches

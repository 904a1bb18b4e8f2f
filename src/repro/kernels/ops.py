"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU and False on TPU,
where the kernels lower natively. The XLA model path (models/*) remains the
portable implementation; these kernels are the TPU hot-path variants and are
cross-validated against ``ref.py`` in tests/test_kernels.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import (
    flash_attention_decode,
    flash_attention_prefill,
)
from repro.kernels.moe_gmm import fused_moe_ffn, gmm
from repro.kernels.topk_router import topk_router


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("top_k", "normalize", "interpret"))
def topk_router_op(logits, expert_to_slot, replica_count, token_ids, *,
                   top_k: int, normalize: bool = True,
                   interpret: bool | None = None):
    it = default_interpret() if interpret is None else interpret
    return topk_router(logits, expert_to_slot, replica_count, token_ids,
                       top_k=top_k, normalize=normalize, interpret=it)


@partial(jax.jit, static_argnames=("activation", "interpret"))
def fused_moe_ffn_op(x, w_in, w_out, w_gate=None, *,
                     activation: str = "swiglu",
                     interpret: bool | None = None):
    it = default_interpret() if interpret is None else interpret
    return fused_moe_ffn(x, w_in, w_out, w_gate, activation=activation,
                         interpret=it)


@partial(jax.jit, static_argnames=("interpret",))
def gmm_op(x, w, group_sizes, *, interpret: bool | None = None):
    it = default_interpret() if interpret is None else interpret
    return gmm(x, w, group_sizes, interpret=it)


@partial(jax.jit, static_argnames=("window", "interpret"))
def flash_prefill_op(q, k, v, *, window: int = 0,
                     interpret: bool | None = None):
    it = default_interpret() if interpret is None else interpret
    return flash_attention_prefill(q, k, v, window=window, interpret=it)


@partial(jax.jit, static_argnames=("interpret",))
def flash_decode_op(q, k, v, lengths, *, interpret: bool | None = None):
    it = default_interpret() if interpret is None else interpret
    return flash_attention_decode(q, k, v, lengths, interpret=it)

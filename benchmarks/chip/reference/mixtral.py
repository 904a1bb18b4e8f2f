"""Plain float32 reference of the Mixtral decoder (arXiv:2401.04088).

Pre-norm decoder layers: RMSNorm, grouped-query causal self-attention with
rotary positions, residual; RMSNorm, a sparse MoE block (softmax router,
top-k experts renormalized over the chosen k, SwiGLU experts), residual;
a final RMSNorm and the LM head. Full attention over the whole context
(the configurations run ``sliding_window: null``).

It imports nothing of the program under test and takes nothing it made:
the weights are drawn here from the seed by the init recipe each
configuration file states (float32 normals rounded to the dtype they are
served in), then computed in float32 with matmuls at
``highest`` precision. It runs layer by layer and expert by expert, drawing
each block's weights inside the jitted call that uses them, so a chip holds
one expert's float32 weights at a time.

``control="fp8"`` is the lower-precision control: every matmul operand
(weights and activations, attention included) is rounded to float8_e4m3fn
with one scale per tensor, norms and softmax staying in float32.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0          # largest finite float8_e4m3fn


def _draw(key, shape, scale, dtype):
    """A float32 normal draw, rounded to the dtype the weights are served
    in, computed on in float32."""
    return (jax.random.normal(key, shape, jnp.float32) * scale
            ).astype(dtype).astype(jnp.float32)


def _q(x, precision):
    """Round a matmul operand to the precision under test."""
    if precision == "float32":
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, precision):
    return jnp.einsum(eq, _q(a, precision), _q(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x [B, S, H, hd]; rotate interleaved pairs (x[2i], x[2i+1])."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, :, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


class Mixtral:
    """The reference for one configuration file's ``model`` block."""

    def __init__(self, model: dict, dtype: str = "bfloat16"):
        self.dtype = jnp.dtype(dtype)
        self.d = model["hidden_size"]
        self.de = model["intermediate_size"]
        self.H = model["num_attention_heads"]
        self.KV = model["num_key_value_heads"]
        self.hd = model.get("head_dim") or self.d // self.H
        self.L = model["num_hidden_layers"]
        self.E = model["num_local_experts"]
        self.k = model["num_experts_per_tok"]
        self.V = model["vocab_size"]
        self.theta = float(model["rope_theta"])
        self.eps = float(model["rms_norm_eps"])
        if model.get("sliding_window") is not None:
            raise ValueError("the reference runs full attention only")
        if model.get("hidden_act") != "silu":
            raise ValueError("the reference runs SwiGLU experts only")

    def _key(self):
        return (self.d, self.de, self.H, self.KV, self.hd, self.L, self.E,
                self.k, self.V, self.theta, self.eps, self.dtype)

    # equal configurations share their compiled blocks (``self`` is a
    # static argument of every jitted method)
    def __eq__(self, other):
        return isinstance(other, Mixtral) and self._key() == other._key()

    def __hash__(self):
        return hash(self._key())

    # -- the init recipe ---------------------------------------------------
    def _layer_keys(self, key, layer):
        gk = jax.random.fold_in(key, zlib.crc32(b"moe") % (2 ** 31))
        return jax.random.split(
            jax.random.fold_in(jax.random.fold_in(gk, layer), 0), 8)

    # -- blocks (each jitted with its weights drawn inside; the layer and
    #    expert indices are traced, so one compile serves them all) --------
    @partial(jax.jit, static_argnums=(0, 3))
    def _embed(self, key, tokens, precision):
        del precision
        return jnp.take(_draw(key, (self.V, self.d), 0.02,
                     self.dtype), tokens, axis=0)

    @partial(jax.jit, static_argnums=(0, 5))
    def _attention(self, key, x, pos, layer, precision):
        d, H, KV, hd = self.d, self.H, self.KV, self.hd
        a = jax.random.split(self._layer_keys(key, layer)[0], 4)
        wq = _draw(a[0], (d, H, hd), 1 / np.sqrt(d),
                     self.dtype)
        wk = _draw(a[1], (d, KV, hd), 1 / np.sqrt(d),
                     self.dtype)
        wv = _draw(a[2], (d, KV, hd), 1 / np.sqrt(d),
                     self.dtype)
        wo = _draw(a[3], (H, hd, d), 1 / np.sqrt(H * hd),
                     self.dtype)
        h = _rmsnorm(x, self.eps)
        q = _rope(_mm("bsd,dhe->bshe", h, wq, precision), pos, self.theta)
        k = _rope(_mm("bsd,dhe->bshe", h, wk, precision), pos, self.theta)
        v = _mm("bsd,dhe->bshe", h, wv, precision)
        B, S = x.shape[:2]
        qg = q.reshape(B, S, KV, H // KV, hd)
        s = _mm("bqkgd,bskd->bkgqs", qg, k, precision) / np.sqrt(hd)
        causal = pos[:, None, :] <= pos[:, :, None]               # [B, q, s]
        s = jnp.where(causal[:, None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = _mm("bkgqs,bskd->bqkgd", w, v, precision).reshape(B, S, H, hd)
        return x + _mm("bshe,hed->bsd", o, wo, precision)

    @partial(jax.jit, static_argnums=(0, 4))
    def _route(self, key, x, layer, precision):
        """Normalized MoE input [N, d] and combine weights [N, E]."""
        m = jax.random.split(self._layer_keys(key, layer)[2], 5)
        router = _draw(m[2], (self.d, self.E), 1 / np.sqrt(self.d),
                     self.dtype)
        h = _rmsnorm(x, self.eps)
        probs = jax.nn.softmax(_mm("nd,de->ne", h, router, precision), -1)
        top, idx = jax.lax.top_k(probs, self.k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        comb = jnp.zeros_like(probs).at[
            jnp.arange(probs.shape[0])[:, None], idx].set(top)
        return h, comb

    @partial(jax.jit, static_argnums=(0, 6))
    def _expert(self, key, h, comb_e, e, layer, precision):
        """One expert's SwiGLU FFN over every token, times its combine
        weight (zero for tokens not routed to it)."""
        m = jax.random.split(self._layer_keys(key, layer)[2], 5)
        d, de = self.d, self.de
        w_in = _draw(jax.random.fold_in(m[0], e), (d, de), 1 / np.sqrt(d),
                     self.dtype)
        w_out = _draw(jax.random.fold_in(m[1], e), (de, d), 1 / np.sqrt(de),
                     self.dtype)
        w_gate = _draw(jax.random.fold_in(m[3], e), (d, de), 1 / np.sqrt(d),
                     self.dtype)
        g = jax.nn.silu(_mm("nd,df->nf", h, w_gate, precision))
        u = _mm("nd,df->nf", h, w_in, precision)
        return comb_e[:, None] * _mm("nf,fd->nd", g * u, w_out, precision)

    @partial(jax.jit, static_argnums=(0, 4))
    def _logits(self, key, x, rows, precision):
        head = _draw(jax.random.fold_in(key, 1), (self.V, self.d), 0.02,
                     self.dtype).T
        return _mm("nd,dv->nv", _rmsnorm(x[rows], self.eps), head,
                   precision)

    @partial(jax.jit, static_argnums=(0,))
    def _gap(self, key, x, rows, chosen):
        lg = self._logits(key, x, rows, "float32")
        return jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, chosen[:, None], axis=-1)[:, 0]

    @partial(jax.jit, static_argnums=(0, 4))
    def _first(self, key, x, rows, precision):
        return jnp.argmax(self._logits(key, x, rows, precision), axis=-1)

    # -- forward -----------------------------------------------------------
    def _hidden(self, key, tokens, precision):
        """Final hidden states [B * S, d] of the right-padded batch."""
        B, S = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = self._embed(key, jnp.asarray(tokens, jnp.int32), precision)
        for layer in map(jnp.int32, range(self.L)):
            x = self._attention(key, x, pos, layer, precision)
            flat = x.reshape(B * S, self.d)
            h, comb = self._route(key, flat, layer, precision)
            for e in map(jnp.int32, range(self.E)):
                flat = flat + self._expert(key, h, comb[:, e], e, layer,
                                           precision)
            x = flat.reshape(B, S, self.d)
        return x.reshape(B * S, self.d)

    def logits(self, seed: int, tokens: np.ndarray, rows: np.ndarray
               ) -> np.ndarray:
        """Float32 logits [len(rows), V] at the flat positions ``rows``."""
        key = jax.random.key(seed)
        return np.asarray(self._logits(key, self._hidden(key, tokens,
                                                         "float32"),
                                       jnp.asarray(rows), "float32"))

    def gaps(self, seed: int, tokens: np.ndarray, rows: np.ndarray,
             served: np.ndarray, control: str = "") -> np.ndarray:
        """At each flat position ``rows`` of the right-padded batch
        ``tokens`` [B, S] (row = b * S + s), how far the float32
        reference's logit of the chosen token lies below its best. The
        chosen token is ``served``, or with ``control`` the one the
        reference at that precision puts first. Positions are padded to
        B * S, so every call of a batch shape runs the same programs."""
        key = jax.random.key(seed)
        n = len(rows)
        pad = np.zeros(tokens.size, np.int32)
        r = pad.copy()
        r[:n] = rows
        r = jnp.asarray(r)
        if control:
            chosen = self._first(key, self._hidden(key, tokens, control), r,
                                 control)
        else:
            c = pad.copy()
            c[:n] = served
            chosen = jnp.asarray(c)
        x = self._hidden(key, tokens, "float32")
        return np.asarray(self._gap(key, x, r, chosen))[:n]


def build(model: dict, dtype: str) -> Mixtral:
    return Mixtral(model, dtype)

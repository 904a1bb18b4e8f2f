"""Fused MoE expert FFN — Pallas TPU kernel (the MoE compute hot spot).

Computes, per expert slot s over its dispatched token block:

    out = (act(x @ w_gate[s]) * (x @ w_in[s])) @ w_out[s]      (gated)
    out = act(x @ w_in[s]) @ w_out[s]                          (non-gated)

in ONE kernel: the expert-hidden activation h [bt, bf] never leaves VMEM,
saving two HBM round trips of the [R, d_e] intermediate relative to the
unfused einsum chain. Grid (slots, token-blocks, d_e-blocks) with an fp32
accumulator over the d_e axis (last grid dim = sequential on TPU).

VMEM budget per step (bt=128, bf=256, d=7168, bf16):
  x 1.8 MB + w_in/w_gate/w_out 3.5 MB each + acc 3.5 MB fp32  ~= 16 MB.

Also provides ``gmm`` (grouped matmul over group-sorted tokens with
group_sizes) — the dropless-dispatch building block used by the §Perf
ragged path. Validated in interpret mode vs ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _act(h, activation: str):
    if activation in ("swiglu",):
        return jax.nn.silu(h)
    if activation in ("geglu", "gelu"):
        return jax.nn.gelu(h, approximate=True)
    if activation == "relu2":
        return jnp.square(jax.nn.relu(h))
    raise ValueError(activation)


def _fused_ffn_kernel(x_ref, wi_ref, wg_ref, wo_ref, o_ref, acc_ref, *,
                      activation: str, gated: bool):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                   # [bt, d]
    wi = wi_ref[0]                                 # [d, bf]
    h = jnp.dot(x, wi, preferred_element_type=jnp.float32)
    if gated:
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        h = _act(g, activation) * h
    else:
        h = _act(h, activation)
    wo = wo_ref[0]                                 # [bf, d]
    acc_ref[...] += jnp.dot(h.astype(wo.dtype), wo,
                            preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def fused_moe_ffn(x, w_in, w_out, w_gate=None, *, activation: str = "swiglu",
                  block_t: int = 128, block_f: int = 256,
                  interpret: bool = False):
    """x: [S, R, d] per-slot token blocks; w_in: [S, d, de]; w_out: [S, de, d];
    w_gate: [S, d, de] or None. Returns [S, R, d] (same dtype as x)."""
    S, R, d = x.shape
    de = w_in.shape[2]
    bt = min(block_t, R)
    bf = min(block_f, de)
    pad_r = (-R) % bt
    pad_f = (-de) % bf
    if pad_r:
        x = jnp.pad(x, ((0, 0), (0, pad_r), (0, 0)))
    if pad_f:
        w_in = jnp.pad(w_in, ((0, 0), (0, 0), (0, pad_f)))
        w_out = jnp.pad(w_out, ((0, 0), (0, pad_f), (0, 0)))
        if w_gate is not None:
            w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, pad_f)))
    Rp, dep = R + pad_r, de + pad_f
    gated = w_gate is not None
    if not gated:
        w_gate = w_in  # placeholder operand (unread)

    kernel = functools.partial(_fused_ffn_kernel, activation=activation,
                               gated=gated)
    out = pl.pallas_call(
        kernel,
        grid=(S, Rp // bt, dep // bf),
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda s, i, j: (s, i, 0)),
            pl.BlockSpec((1, d, bf), lambda s, i, j: (s, 0, j)),
            pl.BlockSpec((1, d, bf), lambda s, i, j: (s, 0, j)),
            pl.BlockSpec((1, bf, d), lambda s, i, j: (s, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, d), lambda s, i, j: (s, i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, Rp, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
    )(x, w_in, w_gate, w_out)
    return out[:, :R]


# ---------------------------------------------------------------------------
# Grouped matmul (dropless path): tokens sorted by group, sizes per group
# ---------------------------------------------------------------------------


def _gmm_metadata(group_sizes, bt: int, nblocks: int):
    """Logical-tile schedule for groups of ARBITRARY (traced) size.

    A logical tile is one (group, row-block) pair whose row ranges intersect:
    a row block straddling a group boundary is visited once per overlapping
    group, each visit masked to its own rows (megablocks-style). The tile
    count is data-dependent but bounded by ``nblocks + G - 1`` (each interior
    group boundary adds at most one shared block), so the grid is static;
    logical tiles past the real schedule degenerate into masked no-op
    revisits of the last row block.

    Returns (tile_group, tile_block) int32[nblocks + G - 1], both
    non-decreasing in tile order (Pallas output-block revisits stay
    consecutive).
    """
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // bt
    last_tile = (ends + bt - 1) // bt               # exclusive
    tiles_of = jnp.where(sizes > 0, last_tile - first_tile, 0)
    seq_start = jnp.cumsum(tiles_of) - tiles_of     # tile index where each
    ntiles = nblocks + G - 1                        # group's run begins
    t = jnp.arange(ntiles, dtype=jnp.int32)
    # side="right" skips zero-tile groups at ties (their run is empty)
    tile_group = jnp.clip(
        jnp.searchsorted(seq_start, t, side="right") - 1, 0, G - 1
    ).astype(jnp.int32)
    off = t - seq_start[tile_group]
    tile_block = jnp.clip(first_tile[tile_group] + off, 0, nblocks - 1
                          ).astype(jnp.int32)
    return tile_group, tile_block, starts, ends


def _gmm_kernel(tg_ref, tb_ref, gs_ref, ge_ref, x_ref, w_ref, o_ref, acc_ref,
                *, bt: int):
    i = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                  # [bt, bk]
    w = w_ref[0]                                    # [bk, bf]
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        # blend-store only the rows belonging to this tile's group: a
        # boundary block is completed by its other group's visit(s), and
        # degenerate trailing tiles rewrite identical values (idempotent)
        g = tg_ref[i]
        rows = tb_ref[i] * bt + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mask = (rows >= gs_ref[g]) & (rows < ge_ref[g])
        o_ref[...] = jnp.where(mask, acc_ref[...],
                               o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def _sublane_rows(dtype) -> int:
    """Least legal row-block multiple: 8 rows of 32 bits per sublane tile,
    so 16 for bf16 and 32 for 8-bit types (packed rows)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def gmm(x, w, group_sizes, *, block_t: int = 128, block_k: int = 512,
        block_f: int = 1024, interpret: bool = False):
    """Grouped matmul over group-sorted rows: the first ``group_sizes[0]``
    rows of x [T, d] belong to group 0, and so on; w [G, d, f].

    ``group_sizes`` may be traced, contain zeros, and need not be multiples
    of ``block_t`` — boundary row blocks are revisited once per overlapping
    group with a row mask, so ragged dispatch needs NO per-group padding.
    Rows beyond ``sum(group_sizes)`` (receive-buffer slack) produce
    unspecified output; callers must never read them. Returns [T, f].

    Grid (f-blocks, logical tiles, k-blocks): the output width is tiled so
    the weight block (1, bk, bf) and the fp32 accumulator (bt, bf) stay in
    the default scoped VMEM at any expert width. The f axis is OUTERMOST so
    that, for each output column block, the revisits of a boundary row
    block by successive tiles stay consecutive (the blend-store reads the
    rows the previous group's visit left in the resident output block)."""
    T, d = x.shape
    G, _, f = w.shape
    if T == 0:
        return jnp.zeros((0, f), x.dtype)
    sub = _sublane_rows(x.dtype)
    bt = min(block_t, -(-T // sub) * sub)
    pad_t = (-T) % bt
    if pad_t:
        x = jnp.pad(x, ((0, pad_t), (0, 0)))
    nblocks = (T + pad_t) // bt

    bk = min(block_k, d)
    pad_k = (-d) % bk
    if pad_k:
        x = jnp.pad(x, ((0, 0), (0, pad_k)))
        w = jnp.pad(w, ((0, 0), (0, pad_k), (0, 0)))
    dp = d + pad_k

    bf = min(block_f, f)
    pad_f = (-f) % bf
    if pad_f:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, pad_f)))
    fp = f + pad_f

    tile_group, tile_block, starts, ends = _gmm_metadata(group_sizes, bt,
                                                         nblocks)
    kernel = functools.partial(_gmm_kernel, bt=bt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(fp // bf, nblocks + G - 1, dp // bk),
        in_specs=[
            pl.BlockSpec((bt, bk), lambda j, i, k, tg, tb, gs, ge: (tb[i], k)),
            pl.BlockSpec((1, bk, bf),
                         lambda j, i, k, tg, tb, gs, ge: (tg[i], k, j)),
        ],
        out_specs=pl.BlockSpec((bt, bf),
                               lambda j, i, k, tg, tb, gs, ge: (tb[i], j)),
        scratch_shapes=[pltpu.VMEM((bt, bf), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T + pad_t, fp), x.dtype),
        interpret=interpret,
    )(tile_group, tile_block, starts, ends, x, w)
    return out[:T, :f]

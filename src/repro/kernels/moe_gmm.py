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


def _sublane_rows(dtype) -> int:
    """Least legal row-block multiple: 8 rows of 32 bits per sublane tile,
    so 16 for bf16 and 32 for 8-bit types (packed rows)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _gmm_tiled_kernel(tg_ref, tb_ref, gs_ref, ge_ref, x_ref, w_ref, o_ref,
                      acc_ref, *, bt: int):
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


def _gmm_tiled(x, w, group_sizes, *, bt: int, bk: int, bf: int,
               interpret: bool):
    """Grid (f-blocks, logical tiles, k-blocks) over row blocks of ``bt``:
    the row block and the weight block are both fetched on every step, so
    a group straddling a row-block boundary streams its weights once per
    block. For calls whose rows do not fit VMEM (large T)."""
    T, dp = x.shape
    G, _, fp = w.shape
    nblocks = T // bt
    tile_group, tile_block, starts, ends = _gmm_metadata(group_sizes, bt,
                                                         nblocks)
    kernel = functools.partial(_gmm_tiled_kernel, bt=bt)
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
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, fp), x.dtype),
        interpret=interpret,
        name="gmm_tiled",
    )(tile_group, tile_block, starts, ends, x, w)


# Rows resident in VMEM. The resident schedule holds all of x (one buffer),
# the [T, bf] output block (two buffers), its fp32 accumulator and the
# weight block (two buffers). Sized for Mixtral-8x22B's w_out call at 256
# rows (128 decode rows x top-2), the largest the serve step makes: x
# 256 x 16384 x 2 B = 8 MiB, output 2 x 0.5 MiB, accumulator 1 MiB,
# weights 2 x 1 MiB = 12 MiB; with the window's temporaries that call
# compiles to 13.9 MiB of v5e's 16 MiB default scoped VMEM.
_RESIDENT_VMEM_BYTES = 12 * 2**20
# Rows multiplied at a time in the resident schedule, a multiple of every
# dtype's sublane rows. The MXU's time grows with a window's rows and has
# to hide under each step's 1 MiB weight DMA (~1.3 us on v5e): at
# Mixtral-8x22B's widths and ~16-row groups a call took 4.31 ms with
# 32-row windows, 4.41 with 64 and 4.85 with 128; 16-row ones need two
# windows for most groups.
_WINDOW_ROWS = 32


def _resident_vmem_bytes(rows: int, dp: int, bk: int, bf: int,
                         itemsize: int) -> int:
    return (rows * dp * itemsize + 2 * rows * bf * itemsize + rows * bf * 4
            + 2 * bk * bf * itemsize)


def _resident_metadata(group_sizes, nk: int):
    """Scalar-prefetch operands of the resident schedule: each group's row
    range, and the weight block (group, k) its steps fetch. A non-empty
    group fetches its own blocks; an empty group repeats the block of the
    step before it (the last non-empty group's last k-block, or, ahead of
    every non-empty group, the first one's first), so no DMA is issued."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    live = sizes > 0
    prev = jax.lax.cummax(jnp.where(live, jnp.arange(G, dtype=jnp.int32),
                                    -1))
    first = jnp.argmax(live).astype(jnp.int32)
    w_group = jnp.where(prev >= 0, prev, first)
    # the k-block an empty group's steps fetch; -1: each step's own k
    w_k = jnp.where(live, -1, jnp.where(prev >= 0, nk - 1, 0))
    return starts, ends, w_group, w_k.astype(jnp.int32)


def _resident_grid_spec(rows: int, dp: int, G: int, fp: int, bk: int,
                        bf: int):
    """Grid (f-blocks j, groups g, k-blocks): x is one block fetched once
    per call, each non-empty group's weight block (g, k, j) is fetched
    once, and the [rows, bf] output block is written once per j."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(fp // bf, G, dp // bk),
        in_specs=[
            pl.BlockSpec((rows, dp), lambda j, g, k, *_: (0, 0),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((1, bk, bf), lambda j, g, k, gs, ge, wg, wk: (
                wg[g], jnp.where(wk[g] < 0, k, wk[g]), j)),
        ],
        out_specs=pl.BlockSpec((rows, bf), lambda j, g, k, *_: (0, j)),
        scratch_shapes=[pltpu.VMEM((rows, bf), jnp.float32)],
    )


def _gmm_resident_kernel(gs_ref, ge_ref, wg_ref, wk_ref, x_ref, w_ref, o_ref,
                         acc_ref, *, bk: int, window: int, sub: int):
    g = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((g == 0) & (k == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start, end = gs_ref[g], ge_ref[g]

    @pl.when(end > start)
    def _():
        w = w_ref[0]                                # [bk, bf]
        col = pl.multiple_of(k * bk, bk)
        first = (start // sub) * sub
        last = acc_ref.shape[0] - window            # the last window in x

        def body(i, carry):
            # window i covers rows [r, r + window); the last one is pulled
            # back inside x, and the rows it shares with window i - 1 are
            # masked out with the neighbouring groups' rows
            r = first + i * window
            c = pl.multiple_of(jnp.minimum(r, last), sub)
            p = jnp.dot(x_ref[pl.ds(c, window), pl.ds(col, bk)], w,
                        preferred_element_type=jnp.float32)
            rows = c + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
            mask = (rows >= jnp.maximum(start, r)) & (rows < end)
            acc_ref[pl.ds(c, window), :] += jnp.where(mask, p, 0.0)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(end - first, window), body, 0)

    @pl.when((g == pl.num_programs(1) - 1) & (k == pl.num_programs(2) - 1))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm_resident(x, w, group_sizes, *, bk: int, bf: int, interpret: bool):
    rows, dp = x.shape
    G, _, fp = w.shape
    sub = _sublane_rows(x.dtype)
    kernel = functools.partial(_gmm_resident_kernel, bk=bk, sub=sub,
                               window=min(_WINDOW_ROWS, rows))
    return pl.pallas_call(
        kernel,
        grid_spec=_resident_grid_spec(rows, dp, G, fp, bk, bf),
        out_shape=jax.ShapeDtypeStruct((rows, fp), x.dtype),
        interpret=interpret,
        name="gmm_resident",
    )(*_resident_metadata(group_sizes, dp // bk), x, w)


def gmm(x, w, group_sizes, *, block_t: int = 128, block_k: int = 512,
        block_f: int = 1024, interpret: bool = False):
    """Grouped matmul over group-sorted rows: the first ``group_sizes[0]``
    rows of x [T, d] belong to group 0, and so on; w [G, d, f].

    ``group_sizes`` may be traced, contain zeros, and need not be multiples
    of any block; ragged dispatch needs NO per-group padding. Rows beyond
    ``sum(group_sizes)`` (receive-buffer slack) produce unspecified output;
    callers must never read them. Returns [T, f].

    Two schedules compute the same product, each row accumulated in fp32
    over the k-blocks of ``block_k`` in order; the call's shapes choose:

    - resident (``gmm_resident``), when x, the output block, the
      accumulator and the weight block fit ``_RESIDENT_VMEM_BYTES``: x is
      read once, each non-empty group's weights once, and each step
      multiplies only its group's rows (the serve step's decode calls);
    - tiled (``gmm_tiled``) otherwise: row blocks of ``block_t``, each
      boundary block revisited once per overlapping group with a row mask,
      so a straddling group's weights and every row block are streamed
      again per visit (training, prefill, large EP receive buffers).

    The output width is tiled by ``block_f`` so the weight block and the
    accumulator stay in the default scoped VMEM at any expert width; the f
    axis is outermost in both grids."""
    T, d = x.shape
    G, _, f = w.shape
    if T == 0:
        return jnp.zeros((0, f), x.dtype)
    sub = _sublane_rows(x.dtype)
    rows = -(-T // sub) * sub
    bk = min(block_k, d)
    bf = min(block_f, f)
    pad_k, pad_f = (-d) % bk, (-f) % bf
    if pad_k or pad_f:
        w = jnp.pad(w, ((0, 0), (0, pad_k), (0, pad_f)))
    resident = _resident_vmem_bytes(rows, d + pad_k, bk, bf,
                                    x.dtype.itemsize) <= _RESIDENT_VMEM_BYTES
    bt = min(block_t, rows)
    pad_t = rows - T if resident else (-T) % bt
    if pad_t or pad_k:
        x = jnp.pad(x, ((0, pad_t), (0, pad_k)))
    if resident:
        out = _gmm_resident(x, w, group_sizes, bk=bk, bf=bf,
                            interpret=interpret)
    else:
        out = _gmm_tiled(x, w, group_sizes, bt=bt, bk=bk, bf=bf,
                         interpret=interpret)
    return out[:T, :f]

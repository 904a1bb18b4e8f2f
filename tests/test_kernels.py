"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import moe_gmm, ref
from repro.kernels.flash_attention import (
    flash_attention_decode,
    flash_attention_prefill,
)
from repro.kernels.moe_gmm import fused_moe_ffn, gmm
from repro.kernels.topk_router import topk_router

TOL = dict(rtol=3e-2, atol=3e-2)      # bf16: 1-2 ulp accumulation-order noise
TOL32 = dict(rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("T,E,R,k", [(64, 8, 2, 2), (100, 16, 3, 4),
                                     (256, 256, 4, 8), (7, 4, 1, 1)])
def test_topk_router_sweep(T, E, R, k):
    key = jax.random.key(T + E)
    logits = jax.random.normal(key, (T, E), jnp.float32)
    e2s = jax.random.randint(jax.random.fold_in(key, 1), (E, R), 0, 64)
    rc = jax.random.randint(jax.random.fold_in(key, 2), (E,), 1, R + 1)
    rc = rc.at[0].set(0)  # one unreachable expert
    tid = jnp.arange(T)
    got = topk_router(logits, e2s, rc, tid, top_k=k, interpret=True)
    want = ref.topk_router_ref(logits, e2s, rc.astype(jnp.int32), tid, top_k=k)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               **TOL32)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,R,d,de,act,gated", [
    (2, 64, 128, 256, "swiglu", True),
    (4, 33, 64, 96, "gelu", False),
    (1, 128, 256, 128, "relu2", False),
])
def test_fused_moe_ffn_sweep(S, R, d, de, act, gated, dtype):
    key = jax.random.key(S * R)
    x = jax.random.normal(key, (S, R, d), jnp.float32).astype(dtype)
    wi = (jax.random.normal(jax.random.fold_in(key, 1), (S, d, de))
          / np.sqrt(d)).astype(dtype)
    wg = ((jax.random.normal(jax.random.fold_in(key, 2), (S, d, de))
           / np.sqrt(d)).astype(dtype) if gated else None)
    wo = (jax.random.normal(jax.random.fold_in(key, 3), (S, de, d))
          / np.sqrt(de)).astype(dtype)
    got = fused_moe_ffn(x, wi, wo, wg, activation=act, block_t=32,
                        block_f=64, interpret=True)
    want = ref.fused_moe_ffn_ref(x, wi, wo, wg, activation=act)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.fixture(params=["resident", "tiled"])
def schedule(request, monkeypatch):
    """Force one of gmm's two schedules whatever the call's shapes; the
    resident one walks 16-row windows, so small cases take several."""
    if request.param == "tiled":
        monkeypatch.setattr(moe_gmm, "_RESIDENT_VMEM_BYTES", 0)
    else:
        monkeypatch.setattr(moe_gmm, "_RESIDENT_VMEM_BYTES", 2**62)
        monkeypatch.setattr(moe_gmm, "_WINDOW_ROWS", 16)
    return request.param


@pytest.mark.parametrize("sizes", [
    # block-multiple sizes (the original contract)
    [64, 32, 0, 96], [32, 32, 32, 32], [0, 0, 128, 0],
    # ragged: zero-size groups and non-multiple-of-block_t boundaries
    [5, 17, 0, 30], [1, 0, 63], [0, 0, 0, 7], [3], [129, 31, 40],
    [31, 1, 1, 31],
])
def test_gmm_sweep(sizes, schedule):
    G, d, f = len(sizes), 64, 48
    T = int(sum(sizes))
    key = jax.random.key(T + G)
    x = jax.random.normal(key, (T, d), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (G, d, f)) / np.sqrt(d)
    got = gmm(x, w, jnp.asarray(sizes), block_t=32, block_k=32,
              interpret=True)
    want = ref.gmm_ref(x, w, jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sizes", [[5, 17, 0, 30], [40, 0, 0, 9]])
def test_gmm_tiled_output_width(sizes, dtype, schedule):
    """Several output-width blocks (block_f < f, f not a block multiple)
    with boundary row blocks shared by two groups; bf16 rows round the row
    block up to the 16-row sublane packing."""
    G, d, f = len(sizes), 64, 80
    T = int(sum(sizes))
    key = jax.random.key(T + f)
    x = jax.random.normal(key, (T, d), jnp.float32).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (G, d, f))
         / np.sqrt(d)).astype(dtype)
    got = gmm(x, w, jnp.asarray(sizes), block_t=16, block_k=32, block_f=32,
              interpret=True)
    want = ref.gmm_ref(x, w, jnp.asarray(sizes))
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_gmm_receive_buffer_slack_rows(schedule):
    """Rows past sum(group_sizes) (static receive-buffer slack in the ragged
    dispatch) are unspecified but must not corrupt the real rows."""
    sizes = [10, 0, 12]
    T_buf, d, f = 64, 32, 24
    total = sum(sizes)
    key = jax.random.key(7)
    x = jax.random.normal(key, (T_buf, d), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, d, f)) / np.sqrt(d)
    got = gmm(x, w, jnp.asarray(sizes), block_t=16, block_k=32,
              interpret=True)
    want = ref.gmm_ref(x[:total], w, jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(got)[:total], np.asarray(want),
                               **TOL32)


def test_gmm_traced_group_sizes_under_jit(schedule):
    """group_sizes may be a traced value (the size exchange's output): one
    compiled executable serves every load distribution."""
    d, f, G = 48, 32, 4
    key = jax.random.key(11)
    x = jax.random.normal(key, (52, d), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (G, d, f)) / np.sqrt(d)
    fn = jax.jit(lambda x, w, s: gmm(x, w, s, block_t=32, block_k=32,
                                     interpret=True))
    for sizes in ([5, 17, 0, 30], [52, 0, 0, 0], [13, 13, 13, 13]):
        got = fn(x, w, jnp.asarray(sizes))
        want = ref.gmm_ref(x, w, jnp.asarray(sizes))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)


def test_gmm_one_group_holds_every_row(schedule):
    """Every row in one group, several row blocks (and windows) long."""
    sizes = [0, 300, 0]
    d, f = 64, 48
    key = jax.random.key(300)
    x = jax.random.normal(key, (300, d), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, d, f)) / np.sqrt(d)
    got = gmm(x, w, jnp.asarray(sizes), block_t=32, block_k=32,
              interpret=True)
    want = ref.gmm_ref(x, w, jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)


def test_gmm_bf16_groups_off_the_sublane_multiple(schedule):
    """bf16 groups whose sizes and starts are not multiples of the 16-row
    sublane packing: windows and row blocks shared by neighbours."""
    sizes = [7, 23, 0, 41, 9, 1, 15]
    G, d, f = len(sizes), 64, 48
    T = int(sum(sizes))
    key = jax.random.key(96)
    x = jax.random.normal(key, (T, d), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (G, d, f))
         / np.sqrt(d)).astype(jnp.bfloat16)
    got = gmm(x, w, jnp.asarray(sizes), block_t=16, block_k=32,
              interpret=True)
    want = ref.gmm_ref(x, w, jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL)


@pytest.mark.parametrize("sizes", [
    [0, 5, 0, 0, 17, 30, 0], [0, 0, 9], [12, 0, 0, 0], [3, 3, 3],
])
def test_gmm_resident_reads_weights_once_per_live_group(sizes):
    """The resident schedule's index maps, evaluated over its grid: each
    non-empty group's weight blocks are fetched once (a block index that
    repeats the previous step's issues no DMA), empty groups fetch
    nothing, and x is one block for the whole call."""
    nk, nf = 3, 2
    spec = moe_gmm._resident_grid_spec(rows=64, dp=32 * nk, G=len(sizes),
                                       fp=16 * nf, bk=32, bf=16)
    meta = [np.asarray(a) for a in
            moe_gmm._resident_metadata(jnp.asarray(sizes), nk)]
    x_spec, w_spec = spec.in_specs
    steps = list(itertools.product(*map(range, spec.grid)))

    def blocks(bspec):
        return [tuple(int(v) for v in bspec.index_map(*s, *meta))
                for s in steps]

    w_blocks = blocks(w_spec)
    fetches = 1 + sum(a != b for a, b in zip(w_blocks, w_blocks[1:]))
    live = [g for g, n in enumerate(sizes) if n]
    assert fetches == len(live) * nk * nf
    assert set(w_blocks) == set(itertools.product(live, range(nk),
                                                  range(nf)))
    assert set(blocks(x_spec)) == {(0, 0)}


def test_gmm_schedule_follows_shapes():
    """Mixtral-8x22B's decode calls (256 rows) take the resident schedule
    at both projections; a large receive buffer takes the tiled one."""
    def kernel(rows, d_in, d_out):
        x = jax.ShapeDtypeStruct((rows, d_in), jnp.bfloat16)
        w = jax.ShapeDtypeStruct((16, d_in, d_out), jnp.bfloat16)
        gs = jax.ShapeDtypeStruct((16,), jnp.int32)
        text = str(jax.make_jaxpr(gmm)(x, w, gs))
        return {n for n in ("gmm_resident", "gmm_tiled") if n in text}

    for d_in, d_out in [(6144, 16384), (16384, 6144)]:
        assert kernel(256, d_in, d_out) == {"gmm_resident"}
        assert kernel(8192, d_in, d_out) == {"gmm_tiled"}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,H,KV,hd,window", [
    (1, 128, 4, 4, 64, 0),
    (2, 256, 8, 2, 64, 0),
    (1, 256, 4, 4, 32, 64),   # sliding window
])
def test_flash_prefill_sweep(B, Sq, H, KV, hd, window, dtype):
    key = jax.random.key(Sq + H)
    q = jax.random.normal(key, (B, Sq, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (B, Sq, KV, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, Sq, KV, hd), jnp.float32).astype(dtype)
    got = flash_attention_prefill(q, k, v, window=window, block_q=64,
                                  block_k=64, interpret=True)
    want = ref.flash_attention_prefill_ref(q, k, v, window=window)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("B,H,KV,hd,W", [(2, 8, 4, 64, 256), (3, 4, 1, 32, 128)])
def test_flash_decode_sweep(B, H, KV, hd, W):
    key = jax.random.key(B * H)
    q = jax.random.normal(key, (B, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, W, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, W, KV, hd))
    lengths = jnp.asarray(
        np.random.RandomState(0).randint(1, W - 1, size=(B,)))
    got = flash_attention_decode(q, k, v, lengths, block_k=64, interpret=True)
    want = ref.flash_attention_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL32)


def test_router_matches_model_path():
    """The kernel implements exactly models/moe elastic_route semantics."""
    from repro.core import elastic_route, make_initial_membership
    t = make_initial_membership(4, 8, 2)
    ms = t.to_device()
    T, k = 33, 2
    logits = jax.random.normal(jax.random.key(5), (T, 8), jnp.float32)
    tid = jnp.arange(T)
    e1, w1, s1 = elastic_route(logits, ms, k, tid)
    e2, w2, s2 = topk_router(logits, ms.expert_to_slot, ms.replica_count,
                             tid, top_k=k, interpret=True)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), **TOL32)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))

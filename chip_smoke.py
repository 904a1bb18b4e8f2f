#!/usr/bin/env python3
"""Chip smoke test: the serving main path at Mixtral-8x22B's published widths
on TPU, end to end through a rank failure, repair and rejoin.

    python chip_smoke.py [--seed N]            # one chip
    python chip_smoke.py --chips 4 [--seed N]  # the EP path across four chips

One chip: ``mixtral-8x22b`` at published widths (d_model 6144, 48 heads /
8 KV heads of 128, 8 experts of d_expert 16384, top-2, vocab 32768, SwiGLU)
with the depth cut to ONE layer (every layer is MoE, so one layer is a whole
period of the layer pattern): 16 expert slots (8 experts x R=2) in bf16 hold
9.66 GB, and two layers would not fit the chip. The instance is built the way
``repro.launch.serve`` builds it, then serves 8 requests (16-token prompts
from ``--seed``, 16 new tokens each) while rank 3 fails, is repaired around
and rejoins. This runs once with the dense dispatch and once with the ragged
(dropless, ``gmm`` kernel) dispatch; only one instance fits, so the first is
freed before the second is built.

Four chips (``--chips 4``): one full-width MoE layer over a (4, 1)
("data", "model") mesh, 4 ranks x 4 slots, the weights drawn straight into
their EP shardings. The shard_map island (dense all-to-all and ragged
exchange) is compared with the local path, healthy and after rank 2 leaves.

Correctness is checked as relative error, max|a - b| / max|b|, under
``TOL``: bf16 carries an 8-bit mantissa (eps = 7.8e-3), and the paths
compared round the [T, 16384] expert hidden to bf16 after different
accumulation blockings (XLA einsum vs the gmm kernel, one device vs the
shard_map island), so agreement is to a few bf16 ulps of the largest value,
while a wrong expert or a lost slot moves the output by O(1).

Random weights from ``--seed``. Any failed check raises. The last line of
stdout is one JSON object naming the device. Exits nonzero, printing no
result, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
TOL = 2e-2                       # relative to the largest magnitude, see above
ARCH = "mixtral-8x22b"
WORLD, SLOTS_PER_RANK = 16, 1    # the published deployment: EP=16, R=2
BATCH, PROMPT, MAX_NEW = 8, 16, 16
FAIL_RANK, FAIL_AT = 3, 1.0      # sim seconds: about 20 steps in
EP_WORLD, EP_SPR = 4, 4          # four chips x 4 slots = 16 slots
EP_TOKENS = 64


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def require_tpu(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < n:
        sys.exit(f"chip_smoke: needs {n} TPU chips, found {len(devs)}")
    return devs


def collapse(kinds) -> list[str]:
    """Timeline kinds with consecutive repeats folded (kind x n)."""
    out: list[list] = []
    for k in kinds:
        if out and out[-1][0] == k:
            out[-1][1] += 1
        else:
            out.append([k, 1])
    return [k if n == 1 else f"{k} x{n}" for k, n in out]


# ---------------------------------------------------------------------------
# One chip: the serving main path through fail -> repair -> rejoin
# ---------------------------------------------------------------------------


def serve_phase(cfg, dispatch: str, seed: int, dtype) -> tuple[dict, dict]:
    """Build one instance, probe its logits on fixed decode inputs, serve
    the traffic through a rank failure and rejoin, probe again after each
    commit. Returns (report, probe logits by moment)."""
    import jax
    import jax.numpy as jnp

    from repro.core.topology import FaultDomainTree
    from repro.core.transitions import moe_slot_leaves
    from repro.launch.serve import build_frontend
    from repro.models.model import init_caches

    max_len = PROMPT + MAX_NEW + 8
    t0 = time.perf_counter()
    rt, eng, fe = build_frontend(
        cfg, world=WORLD, slots_per_rank=SLOTS_PER_RANK,
        topology=FaultDomainTree(WORLD, ranks_per_host=cfg.ranks_per_host,
                                 hosts_per_switch=cfg.hosts_per_switch),
        seed=seed, dtype=dtype, dispatch=dispatch, max_batch=BATCH,
        max_len=max_len)
    jax.block_until_ready(rt.params)
    build_s = time.perf_counter() - t0

    rng = np.random.RandomState(seed)
    probe_tokens = jnp.asarray(rng.randint(1, cfg.vocab_size,
                                           size=(BATCH, 1)), jnp.int32)
    probe_lengths = jnp.zeros((BATCH,), jnp.int32)

    def probe_args():
        return (rt.params, init_caches(cfg, BATCH, max_len, dtype),
                rt.membership, probe_tokens, probe_lengths)

    # the serve step's compile is set-up time: compile it ahead, then the
    # engine's own calls load it from the persistent compile cache
    t0 = time.perf_counter()
    compiled = eng._step.lower(*probe_args()).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()

    def probe():
        # one decode step of the engine's own compiled executable on fresh
        # caches: the same inputs before and after every commit
        _, logits, _ = eng._step(*probe_args())
        return np.asarray(logits, np.float32)

    logits = {"before": probe()}

    prompts = rng.randint(1, cfg.vocab_size, size=(BATCH, PROMPT))
    for prompt in prompts:
        fe.submit(prompt.tolist(), max_new=MAX_NEW)
    rt.injector.inject_at(FAIL_AT, [FAIL_RANK])

    def commits(kind):
        return [ev.detail["epoch"] for ev in rt.timeline
                if ev.kind == "membership_commit"
                and ev.detail.get("transition") == kind]

    def moved(before):
        # slots live on both sides of a commit whose expert changed: the
        # ones the repair rewrote
        return int(((before != rt.table.slot_to_expert) & (before >= 0)
                    & (rt.table.slot_to_expert >= 0)).sum())

    def replicas_consistent() -> bool:
        # every slot of one expert holds the same bytes, on every leaf
        leaves = moe_slot_leaves(cfg, rt.params).values()
        return all(bool(jnp.array_equal(w[:, s], w[:, slots[0]]))
                   for slots in rt.table.expert_to_slots().values()
                   for s in slots[1:] for w in leaves)

    placement = rt.table.slot_to_expert.copy()
    t0 = time.perf_counter()
    steps = 0
    while not commits("fault"):
        check(steps < 10_000, "no fault commit after 10000 steps")
        fe.step()
        steps += 1
    logits["after_fault"] = probe()
    moved_fault = moved(placement)
    placement = rt.table.slot_to_expert.copy()
    fe.run(until=600.0, max_steps=20_000)
    serve_s = time.perf_counter() - t0
    logits["after_rejoin"] = probe()
    moved_join = moved(placement) + int(placement[FAIL_RANK] < 0)

    st = eng.sched.stats
    m = fe.metrics()
    mem = jax.devices()[0].memory_stats() or {}
    report = {
        "phase": dispatch,
        "finished": st.finished, "failed": st.failed,
        "tokens_out": st.tokens_out,
        "error_events": m["error_events"],
        "stream_violations": len(fe.stream_violations()),
        "compile_count": eng.compile_count(),
        "epoch": rt.epoch,
        "fault_commit_epochs": commits("fault"),
        "join_commit_epochs": commits("join"),
        "active_ranks": int(rt.table.active_mask.sum()),
        "slots_rewritten_fault": moved_fault,
        "slots_rewritten_join": moved_join,
        "replicas_consistent": replicas_consistent(),
        "timeline": collapse(ev.kind for ev in rt.timeline),
        "setup_build_params_s": build_s,
        "setup_step_compile_s": compile_s,
        "serve_wall_s": serve_s,
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "bytes_limit": mem.get("bytes_limit"),
        "tpu_custom_call": "tpu_custom_call" in hlo,
    }
    # free the instance: only one full-width instance fits the chip
    del rt, eng, fe, compiled
    gc.collect()
    for a in jax.live_arrays():
        if a.nbytes > 1 << 20:
            a.delete()
    return report, logits


def check_serve(r: dict) -> None:
    ph = r["phase"]
    check(r["finished"] == BATCH and r["failed"] == 0,
          f"{ph}: {r['finished']}/{BATCH} finished, {r['failed']} failed")
    check(r["error_events"] == 0, f"{ph}: {r['error_events']} error events")
    check(r["stream_violations"] == 0,
          f"{ph}: {r['stream_violations']} stream violations")
    check(r["compile_count"] == 1,
          f"{ph}: serve step compiled {r['compile_count']} times")
    check(bool(r["fault_commit_epochs"]) and bool(r["join_commit_epochs"]),
          f"{ph}: missing fault or join commit: {r['timeline']}")
    check(r["epoch"] >= max(r["join_commit_epochs"])
          > max(r["fault_commit_epochs"]),
          f"{ph}: epoch {r['epoch']} did not advance past the fault and "
          f"join commits")
    check(r["active_ranks"] == WORLD, f"{ph}: rank {FAIL_RANK} not rejoined")
    check(r["replicas_consistent"],
          f"{ph}: replicas of one expert differ after the repairs")
    check(r["peak_bytes_in_use"] < r["bytes_limit"],
          f"{ph}: peak {r['peak_bytes_in_use']} >= {r['bytes_limit']}")
    if ph == "ragged":
        check(r["tpu_custom_call"],
              "ragged: no tpu_custom_call in the serve step (gmm fell back)")


def check_logits(dense: dict, ragged: dict) -> dict:
    errs = {}
    for name, lg in (("dense", dense), ("ragged", ragged)):
        for moment in ("after_fault", "after_rejoin"):
            errs[f"{name}_{moment}_vs_before"] = rel_err(lg[moment],
                                                         lg["before"])
    errs["ragged_vs_dense"] = rel_err(ragged["before"], dense["before"])
    for k, v in errs.items():
        check(np.isfinite(v) and v <= TOL, f"logits {k}: {v} > {TOL}")
    for name, lg in (("dense", dense), ("ragged", ragged)):
        for moment, a in lg.items():
            check(a.shape == (BATCH, dense["before"].shape[1])
                  and bool(np.isfinite(a).all()),
                  f"{name} {moment} logits not finite or mis-shaped")
    return errs


def one_chip(seed: int) -> None:
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.elastic_moe import EPContext

    cfg = dataclasses.replace(get_config(ARCH), num_layers=1)
    m = cfg.moe
    print(f"config: {ARCH} at published widths, depth cut to num_layers=1 "
          f"(of {get_config(ARCH).num_layers}): d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads}x{cfg.head_dim} "
          f"experts={m.num_experts} top_k={m.top_k} d_expert={m.d_expert} "
          f"vocab={cfg.vocab_size} act={cfg.activation}; {WORLD} slots "
          f"(R=2), bf16 params and caches", flush=True)
    # dense drops nothing at this batch: a slot receives at most one pair
    # per token, and the per-slot capacity is at least the batch
    cap = EPContext(axis_names=(), world=1, slots_per_rank=WORLD,
                    capacity_factor=cfg.capacity_factor).capacity(BATCH,
                                                                  m.top_k)
    check(cap >= BATCH, f"dense capacity {cap} < batch {BATCH}: may drop")
    print(f"dense capacity per slot {cap} >= {BATCH} tokens: "
          f"dropped_fraction == 0", flush=True)
    logits = {}
    for dispatch in ("dense", "ragged"):
        report, logits[dispatch] = serve_phase(cfg, dispatch, seed,
                                               jnp.bfloat16)
        print(json.dumps(report), flush=True)
        check_serve(report)
    errs = check_logits(logits["dense"], logits["ragged"])
    print(json.dumps({"logit_rel_err": errs, "tol": TOL}), flush=True)


# ---------------------------------------------------------------------------
# Four chips: the EP shard_map island vs the local path
# ---------------------------------------------------------------------------


def ep_phase(cfg, mesh, seed: int, dtype) -> dict:
    """Distributed moe_apply (dense and ragged) against the local path,
    healthy and after rank 2 leaves, one compiled function per mode."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import elastic_route, eplb_place, make_initial_membership
    from repro.core.elastic_moe import EPContext
    from repro.models.moe import (
        MoEDeployment,
        local_deployment,
        moe_apply,
        moe_layer_init,
        moe_param_specs,
    )

    world = mesh.shape["data"]
    num_slots = world * EP_SPR
    table = make_initial_membership(world, cfg.moe.num_experts, EP_SPR)
    dep = MoEDeployment(
        ep=EPContext(axis_names=("data",), world=world,
                     slots_per_rank=EP_SPR, capacity_factor=8.0),
        tp_axes=("model",), mesh=mesh)
    # the local path is the reference: dense with capacity for every pair
    # (dropped_fraction == 0 is checked), partitioned by XLA over the same
    # sharded weights
    dep_local = local_deployment(num_slots, capacity_factor=8.0)

    s2e = table.slot_to_expert.copy()

    def init(key):
        return moe_layer_init(key, cfg, num_slots, s2e, dtype)

    shapes = jax.eval_shape(init, jax.random.key(seed))
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), moe_param_specs(dep, shapes),
        is_leaf=lambda x: isinstance(x, P))
    t0 = time.perf_counter()
    # drawn under jit straight into the EP shardings: no device holds the
    # whole layer at any point
    p = jax.jit(init, out_shardings=shardings)(jax.random.key(seed))
    x = jax.jit(lambda k: jax.random.normal(k, (EP_TOKENS, cfg.d_model),
                                            dtype),
                out_shardings=NamedSharding(mesh, P("data", None)))(
        jax.random.key(seed + 1))
    jax.block_until_ready((p, x))
    init_s = time.perf_counter() - t0
    per_dev = {str(d.id): int(sum(s.data.nbytes
                                  for leaf in jax.tree_util.tree_leaves(p)
                                  for s in leaf.addressable_shards
                                  if s.device == d))
               for d in mesh.devices.flat}
    check(max(per_dev.values()) <= 1.01 * sum(per_dev.values()) / world,
          f"params not spread over the EP ranks: {per_dev}")

    fns = {
        "local": jax.jit(lambda x, p, m: moe_apply(cfg, p, x, m, dep_local)),
        "dense": jax.jit(lambda x, p, m: moe_apply(cfg, p, x, m, dep)),
        "ragged": jax.jit(lambda x, p, m: moe_apply(
            cfg, p, x, m, dataclasses.replace(dep, dispatch="ragged"))),
    }
    ms_healthy = table.to_device()
    table.deactivate(2)
    res = eplb_place(cfg.moe.num_experts, world, EP_SPR, table.active_mask,
                     prev_slot_to_expert=table.slot_to_expert)
    check(not res.infeasible, f"degraded placement infeasible: {res.reason}")
    table.set_placement(res.slot_to_expert)
    ms_degraded = table.to_device()

    report = {"mesh": dict(mesh.shape), "slots": num_slots,
              "setup_init_s": init_s, "param_bytes_per_device": per_dev}
    for label, ms in (("healthy", ms_healthy), ("degraded", ms_degraded)):
        t0 = time.perf_counter()
        out = {k: fn(x, p, ms) for k, fn in fns.items()}
        jax.block_until_ready(out)
        report[f"{label}_wall_s"] = time.perf_counter() - t0
        ref, aux = out["local"]
        check(float(aux["dropped_fraction"]) == 0.0,
              f"{label}: local reference dropped tokens")
        check(bool(np.isfinite(np.asarray(ref, np.float32)).all()),
              f"{label}: local output not finite")
        for mode in ("dense", "ragged"):
            y, a = out[mode]
            check(float(a["dropped_fraction"]) == 0.0,
                  f"{label} {mode}: dropped tokens")
            err = rel_err(y, ref)
            report[f"{label}_{mode}_rel_err"] = err
            check(err <= TOL, f"{label} {mode} dist vs local: {err} > {TOL}")
    # degraded routing never targets the departed rank's slots
    _, _, slots = elastic_route(x @ p["router"], ms_degraded,
                                cfg.moe.top_k, jnp.arange(EP_TOKENS))
    check(not np.isin(np.asarray(slots) // EP_SPR, [2]).any(),
          "degraded routing still targets rank 2")
    hlo = {}
    for mode, coll in (("dense", "all-to-all"),
                       ("ragged", "ragged-all-to-all")):
        fn = fns[mode]
        report[f"{mode}_compiles"] = fn._cache_size()
        check(fn._cache_size() == 1,
              f"{mode}: {fn._cache_size()} compilations across the "
              f"membership change")
        hlo[mode] = fn.lower(x, p, ms_degraded).compile().as_text()
        report[f"{mode}_hlo_{coll}"] = coll in hlo[mode]
        check(coll in hlo[mode], f"{mode}: no {coll} in the compiled HLO")
    report["ragged_tpu_custom_call"] = "tpu_custom_call" in hlo["ragged"]
    check(report["ragged_tpu_custom_call"],
          "ragged: no tpu_custom_call in the distributed step")
    report["peak_bytes_in_use"] = {
        str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in mesh.devices.flat}
    return report


def four_chips(seed: int, devices) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType

    from repro.configs import get_config

    cfg = get_config(ARCH)
    mesh = jax.make_mesh((EP_WORLD, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=devices[:EP_WORLD])
    print(f"config: {ARCH} MoE layer at published widths (d_model="
          f"{cfg.d_model}, {cfg.moe.num_experts} experts of d_expert "
          f"{cfg.moe.d_expert}, top-{cfg.moe.top_k}), {EP_WORLD} ranks x "
          f"{EP_SPR} slots in bf16, {EP_TOKENS} tokens", flush=True)
    report = ep_phase(cfg, mesh, seed, jnp.bfloat16)
    print(json.dumps(report), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the EP path across four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the weights, prompts and probe inputs")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: the repro package is not next to this "
                 f"script ({ROOT / 'src'})")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = pathlib.Path(enable_compile_cache())
    warm = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"compile cache: {cache} ({warm} entries at start)", flush=True)
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)

    if args.chips == 4:
        four_chips(args.seed, devices)
    else:
        one_chip(args.seed)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())

"""Serving driver: client sessions + planned transitions through the
serving frontend (``repro.serving.api``) — requests stream through
``ServingFrontend.submit``; drains/scales are JSON commands on the
``AdminGateway``.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b --smoke \
      --world 8 --requests 32 --fail-rank 3 --fail-at 2.0

  # rolling maintenance: drain rank 2 at t=2, bring it back at t=10
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b --smoke \
      --drain-rank 2 --drain-at 2.0 --undrain-at 10.0

  # elastic shrink/regrow riding the deferred-join path
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b --smoke \
      --scale-down-rank 6 --scale-down-rank 7 --scale-down-at 2.0 \
      --scale-up-at 12.0

  # one-off admin command against a fresh instance (JSON in, JSON out)
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b --smoke \
      --requests 0 --admin '{"cmd": "status"}'

  # off-box: HTTP/SSE on an ephemeral port + admin socket, until ^C
  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b --smoke \
      --requests 0 --http 0 --admin-socket /tmp/repro-admin.sock
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np


def build_frontend(cfg, *, world: int, slots_per_rank: int = 1,
                   topology=None, seed: int = 0, dtype=jnp.float32,
                   dispatch=None, detect_timeout=None, max_batch: int = 8,
                   max_len: int = 32, fixed_membership: bool = False,
                   kv_pool=None, queue_policy: str = "fifo",
                   max_queue_depth=None, tenant_quotas=None):
    """One serving instance: membership table (``world`` ranks x
    ``slots_per_rank`` slots), params drawn from ``seed`` in ``dtype``,
    the elastic runtime, the engine (KV caches in ``dtype``) and its
    frontend. ``topology`` defaults to the config's host/switch layout.
    Returns (runtime, engine, frontend)."""
    from repro.core import make_initial_membership
    from repro.models import init_params
    from repro.runtime.elastic import ElasticEPRuntime
    from repro.serving.api import ServingFrontend
    from repro.serving.engine import ServingEngine

    E = cfg.moe.num_experts if cfg.is_moe else 1
    table = make_initial_membership(world, E, slots_per_rank,
                                    topology=topology)
    params = init_params(cfg, jax.random.key(seed), dtype,
                         table.slot_to_expert, table.num_slots)
    rt = ElasticEPRuntime(cfg, params, table, dispatch=dispatch)
    if detect_timeout is not None:
        rt.detector.timeout_s = detect_timeout
    eng = ServingEngine(rt, max_batch=max_batch, max_len=max_len,
                        dtype=dtype, fixed_membership=fixed_membership,
                        kv_pool=kv_pool, queue_policy=queue_policy)
    fe = ServingFrontend(eng, max_queue_depth=max_queue_depth,
                         tenant_quotas=tenant_quotas or {})
    return rt, eng, fe


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--slots-per-rank", type=int, default=1)
    ap.add_argument("--hosts", type=int, default=None,
                    help="fault-domain layout: number of hosts the ranks "
                    "are packed onto (overrides --ranks-per-host)")
    ap.add_argument("--ranks-per-host", type=int, default=None,
                    help="fault-domain layout: ranks per host (default: "
                    "the arch config's ranks_per_host)")
    ap.add_argument("--hosts-per-switch", type=int, default=None,
                    help="fault-domain layout: hosts per switch (default: "
                    "the arch config's hosts_per_switch)")
    ap.add_argument("--detect-timeout", type=float, default=None,
                    help="heartbeat timeout (sim seconds) before an "
                    "unreachable rank is confirmed failed; reachable-but-"
                    "silent ranks get timeout * suspect-grace")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--fail-rank", type=int, action="append", default=None)
    ap.add_argument("--fail-at", type=float, default=None)
    ap.add_argument("--drain-rank", type=int, action="append", default=None,
                    help="rank(s) to drain for planned maintenance")
    ap.add_argument("--drain-at", type=float, default=None)
    ap.add_argument("--undrain-at", type=float, default=None,
                    help="bring the drained rank(s) back at this time")
    ap.add_argument("--scale-down-rank", type=int, action="append",
                    default=None, help="rank(s) to decommission (elastic "
                    "shrink)")
    ap.add_argument("--scale-down-at", type=float, default=None)
    ap.add_argument("--scale-up-at", type=float, default=None,
                    help="re-add the scaled-down rank(s) (deferred join)")
    ap.add_argument("--rebalance-at", type=float, action="append",
                    default=None,
                    help="popularity rebalance: re-place expert replicas "
                    "against the tracked routing load at this time (rank-"
                    "less planned transition; repeatable)")
    ap.add_argument("--fixed-membership", action="store_true",
                    help="full-restart baseline instead of EEP (a "
                    "TransitionPolicy: planned drains become full restarts "
                    "too — the paper's point)")
    ap.add_argument("--dispatch", choices=["dense", "ragged"], default=None,
                    help="capacity-padded vs dropless size-exchange dispatch "
                    "(default: the arch config's dispatch_mode)")
    ap.add_argument("--kv-pool", choices=["slot", "paged"], default=None,
                    help="KV cache layout: contiguous per-request slots "
                    "(replay on drain) vs paged blocks with live migration "
                    "(default: the arch config's kv_pool, normally paged)")
    ap.add_argument("--kv-block-size", type=int, default=None,
                    help="tokens per KV page (paged pool only)")
    ap.add_argument("--prefix-cache", choices=["on", "off"], default=None,
                    help="override ArchConfig.prefix_cache (cross-session "
                    "prompt-prefix sharing over the paged pool)")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="admission control: reject submits past this queue "
                    "depth with a structured REJECTED event")
    ap.add_argument("--sched", choices=["fifo", "edf"], default="fifo",
                    help="queue ordering: FIFO or earliest-deadline-first "
                    "(stalled continuations always resume first)")
    ap.add_argument("--tenant-quota", action="append", default=None,
                    metavar="NAME=N", help="per-tenant cap on live streams "
                    "(repeatable), e.g. --tenant-quota free=8")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline (sim seconds from submit)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for params init and the request prompts — "
                    "same seed, same flags => identical run")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve POST /v1/generate as SSE wire frames on "
                    "this port (0 = ephemeral) instead of running the "
                    "inline request loop; runs until interrupted")
    ap.add_argument("--admin-socket", default=None, metavar="PATH",
                    help="serve the AdminGateway JSON protocol on this "
                    "unix socket (with --http)")
    ap.add_argument("--heartbeat-s", type=float, default=15.0,
                    help="SSE keepalive interval (wall seconds, --http)")
    ap.add_argument("--admin", action="append", default=None,
                    help="JSON admin command(s) to execute up front, e.g. "
                    "'{\"cmd\": \"drain\", \"ranks\": [2], \"at\": 5.0}'")
    ap.add_argument("--until", type=float, default=600.0)
    args = ap.parse_args(argv)

    import json

    from repro.configs import get_config
    from repro.core.topology import FaultDomainTree
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.kv_block_size is not None or args.prefix_cache is not None:
        import dataclasses
        repl = {}
        if args.kv_block_size is not None:
            repl["kv_block_size"] = args.kv_block_size
        if args.prefix_cache is not None:
            repl["prefix_cache"] = args.prefix_cache == "on"
        cfg = dataclasses.replace(cfg, **repl)
    rph = args.ranks_per_host or cfg.ranks_per_host
    if args.hosts is not None:
        rph = -(-args.world // args.hosts)     # pack ranks onto N hosts
    topology = FaultDomainTree(
        args.world, ranks_per_host=rph,
        hosts_per_switch=args.hosts_per_switch or cfg.hosts_per_switch)
    quotas = {}
    for spec in (args.tenant_quota or []):
        name, _, n = spec.partition("=")
        quotas[name] = int(n)
    rt, eng, fe = build_frontend(
        cfg, world=args.world, slots_per_rank=args.slots_per_rank,
        topology=topology, seed=args.seed, dispatch=args.dispatch,
        detect_timeout=args.detect_timeout, max_batch=args.max_batch,
        max_len=args.prompt_len + args.max_new + 8,
        fixed_membership=args.fixed_membership, kv_pool=args.kv_pool,
        queue_policy=args.sched, max_queue_depth=args.max_queue_depth,
        tenant_quotas=quotas)

    if args.http is not None:
        # off-box mode: everything below (inline submits, scheduled admin
        # convenience flags) is the in-process driver's business — the
        # wire serves clients and the admin socket serves operators
        if args.fail_at is not None and args.fail_rank:
            rt.injector.inject_at(args.fail_at, args.fail_rank)
        import asyncio

        from repro.serving.transport import ServingTransport
        tr = ServingTransport(fe, port=args.http,
                              admin_path=args.admin_socket,
                              heartbeat_s=args.heartbeat_s)

        def _ready(t):
            print(f"serving http://127.0.0.1:{t.http.port} "
                  f"(wire v1, admin socket: "
                  f"{args.admin_socket or 'disabled'})", flush=True)

        try:
            asyncio.run(tr.serve_forever(_ready))
        except KeyboardInterrupt:
            pass
        return

    rng = np.random.RandomState(args.seed)
    for _ in range(args.requests):
        prompt = rng.randint(1, cfg.vocab_size,
                             size=(args.prompt_len,)).tolist()
        fe.submit(prompt, max_new=args.max_new, deadline=args.deadline)
    if args.fail_at is not None and args.fail_rank:
        rt.injector.inject_at(args.fail_at, args.fail_rank)

    # planned transitions are admin-gateway commands: scheduled ("at") ops
    # fire when the sim clock crosses their time and commit at the next
    # step boundary; the frontend's run loop never exits while one is
    # pending. The convenience flags just render the JSON for you.
    commands = [json.loads(c) for c in (args.admin or [])]
    if args.drain_at is not None and args.drain_rank:
        commands.append({"cmd": "drain", "ranks": args.drain_rank,
                         "at": args.drain_at})
    if args.undrain_at is not None and args.drain_rank:
        commands.append({"cmd": "undrain", "ranks": args.drain_rank,
                         "at": args.undrain_at})
    if args.scale_down_at is not None and args.scale_down_rank:
        commands.append({"cmd": "scale_down", "ranks": args.scale_down_rank,
                         "at": args.scale_down_at})
    if args.scale_up_at is not None and args.scale_down_rank:
        commands.append({"cmd": "scale_up", "ranks": args.scale_down_rank,
                         "at": args.scale_up_at})
    for t in (args.rebalance_at or []):
        commands.append({"cmd": "rebalance", "at": t})
    for command in commands:
        resp = fe.admin.execute(command)
        print(f"admin> {json.dumps(command)}")
        print(f"admin< {json.dumps(resp, sort_keys=True)}")

    fe.run(until=args.until, max_steps=100_000)

    s = eng.sched.stats
    print(f"finished={s.finished} failed={s.failed} retried={s.retried} "
          f"preempted={s.preempted} suspended={s.suspended} "
          f"cancelled={s.cancelled} rejected={s.rejected} "
          f"tokens={s.tokens_out}")
    m = fe.metrics()
    print(f"client-perceived: ttft_p50={m['ttft_p50_s']}s "
          f"stall_p50={m['stall_p50_s']}s stall_p99={m['stall_p99_s']}s "
          f"stall_max={m['stall_max_s']}s goodput={m['goodput_tok_s']} tok/s "
          f"recomputed={m['tokens_recomputed']} "
          f"migrated={m['tokens_migrated']} "
          f"error_events={m['error_events']}")
    bad = fe.stream_violations()
    print(f"stream contract: {'OK (exactly-once, in-order)' if not bad else bad[:3]}")
    kvp = eng.kv.stats().get("prefix", {})
    print(f"prefix cache: enabled={eng.prefix_enabled} "
          f"hits={m['prefix_hits']} hit_rate={m['prefix_hit_rate']} "
          f"prefill_skipped={m['tokens_prefill_skipped']} "
          f"nodes={kvp.get('nodes', 0)} "
          f"shared_blocks={kvp.get('shared_blocks', 0)} "
          f"evictions={kvp.get('evictions', 0)}")
    print(f"serve-step compilations: {eng.compile_count()} (no recompile "
          f"across membership changes; dispatch={eng.dispatch})")
    print(f"membership epoch: {rt.epoch} (every transition committed "
          f"through MembershipTransaction; policy={rt.policy.name})")
    for ev in rt.timeline:
        print(f"  t={ev.t:8.2f}s {ev.kind} {ev.detail if ev.detail else ''}")


if __name__ == "__main__":
    main()

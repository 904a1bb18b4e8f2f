"""Client-storm driver: synthesize an open-loop workload and fire it at a
serving frontend — in-process on the SimClock, or over the HTTP/SSE wire
against a transport this command boots itself.

  # in-process: 8 req/s for 20 sim-seconds through a mid-storm fault
  PYTHONPATH=src python -m repro.launch.storm --arch mixtral-8x22b --smoke \
      --rate 8 --duration 20 --fail-rank 2 --fail-at 4.0 --seed 0

  # same workload over the wire (boots HTTP + admin socket, drives real
  # sockets, checks the ordering contract on the DECODED streams) and
  # fail the process if any client saw an error or a contract violation
  PYTHONPATH=src python -m repro.launch.storm --arch mixtral-8x22b --smoke \
      --rate 8 --duration 4 --fail-rank 2 --fail-at 1.0 --wire --check

  # multi-tenant SLO mix: paid traffic carries a deadline, free traffic
  # is quota-capped; EDF orders the queue by deadline
  PYTHONPATH=src python -m repro.launch.storm --arch mixtral-8x22b --smoke \
      --tenant paid:2.0:30.0 --tenant free:1.0::8 --sched edf

  # prefix-heavy storm: every arrival shares one of 2 per-tenant system
  # prompts (16 tokens = one KV block), exercising the cross-session
  # prefix cache; the scorecard carries hit-rate + skipped-prefill counts
  PYTHONPATH=src python -m repro.launch.storm --arch mixtral-8x22b --smoke \
      --max-len 32 --prefix-groups 2 --prefix-len 16

  # drive an ALREADY RUNNING server (e.g. a `serve --http` child process)
  # over the wire — no engine is built in this process
  PYTHONPATH=src python -m repro.launch.storm --arch mixtral-8x22b --smoke \
      --connect 127.0.0.1:8080 --admin-socket /tmp/admin.sock --check

The scorecard (``loadgen.storm.summarize``) prints as JSON: goodput,
TTFT/stall percentiles, deadline misses, per-tenant outcomes, transport
errors and stream-contract violations. ``--seed`` fixes the entire
workload — same seed, same flags => identical sessions, identical
scorecard in-process.
"""
from __future__ import annotations

import argparse
import json
import sys


def _parse_tenant(spec: str):
    """``name[:weight[:deadline[:quota]]]`` with empty fields allowed:
    ``free:1.0::8`` is weight 1, no deadline, quota 8."""
    from repro.serving.loadgen import TenantSpec
    parts = spec.split(":")
    name = parts[0]
    weight = float(parts[1]) if len(parts) > 1 and parts[1] else 1.0
    deadline = float(parts[2]) if len(parts) > 2 and parts[2] else None
    quota = int(parts[3]) if len(parts) > 3 and parts[3] else None
    return TenantSpec(name, weight, deadline_s=deadline, quota=quota)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--slots-per-rank", type=int, default=1)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--seed", type=int, default=0,
                    help="drives params init AND the whole workload: same "
                    "seed, same flags => identical storm")
    # workload shape
    ap.add_argument("--rate", type=float, default=8.0,
                    help="open-loop Poisson arrival rate (sessions / sim s)")
    ap.add_argument("--duration", type=float, default=10.0,
                    help="arrival window (sim seconds)")
    ap.add_argument("--sessions-max", type=int, default=10_000)
    ap.add_argument("--prompt-mean", type=int, default=12)
    ap.add_argument("--prompt-max", type=int, default=32)
    ap.add_argument("--out-mean", type=int, default=10)
    ap.add_argument("--out-max", type=int, default=24)
    ap.add_argument("--tenant", action="append", default=None,
                    metavar="NAME[:W[:DL[:Q]]]",
                    help="tenant mix entry: name:weight:deadline_s:quota "
                    "(repeatable; empty fields allowed)")
    ap.add_argument("--prefix-groups", type=int, default=0,
                    help="shared system prompts per tenant (0 = off): "
                    "every arrival prepends one, producing the prompt "
                    "reuse the prefix cache feeds on")
    ap.add_argument("--prefix-len", type=int, default=16,
                    help="tokens per shared system prompt (block-align to "
                    "kv_block_size for full cache effect)")
    # serving knobs
    ap.add_argument("--sched", choices=["fifo", "edf"], default="fifo")
    ap.add_argument("--max-queue-depth", type=int, default=None)
    ap.add_argument("--fixed-membership", action="store_true",
                    help="full-restart baseline instead of elastic EP")
    ap.add_argument("--kv-pool", choices=["slot", "paged"], default=None)
    ap.add_argument("--prefix-cache", choices=["on", "off"], default=None,
                    help="override ArchConfig.prefix_cache (cross-session "
                    "prompt-prefix sharing over the paged pool)")
    # mid-storm fault / drain
    ap.add_argument("--fail-rank", type=int, action="append", default=None)
    ap.add_argument("--fail-at", type=float, default=None)
    ap.add_argument("--drain-rank", type=int, action="append", default=None)
    ap.add_argument("--drain-at", type=float, default=None)
    # wire mode
    ap.add_argument("--wire", action="store_true",
                    help="boot the HTTP/SSE transport + admin socket and "
                    "drive the storm over real sockets instead of the "
                    "in-process frontend")
    ap.add_argument("--connect", default=None, metavar="HOST:PORT",
                    help="drive an ALREADY RUNNING server over the wire "
                    "(e.g. a `serve --http` child process) instead of "
                    "booting one here — no engine is built in this "
                    "process, so jax never loads; pair with "
                    "--admin-socket to health-check + pull kv.prefix "
                    "stats from the server")
    ap.add_argument("--time-scale", type=float, default=0.02,
                    help="wire mode: wall seconds per sim-second of "
                    "arrival spacing (0 = all sessions fire at once)")
    ap.add_argument("--admin-socket", default=None, metavar="PATH",
                    help="wire mode: admin socket path (default: a "
                    "temp-dir socket; a status round-trip is always run)")
    # output / gating
    ap.add_argument("--out", default=None, help="write the scorecard JSON "
                    "here as well as stdout")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero on any transport error, client-"
                    "visible error event or stream-contract violation "
                    "(the CI smoke gate)")
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.serving.loadgen import (
        WorkloadSpec,
        build_sessions,
        run_storm,
        run_storm_http,
        summarize,
    )

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.prefix_cache is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg,
                                  prefix_cache=args.prefix_cache == "on")

    tenants = tuple(_parse_tenant(s) for s in (args.tenant or []))
    spec = WorkloadSpec(rate_rps=args.rate, duration_s=args.duration,
                        n_max=args.sessions_max,
                        prompt_mean=args.prompt_mean,
                        prompt_max=min(args.prompt_max, args.max_len // 2),
                        out_mean=args.out_mean,
                        out_max=min(args.out_max, args.max_len // 2),
                        vocab=cfg.vocab_size,
                        prefix_groups=args.prefix_groups,
                        prefix_len=(args.prefix_len
                                    if args.prefix_groups else 0),
                        **({"tenants": tenants} if tenants else {}))
    sessions = build_sessions(spec, seed=args.seed)

    admin_status = None
    if args.connect:
        # external-server mode: this process is a pure wire client — the
        # session list is the only thing built locally (stdlib only; the
        # subprocess e2e relies on jax never loading here)
        from repro.serving.transport import admin_request
        host, _, port = args.connect.rpartition(":")
        if args.admin_socket:
            admin_status = admin_request(args.admin_socket, {"cmd": "status"})
        results = run_storm_http(host or "127.0.0.1", int(port), sessions,
                                 time_scale=args.time_scale)
        card = summarize(results)
        card["mode"] = "connect"
        card["sched"] = args.sched
        card["seed"] = args.seed
    else:
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.serve import build_frontend

        enable_compile_cache()
        rt, eng, fe = build_frontend(
            cfg, world=args.world, slots_per_rank=args.slots_per_rank,
            seed=args.seed, max_batch=args.max_batch, max_len=args.max_len,
            fixed_membership=args.fixed_membership, kv_pool=args.kv_pool,
            queue_policy=args.sched, max_queue_depth=args.max_queue_depth,
            tenant_quotas=spec.quotas())

        # mid-storm events are scheduled BEFORE anything serves: the
        # injector fires when the sim clock crosses, whichever driver is
        # stepping
        if args.fail_at is not None and args.fail_rank:
            rt.injector.inject_at(args.fail_at, args.fail_rank)
        if args.drain_at is not None and args.drain_rank:
            fe.admin.execute({"cmd": "drain", "ranks": args.drain_rank,
                              "at": args.drain_at})

        if args.wire:
            import tempfile

            from repro.serving.transport import ServingTransport, \
                admin_request
            admin_path = args.admin_socket or (
                tempfile.mkdtemp(prefix="repro-storm-") + "/admin.sock")
            tr = ServingTransport(fe, admin_path=admin_path)
            tr.start_background()
            try:
                admin_status = admin_request(admin_path, {"cmd": "status"})
                results = run_storm_http("127.0.0.1", tr.http.port, sessions,
                                         time_scale=args.time_scale)
            finally:
                tr.stop()
        else:
            results = run_storm(fe, sessions)

        card = summarize(results)
        card["mode"] = "wire" if args.wire else "in_process"
        card["sched"] = args.sched
        card["policy"] = rt.policy.name
        card["seed"] = args.seed
        m = fe.metrics()
        card["prefix_cache"] = eng.prefix_enabled
        card["prefix_hits"] = m["prefix_hits"]
        card["prefix_hit_rate"] = m["prefix_hit_rate"]
        card["tokens_prefill_skipped"] = m["tokens_prefill_skipped"]
    if admin_status is not None:
        card["admin_ok"] = bool(admin_status.get("ok"))
        card["epoch"] = admin_status.get("epoch")
        kv = (admin_status.get("result") or {}).get("kv") or {}
        if kv.get("prefix", {}).get("enabled"):
            card["kv_prefix"] = kv["prefix"]
    print(json.dumps(card, indent=2, sort_keys=True))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(card, f, indent=2, sort_keys=True)

    if args.check:
        bad = []
        if card["transport_errors"]:
            bad.append(f"{card['transport_errors']} transport errors")
        if card["error_events"]:
            bad.append(f"{card['error_events']} client-visible error events")
        if card["stream_violations"]:
            bad.append(f"{card['stream_violations']} stream-contract "
                       f"violations")
        if args.wire and not card.get("admin_ok"):
            bad.append("admin socket status round-trip failed")
        if bad:
            print(f"STORM CHECK FAILED: {'; '.join(bad)}", file=sys.stderr)
            return 1
        print("storm check: OK (no errors, exactly-once in-order streams)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

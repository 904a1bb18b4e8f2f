import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on the
production meshes, print memory/cost analysis, and dump the roofline terms.

  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-v3-671b \
      --shape decode_32k [--multi-pod] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

This is the ONLY entry point that forces 512 host devices (set above, before
any jax import). Roofline terms per the hardware model: 197 TFLOP/s bf16,
819 GB/s HBM, ~50 GB/s/link ICI per chip (TPU v5e).
"""
import argparse
import json
import re
import sys
import time
import traceback
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, cell_is_supported, get_config, list_configs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    input_specs,
    make_deployment,
    make_membership_table,
    make_prefill_step,
    make_serve_step,
    make_train_step,
    membership_shapes,
    num_slots,
)
from repro.models.model import param_shapes
from repro.runtime.sharding import (
    batch_specs,
    cache_specs,
    membership_specs,
    opt_state_specs,
    param_specs,
    specs_to_shardings,
)
from repro.train.optim import OptimizerConfig, make_optimizer

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s /link (per-chip effective, one link)

COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"ragged-all-to-all)[^\n=]*?=\s*(\([^)]*\)|\S+)\s")
SHAPE_RE = re.compile(r"(bf16|f32|f16|s32|u32|s8|u8|pred|f64|s64|f8e4m3fn|"
                      r"f8e5m2)\[([0-9,]*)\]")

DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s32": 4, "u32": 4,
               "s8": 1, "u8": 1, "pred": 1, "s64": 8,
               "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> tuple[int, Counter]:
    """Sum output-shape bytes of every collective op in the (post-SPMD) HLO.
    Shapes in the compiled module are per-device; output size ~= bytes each
    device contributes to the wire for AG/RS/A2A (a conservative proxy)."""
    total = 0
    kinds = Counter()
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(
            r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*((?:\([^)]*\))|(?:\S+))\s+"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute|ragged-all-to-all)", stripped)
        if not m:
            continue
        shapes_str, kind = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in SHAPE_RE.findall(shapes_str):
            n = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        n *= int(d)
            nbytes += n * DTYPE_BYTES.get(dt, 4)
        kinds[kind] += 1
        total += nbytes
    return total, kinds


def dispatch_model(cfg, shape, mesh, dpl, dtype_bytes: int = 2):
    """Analytic dense-vs-ragged dispatch bytes for one MoE cell (per device,
    one dispatch+combine round trip per MoE layer). HLO byte counting sees
    the ragged exchange's worst-case receive buffer, not the real rows on the
    wire, so this model is the trajectory source of truth (see
    core.elastic_moe.dispatch_bytes_model)."""
    from repro.core.elastic_moe import dispatch_bytes_model
    ep = dpl.moe.ep
    if not cfg.is_moe or not ep.axis_names:
        return None
    x_axes = ((("pod",) if "pod" in mesh.axis_names else ())
              + tuple(ep.axis_names))
    denom = int(np.prod([mesh.shape[a] for a in x_axes]))
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind in ("train", "prefill")
                                   else 1)
    t_local = max(1, -(-tokens // denom))
    m = dispatch_bytes_model(ep, t_local, cfg.moe.top_k, cfg.d_model,
                             itemsize=dtype_bytes)
    m["tokens_per_rank"] = t_local
    m["moe_layers"] = len(cfg.moe_layer_ids())
    return m


def lower_cell(arch: str, shape_name: str, multi_pod: bool, dtype=jnp.bfloat16,
               dispatch=None):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = cell_is_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    seq_shard = (shape.name == "long_500k" and cfg.family == "hybrid")
    kind = "train" if shape.kind == "train" else "serve"
    dpl = make_deployment(cfg, mesh, seq_shard=seq_shard, kind=kind,
                          dispatch=dispatch)
    table = make_membership_table(cfg, mesh, kind)
    ms_shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), table.to_device())
    slots = num_slots(cfg, mesh, kind)
    pshapes = param_shapes(cfg, dtype, table.slot_to_expert, slots,
                           serving=(kind == "serve"))
    pspecs = param_specs(cfg, mesh, pshapes)
    p_shardings = specs_to_shardings(mesh, pspecs)
    ms_spec = membership_specs(ms_shapes)
    ms_shardings = specs_to_shardings(mesh, ms_spec)
    ins = input_specs(cfg, shape, dtype)

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_cfg = OptimizerConfig(name=cfg.optimizer)
            opt_init, _ = make_optimizer(opt_cfg)
            opt_shapes = jax.eval_shape(opt_init, pshapes)
            ospecs = opt_state_specs(cfg, mesh, opt_shapes, pspecs)
            o_shardings = specs_to_shardings(mesh, ospecs)
            b_shardings = specs_to_shardings(
                mesh, batch_specs(cfg, mesh, ins["batch"]))
            step = make_train_step(cfg, dpl, opt_cfg)
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, o_shardings, ms_shardings,
                              b_shardings),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(pshapes, opt_shapes, ms_shapes,
                                   ins["batch"])
        elif shape.kind == "prefill":
            c_shardings = specs_to_shardings(
                mesh, cache_specs(cfg, mesh, ins["caches"],
                                  seq_shard=seq_shard))
            b_shardings = specs_to_shardings(
                mesh, batch_specs(cfg, mesh, ins["batch"]))
            step = make_prefill_step(cfg, dpl)
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, c_shardings, ms_shardings,
                              b_shardings),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(pshapes, ins["caches"], ms_shapes,
                                   ins["batch"])
        else:  # decode
            c_shardings = specs_to_shardings(
                mesh, cache_specs(cfg, mesh, ins["caches"],
                                  seq_shard=seq_shard))
            b_shardings = specs_to_shardings(
                mesh, batch_specs(cfg, mesh,
                                  {"tokens": ins["tokens"],
                                   "lengths": ins["lengths"]}))
            step = make_serve_step(cfg, dpl)
            jitted = jax.jit(
                step,
                in_shardings=(p_shardings, c_shardings, ms_shardings,
                              b_shardings["tokens"], b_shardings["lengths"]),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(pshapes, ins["caches"], ms_shapes,
                                   ins["tokens"], ins["lengths"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    cbytes, ckinds = collective_bytes(hlo)
    chips = int(np.prod(list(mesh.shape.values())))

    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    # cost_analysis is per-device for SPMD-partitioned modules (calibrated in
    # benchmarks/roofline.py); the three roofline terms:
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_acc / HBM_BW
    t_collective = cbytes / ICI_BW

    # analytic model flops (2*N_active*D fwd, x3 for train)
    cfg_np = get_config(arch)
    n_active = cfg_np.param_count(active_only=True)
    tokens = shape.global_batch * (shape.seq_len if shape.kind in
                                   ("train", "prefill") else 1)
    model_flops = 2 * n_active * tokens * (3 if shape.kind == "train" else 1)
    model_flops_per_chip = model_flops / chips

    result = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "skipped": False,
        "dispatch": dpl.moe.dispatch if cfg.is_moe else None,
        "dispatch_model": dispatch_model(cfg, shape, mesh, dpl),
        "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes_cpu_backend": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            # static residency (params+caches+opt state) is exact per device;
            # temp_bytes comes from the CPU backend, which legalizes bf16
            # dots via f32 buffers (~2x the TPU-native transients) — see
            # EXPERIMENTS.md SS Dry-run notes.
            "static_per_device_gb": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes) / 1e9, 3),
            "total_per_device_gb": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes)
                / 1e9, 3),
        },
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "collective_bytes_per_device": cbytes,
        "collectives": dict(ckinds),
        "roofline": {
            "compute_s": t_compute,
            "memory_s": t_memory,
            "collective_s": t_collective,
            "bottleneck": max(
                [("compute", t_compute), ("memory", t_memory),
                 ("collective", t_collective)], key=lambda kv: kv[1])[0],
        },
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flops_ratio": (model_flops_per_chip / flops
                               if flops else None),
    }
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--dispatch", choices=["dense", "ragged"], default=None,
                    help="dispatch layout to lower (default: cfg policy); "
                    "the analytic dense-vs-ragged byte model is reported "
                    "either way")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--append", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args(argv)

    cells = []
    archs = list_configs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for mp in meshes:
        for a in archs:
            for s in shapes:
                cells.append((a, s, mp))

    done = {}
    if args.append and os.path.exists(args.out):
        for r in json.load(open(args.out)):
            done[(r["arch"], r["shape"], r.get("multi_pod", False))] = r

    results = list(done.values())
    for a, s, mp in cells:
        if (a, s, mp) in done:
            print(f"[cached] {a} x {s} multi_pod={mp}")
            continue
        print(f"[dryrun] {a} x {s} multi_pod={mp} ...", flush=True)
        try:
            r = lower_cell(a, s, mp, dispatch=args.dispatch)
        except Exception as e:
            traceback.print_exc()
            r = {"arch": a, "shape": s, "multi_pod": mp, "skipped": False,
                 "error": f"{type(e).__name__}: {e}"}
        results.append(r)
        if r.get("skipped"):
            print(f"  SKIP: {r['reason']}")
        elif "error" in r:
            print(f"  ERROR: {r['error']}")
        else:
            rl = r["roofline"]
            dm = r.get("dispatch_model")
            disp = (f" a2a_dense/ragged={dm['dense_over_ragged']:.1f}x"
                    if dm else "")
            print(f"  ok compile={r['compile_s']}s "
                  f"static/dev={r['memory']['static_per_device_gb']}GB "
                  f"(+cpu-temp {r['memory']['temp_bytes_cpu_backend']/1e9:.1f}) "
                  f"compute={rl['compute_s']:.2e}s memory={rl['memory_s']:.2e}s "
                  f"collective={rl['collective_s']:.2e}s "
                  f"bottleneck={rl['bottleneck']}{disp}", flush=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    print(f"wrote {args.out} ({len(results)} cells)")


if __name__ == "__main__":
    main()

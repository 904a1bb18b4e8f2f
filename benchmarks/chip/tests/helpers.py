"""Shared pieces of the benchmark's tests."""
import json
import pathlib
import shutil

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]

#: a Mixtral-shaped model small enough for the CPU: 8 experts top-2 over
#: 16 slots as the deployments have, computed in float32
TINY_MODEL = dict(hidden_size=64, intermediate_size=96,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  num_hidden_layers=2, vocab_size=256)
#: every end-to-end metric a reader is written for
END_TO_END = {"ttft_p95_ms": "ms", "itl_p95_ms": "ms",
              "output_tok_s": "tokens/s", "fault_stall_p95_ms": "ms",
              "setup_s": "s"}
TINY_MIX = {"why": "a CPU rehearsal", "source": "this test",
            "rate_rps": 15.0, "preroll_s": 1.0,
            "prompt": {"median": 8, "sigma": 0.6, "min": 4, "max": 16},
            "output": {"median": 8, "sigma": 0.6, "min": 4, "max": 16},
            "drain_cap_s": 30.0,
            "sample": {"min_tokens": 30, "max_requests": 3},
            "check": {"gap_tolerance": 1e-3, "max_mismatch_pct": 0.0}}
#: the closed batch: every row busy, refilled from one queue
TINY_CLOSED = {k: v for k, v in TINY_MIX.items()
               if k not in ("rate_rps", "drain_cap_s")}
TINY_CLOSED.update(closed={"block": 16},
                   sample={"min_tokens": 30, "max_requests": 5})


def tiny_conf() -> dict:
    """The tiny Mixtral-shaped configuration: ``mixtral-8x22b``'s file at
    ``TINY_MODEL``'s widths, in float32, 8 rows of 160 positions."""
    conf = json.loads((CHIP / "configs" / "mixtral-8x22b.json").read_text())
    conf["name"] = "tiny"
    conf["model"].update(TINY_MODEL)
    conf["deployment"].update(dtype="float32", max_batch=8, max_len=160)
    return conf


def tiny_tree(tmp: pathlib.Path, mix: dict, name: str = "tiny",
              end_to_end=tuple(END_TO_END), conf=None, adapter=None):
    """A copy of the benchmark's files plus a configuration (``tiny_conf``
    unless given), the source of its adapter where given, and a new mix
    file; returns (spec, chip_dir, root) for ``bench.CellRun``. No file of
    the copy is edited: the cell is found by the names alone."""
    conf = conf or tiny_conf()
    root = tmp / "tree"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    conf_file = f"benchmarks/chip/configs/{conf['name']}.json"
    (root / conf_file).write_text(json.dumps(conf))
    if adapter is not None:
        (chip / "adapters" / f"{conf['adapter']}.py").write_text(adapter)
    (chip / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    spec = {"configs": [{"name": conf["name"], "file": conf_file}],
            "workloads": [{"name": "tiny", "config": conf["name"],
                           "traffic": name, "chips": 1}],
            "end_to_end": [{"name": n, "unit": END_TO_END[n]}
                           for n in end_to_end],
            "per_layer": [{"name": p.stem, "unit": "-"}
                          for p in sorted((chip / "metrics").glob("*.py"))
                          if p.stem not in END_TO_END]}
    return spec, chip, root

"""Served tokens against an independent reference: the engine's admission
writes every context position into the KV cache.

A tiny Mixtral in float32 serves through the frontend (the engine's
chunk-1 prompt replay, its decode, its continuation after a fault), and
each finished request's prompt and served tokens are teacher-forced
through the benchmark's float32 reference (``benchmarks/chip/reference``),
independent of the program. At every served position the reference's
logit of the served token must equal its best: a gap of 0. An admission
that leaves a slot's resident length at the whole context (the pool's
allocation) writes the first replayed token past the context's end, no
query attends to it, and the gaps reach 0.44 on fresh admissions.

Three admission paths: a fresh one, a prefix hit (positions borrowed from
a cached prompt), and a resume after a rank fault (prompt plus generated
prefix replayed)."""
import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
CHIP = ROOT / "benchmarks" / "chip"
for p in (str(CHIP), str(CHIP / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench  # noqa: E402
from helpers import TINY_MODEL  # noqa: E402

SEED = 2**31 + 9
MAX_BATCH, MAX_LEN, BLOCK = 8, 160, 16
MODEL = dict(TINY_MODEL, num_local_experts=8, num_experts_per_tok=2,
             rope_theta=1e6, rms_norm_eps=1e-6, sliding_window=None,
             hidden_act="silu", tie_word_embeddings=False)
CONF = {"name": "tiny", "model": MODEL,
        "deployment": {"slots_per_rank": 1, "ranks_per_host": 2,
                       "hosts_per_switch": 2, "dispatch": "ragged",
                       "kv_block_size": BLOCK}}


def _drain(fe, handles, limit=3000):
    for _ in range(limit):
        if all(h.done for h in handles):
            return
        fe.step()
    raise AssertionError("requests did not finish")


@pytest.fixture(scope="module")
def served():
    """{case: [(prompt, served tokens)]} from one serving instance."""
    from repro.core.topology import FaultDomainTree
    from repro.launch.serve import build_frontend

    cfg = bench.arch_config(CONF)
    rt, eng, fe = build_frontend(
        cfg, world=16, slots_per_rank=1,
        topology=FaultDomainTree(16, ranks_per_host=2, hosts_per_switch=2),
        seed=SEED, dtype=jnp.float32, dispatch="ragged",
        max_batch=MAX_BATCH, max_len=MAX_LEN)
    assert eng.prefix_enabled
    rng = np.random.default_rng(7)

    def prompt(n):
        return rng.integers(1, MODEL["vocab_size"], n).tolist()

    out = {}
    # fresh admissions (no prompt shares a full block with another)
    fresh = [fe.submit(prompt(n), max_new=24) for n in (5, 17, 33)]
    _drain(fe, fresh)
    out["fresh"] = fresh
    # prefix hits: a donor's prompt is cached once its prefill completes;
    # later prompts that start with its first blocks borrow them
    base = prompt(3 * BLOCK)
    donor = fe.submit(base, max_new=8)
    _drain(fe, [donor])
    skipped = eng.sched.stats.tokens_prefill_skipped
    hits = [fe.submit(base[:2 * BLOCK] + prompt(n), max_new=24)
            for n in (3, 20)]
    _drain(fe, hits)
    assert (eng.sched.stats.tokens_prefill_skipped - skipped
            >= 2 * BLOCK * len(hits))
    out["prefix_hit"] = hits
    # resume after a fault: suspended mid-decode, replayed, continued
    resumed = [fe.submit(prompt(n), max_new=100) for n in (9, 26)]
    while min(len(h.tokens) for h in resumed) < 4:
        fe.step()
    assert not any(h.done for h in resumed)
    rt.injector.inject_at(rt.clock.now(), [3], "sigkill")
    _drain(fe, resumed)
    assert all("RESUMED" in [e.kind for e in h.events] for h in resumed)
    out["resume"] = resumed
    for hs in out.values():
        assert all(h.outcome == "FINISHED" for h in hs)
    return {k: [(list(h.prompt), list(h.tokens)) for h in hs]
            for k, hs in out.items()}


@pytest.fixture(scope="module")
def reference():
    return bench.load_reference(CHIP, "mixtral", MODEL, "float32")


@pytest.mark.parametrize("case", ["fresh", "prefix_hit", "resume"])
def test_served_tokens_match_the_reference(served, reference, case):
    picked = [bench.Req(rid=i, t_sched=-1.0, prompt=tuple(p), max_new=len(t),
                        in_window=True, tokens=t)
              for i, (p, t) in enumerate(served[case])]
    gaps = bench.reference_gaps(reference, SEED, picked, MAX_BATCH, MAX_LEN)
    allg = np.concatenate(list(gaps.values()))
    assert allg.size == sum(len(t) for _, t in served[case])
    assert float(allg.max()) == 0.0

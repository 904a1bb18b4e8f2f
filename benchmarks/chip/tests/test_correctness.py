"""The comparison that decides ``correct``: the reference agrees with the
program's model path, a sound run reads ``correct`` true, the float8
control fails the limit, and a broken timed path turns ``correct`` false
(faults planted on the CPU, at a small size; the harness's look for a chip
is skipped).

The engine as it stands never writes a sequence's first context token
into the KV cache (PERF.md, Open questions), so every served stream misses
the reference. The sound runs of the open-loop mixes repair that in the
test alone; the closed batch drives the serve step without the engine's
admission and needs no repair."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
from helpers import TINY_CLOSED, TINY_MIX, TINY_MODEL, tiny_tree

SEED = 2**31 + 9


def _model():
    return dict(TINY_MODEL, num_local_experts=8, num_experts_per_tok=2,
                rope_theta=1e6, rms_norm_eps=1e-6, sliding_window=None,
                hidden_act="silu", tie_word_embeddings=False)


def test_reference_agrees_with_the_program_model_path():
    """A witness: the program's own prefill and cached decode (not the
    serving engine) against the reference, float32 weights."""
    from reference.mixtral import Mixtral
    from repro.core import make_initial_membership
    from repro.models import init_params
    from repro.models.model import Deployment, decode_step, init_caches, \
        prefill
    from repro.models.moe import local_deployment

    conf = {"name": "tiny", "adapter": "mixtral", "model": _model(),
            "deployment": {"ep_ranks": 16, "slots_per_rank": 1,
                           "ranks_per_host": 2, "hosts_per_switch": 2,
                           "dispatch": "ragged", "kv_block_size": 16}}
    cfg = bench.arch_config(conf)
    table = make_initial_membership(16, 8, 1)
    params = init_params(cfg, jax.random.key(SEED), jnp.float32,
                         table.slot_to_expert, 16)
    dpl = Deployment(moe=local_deployment(16, dispatch="ragged"))
    ms = table.to_device()
    prompt = np.random.RandomState(0).randint(1, 256, size=(1, 12))
    lg, _ = prefill(cfg, params, {"tokens": jnp.asarray(prompt)},
                    init_caches(cfg, 1, 32, jnp.float32), ms, dpl)
    caches = init_caches(cfg, 1, 32, jnp.float32)
    for i, t in enumerate(prompt[0]):
        dl, caches = decode_step(cfg, params, jnp.asarray([[t]]),
                                 jnp.asarray([i]), caches, ms, dpl)
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :12] = prompt[0]
    ref = Mixtral(conf["model"], "float32").logits(SEED, tokens,
                                                   np.array([11]))
    assert np.abs(np.asarray(lg) - ref).max() < 1e-5
    assert np.abs(np.asarray(dl) - ref).max() < 1e-5


def _repaired_admission(cell):
    """The engine's admission with the length rewind it lacks: every fresh
    admission (not only a prefix hit) starts its replay at the prefix skip,
    so the first context token lands at position 0. A prefix hit needs it
    too: the cached prefix it borrows was written by a fresh admission."""
    sched, kv = cell.eng.sched, cell.eng.kv
    admit = sched.admit

    def repaired(*args, **kwargs):
        admitted = admit(*args, **kwargs)
        for req in admitted:
            if not req.kv_intact:
                kv.set_length(req.slot, req.prefix_skip)
        return admitted

    sched.admit = repaired


def _run(tmp_path, mix, breaker=None):
    spec, chip, root = tiny_tree(tmp_path, mix)
    cell = bench.CellRun(spec, "tiny", SEED, 2.0, False, chip_dir=chip,
                         root=root)
    build = cell.build

    def patched_build():
        build()
        if "closed" in mix:
            if breaker is not None:
                cell.batch_step = breaker(cell.batch_step)
            return
        _repaired_admission(cell)
        if breaker is not None:
            cell.eng._step = breaker(cell.eng._step)

    cell.build = patched_build
    return cell, cell.execute()


#: every request borrows one of two cached system prompts: the engine's
#: prefix-hit path and its donor-row gather
PREFIX_MIX = dict(TINY_MIX, prefix={"groups": 2, "len": 64})
MIXES = pytest.mark.parametrize("mix", [TINY_MIX, PREFIX_MIX, TINY_CLOSED],
                                ids=["fresh", "prefix_hits", "closed"])


@MIXES
def test_sound_run_is_correct(tmp_path, mix):
    cell, out = _run(tmp_path, mix)
    assert out["correct"] is True
    assert out["checks"]["mismatch_pct"]["value"] == 0.0
    assert sum(len(r.tokens) for r in cell.picked) >= mix["sample"][
        "min_tokens"]
    if "prefix" in mix:
        assert all(r.prefix_skip > 0 for r in cell.run.window_requests()
                   if r.t_admit >= 0)


@pytest.mark.parametrize("mix", [TINY_MIX, TINY_CLOSED],
                         ids=["fresh", "closed"])
def test_float8_control_fails_the_limit(tmp_path, mix):
    cell, out = _run(tmp_path, mix)
    assert out["correct"] is True
    checks = bench.gap_checks(cell.gaps(cell.picked, "fp8"), mix["check"])
    assert checks["mismatch_pct"]["value"] > checks["mismatch_pct"]["limit"]


def _altered_token(step):
    def broken(params, caches, membership, tokens, lengths):
        nxt, logits, caches = step(params, caches, membership, tokens,
                                   lengths)
        return (nxt + 1) % TINY_MODEL["vocab_size"], logits, caches
    return broken


def _state_unchanged(step):
    def broken(params, caches, membership, tokens, lengths):
        kept = jax.tree_util.tree_map(jnp.copy, caches)
        nxt, logits, _ = step(params, caches, membership, tokens, lengths)
        return nxt, logits, kept
    return broken


@MIXES
@pytest.mark.parametrize("breaker", [_altered_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(tmp_path, breaker, mix):
    _, out = _run(tmp_path, mix, breaker)
    assert out["correct"] is False
    share = out["checks"]["mismatch_pct"]
    assert share["value"] > share["limit"]

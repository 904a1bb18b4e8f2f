"""EPLB placement + 3-tier repair: unit + hypothesis property tests."""
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="dev extra not installed: pip install -e .[dev]")
from hypothesis import given, settings, strategies as st

from repro.core import eplb_place, make_initial_membership, plan_repair
from repro.core.backup import BackupStore
from repro.core.placement import placement_overlap
from repro.core.repair import apply_repair

import jax
import jax.numpy as jnp


def test_eplb_uniform_coverage():
    res = eplb_place(num_experts=8, world=8, slots_per_rank=2,
                     active=np.ones(8, bool))
    assert not res.infeasible
    assert all(len(v) >= 1 for v in res.replicas.values())
    assert (res.slot_to_expert >= 0).sum() == 16


def test_eplb_load_proportional_replication():
    load = np.ones(4)
    load[0] = 10.0
    res = eplb_place(4, 8, 2, np.ones(8, bool), load=load)
    counts = {e: len(s) for e, s in res.replicas.items()}
    assert counts[0] > counts[1]


def test_eplb_infeasible_when_slots_short():
    # 8 experts, 6 live slots
    active = np.ones(8, bool)
    active[:2] = False
    res = eplb_place(8, 8, 1, active)
    assert res.infeasible


def test_eplb_prefers_reuse():
    t = make_initial_membership(8, 8, 2)
    active = np.ones(8, bool)
    active[3] = False
    res = eplb_place(8, 8, 2, active, prev_slot_to_expert=t.slot_to_expert)
    overlap = placement_overlap(t.slot_to_expert, res.slot_to_expert)
    assert overlap > 0.8  # surviving slots keep their experts (Tier-1)


@settings(max_examples=60, deadline=None)
@given(
    world=st.integers(2, 12),
    spr=st.integers(1, 3),
    e_log=st.integers(2, 24),
    fails=st.data(),
)
def test_property_repair_always_covers_or_reports(world, spr, e_log, fails):
    """For ANY failure pattern: the repaired placement covers every logical
    expert using only active ranks, or EPLB reports infeasibility."""
    E = min(e_log, world * spr)
    n_fail = fails.draw(st.integers(0, world - 1))
    failed = fails.draw(st.permutations(range(world))) [:n_fail]
    t = make_initial_membership(world, E, spr)
    active = np.ones(world, bool)
    active[list(failed)] = False
    res = eplb_place(E, world, spr, active,
                     prev_slot_to_expert=t.slot_to_expert)
    live_slots = active.sum() * spr
    if live_slots < E:
        assert res.infeasible
        return
    assert not res.infeasible
    for e, slots in res.replicas.items():
        assert len(slots) >= 1
        for s in slots:
            assert active[s // spr]  # never places on a dead rank


@settings(max_examples=40, deadline=None)
@given(world=st.integers(2, 8), data=st.data())
def test_property_plan_sources_are_active_and_exhaustive(world, data):
    spr = 2
    E = world  # R=2
    t = make_initial_membership(world, E, spr)
    n_fail = data.draw(st.integers(1, world // 2))
    failed = list(data.draw(st.permutations(range(world)))[:n_fail])
    active = np.ones(world, bool)
    active[failed] = False
    res = eplb_place(E, world, spr, active,
                     prev_slot_to_expert=t.slot_to_expert)
    bk = BackupStore(2)
    for e in range(E):
        bk.store(e, {"w": np.zeros((2, 2))})
    plan = plan_repair(t.slot_to_expert, res.slot_to_expert, active, spr, bk,
                       bytes_per_slot=8)
    # every Tier-2 source is on an active rank
    for dst, src in plan.tier2:
        assert active[src // spr]
        assert active[dst // spr]
    assert not plan.unrecoverable
    # every active slot with an assigned expert is covered by exactly one tier
    covered = set(plan.tier1) | {d for d, _ in plan.tier2} | {
        d for d, _ in plan.tier3}
    for s in range(t.num_slots):
        if active[s // spr] and res.slot_to_expert[s] >= 0:
            assert s in covered


def test_apply_repair_restores_replica_consistency():
    """After repair, every slot holds its logical expert's canonical bytes."""
    world, E, spr = 6, 6, 2
    t = make_initial_membership(world, E, spr)
    L, d, de = 2, 4, 3
    key = jax.random.key(0)
    logical = jax.random.normal(key, (E, L, d, de))
    w = {"w": jnp.stack([logical[e].reshape(L, d, de)
                         for e in t.slot_to_expert], axis=1)}
    bk = BackupStore(2)
    bk.build_from_slots(w, t.slot_to_expert)

    active = np.ones(world, bool)
    active[[1, 4]] = False
    res = eplb_place(E, world, spr, active,
                     prev_slot_to_expert=t.slot_to_expert)
    plan = plan_repair(t.slot_to_expert, res.slot_to_expert, active, spr, bk,
                       bytes_per_slot=int(L * d * de * 4))
    w2 = apply_repair(w, plan, bk)
    for s, e in enumerate(res.slot_to_expert):
        if e < 0 or not active[s // spr]:
            continue
        np.testing.assert_allclose(np.asarray(w2["w"][:, s]),
                                   np.asarray(logical[int(e)]))


def test_tier3_used_when_all_replicas_die():
    """Kill every host of one expert -> DRAM reload path must fire."""
    world, E, spr = 4, 4, 2  # R=2: expert 2 lives on ranks 1 and 3
    t = make_initial_membership(world, E, spr)
    bk = BackupStore(1)
    for e in range(E):
        bk.store(e, {"w": np.full((1, 2), float(e))})
    active = np.ones(world, bool)
    active[[1, 3]] = False  # both replicas of experts 2 and 3 die
    res = eplb_place(E, world, spr, active,
                     prev_slot_to_expert=t.slot_to_expert)
    assert not res.infeasible
    plan = plan_repair(t.slot_to_expert, res.slot_to_expert, active, spr, bk,
                       bytes_per_slot=8)
    assert any(e == 2 for _, e in plan.tier3)
    assert plan.source_mix()["dram_reload"] >= 1

import json
from collections import Counter

import pytest

import bench
import loadgen
from helpers import TINY_MIX, tiny_tree

MIX = dict(TINY_MIX, rate_rps=7.0,
           prompt={"median": 64, "sigma": 0.6, "min": 16, "max": 256},
           output={"median": 192, "sigma": 0.7, "min": 32, "max": 512})


def _window(mix, arrivals):
    return [a for a in arrivals if a.t >= mix["preroll_s"]]


def test_every_seed_gets_the_same_work_in_another_order():
    runs = [_window(MIX, loadgen.build(MIX, seed, 30.0, 32000)[1])
            for seed in (1, 2, 2**31 + 12345)]
    assert all(len(r) == round(MIX["rate_rps"] * 30.0) for r in runs)
    prompts = [sorted(len(a.prompt) for a in r) for r in runs]
    outs = [sorted(a.max_new for a in r) for r in runs]
    assert prompts[0] == prompts[1] == prompts[2]
    assert outs[0] == outs[1] == outs[2]
    assert [a.t for a in runs[0]] != [a.t for a in runs[1]]
    gaps = [sorted(round(b.t - a.t, 9) for a, b in zip(r, r[1:]))
            for r in runs]
    # the same arrival gaps, up to the first and last half gaps
    assert abs(sum(gaps[0]) - sum(gaps[1])) < 30.0 / len(runs[0])


def test_same_seed_same_requests():
    a = loadgen.build(MIX, 7, 10.0, 32000)
    b = loadgen.build(MIX, 7, 10.0, 32000)
    assert a == b


def test_arrivals_fill_the_preroll_then_the_window_in_order():
    _, arr = loadgen.build(MIX, 3, 20.0, 32000)
    pre = MIX["preroll_s"]
    assert len(arr) == round(MIX["rate_rps"] * pre) + round(
        MIX["rate_rps"] * 20.0)
    assert len(_window(MIX, arr)) == round(MIX["rate_rps"] * 20.0)
    ts = [a.t for a in arr]
    assert ts == sorted(ts) and 0 < ts[0] and ts[-1] < pre + 20.0
    for a in arr:
        assert 16 <= len(a.prompt) <= 256 and 32 <= a.max_new <= 512
        assert all(1 <= t < 32000 for t in a.prompt)


def test_shared_prefixes_balanced_over_groups():
    mix = dict(MIX, prefix={"groups": 4, "len": 64})
    prefixes, arr = loadgen.build(mix, 11, 20.0, 32000)
    assert len(prefixes) == 4 and all(len(p) == 64 for p in prefixes)
    counts = Counter(a.group for a in arr)
    assert max(counts.values()) - min(counts.values()) <= 1
    for a in arr:
        assert a.prompt[:64] == prefixes[a.group]


def test_fault_times_inside_the_window():
    mix = dict(MIX, faults={"kind": "sigkill", "first_s": 5.0,
                            "every_s": 15.0})
    assert loadgen.fault_times(mix, 30.0) == [5.0, 20.0]
    assert loadgen.fault_times(MIX, 30.0) == []


def test_a_new_mix_file_is_found_by_its_name(tmp_path):
    mix = dict(TINY_MIX, rate_rps=3.0)
    spec, chip, root = tiny_tree(tmp_path, mix, name="brand-new-mix")
    before = {p: p.read_bytes() for p in chip.rglob("*")
              if p.is_file() and p.name != "brand-new-mix.json"
              and p.name != "tiny.json"}
    cell, conf, loaded = bench.load_cell(spec, "tiny", chip, root)
    assert cell["traffic"] == "brand-new-mix" and loaded == mix
    assert conf["name"] == "tiny"
    # nothing the harness already had was edited to find it
    assert all(p.read_bytes() == b for p, b in before.items())
    assert json.loads((chip / "traffic" / "brand-new-mix.json")
                      .read_text()) == mix


def test_closed_queue_gives_every_seed_the_same_blocks():
    mix = loadgen.load_mix(bench.CHIP_DIR / "traffic" / "batch-8x22b.json")
    n = mix["closed"]["block"]
    blocks = []
    for seed in (1, 2**31 + 12345):
        q = loadgen.closed_queue(mix, seed, 32768)
        reqs = [next(q) for _ in range(3 * n)]
        blocks.append([reqs[i * n:(i + 1) * n] for i in range(3)])
        # a block's prompt and output lengths are the mix's quantiles
        for blk in blocks[-1]:
            assert sorted(len(p) for p, _ in blk) == sorted(
                loadgen.lengths(mix["prompt"], n))
            assert sorted(o for _, o in blk) == sorted(
                loadgen.lengths(mix["output"], n))
    # in another order, with other token ids
    assert [len(p) for p, _ in blocks[0][0]] != [len(p) for p, _ in
                                                 blocks[1][0]]
    q = loadgen.closed_queue(mix, 1, 32768)
    assert [next(q) for _ in range(n)] == blocks[0][0]


def test_a_closed_mix_takes_no_rate(tmp_path):
    mix = json.loads((bench.CHIP_DIR / "traffic" / "batch-8x22b.json")
                     .read_text())
    path = tmp_path / "m.json"
    for extra in ({"rate_rps": 4.0}, {"prefix": {"groups": 2, "len": 16}}):
        path.write_text(json.dumps(dict(mix, **extra)))
        with pytest.raises(ValueError, match="unknown keys"):
            loadgen.load_mix(path)

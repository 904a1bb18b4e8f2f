"""Open-loop traffic from a mix file (``traffic/<mix>.json``).

The arithmetic is that of ``repro.serving.loadgen.workload``: lognormal
prompt and output lengths given by a median and a shape and clipped to a
range, exponential gaps between arrivals at a fixed rate, and optional
shared system prompts that every request prepends to its own suffix. Here
it runs on the wall clock, at the sizes the mix file states, and without
that module's clips.

The load comes in two spans, the preroll and the measured window. Each gets
``rate_rps`` times its length of requests, with the same multiset of prompt
lengths, output lengths and arrival gaps (the quantiles of the mix's
distributions at ``(i + 0.5) / n``) in an order drawn from the seed, and
token ids drawn from the seed. A seed therefore changes which request comes
when and what it says, never how much work the window holds.

A closed mix (``closed`` in the file) has no arrivals: an offline job whose
requests all wait in one queue, each batch row taking the next as soon as
its last one finishes. The queue is made of blocks of ``closed.block``
requests, every block the same multiset of prompt and output lengths (the
quantiles as above), each in its own order drawn from the seed.
"""
from __future__ import annotations

import json
import math
import pathlib
import statistics
from dataclasses import dataclass
from typing import Iterator

import numpy as np

#: keys every mix file must carry, those of an open-loop mix and of a closed
#: one, and the optional ones the harness reads
REQUIRED = ("why", "source", "preroll_s", "prompt", "output", "sample",
            "check")
OPEN = ("rate_rps", "drain_cap_s")
CLOSED = ("closed",)
OPTIONAL = ("prefix", "faults")


@dataclass(frozen=True)
class Arrival:
    t: float            # scheduled send, seconds from the start of the load
    prompt: tuple       # token ids, shared prefix included
    max_new: int
    group: int = -1     # shared-prefix index, -1 for none


def load_mix(path: pathlib.Path) -> dict:
    mix = json.loads(pathlib.Path(path).read_text())
    required = REQUIRED + (CLOSED if "closed" in mix else OPEN)
    missing = [k for k in required if k not in mix]
    unknown = [k for k in mix if k not in required + OPTIONAL]
    if "closed" in mix:
        unknown += [k for k in OPTIONAL if k in mix]
    if missing or unknown:
        raise ValueError(f"{path}: missing keys {missing}, unknown keys "
                         f"{unknown}")
    for part in ("prompt", "output"):
        d = mix[part]
        if not 1 <= d["min"] <= d["median"] <= d["max"]:
            raise ValueError(f"{path}: {part} needs 1 <= min <= median <= "
                             f"max, got {d}")
    return mix


def lengths(dist: dict, n: int) -> list[int]:
    """``n`` lognormal quantiles with the given median and shape, clipped."""
    nd = statistics.NormalDist()
    mu = math.log(dist["median"])
    out = []
    for i in range(n):
        x = math.exp(mu + dist["sigma"] * nd.inv_cdf((i + 0.5) / n))
        out.append(max(dist["min"], min(dist["max"], int(round(x)))))
    return out


def longest_context(mix: dict) -> int:
    """Most positions one request of the mix can hold."""
    pre = mix.get("prefix", {}).get("len", 0)
    return pre + mix["prompt"]["max"] + mix["output"]["max"]


def build(mix: dict, seed: int, seconds: float, vocab: int
          ) -> tuple[list[tuple], list[Arrival]]:
    """The shared prefixes and the arrivals of one run, sorted by send time
    from the start of the load: ``rate_rps * preroll_s`` requests spread
    over the preroll, then ``rate_rps * seconds`` over the window."""
    order, tokens = (np.random.default_rng(s) for s in
                     np.random.SeedSequence(seed).spawn(2))
    prefixes: list[tuple] = []
    if "prefix" in mix:
        prefixes = [tuple(int(x) for x in
                          tokens.integers(1, vocab, mix["prefix"]["len"]))
                    for _ in range(mix["prefix"]["groups"])]
    arrivals = []
    start = 0.0
    for span in (mix["preroll_s"], seconds):
        n = int(round(mix["rate_rps"] * span))
        if n == 0:
            continue
        gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
        gaps = order.permutation(gaps)
        # each send at the middle of its gap, the n gaps filling the span
        t = start + (np.cumsum(gaps) - 0.5 * gaps) * (span / gaps.sum())
        plens = order.permutation(lengths(mix["prompt"], n))
        outs = order.permutation(lengths(mix["output"], n))
        groups = (order.permutation(np.arange(n) % len(prefixes))
                  if prefixes else np.full(n, -1))
        for i in range(n):
            suffix = tuple(int(x) for x in
                           tokens.integers(1, vocab, plens[i]))
            pre = prefixes[groups[i]] if groups[i] >= 0 else ()
            arrivals.append(Arrival(t=float(t[i]), prompt=pre + suffix,
                                    max_new=int(outs[i]),
                                    group=int(groups[i])))
        start += span
    return prefixes, arrivals


def closed_queue(mix: dict, seed: int, vocab: int) -> Iterator[tuple]:
    """The endless queue of a closed mix: (prompt token ids, output
    length), block after block."""
    order, tokens = (np.random.default_rng(s) for s in
                     np.random.SeedSequence(seed).spawn(2))
    n = mix["closed"]["block"]
    plens, outs = lengths(mix["prompt"], n), lengths(mix["output"], n)
    while True:
        for p, o in zip(order.permutation(plens), order.permutation(outs)):
            yield tuple(int(x) for x in tokens.integers(1, vocab, p)), int(o)


def fault_times(mix: dict, seconds: float) -> list[float]:
    """Window-relative times of the mix's rank faults."""
    f = mix.get("faults")
    if not f:
        return []
    out, t = [], f["first_s"]
    while t < seconds:
        out.append(t)
        t += f["every_s"]
    return out

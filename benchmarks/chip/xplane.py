"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metric
readers need: the device's busy time, the executions of a jitted module,
the calls of a kernel, the busiest device operations, and the idle gaps
labelled by the harness's host span that was open across them.

Device planes are named ``/device:TPU:<n>``; their ``XLA Modules`` line holds
one event per execution of a compiled program (``jit_<name>(<id>)``) and
their ``XLA Ops`` line one event per operation, kernels included. The host
plane ``/host:CPU`` holds the ``jax.profiler.TraceAnnotation`` spans that
the harness opens around its own calls.
"""
from __future__ import annotations

import pathlib
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
HOST_PLANE = "/host:CPU"
MODULES, OPS = "XLA Modules", "XLA Ops"


@dataclass
class Reduced:
    planes: int = 0
    busy_s: float = 0.0             # union of op intervals, mean over chips
    span_s: float = 0.0             # first op start to last op end
    modules: list = field(default_factory=list)   # (name, start_ns, dur_ns)
    ops: list = field(default_factory=list)       # (name, start_ns, dur_ns)
    host: list = field(default_factory=list)      # (name, start_ns, dur_ns)
    line_names: dict = field(default_factory=dict)

    def module_durations(self, pattern: str) -> list[float]:
        """Seconds of each execution of modules whose name matches."""
        rx = re.compile(pattern)
        return [d * 1e-9 for n, _, d in self.modules if rx.search(n)]

    def op_durations(self, pattern: str) -> list[float]:
        rx = re.compile(pattern)
        return [d * 1e-9 for n, _, d in self.ops if rx.search(n)]

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations with the most device time, by name."""
        tot: dict[str, float] = {}
        for name, _, d in self.ops:
            k = short_name(name)
            tot[k] = tot.get(k, 0.0) + d * 1e-9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest gaps between device ops, each named by the
        innermost host span covering its middle (``idle`` if none)."""
        gaps = [(s, d) for s, d in _gaps(_merge(
            (s, s + d) for _, s, d in self.ops))]
        gaps.sort(key=lambda g: -g[1])
        out = []
        for s, d in gaps[:n]:
            mid = s + d / 2
            cover = [(hd, name) for name, hs, hd in self.host
                     if hs <= mid <= hs + hd]
            out.append([min(cover)[1] if cover else "idle", d * 1e-9])
        return out


_OP = re.compile(r"^(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_WEIGHTS = re.compile(r"bf16\[\d+,\d+,\d+\]")


def short_name(hlo: str) -> str:
    """An op event's HLO text cut to its instruction name and opcode; a
    custom call also keeps its first 3-D bf16 operand, which names the
    weights a kernel streams."""
    m = _OP.match(hlo)
    if not m:
        return hlo[:80]
    name = f"{m.group(1)} {m.group(2)}"
    if m.group(2) == "custom-call":
        w = _WEIGHTS.search(hlo)
        name += f" {w.group(0)}" if w else ""
    return name


def find_xplane(directory) -> pathlib.Path:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _merge(intervals):
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _gaps(merged):
    return [(a[1], b[0] - a[1]) for a, b in zip(merged, merged[1:])]


def busy_ns(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    return sum(e - s for s, e in _merge(intervals))


def reduce(path, host_prefix: str = "bench.") -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    r = Reduced()
    busy, spans = [], []
    for plane in pd.planes:
        lines = {ln.name: [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                           for ev in ln.events] for ln in plane.lines}
        if DEVICE_PLANE.fullmatch(plane.name):
            r.planes += 1
            r.line_names[plane.name] = sorted(lines)
            ops = lines.get(OPS, [])
            r.ops += ops
            r.modules += lines.get(MODULES, [])
            if ops:
                busy.append(busy_ns((s, s + d) for _, s, d in ops))
                spans.append(max(s + d for _, s, d in ops)
                             - min(s for _, s, _ in ops))
        elif plane.name == HOST_PLANE:
            for evs in lines.values():
                r.host += [e for e in evs if e[0].startswith(host_prefix)]
    if r.planes:
        r.busy_s = sum(busy) / r.planes * 1e-9
        r.span_s = (max(spans) if spans else 0.0) * 1e-9
    return r

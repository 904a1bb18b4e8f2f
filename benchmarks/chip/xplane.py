"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the metric
readers need: the device's busy time, the executions of a jitted module,
the calls of a kernel, each op's HLO ``op_name`` (its named scopes, which
``scopes.stage_ms`` reads), the busiest device operations, and the idle
gaps labelled by the host span that was open across them.

Device planes are named ``/device:TPU:<n>``; their ``XLA Modules`` line holds
one event per execution of a compiled program (``jit_<name>(<id>)``) and
their ``XLA Ops`` line one event per operation, kernels included. The host
plane ``/host:CPU`` holds the ``jax.profiler.TraceAnnotation`` spans that
the harness opens around its own calls (``bench.*``) and the program's own
(``repro.*``). The file is read by ``scopes.read``.
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
    #: (HLO text, start_ns, dur_ns, HLO op_name or "", program id) per op
    ops: list = field(default_factory=list)
    host: list = field(default_factory=list)      # (name, start_ns, dur_ns)
    line_names: dict = field(default_factory=dict)

    def module_durations(self, pattern: str) -> list[float]:
        """Seconds of each execution of modules whose name matches."""
        rx = re.compile(pattern)
        return [d * 1e-9 for n, _, d in self.modules if rx.search(n)]

    def op_durations(self, pattern: str) -> list[float]:
        rx = re.compile(pattern)
        return [d * 1e-9 for n, _, d, *_ in self.ops if rx.search(n)]

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` operations with the most device time, by name."""
        tot: dict[str, float] = {}
        for name, _, d, *_ in self.ops:
            k = short_name(name)
            tot[k] = tot.get(k, 0.0) + d * 1e-9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The ``n`` longest gaps between device ops, each named by the
        innermost host span covering its middle (``idle`` if none)."""
        gaps = [(s, d) for s, d in _gaps(_merge(
            (s, s + d) for _, s, d, *_ in self.ops))]
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


def opcode(hlo: str) -> str:
    """The opcode of an op event's HLO text ("" where it has none)."""
    m = _OP.match(hlo)
    return m.group(2) if m else ""


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


def reduce(path, host_prefix=("bench.", "repro.")) -> Reduced:
    """The trace at ``path`` as the readers get it: every TPU plane's op
    and module events and the host spans named ``host_prefix``."""
    import scopes    # here: scopes imports this module

    r = Reduced()
    busy, spans = [], []
    for plane in scopes.read(path):
        if DEVICE_PLANE.fullmatch(plane.name):
            r.planes += 1
            r.line_names[plane.name] = sorted({n for n, _ in plane.lines})
            ops = [(n, float(s), float(d), st.get("tf_op") or "",
                    st.get("program_id"))
                   for ln, evs in plane.lines if ln == OPS
                   for n, s, d, st in evs]
            r.ops += ops
            r.modules += [(n, float(s), float(d))
                          for ln, evs in plane.lines if ln == MODULES
                          for n, s, d, _ in evs]
            if ops:
                busy.append(busy_ns((s, s + d) for _, s, d, *_ in ops))
                spans.append(max(s + d for _, s, d, *_ in ops)
                             - min(s for _, s, *_ in ops))
        elif plane.name == HOST_PLANE:
            r.host += [(n, float(s), float(d)) for _, evs in plane.lines
                       for n, s, d, _ in evs if n.startswith(host_prefix)]
    if r.planes:
        r.busy_s = sum(busy) / r.planes * 1e-9
        r.span_s = (max(spans) if spans else 0.0) * 1e-9
    return r

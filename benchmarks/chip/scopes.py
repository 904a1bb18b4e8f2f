"""Program stages in a JAX profiler trace (``.xplane.pb``): the file's
planes, lines and events (``read``), which ``jax.named_scope`` each device
operation ran under, and the program's own host spans (``repro.*``, opened
through ``repro.obs.trace``), over the ``xplane.Reduced`` that
``xplane.reduce`` builds from ``read``.

    python3 benchmarks/chip/scopes.py <trace.xplane.pb | directory>

prints the stage split of one ``jit_serve_step`` execution, the engine's
host time per frontend step and the longest idle gaps named by the
innermost host span over them, as one JSON object.

Where an operation's scope comes from: the TPU profiler gives each op
event's metadata a ``tf_op`` stat, the HLO ``op_name`` of the instruction
(``jit(serve_step)/while/body/moe.exchange/moe.expert/...``); a fusion
carries its own. The stage of an op is the innermost segment of its
``op_name`` that names a stage.

The file is read with the standard library alone: the protobuf wire format
of ``XSpace`` (``xplane.proto``), only the fields named below. It is not
read through ``jax.profiler.ProfileData``, because ProfileData (jax 0.9.0)
exposes an event's own stats only (``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier`` on a TPU op) and not its
metadata's, where ``tf_op`` and ``program_id`` are. The events and times
are those ProfileData gives.
"""
from __future__ import annotations

import bisect
import json
import pathlib
import re
import sys
from dataclasses import dataclass, field
from typing import Optional

import xplane

#: the serve step's scopes (``models/model.py``, ``models/moe.py``)
STAGES = ("attention", "moe.route", "moe.exchange", "moe.expert", "lm_head")
#: the program's host spans; the frontend step and the device wait in it
HOST_PREFIX = "repro."
FRONTEND_STEP, ENGINE_SYNC = "repro.frontend.step", "repro.engine.sync"
SERVE_STEP = r"^jit_serve_step\("
#: ops whose event spans their body's ops, which are events of their own
CONTROL_FLOW = ("while", "conditional")

# field numbers: XSpace.planes 1; XPlane name 2, lines 3, event_metadata 4
# (map: key 1, value 2), stat_metadata 5; XLine name 2, timestamp_ns 3,
# events 4; XEvent metadata_id 1, offset_ps 2, duration_ps 3;
# XEventMetadata id 1, name 2, stats 5; XStatMetadata id 1, name 2; XStat
# metadata_id 1, uint64 3, str 5.


def _varint(b, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b):
    """(field number, value) of one message: ints for varints, a
    memoryview for length-delimited fields, bytes for fixed ones."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        num, kind = key >> 3, key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif kind == 1:
            v, i = bytes(b[i:i + 8]), i + 8
        elif kind == 5:
            v, i = bytes(b[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {kind} in a trace")
        yield num, v


def _stat_value(stat, names: dict):
    """(stat name, value) of one XStat whose value is a uint64 or a string
    (``program_id``, ``tf_op``); None for any other kind."""
    sid, value = None, None
    for num, v in _fields(stat):
        if num == 1:
            sid = v
        elif num == 3:
            value = v
        elif num == 5:
            value = bytes(v).decode(errors="replace")
    return names.get(sid), value


def _map_entries(entry):
    key = value = None
    for num, v in _fields(entry):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


@dataclass
class Plane:
    """One plane of a trace: its name and its lines, in file order, each
    ``(line name, [(event name, start ns, duration ns, stats)])`` where
    ``stats`` are the event metadata's uint64 and string stats."""
    name: str
    lines: list = field(default_factory=list)


def _plane(buf) -> tuple[str, list, list, dict]:
    name, lines, emeta, snames = "", [], [], {}
    for num, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode(errors="replace")
        elif num == 3:
            lines.append(v)
        elif num == 4:
            emeta.append(v)
        elif num == 5:
            _, meta = _map_entries(v)
            sid = sname = None
            for n2, v2 in _fields(meta):
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = bytes(v2).decode(errors="replace")
            snames[sid] = sname
    return name, lines, emeta, snames


def _event_metadata(entries, snames) -> dict:
    """id -> (name, {stat name: value})."""
    out = {}
    for entry in entries:
        _, meta = _map_entries(entry)
        mid, name, stats = None, "", {}
        for num, v in _fields(meta):
            if num == 1:
                mid = v
            elif num == 2:
                name = bytes(v).decode(errors="replace")
            elif num == 5:
                k, val = _stat_value(v, snames)
                stats[k] = val
        out[mid] = (name, stats)
    return out


def _line(buf) -> tuple[str, list]:
    """(line name, [(metadata id, start ns, duration ns)]), the times cut
    to whole nanoseconds as ``jax.profiler.ProfileData`` gives them."""
    name, t0, events = "", 0, []
    for num, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode(errors="replace")
        elif num == 3:
            t0 = v
        elif num == 4:
            events.append(v)
    out = []
    for ev in events:
        mid = off = dur = 0
        for num, v in _fields(ev):
            if num == 1:
                mid = v
            elif num == 2:
                off = v
            elif num == 3:
                dur = v
        out.append((mid, t0 + off // 1000, dur // 1000))
    return name, out


def read(path) -> list[Plane]:
    """Every plane of the trace with its lines and events: the names and
    times ``jax.profiler.ProfileData`` gives (``test_scopes.py``), and each
    event's metadata stats, among them an op's HLO ``op_name`` (``tf_op``)
    and ``program_id``."""
    data = memoryview(pathlib.Path(path).read_bytes())
    planes = []
    for num, buf in _fields(data):
        if num != 1:
            continue
        name, lines, emeta, snames = _plane(buf)
        meta = _event_metadata(emeta, snames)
        plane = Plane(name)
        for ln in lines:
            lname, events = _line(ln)
            plane.lines.append((lname, [(meta[m][0], s, d, meta[m][1])
                                        for m, s, d in events]))
        planes.append(plane)
    return planes


def stage_of(op_name: str, stages=STAGES) -> Optional[str]:
    """The innermost segment of an HLO ``op_name`` that names a stage."""
    for seg in reversed(op_name.split("/")):
        seg = seg.split(":")[0]
        if seg in stages:
            return seg
    return None


def _program_id(module_name: str) -> Optional[int]:
    m = re.search(r"\((\d+)\)$", module_name)
    return int(m.group(1)) if m else None


def stage_ms(tr, module: str = SERVE_STEP, stages=STAGES) -> dict:
    """Device time per execution of ``module`` (ms) in an
    ``xplane.Reduced``: summed over the ops of each complete execution in
    the trace, by the innermost of ``stages`` each op ran under, plus
    ``unscoped`` (ops in no stage) and ``total`` (every op of the
    execution); ``executions`` counts them. A loop's or a branch's own op
    event spans the ops of its body, which are events of their own, and is
    left out. Empty when the trace holds none."""
    rx = re.compile(module)
    runs = sorted((s, s + d, _program_id(n)) for n, s, d in tr.modules
                  if rx.search(n))
    if not runs:
        return {}
    starts = [r[0] for r in runs]
    sums = dict.fromkeys(stages + ("unscoped",), 0.0)
    for text, s, d, op_name, pid in tr.ops:
        k = bisect.bisect_right(starts, s) - 1
        if (k < 0 or s > runs[k][1] or pid != runs[k][2]
                or xplane.opcode(text) in CONTROL_FLOW):
            continue
        stage = stage_of(op_name, stages) or "unscoped"
        sums[stage] += d
    out = {k: v * 1e-6 / len(runs) for k, v in sums.items()}
    out["total"] = sum(sums.values()) * 1e-6 / len(runs)
    out["executions"] = len(runs)
    return out


def span_ms_per_step(tr, outer: str = FRONTEND_STEP) -> dict:
    """Each ``repro.*`` host span's time per ``outer`` span (ms), the
    ``outer`` spans included, over the spans that lie inside one: a trace
    starts and stops at any point of a step, so it can hold the inner
    spans of a step whose own span it lacks. Empty when it holds none."""
    outs = sorted((s, s + d) for n, s, d in tr.host if n == outer)
    if not outs:
        return {}
    starts = [o[0] for o in outs]
    out: dict = {}
    for n, s, d in tr.host:
        k = bisect.bisect_right(starts, s) - 1
        if n.startswith(HOST_PREFIX) and k >= 0 and s + d <= outs[k][1]:
            out[n] = out.get(n, 0.0) + d * 1e-6 / len(outs)
    return out


def host_step_ms(tr, outer: str = FRONTEND_STEP,
                 inner: str = ENGINE_SYNC) -> Optional[float]:
    """Mean over the ``outer`` spans of the trace of the span less its
    ``inner`` children (ms): the engine's host time on the critical path
    per frontend step, the wait on the device left out."""
    per = span_ms_per_step(tr, outer)
    return per[outer] - per.get(inner, 0.0) if per else None


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    path = pathlib.Path(argv[0])
    tr = xplane.reduce(xplane.find_xplane(path) if path.is_dir() else path)
    print(json.dumps({"stage_ms": stage_ms(tr),
                      "engine_host_ms": host_step_ms(tr),
                      "idle_gaps": tr.idle_gaps()}))


if __name__ == "__main__":
    main()

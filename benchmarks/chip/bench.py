#!/usr/bin/env python3
"""On-chip benchmark of elastic expert-parallel MoE serving.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for. ``BENCHMARK.json`` at the root names the cell; the harness finds
everything else under this directory by name:

- ``configs/<config>.json``: the model at published widths under its
  family's Hugging Face keys, its cuts, the deployment (EP ranks, the
  ranks whose slots this chip holds, slots, dispatch, dtype, batch, cache
  length), and the names of its adapter and its reference;
- ``adapters/<adapter>.py``: the configuration onto the program:
  ``arch_config(conf)`` (the program's ``ArchConfig``; ValueError for a
  file the program cannot run), ``build(cfg, conf, seed)`` (the serving
  instance: runtime, engine, frontend) and ``compile_parts(cfg, conf)``
  (what ``compile_check.py`` compiles); the harness refuses an
  ``ArchConfig`` whose widths differ from the file's;
- ``traffic/<mix>.json``: the open-loop load or the closed batch
  (``loadgen.py``), the rank faults, the drain cap, the correctness sample
  and its limit;
- ``metrics/<metric>.py``: one reader per metric, ``read(run)``, returning
  a number or ``None`` when the run has nothing for it to read;
- ``reference/<family>.py``: the plain float32 model that decides
  ``correct``; ``peaks.json``: the chip's peaks by ``device_kind``.

A run builds the instance through the configuration's adapter, warms
every program the window calls (the serve step, the slot reset, the
donor-row gather after a prefix fill, and one rank fault with its repair
and rejoin in a fault cell), offers the load from ``preroll_s`` before the
window opens, measures ``--seconds`` on the wall clock, follows every
request sent in the window to its end (at most ``drain_cap_s``), frees the
instance, and compares a sample of the served tokens with the reference.
Latency runs from each request's scheduled send time.

A closed mix is an offline batch job instead: every row of the batch holds
a request from the job's queue and takes the next as soon as it finishes.
It drives the program's compiled serve step (``make_serve_step``, the step
the engine compiles) over the instance's parameters, membership and KV
cache, feeding each row its prompt one position per step and then its
own greedy tokens, as the engine's replay does; the preroll warms the step
and mixes the rows' phases. The window closes once every step sent in its
``--seconds`` has been read back. The last line of
standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error.

Exits nonzero with no result when JAX finds no TPU, fewer chips than the
cell asks for, or no program beside the benchmark.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import deque  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Optional  # noqa: E402

import numpy as np  # noqa: E402

CHIP_DIR = pathlib.Path(__file__).resolve().parent
ROOT = CHIP_DIR.parents[1]
if str(CHIP_DIR) not in sys.path:
    sys.path.insert(0, str(CHIP_DIR))

import flops  # noqa: E402
import loadgen  # noqa: E402
import scopes  # noqa: E402
import xplane  # noqa: E402

#: the traced slice: the window's last seconds, so that stopping the
#: profiler (which holds the host for a while) delays no request's send
TRACE_S = 3.0
WARM_NEW = 4                     # tokens a warm-up request asks for
#: steps a closed batch dispatches before it reads one back: about a second
#: of the serve step, so that the host standing still that long leaves the
#: chip fed (each holds the step's float32 logits, 16 MiB at 128 x 32768,
#: until it has run)
AHEAD = 64


class Refused(SystemExit):
    """A run that cannot measure: exits nonzero and prints no result."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


# ---------------------------------------------------------------------------
# The files a cell is made of
# ---------------------------------------------------------------------------


def load_cell(spec: dict, workload: str, chip_dir: pathlib.Path,
              root: pathlib.Path) -> tuple[dict, dict, dict]:
    """(cell, configuration file, mix file) of ``workload``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json; have "
                      f"{sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = json.loads((root / confs[cell["config"]]["file"]).read_text())
    mix = loadgen.load_mix(chip_dir / "traffic" / f"{cell['traffic']}.json")
    if loadgen.longest_context(mix) > conf["deployment"]["max_len"]:
        raise Refused(f"{cell['traffic']}: a request can hold "
                      f"{loadgen.longest_context(mix)} positions, more than "
                      f"max_len {conf['deployment']['max_len']}")
    return cell, conf, mix


def cell_metrics(spec: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(chip_dir: pathlib.Path, name: str):
    path = chip_dir / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def load_reference(chip_dir: pathlib.Path, family: str, model: dict,
                   dtype: str):
    path = chip_dir / "reference" / f"{family}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_reference_{family}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.build(model, dtype)


def load_adapter(chip_dir: pathlib.Path, conf: dict):
    """The module ``adapters/<conf["adapter"]>.py``."""
    name = conf.get("adapter")
    path = chip_dir / "adapters" / f"{name}.py"
    if not name or not path.is_file():
        raise Refused(f"{conf['name']}: no adapter {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_adapter_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def built_widths(cfg, model: dict) -> dict:
    """{file key: (the file's value, the ArchConfig's)} of every width the
    harness holds an adapter to: hidden size, heads, head size where the
    file gives one, vocabulary, depth, routed experts, experts per token
    and the expert width, each under the key the file names it by."""
    moe = cfg.moe
    pairs = {"hidden_size": cfg.d_model,
             "num_attention_heads": cfg.num_heads,
             "vocab_size": cfg.vocab_size,
             "num_hidden_layers": cfg.num_layers,
             flops.expert_count_key(model) or "num_local_experts":
                 moe and moe.num_experts,
             "num_experts_per_tok": moe and moe.top_k,
             flops.expert_width_key(model): moe and moe.d_expert}
    if model.get("head_dim"):
        pairs["head_dim"] = cfg.head_dim
    return {k: (model.get(k), v) for k, v in pairs.items()}


def arch_config(conf: dict, adapter=None):
    """The adapter's ArchConfig for a configuration file, refused where
    the adapter cannot run the file or built other widths than it gives.
    ``adapter`` is the module ``load_adapter`` gives; by default the one
    under this directory that the file names, or ``mixtral`` for a file
    that names none (the program's own tests build such files)."""
    if adapter is None:
        adapter = load_adapter(CHIP_DIR, dict({"adapter": "mixtral"}, **conf))
    try:
        cfg = adapter.arch_config(conf)
    except ValueError as e:
        raise Refused(f"{conf['name']}: {e}") from None
    for key, (given, built) in built_widths(cfg, conf["model"]).items():
        if given != built:
            raise Refused(f"{conf['name']}: the adapter built {key}={built}"
                          f", the file gives {given}")
    return cfg


# ---------------------------------------------------------------------------
# What a run records
# ---------------------------------------------------------------------------


@dataclass
class Req:
    rid: int
    t_sched: float            # scheduled send (perf_counter seconds)
    prompt: tuple
    max_new: int
    in_window: bool           # sent in the window (closed: finished in it)
    t_submit: float = -1.0
    t_admit: float = -1.0     # first seen in the scheduler's running set
    prefix_skip: int = 0
    token_t: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    outcome: Optional[str] = None
    stall: Optional[tuple] = None      # (fault index, last token time)
    resumed_after: list = field(default_factory=list)   # fault indices


@dataclass
class Fault:
    rank: int
    t_inject: float
    t_commit: float = -1.0
    step_s: float = -1.0               # wall time of the committing step
    suspended: list = field(default_factory=list)     # rids
    replay_positions: int = 0
    stalls_s: list = field(default_factory=list)      # per resumed stream
    t_rejoin: float = -1.0


@dataclass
class Run:
    """Everything a metric reader may read."""
    cell: dict
    model: dict
    deployment: dict
    peak: dict
    seconds: float
    t_process: float
    t_window: float = 0.0
    t_window_end: float = 0.0
    requests: list = field(default_factory=list)
    faults: list = field(default_factory=list)
    counters_start: dict = field(default_factory=dict)
    counters_end: dict = field(default_factory=dict)
    steps: list = field(default_factory=list)    # (t0, t1, fed, ctx_sum)
    compiles_in_window: int = 0
    memory: dict = field(default_factory=dict)
    trace: Optional[xplane.Reduced] = None
    trace_window: tuple = ()                     # (t0, t1) of the trace

    def window_requests(self) -> list[Req]:
        return [r for r in self.requests if r.in_window]

    def steps_between(self, t0: float, t1: float) -> list[tuple]:
        return [s for s in self.steps if s[0] >= t0 and s[1] <= t1]


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


class CellRun:
    def __init__(self, spec: dict, workload: str, seed: int, seconds: float,
                 trace: bool, *, chip_dir: pathlib.Path = CHIP_DIR,
                 root: pathlib.Path = ROOT):
        self.spec, self.workload = spec, workload
        self.seed, self.seconds, self.trace = seed, float(seconds), trace
        self.chip_dir = chip_dir
        self.cell, self.conf, self.mix = load_cell(spec, workload, chip_dir,
                                                   root)
        peaks = json.loads((chip_dir / "peaks.json").read_text())
        self.peaks = peaks
        self.run = Run(cell=self.cell, model=self.conf["model"],
                       deployment=self.conf["deployment"], peak={},
                       seconds=self.seconds, t_process=T_PROCESS)
        self._lowerings = 0
        self._gc_t0 = 0.0
        self._gc_pauses: list[tuple] = []     # (start, seconds), full ones

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self._gc_pauses.append((self._gc_t0,
                                    time.perf_counter() - self._gc_t0))

    # -- set-up ---------------------------------------------------------------
    def build(self):
        import jax
        import jax.numpy as jnp
        from jax import monitoring

        adapter = load_adapter(self.chip_dir, self.conf)
        self.cfg = arch_config(self.conf, adapter)
        kind = jax.devices()[0].device_kind
        if kind not in self.peaks and jax.devices()[0].platform == "tpu":
            raise Refused(f"no peaks for device_kind {kind!r} in peaks.json")
        self.run.peak = self.peaks.get(kind, {})
        monitoring.register_event_duration_secs_listener(self._on_event)
        gc.callbacks.append(self._on_gc)
        self.rt, self.eng, self.fe = adapter.build(self.cfg, self.conf,
                                                   self.seed)
        jax.block_until_ready(self.rt.params)
        if "closed" in self.mix:
            from repro.launch.steps import make_serve_step
            self.batch_step = jax.jit(make_serve_step(self.cfg, self.rt.dpl),
                                      donate_argnums=(1,))
            vocab = self.cfg.vocab_size
            # a row's input: its prompt token where the host gives one (>= 0),
            # else the token the last step served it
            self.pick_tokens = jax.jit(lambda given, nxt: jnp.where(
                given >= 0, given, nxt[:, 0] % vocab)[:, None])

    def _on_event(self, name: str, _dur: float, **_kw) -> None:
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self._lowerings += 1

    def _step(self) -> float:
        """One frontend step; returns the wall time it ended. The positions
        the step fed are read from the KV pool's public state around it: a
        row that kept its request advanced by what its length grew, a row
        admitted in the step from its request's prefix skip. A row that
        finished in the step has left the pool and is not counted."""
        import jax

        kv = self.eng.kv
        before = {s: (kv.owner_of(s), kv.length_of(s))
                  for s in kv.active_slots()}
        with jax.profiler.TraceAnnotation("bench.step"):
            t0 = time.perf_counter()
            self.fe.step()
            t1 = time.perf_counter()
        running = self.eng.sched.running
        fed = ctx = 0
        for s in kv.active_slots():
            rid, c = kv.owner_of(s), kv.length_of(s)
            if before.get(s, (None,))[0] == rid:
                start = before[s][1]
            else:
                req = running.get(rid)
                start = req.prefix_skip if req is not None else c - 1
            n = max(1, c - start)
            fed += n
            ctx += n * c - n * (n - 1) // 2   # contexts c-n+1 .. c
        if fed:
            self.run.steps.append((t0, t1, fed, ctx))
        return t1

    def _busy(self) -> bool:
        s, rt = self.eng.sched, self.rt
        return bool(s.queue or s.inflight or rt.control_queue
                    or rt.controller.recovering)

    def warm(self, prefixes) -> None:
        """Run every program the window will call, once."""
        rng = np.random.default_rng(np.random.SeedSequence(self.seed)
                                    .spawn(3)[2])
        if prefixes:
            # the shared system prompts, prefilled into the prefix cache
            prompts = [list(p) for p in prefixes]
        else:
            n = 2 * self.conf["deployment"]["kv_block_size"] + 1
            prompts = [rng.integers(1, self.cfg.vocab_size, n).tolist()
                       for _ in range(2)]
        self._serve([(p, 1 if prefixes else WARM_NEW) for p in prompts])
        if prefixes or self.mix.get("faults"):
            # requests that borrow a cached prompt: the donor-row gather of
            # a prefix hit, and of a resume after a fault (a resumed
            # request finds its own prompt's blocks in the cache)
            self._serve([(p + [1], 1) for p in prompts])
        if self.mix.get("faults"):
            # one fault, its repair and the rank's rejoin, on the idle engine
            self.rt.injector.inject_at(self.rt.clock.now(), [0], "sigkill")
            commits = len(self._commits())
            while self._busy() or len(self._commits()) < commits + 2:
                self._step()
        self.run.steps.clear()

    def _serve(self, requests) -> None:
        for prompt, max_new in requests:
            self.fe.submit(prompt, max_new=max_new)
        while self._busy():
            self._step()

    def _commits(self) -> list:
        return [ev for ev in self.rt.timeline
                if ev.kind == "membership_commit"]

    # -- the load ---------------------------------------------------------------
    def drive(self, arrivals) -> None:
        import jax

        run, mix = self.run, self.mix
        t0 = time.perf_counter()
        run.t_window = t0 + mix["preroll_s"]
        run.t_window_end = run.t_window + self.seconds
        cap = run.t_window_end + mix["drain_cap_s"]
        reqs = [Req(rid=-1, t_sched=t0 + a.t, prompt=a.prompt,
                    max_new=a.max_new,
                    in_window=run.t_window <= t0 + a.t < run.t_window_end)
                for a in arrivals]
        run.requests = reqs
        by_rid: dict[int, Req] = {}
        live: dict[int, list] = {}       # rid -> [handle, events read]
        faults = [run.t_window + t
                  for t in loadgen.fault_times(mix, self.seconds)]
        order = np.random.default_rng(np.random.SeedSequence(self.seed)
                                      .spawn(4)[3]).permutation(
            self.conf["deployment"]["ep_ranks"])
        waiting: set[int] = set()
        trace_dir = None
        tr0, tr1 = run.t_window_end - TRACE_S, run.t_window_end
        tracing_on = False
        window_open = window_closed = False
        i = 0
        while True:
            now = time.perf_counter()
            while i < len(reqs) and reqs[i].t_sched <= now:
                r = reqs[i]
                with jax.profiler.TraceAnnotation("bench.submit"):
                    h = self.fe.submit(list(r.prompt), max_new=r.max_new)
                r.rid, r.t_submit = h.rid, time.perf_counter()
                by_rid[h.rid] = r
                if h.done:
                    r.outcome = h.outcome
                else:
                    waiting.add(h.rid)
                    live[h.rid] = [h, 0]
                i += 1
            if faults and now >= faults[0]:
                faults.pop(0)
                rank = self._pick_rank(order)
                self.rt.injector.inject_at(self.rt.clock.now(), [rank],
                                           "sigkill")
                run.faults.append(Fault(rank=rank, t_inject=now))
            if not window_open and now >= run.t_window:
                window_open = True
                run.counters_start = self._counters()
                self._lowerings = 0
            if self.trace and not tracing_on and trace_dir is None \
                    and now >= tr0:
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(trace_dir)
                tracing_on = True
                run.trace_window = (time.perf_counter(),)
            if tracing_on and now >= tr1:
                run.trace_window += (time.perf_counter(),)
                jax.profiler.stop_trace()
                tracing_on = False
            if not window_closed and now >= run.t_window_end:
                window_closed = True
                run.counters_end = self._counters()
                run.compiles_in_window = self._lowerings
            if window_closed and not tracing_on and (
                    now >= cap or all(r.outcome for r in reqs
                                      if r.in_window)):
                break
            if not self._busy():
                with jax.profiler.TraceAnnotation("bench.idle"):
                    nxt = reqs[i].t_sched if i < len(reqs) else now + 1e-3
                    time.sleep(max(0.0, min(nxt - now, 2e-3)))
                continue
            b = len(self.rt.timeline)
            s0 = time.perf_counter()
            t = self._step()
            with jax.profiler.TraceAnnotation("bench.stamp"):
                self._stamp(t, by_rid, live, waiting)
                for ev in self.rt.timeline[b:]:
                    if ev.kind != "membership_commit":
                        continue
                    kind = ev.detail.get("transition")
                    if kind == "fault" and run.faults and \
                            run.faults[-1].t_commit < 0:
                        f = run.faults[-1]
                        f.t_commit, f.step_s = t, t - s0
                    elif kind == "join":
                        for f in run.faults:
                            if f.t_commit >= 0 and f.t_rejoin < 0:
                                f.t_rejoin = t
                                break
        if trace_dir is not None:
            run.trace = xplane.reduce(xplane.find_xplane(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
        for r in reqs:
            h = self.fe.streams.get(r.rid)
            if h is not None:
                r.tokens = list(h.tokens)
                r.outcome = r.outcome or h.outcome

    def drive_closed(self) -> None:
        """The closed batch: every row busy from the first step. Which row
        feeds what position, and when a request finishes, follow from the
        lengths alone, so the host dispatches up to ``AHEAD`` steps before
        it reads one back: a row's next input, when it is its own last
        token, is picked on the device (``pick_tokens``). The preroll's
        steps are all read before the window opens; when the window's time
        is up nothing more is sent, every step sent is read, and the clock
        read after that closes the window."""
        import jax
        import jax.numpy as jnp

        run, dep = self.run, self.conf["deployment"]
        B, vocab = dep["max_batch"], self.cfg.vocab_size
        queue = loadgen.closed_queue(self.mix, self.seed, vocab)
        rows = np.arange(B)
        prompts = np.zeros((B, dep["max_len"]), np.int32)
        plen = np.zeros(B, np.int64)
        pos = np.zeros(B, np.int64)       # the position each row feeds next
        left = np.zeros(B, np.int64)      # tokens each row has still to give
        held: list[Req] = [None] * B
        sent: deque = deque()             # steps dispatched, not yet read

        def take(b: int) -> None:
            prompt, max_new = next(queue)
            r = Req(rid=len(run.requests), t_sched=-1.0, prompt=prompt,
                    max_new=max_new, in_window=False)
            run.requests.append(r)
            held[b] = r
            prompts[b, :len(prompt)] = prompt
            plen[b], pos[b], left[b] = len(prompt), 0, max_new

        def read_one() -> float:
            s0, out, emit, owners, ctx, in_window = sent.popleft()
            with jax.profiler.TraceAnnotation("bench.read"):
                vals = np.asarray(out)[:, 0] % vocab
                t = time.perf_counter()
                for b, r in zip(emit, owners):
                    r.tokens.append(int(vals[b]))
                    r.token_t.append(t)
                    if len(r.tokens) == r.max_new:
                        r.outcome, r.in_window = "FINISHED", in_window
            if in_window:
                run.steps.append((s0, t, B, ctx))
            return t

        for b in rows:
            take(int(b))
        params, membership = self.rt.params, self.rt.membership
        caches = self.eng.caches
        nxt = jnp.zeros((B, 1), jnp.int32)
        t_open = time.perf_counter() + self.mix["preroll_s"]
        window_open = False
        close = np.inf
        trace_dir = None
        while True:
            now = time.perf_counter()
            if not window_open and now >= t_open:
                while sent:
                    read_one()
                window_open = True
                run.t_window = time.perf_counter()
                close = run.t_window + self.seconds
                self._lowerings = 0
            elif window_open and now >= close:
                break
            if self.trace and trace_dir is None and window_open \
                    and now >= close - TRACE_S:
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                jax.profiler.start_trace(trace_dir)
                run.trace_window = (time.perf_counter(),)
            with jax.profiler.TraceAnnotation("bench.step"):
                given = np.where(pos < plen,
                                 prompts[rows, np.minimum(pos, plen - 1)],
                                 -1).astype(np.int32)
                s0 = time.perf_counter()
                tokens = self.pick_tokens(jnp.asarray(given), nxt)
                nxt, _, caches = self.batch_step(
                    params, caches, membership, tokens,
                    jnp.asarray(pos.astype(np.int32)))
                ctx = int((pos + 1).sum())
                pos += 1
                emit = np.flatnonzero(pos >= plen)
                sent.append((s0, nxt, emit, [held[b] for b in emit], ctx,
                             window_open))
                left[emit] -= 1
                for b in emit[left[emit] == 0]:
                    take(int(b))
            while len(sent) > AHEAD:
                read_one()
        while sent:
            t = read_one()
        run.t_window_end = t
        run.compiles_in_window = self._lowerings
        del caches, nxt
        if trace_dir is not None:
            run.trace_window += (t,)
            jax.profiler.stop_trace()
            run.trace = xplane.reduce(xplane.find_xplane(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)

    def _pick_rank(self, order) -> int:
        """The next rank of the seeded order that is active and not already
        relaunching."""
        entries = self.rt.table.entries
        for r in order:
            r = int(r)
            if entries[r].active and not self.rt.controller.is_recovering(r):
                order[:] = np.roll(order, -1 - list(order).index(r))
                return r
        raise RuntimeError("no active rank left to fail")

    def _counters(self) -> dict:
        from dataclasses import asdict
        return asdict(self.eng.sched.stats)

    def _stamp(self, t: float, by_rid: dict, live: dict,
               waiting: set) -> None:
        """Stamp the stream events of the step that ended at ``t``, read
        from the clients' handles."""
        run = self.run
        for rid in list(live):
            h, seen = live[rid]
            if seen == len(h.events):
                continue
            r = by_rid[rid]
            for ev in h.events[seen:]:
                if ev.kind == "TOKEN":
                    if ev.index == len(r.token_t):
                        if r.stall is not None:
                            k, last = r.stall
                            if last is not None:
                                run.faults[k].stalls_s.append(t - last)
                            r.resumed_after.append(k)
                            r.stall = None
                        r.token_t.append(t)
                elif ev.kind == "STALL_BEGIN" and run.faults:
                    k = len(run.faults) - 1
                    run.faults[k].suspended.append(rid)
                    run.faults[k].replay_positions += (
                        len(r.prompt) + ev.detail["progress"])
                    r.stall = (k, r.token_t[-1] if r.token_t else None)
            live[rid][1] = len(h.events)
            if h.done:
                r.outcome = h.outcome
                del live[rid]
        running = self.eng.sched.running
        for rid in [x for x in waiting if x in running]:
            waiting.discard(rid)
            r = by_rid[rid]
            r.t_admit = t
            r.prefix_skip = running[rid].prefix_skip

    # -- after the window ---------------------------------------------------------
    def device_info(self) -> dict:
        import jax
        devs = jax.devices()
        stats = [d.memory_stats() or {} for d in devs]
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        limit = min(s.get("bytes_limit", 0) for s in stats) or None
        self.run.memory = {"peak_bytes_in_use": peak, "bytes_limit": limit}
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs), "memory_peak_bytes": peak}

    def sample(self) -> list[Req]:
        """The requests the reference checks: the longest finished one, in
        a fault cell one resumed after each fault and one admitted after
        the last rejoin, in a prefix cell one that borrowed a prefix, then
        others drawn from the seed, up to the mix's token and request
        counts."""
        done = [r for r in self.run.window_requests()
                if r.outcome == "FINISHED" and r.tokens]
        if not done:
            return []
        rng = np.random.default_rng(np.random.SeedSequence(self.seed)
                                    .spawn(5)[4])
        want = self.mix["sample"]
        picked = [max(done, key=lambda r: (len(r.tokens), -r.rid))]

        def take(pool):
            pool = [r for r in pool if r not in picked]
            if pool:
                picked.append(pool[int(rng.integers(len(pool)))])

        for k in range(len(self.run.faults)):
            take([r for r in done if k in r.resumed_after])
        rejoins = [f.t_rejoin for f in self.run.faults if f.t_rejoin > 0]
        if rejoins:
            take([r for r in done if r.t_admit > max(rejoins)])
        if "prefix" in self.mix:
            take([r for r in done if r.prefix_skip > 0])
        rest = [r for r in done if r not in picked]
        for j in rng.permutation(len(rest)):
            if (sum(len(r.tokens) for r in picked) >= want["min_tokens"]
                    or len(picked) >= want["max_requests"]):
                break
            picked.append(rest[int(j)])
        return picked[:want["max_requests"]]

    def free(self) -> None:
        """Drop the serving instance so the reference has the chip."""
        import jax
        del self.rt, self.eng, self.fe
        gc.callbacks.remove(self._on_gc)
        gc.unfreeze()
        gc.collect()
        for a in jax.live_arrays():
            a.delete()

    def gaps(self, picked: list[Req], precision: str = "float32") -> dict:
        """Per sampled request, the gaps of its served tokens against the
        reference (``reference_gaps``)."""
        ref = load_reference(self.chip_dir, self.conf["reference"],
                             self.conf["model"],
                             self.conf["deployment"]["dtype"])
        return reference_gaps(ref, self.seed, picked,
                              self.mix["sample"]["max_requests"],
                              self.conf["deployment"]["max_len"], precision)

    def prepare(self) -> Optional[list]:
        """Build and warm the instance; returns the run's arrivals, or
        ``None`` for a closed mix (its preroll warms the step)."""
        self.build()
        arrivals = None
        if "closed" not in self.mix:
            prefixes, arrivals = loadgen.build(self.mix, self.seed,
                                               self.seconds,
                                               self.cfg.vocab_size)
            self.warm(prefixes)
        # what set-up made lives to the end: out of the collector's way, so
        # that a full collection in the window walks only the load's objects
        gc.collect()
        gc.freeze()
        return arrivals

    def measure(self) -> None:
        """Set-up, the preroll and the window."""
        arrivals = self.prepare()
        if arrivals is None:
            self.drive_closed()
        else:
            self.drive(arrivals)

    def fresh_run(self) -> None:
        """Forget the last load's records (a sweep drives several)."""
        self.run = Run(cell=self.cell, model=self.conf["model"],
                       deployment=self.conf["deployment"], peak=self.run.peak,
                       seconds=self.seconds, t_process=T_PROCESS)

    def execute(self) -> dict:
        t_setup0 = time.perf_counter()
        self.measure()
        run = self.run
        device = self.device_info()
        picked = self.picked = self.sample()
        self.free()
        gaps = self.gaps(picked) if picked else {}
        attempted = run.window_requests()
        if not run.steps and any(r.token_t for r in attempted):
            raise RuntimeError("tokens were delivered, but no step fed a "
                               "row by the KV pool's state: the pool's "
                               "public state no longer shows the work")
        failed = [r for r in attempted if r.outcome != "FINISHED"]
        checks = gap_checks(gaps, self.mix["check"])
        correct = bool(picked) and all(c["value"] <= c["limit"]
                                       for c in checks.values())
        kind = "per_layer" if self.trace else "end_to_end"
        metrics = {}
        for m in cell_metrics(self.spec, self.workload, kind):
            value = load_reader(self.chip_dir, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if self.trace and run.trace is not None:
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace_window[1] - run.trace_window[0]
        info = {
            "setup_build_s": t_setup0 - T_PROCESS,
            "compiles_in_window": run.compiles_in_window,
            "sent": len(run.requests), "window_requests": len(attempted),
            "sample": [[r.rid, len(r.prompt), len(r.tokens)]
                       for r in picked],
            "gaps": gap_stats(gaps) if gaps else None,
            "faults": [{"rank": f.rank, "commit_step_s": f.step_s,
                        "suspended": len(f.suspended),
                        "replay_positions": f.replay_positions,
                        "stalls": len(f.stalls_s),
                        "rejoined": f.t_rejoin > 0} for f in run.faults],
            "steps": len(run.steps),
            "full_gc_in_window_s": [p for t, p in self._gc_pauses
                                    if run.t_window <= t < run.t_window_end],
            "memory": run.memory,
        }
        if run.trace is not None:
            # the serve step's stages with the unscoped rest and the op total
            info["stage_ms"] = scopes.stage_ms(run.trace)
            info["trace_lines"] = run.trace.line_names
            info["trace_modules"] = sorted({n for n, _, _ in
                                            run.trace.modules})[:20]
        print(json.dumps({"info": info}), flush=True)
        result = {"correct": correct, "attempted": len(attempted),
                  "failed": len(failed), "metrics": metrics,
                  "device": device}
        if self.trace and run.trace is not None:
            result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                                   "idle_gaps": run.trace.idle_gaps(10)}
        result["checks"] = checks
        return result


def reference_gaps(ref, seed: int, picked: list, batch: int, max_len: int,
                   precision: str = "float32") -> dict:
    """{rid: per-token gaps} over the served tokens of ``picked``: at each
    served position, how far the reference logit of the token chosen lies
    below the reference's best. The reference runs once over each prompt
    with its served tokens, in one right-padded batch of ``batch`` rows of
    ``max_len`` positions. With ``precision`` other than float32 the token
    chosen is the one that precision's reference puts first (the control);
    otherwise it is the served token."""
    tokens = np.zeros((batch, max_len), np.int32)
    rows, served, owner = [], [], []
    for b, r in enumerate(picked):
        seq = list(r.prompt) + list(r.tokens[:-1])
        tokens[b, :len(seq)] = seq
        p = len(r.prompt)
        for j, tok in enumerate(r.tokens):
            rows.append(b * max_len + p - 1 + j)
            served.append(tok)
            owner.append(r.rid)
    gap = ref.gaps(seed, tokens, np.asarray(rows), np.asarray(served),
                   "" if precision == "float32" else precision)
    owner = np.asarray(owner)
    return {int(rid): gap[owner == rid] for rid in dict.fromkeys(owner)}


def gap_checks(gaps: dict, check: dict) -> dict:
    """The numbers compared, each beside its limit: the share of served
    tokens, in percent, whose gap exceeds the mix's ``gap_tolerance``."""
    allg = np.concatenate(list(gaps.values())) if gaps else np.zeros(0)
    share = (100.0 * float((allg > check["gap_tolerance"]).mean())
             if allg.size else float("inf"))
    return {"mismatch_pct": {"value": share,
                             "limit": check["max_mismatch_pct"]}}


def gap_stats(gaps: dict) -> dict:
    """Readings of one run for setting a limit."""
    allg = np.concatenate(list(gaps.values()))
    return {"tokens": int(allg.size), "max": float(allg.max()),
            "q99": float(np.quantile(allg, 0.99)),
            "q999": float(np.quantile(allg, 0.999)),
            "pct_gt": {str(t): 100.0 * float((allg > t).mean())
                       for t in (0.0, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0)}}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {devs[0].platform!r} "
                      f"({devs[0].device_kind})")
    if len(devs) < n:
        raise Refused(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs


def checkout_spec() -> dict:
    """BENCHMARK.json of this checkout, with the checkout's program on the
    path and JAX's compile cache in the checkout."""
    spec_path = ROOT / "BENCHMARK.json"
    if not spec_path.is_file():
        raise Refused(f"no {spec_path}")
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import repro
    where = [pathlib.Path(p).resolve() for p in repro.__path__]
    if where != [ROOT / "src" / "repro"]:
        raise Refused(f"imported the program from {where}, not from this "
                      f"checkout")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program of a run in the cache, small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return json.loads(spec_path.read_text())


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = checkout_spec()
    run = CellRun(spec, args.workload, args.seed, args.seconds,
                  bool(args.trace))
    require_chips(run.cell["chips"])
    result = run.execute()
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

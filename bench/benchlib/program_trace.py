"""The program's own spans and named scopes in a traced window.

The training program marks its host phases with profiler spans
(``repro.tracing``: ``train.step`` and its children) and its device work
with ``jax.named_scope`` (``core.mace``: ``interaction``, ``symcon``, ...;
``train.engine``: ``optimizer``, ``allreduce``).  This module reloads the
run's ``.xplane.pb`` and gives, in a neutral ``ProgramTrace`` that the tests
can hand-build:

- the program's host spans, with their arguments (``train.step`` carries the
  step's counters) and the host thread each ran on;
- per device, the operations that contain no other operation, each with its
  scope and pass.

Scope and pass come from an operation's HLO ``op_name``, JAX's name stack:
``jit(loss_fn)/transpose(jvp(transpose(jvp(jvp(symcon)))))/...``.  The scope
is the first component whose innermost name is a known scope; the pass is
the number of ``transpose(`` in that component: 0 the forward, 1 a first
derivative (the force pass, or the parameter gradient of the forward), 2
the parameter gradient through the forces, the second-order pass.  A
kernel's own backward rule adds transforms in later components, which do
not count.  On a TPU the op_name is the ``tf_op`` stat of the op's event
metadata in the device plane.  An op the compiler made (a fusion) may have
none: it takes the op_name that most of the instructions it calls carry,
from the program's HLO in the ``/host:metadata`` plane.  JAX's
``ProfileData`` does not expose event metadata, so both are read from the
file's protobuf wire format here.

Device-idle time goes to host spans by overlap: each instant of a gap goes
to the innermost span open then (the latest started) on the training loop's
own thread (the one that holds ``train.step``), or to ``unattributed``.
Spans of other threads (``data.fetch`` on the prefetch thread) overlap the
loop's and are reported on their own, in host ms.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import json
import os
import re
import sys
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

from benchlib import trace as tr

Interval = Tuple[int, int]

SCOPES = ("edge_geometry", "embedding", "radial", "interaction", "linear",
          "symcon", "skip", "readout", "loss", "optimizer", "allreduce")
SPAN_PREFIXES = ("train.", "data.", "engine.")
STEP_SPAN = "train.step"
WAIT_SPAN = "train.wait_batch"
UNATTRIBUTED = "unattributed"
UNSCOPED = "unscoped"
SECOND_ORDER = 2
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_cache", "trace")


class Span(NamedTuple):
    name: str
    start: int
    end: int
    args: Dict[str, object]
    thread: str = ""                     # the host plane's line


class DeviceOp(NamedTuple):
    start: int
    end: int
    scope: Optional[str]
    pass_: int


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Span]                    # program spans, by start
    ops: Dict[str, List[DeviceOp]]       # device -> leaf ops, by start
    window: Interval
    _idle: Optional[Dict[str, float]] = dataclasses.field(default=None,
                                                          repr=False)

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)


def from_events(spans: Sequence[Span], ops: Dict[str, Sequence[DeviceOp]],
                window: Interval) -> ProgramTrace:
    return ProgramTrace(sorted(spans, key=lambda s: (s.start, -s.end)),
                        {d: sorted(o) for d, o in ops.items()}, window)


# ----------------------------- scope and pass --------------------------------

_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def classify(op_name: str) -> Tuple[Optional[str], int]:
    """(scope, pass) of an op's name stack; the scope is None when no
    component names one, and the pass is then read from the first
    component below the ``jit(...)`` wrappers."""
    first = None
    for comp in op_name.split("/"):
        inner = comp
        while (m := _WRAPPED.match(inner)):
            inner = m.group(1)
        if inner in SCOPES:
            return inner, comp.count("transpose(")
        if first is None and not comp.startswith(("jit(", "pjit(")):
            first = comp
    return None, (first or "").count("transpose(")


# ----------------------------- loading ---------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a protobuf message: ints for varints,
    memoryviews for everything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _packed(v) -> List[int]:
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _event_metadata(plane) -> Dict[str, Dict[str, object]]:
    """XPlane fields 4 (event metadata) and 5 (stat metadata): each event
    metadata's name and display name, and its stats by name."""
    stat_names, raw = {}, []
    for f, v in _fields(plane):
        if f == 5:
            entry = dict(_fields(v))
            meta = dict(_fields(entry.get(2, b"")))
            stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        elif f == 4:
            raw.append(dict(_fields(v)).get(2, b""))
    out = {}
    for em in raw:
        rec = {"name": "", "display": "", "stats": {}}
        for f, v in _fields(em):
            if f == 2:
                rec["name"] = _text(v)
            elif f == 4:
                rec["display"] = _text(v)
            elif f == 5:
                st = dict(_fields(v))
                value = st.get(5, st.get(6, st.get(3, st.get(4))))
                rec["stats"][stat_names.get(st.get(1))] = value
        out.setdefault(rec["name"], rec)
    return out


def _hlo_op_names(proto) -> Dict[str, str]:
    """Instruction name -> op_name of one HloProto; an instruction with
    none takes the op_name most of the instructions it calls carry."""
    comps: Dict[int, List[Tuple[str, str, List[int]]]] = {}
    for f, module in _fields(proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            cid, insts = None, []
            for h, v in _fields(comp):
                if h == 5:
                    cid = v
                elif h == 2:
                    name, op_name, called = "", "", []
                    for k, x in _fields(v):
                        if k == 1:
                            name = _text(x)
                        elif k == 7:
                            op_name = next((_text(y) for a, y in _fields(x)
                                            if a == 2), "")
                        elif k == 38:
                            called += _packed(x)
                    insts.append((name, op_name, called))
            comps[cid] = insts

    @functools.lru_cache(maxsize=None)
    def called_name(cid: int, depth: int = 0) -> str:
        votes = collections.Counter()
        for _, op_name, called in comps.get(cid, ()):
            if not op_name and depth < 4:
                op_name = next((n for c in called
                                if (n := called_name(c, depth + 1))), "")
            if op_name:
                votes[op_name] += 1
        return votes.most_common(1)[0][0] if votes else ""

    return {name: op_name or next((n for c in called
                                   if (n := called_name(c))), "")
            for insts in comps.values() for name, op_name, called in insts}


def _op_names(path: str) -> Dict[str, Dict[str, str]]:
    """Per device plane: event name (the HLO text) -> op_name."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for f, plane in _fields(space):
        if f == 1:
            name = next((_text(v) for g, v in _fields(plane) if g == 2), "")
            planes[name] = plane
    programs = {}
    meta = planes.get("/host:metadata")
    if meta is not None:
        for name, rec in _event_metadata(meta).items():
            m = re.search(r"\((\d+)\)$", name)
            proto = rec["stats"].get("Hlo Proto")
            if m and proto is not None:
                programs[int(m.group(1))] = proto
    hlo = {}
    out = {}
    for pname, plane in planes.items():
        if not _is_device(pname):
            continue
        names = {}
        for name, rec in _event_metadata(plane).items():
            op = rec["stats"].get("tf_op")
            if op is None:
                pid = rec["stats"].get("program_id")
                if pid in programs and pid not in hlo:
                    hlo[pid] = _hlo_op_names(programs[pid])
                op = hlo.get(pid, {}).get(rec["display"], "")
            names[name] = _text(op) if isinstance(op, memoryview) else op
        out[pname] = names
    return out


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "TPU" in plane_name


def _arg(value):
    if isinstance(value, str) and value[:1] in "[{":
        try:
            return json.loads(value)
        except ValueError:
            return value
    return value


def load(path: str) -> ProgramTrace:
    """Read a JAX profiler xplane file into a ``ProgramTrace``."""
    from jax.profiler import ProfileData

    names = _op_names(path)
    data = ProfileData.from_file(path)
    ops: Dict[str, List[DeviceOp]] = {}
    spans: List[Span] = []
    window = None
    for plane in data.planes:
        if _is_device(plane.name):
            raw = []
            for line in plane.lines:
                if line.name != tr.OPS_LINE:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    raw.append((e.name, s, s + int(e.duration_ns)))
            if raw:
                table = names.get(plane.name, {})
                kinds = {n: classify(table.get(n, "")) for n in set(
                    r[0] for r in raw)}
                ops[plane.name] = [DeviceOp(s, e, *kinds[n])
                                   for n, s, e in tr.leaf_ops(raw)]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                thread = f"{plane.name}#{i}"
                for e in line.events:
                    s = int(e.start_ns)
                    if e.name == tr.WINDOW_SPAN:
                        window = (s, s + int(e.duration_ns))
                    elif e.name.startswith(SPAN_PREFIXES):
                        spans.append(Span(e.name, s, s + int(e.duration_ns),
                                          {k: _arg(v) for k, v in e.stats},
                                          thread))
    if window is None:
        every = [o for v in ops.values() for o in v] + spans
        window = (min(o.start for o in every), max(o.end for o in every))
    return from_events(spans, ops, window)


def newest_xplane(root: str = TRACE_ROOT) -> Optional[str]:
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


@functools.lru_cache(maxsize=1)
def _steps_trace(path: str, n_steps: int) -> Optional[ProgramTrace]:
    pt = load(path)
    steps = step_spans(pt, n_steps)
    if not steps:
        return None
    pt = dataclasses.replace(pt, window=(pt.window[0], steps[-1].end))
    for line in notes(pt):
        print(line, file=sys.stderr)
    return pt


def of_run(ctx) -> Optional[ProgramTrace]:
    """This run's trace (the newest under the benchmark's trace directory),
    parsed once per process, its window cut at the end of the run's last
    window step; None when the program left no step spans in it."""
    path = newest_xplane()
    if path is None or not ctx["steps"]:
        return None
    return _steps_trace(path, len(ctx["steps"]))


# ----------------------------- reductions ------------------------------------


def _clip(iv: Interval, window: Interval) -> int:
    return max(0, min(iv[1], window[1]) - max(iv[0], window[0]))


def device_ms(pt: ProgramTrace, keep) -> float:
    """Device ms in the window of the ops ``keep(op)`` accepts, summed over
    devices and divided by their count."""
    total = sum(_clip((o.start, o.end), pt.window)
                for ops in pt.ops.values() for o in ops if keep(o))
    return total * 1e-6 / max(len(pt.ops), 1)


def has_scopes(pt: ProgramTrace) -> bool:
    return any(o.scope for ops in pt.ops.values() for o in ops)


def busy(pt: ProgramTrace, device: str) -> List[Interval]:
    return tr.union(((o.start, o.end) for o in pt.ops[device]), pt.window)


def idle(pt: ProgramTrace, device: str) -> List[Interval]:
    edges = [pt.window[0]] + [x for iv in busy(pt, device) for x in iv] \
        + [pt.window[1]]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def loop_threads(pt: ProgramTrace) -> set:
    """The threads that hold ``train.step`` spans: the training loop's."""
    return {s.thread for s in pt.spans if s.name == STEP_SPAN}


def loop_spans(pt: ProgramTrace) -> List[Span]:
    """The spans of the training loop's thread (every span when no thread
    holds a step)."""
    loop = loop_threads(pt)
    return [s for s in pt.spans if not loop or s.thread in loop]


def labels(pt: ProgramTrace) -> List[Tuple[int, int, str]]:
    """The window cut into (start, end, span name) pieces: in each, the
    innermost span open on the training loop's thread (the latest started,
    the shorter on a tie), or ``unattributed``."""
    spans = loop_spans(pt)
    marks = sorted({pt.window[0], pt.window[1]}
                   | {t for s in spans for t in (s.start, s.end)
                      if pt.window[0] < t < pt.window[1]})
    out, k, active = [], 0, []
    for a, b in zip(marks, marks[1:]):
        while k < len(spans) and spans[k].start <= a:
            active.append(spans[k])
            k += 1
        active = [s for s in active if s.end > a]
        best = max(active, key=lambda s: (s.start, -s.end), default=None)
        name = best.name if best else UNATTRIBUTED
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_by_span(pt: ProgramTrace) -> Dict[str, float]:
    """Device-idle ms of the window by the innermost span open at the
    time, summed over devices and divided by their count."""
    if pt._idle is not None:
        return pt._idle
    pieces = labels(pt)
    tot: Dict[str, float] = collections.defaultdict(float)
    for d in pt.devices:
        k = 0
        for a, b in idle(pt, d):
            while k < len(pieces) and pieces[k][1] <= a:
                k += 1
            j = k
            while j < len(pieces) and pieces[j][0] < b:
                lo, hi, name = pieces[j]
                tot[name] += (min(b, hi) - max(a, lo)) * 1e-6
                j += 1
    nd = max(len(pt.ops), 1)
    pt._idle = {k: v / nd for k, v in tot.items()}
    return pt._idle


def step_spans(pt: ProgramTrace, n: int) -> List[Span]:
    """The window's first ``n`` completed steps: ``train.step`` spans that
    start inside the window and carry counters."""
    steps = [s for s in pt.spans if s.name == STEP_SPAN
             and "compiles" in s.args
             and pt.window[0] <= s.start < pt.window[1]]
    return steps[:n]


def host_ms(pt: ProgramTrace, name: str) -> float:
    """Host ms of the spans ``name`` that start in the window."""
    return sum(s.end - s.start for s in pt.spans
               if s.name == name and pt.window[0] <= s.start < pt.window[1]) * 1e-6


def other_thread_ms(pt: ProgramTrace) -> Dict[str, float]:
    """Host ms by span name of the spans off the training loop's thread
    (the prefetch producer's) that start in the window."""
    loop = loop_threads(pt)
    out: Dict[str, float] = collections.defaultdict(float)
    for s in pt.spans:
        if loop and s.thread not in loop \
                and pt.window[0] <= s.start < pt.window[1]:
            out[s.name] += (s.end - s.start) * 1e-6
    return dict(out)


def notes(pt: ProgramTrace) -> List[str]:
    """Lines for standard error: device ms per scope and pass, device-idle
    ms per span of the loop's thread, host ms per span of other threads,
    and the window's steps (traced atoms/s, steps that compiled)."""
    by_scope: Dict[str, float] = collections.defaultdict(float)
    nd = max(len(pt.ops), 1)
    for ops in pt.ops.values():
        for o in ops:
            key = f"{o.scope or UNSCOPED}/{o.pass_}"
            by_scope[key] += _clip((o.start, o.end), pt.window) * 1e-6 / nd
    busy_ms = sum(by_scope.values())
    scoped = busy_ms - sum(v for k, v in by_scope.items()
                           if k.startswith(UNSCOPED))
    idle_by = idle_by_span(pt)
    total_idle = sum(idle_by.values())
    steps = step_spans(pt, len(pt.spans))
    compiled = [s.args.get("step_num") for s in steps if s.args.get("compiles")]
    atoms = sum(sum(s.args.get("atoms", ())) for s in steps)
    fmt = lambda d: ", ".join(f"{k} {v:.1f}" for k, v in
                              sorted(d.items(), key=lambda kv: -kv[1]))
    return [
        f"note: device ms per scope/pass over the window ({busy_ms:.1f} ms "
        f"in ops, {100 * scoped / max(busy_ms, 1e-9):.1f}% scoped): "
        + fmt(by_scope),
        f"note: device-idle ms per program span ({total_idle:.1f} ms, "
        f"{100 * idle_by.get(UNATTRIBUTED, 0.0) / max(total_idle, 1e-9):.1f}% "
        f"unattributed): " + fmt(idle_by),
        "note: host ms per program span off the training loop's thread: "
        + (fmt(other_thread_ms(pt)) or "none"),
        f"note: program steps in the window {len(steps)}, "
        f"{atoms / ((pt.window[1] - pt.window[0]) * 1e-9):.1f} atoms/s traced, "
        f"steps with a compile {compiled}",
    ]


# ----------------------------- per window step --------------------------------


def per_step(ctx, value: Optional[float]) -> Optional[float]:
    n = len(ctx["steps"])
    return value / n if value is not None and n else None


def scope_ms(ctx, scope: str) -> Optional[float]:
    """Device ms per window step under ``scope``, every pass."""
    pt = of_run(ctx)
    if pt is None or not has_scopes(pt):
        return None
    return per_step(ctx, device_ms(pt, lambda o: o.scope == scope))


def second_order_ms(ctx) -> Optional[float]:
    pt = of_run(ctx)
    if pt is None or not has_scopes(pt):
        return None
    return per_step(ctx, device_ms(pt, lambda o: o.pass_ >= SECOND_ORDER))


def idle_ms(ctx, keep) -> Optional[float]:
    """Device-idle ms per window step under the spans ``keep(name)``
    accepts."""
    pt = of_run(ctx)
    if pt is None:
        return None
    return per_step(ctx, sum(v for k, v in idle_by_span(pt).items()
                             if keep(k)))


def wait_ms(ctx) -> Optional[float]:
    pt = of_run(ctx)
    if pt is None:
        return None
    return per_step(ctx, host_ms(pt, WAIT_SPAN))

"""Reduction of a profiler trace to device busy time, kernel time,
collective time and the longest idle gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into a neutral
``Trace``: per device, the operations that ran on it as (name, start, end)
in nanoseconds; and the host spans that the benchmark itself annotates
(names starting ``bench.``).  Every reduction below works on that neutral
form, so the tests can hand-build one.  The window is the ``bench.window``
span when the trace has it, else the extent of all events.

On a TPU the ``XLA Ops`` line names each event by its HLO text
(``%fusion.12 = f32[...] fusion(...)``), and an op that contains others (a
``while`` loop) spans its body's events.  A Pallas kernel's op carries the
kernel's name inside the transformations that produced it:
``jvp_transpose_jvp_jvp_interaction_bwd____.2`` is a call of
``interaction_bwd``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]
Op = Tuple[str, int, int]          # (name, start_ns, end_ns)

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
UNATTRIBUTED = "unattributed"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
# the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    device_ops: Dict[str, List[Op]]
    host_spans: List[Op]
    window: Interval

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> List[str]:
        return sorted(self.device_ops)


# ----------------------------- loading -------------------------------------


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> Trace:
    """Read a JAX profiler xplane file into a ``Trace``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Op]] = {}
    host: List[Op] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    ops.append((op_name(e.name), s, s + int(e.duration_ns)))
            if ops:
                device_ops[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        s = int(e.start_ns)
                        host.append((e.name, s, s + int(e.duration_ns)))
    return from_events(device_ops, host)


def from_events(device_ops: Dict[str, List[Op]], host: List[Op]) -> Trace:
    win = [h for h in host if h[0] == WINDOW_SPAN]
    if win:
        window = (win[0][1], win[0][2])
    else:
        every = [o for ops in device_ops.values() for o in ops] + list(host)
        window = (min(o[1] for o in every), max(o[2] for o in every))
    return Trace({d: sorted(o, key=lambda x: x[1])
                  for d, o in device_ops.items()},
                 sorted(host, key=lambda x: x[1]), window)


# ----------------------------- reductions ----------------------------------


def union(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    """Merged, window-clipped union of intervals."""
    lo, hi = window
    out: List[List[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace, device: str) -> float:
    """Seconds of the window in which some operation ran on ``device``."""
    return sum(e - s for s, e in union(
        ((o[1], o[2]) for o in trace.device_ops[device]), trace.window)) * 1e-9


def mean_busy_s(trace: Trace) -> float:
    devs = trace.devices
    return sum(busy_s(trace, d) for d in devs) / len(devs) if devs else 0.0


def idle_share(trace: Trace) -> float:
    """1 - busy / window, averaged over the devices."""
    return 1.0 - mean_busy_s(trace) / trace.window_s


_TRANSFORMS = re.compile(r"^((jvp|transpose|vmap|remat|checkpoint|pjit)_)+")


def op_name(event_name: str) -> str:
    """``<instruction> <result shape>`` of an event named by its HLO text
    (the shape without its layout, cut at 60 characters)."""
    head, _, rest = event_name.partition(" = ")
    name = head.strip().lstrip("%")
    shape = re.sub(r"\{[^{}]*\}", "", rest.split(" ", 1)[0])[:60] if rest else ""
    return f"{name} {shape}" if shape else name


def base_name(name: str) -> str:
    """An operation's name without XLA's numeric suffix (``fusion.12``),
    its shape kept."""
    inst, _, shape = name.partition(" ")
    inst = re.sub(r"(\.\d+)+$", "", inst)
    return f"{inst} {shape}" if shape else inst


def kernel_name(name: str) -> str:
    """The kernel an op calls, without the transformations around it."""
    inst = re.sub(r"(\.\d+)+$", "", name.partition(" ")[0])
    return _TRANSFORMS.sub("", inst).rstrip("_")


def is_kernel(name: str, kernel: str) -> bool:
    return kernel_name(name) == kernel


def leaf_ops(ops: List[Op]) -> List[Op]:
    """The ops that contain no other op (a loop's body, not the loop)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or not (nxt[1] >= o[1] and nxt[2] <= o[2]):
            out.append(o)
    return out


def kernel_events(trace: Trace, kernel: str) -> Dict[str, List[Op]]:
    return {d: [o for o in ops if is_kernel(o[0], kernel)]
            for d, ops in trace.device_ops.items()}


def kernel_time_s(trace: Trace, kernels: Sequence[str]) -> Dict[str, float]:
    """Summed device seconds of each kernel's events over all devices."""
    return {k: sum(o[2] - o[1] for ops in kernel_events(trace, k).values()
                   for o in ops) * 1e-9 for k in kernels}


def kernel_counts(trace: Trace, kernels: Sequence[str]) -> Dict[str, int]:
    return {k: sum(len(v) for v in kernel_events(trace, k).values())
            for k in kernels}


def is_collective(name: str) -> bool:
    n = kernel_name(name).lower()
    return any(n.startswith(c) for c in COLLECTIVES)


def collective_s(trace: Trace) -> Dict[str, Tuple[float, float]]:
    """Per device: (seconds in collectives, seconds of them during which no
    other operation ran on that device, that is, exposed)."""
    out = {}
    for d, ops in trace.device_ops.items():
        coll = union(((o[1], o[2]) for o in ops if is_collective(o[0])),
                     trace.window)
        comp = union(((o[1], o[2]) for o in ops if not is_collective(o[0])),
                     trace.window)
        total = sum(e - s for s, e in coll)
        covered = sum(_overlap(c, comp) for c in coll)
        out[d] = (total * 1e-9, (total - covered) * 1e-9)
    return out


def _overlap(iv: Interval, merged: List[Interval]) -> int:
    s, e = iv
    return sum(max(0, min(e, b) - max(s, a)) for a, b in merged)


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The operations that took most device time, by base name, in seconds
    per device (summed over devices, divided by their count).  Only ops
    that contain no other op count, so no time is counted twice."""
    tot: Dict[str, float] = {}
    for ops in trace.device_ops.values():
        for name, s, e in leaf_ops(ops):
            lo, hi = max(s, trace.window[0]), min(e, trace.window[1])
            if hi > lo:
                b = base_name(name)
                tot[b] = tot.get(b, 0.0) + (hi - lo) * 1e-9
    nd = max(len(trace.device_ops), 1)
    return sorted(((k, v / nd) for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:n]


def host_label(trace: Trace, t: int) -> str:
    """The innermost benchmark span (other than the window) covering ``t``."""
    best: Optional[Op] = None
    for span in trace.host_spans:
        if span[0] == WINDOW_SPAN or not span[1] <= t < span[2]:
            continue
        if best is None or span[2] - span[1] < best[2] - best[1]:
            best = span
    return best[0] if best else UNATTRIBUTED


def _gaps(trace: Trace, device: str) -> List[Interval]:
    busy = union(((o[1], o[2]) for o in trace.device_ops[device]), trace.window)
    edges = [trace.window[0]] + [x for iv in busy for x in iv] + [trace.window[1]]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def longest_gaps(trace: Trace, n: int = 3) -> List[Tuple[float, float]]:
    """(seconds from the window's start, seconds long) of the longest
    device-idle gaps over all devices."""
    gaps = sorted((g for d in trace.devices for g in _gaps(trace, d)),
                  key=lambda g: g[0] - g[1])[:n]
    return [((a - trace.window[0]) * 1e-9, (b - a) * 1e-9) for a, b in gaps]


def idle_gaps(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Device-idle time of the window by what the host was doing: every
    gap between operations (over all devices) is named by the innermost
    benchmark span at its midpoint, and the gaps of a name are summed.
    Entries read ``<span> x<gaps>``, largest total first."""
    tot: Dict[str, List[float]] = {}
    for d in trace.devices:
        for a, b in _gaps(trace, d):
            acc = tot.setdefault(host_label(trace, a + (b - a) // 2), [0, 0.0])
            acc[0] += 1
            acc[1] += (b - a) * 1e-9
    nd = max(len(trace.device_ops), 1)
    return sorted(((f"{k} x{c}", v / nd) for k, (c, v) in tot.items()),
                  key=lambda kv: -kv[1])[:n]

"""From a profiler trace to busy time, time per program, and idle gaps.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  What is read:

- each ``/device:TPU:<n>`` plane's ``XLA Modules`` line (one event per
  program run, named ``<module>(<id>)``) and ``XLA Ops`` line (one event per
  operation);
- the host plane's events whose names start with ``bench.``: the harness's
  own ``TraceAnnotation`` spans around each call into the engine's layers,
  and ``bench.window`` around the measured window;
- the host plane's events whose names start with ``serve.``: the program's
  own spans (``repro.obs.ProfilerTracer``), with their arguments.

Host and device events share one clock in nanoseconds.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import heapq
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW = "bench.window"
PREFIX = "bench."
PROGRAM = "serve."
UNTRACED = "host outside the harness's spans"

Interval = Tuple[float, float]
Span = Tuple[float, float, str, dict]      # start, end, name, arguments


@dataclasses.dataclass
class Trace:
    window: Interval                                   # ns
    # per device: (start, end, program, id of the executable that ran)
    modules: List[List[Tuple[float, float, str, str]]]
    ops: List[List[Tuple[float, float, str]]]          # per device
    host: List[Tuple[float, float, str]]               # bench.* spans
    spans: List[Span] = dataclasses.field(default_factory=list)  # serve.*


def module_run(event_name: str) -> Tuple[str, str]:
    """``jit_paged_decode1(2635...)`` -> ``("jit_paged_decode1",
    "2635...")``: the program and the id of the executable that ran."""
    name, _, rest = event_name.partition("(")
    return name, rest.rstrip(")")


def op_name(event_name: str) -> str:
    """``%fusion.99 = (s32[1]...) fusion(...)`` -> ``fusion.99``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or the only one under a directory)."""
    if not path.endswith(".pb"):
        found = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} traces under {path}")
        path = found[0]
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    modules, ops, host, spans = [], [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, devops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(e.start_ns, e.start_ns + e.duration_ns,
                             *module_run(e.name)) for e in line.events]
                elif line.name == "XLA Ops":
                    devops = [(e.start_ns, e.start_ns + e.duration_ns,
                               op_name(e.name)) for e in line.events]
            modules.append(sorted(mods))
            ops.append(sorted(devops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
                    elif e.name.startswith(PROGRAM):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name, dict(e.stats)))
    windows = [h for h in host if h[2] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW} spans in {path}")
    return Trace(window=windows[0][:2], modules=modules, ops=ops,
                 host=[h for h in host if h[2] != WINDOW],
                 spans=sorted(spans, key=lambda s: (s[0], -s[1])))


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(merged: Sequence[Interval], clip: Sequence[Interval]) -> float:
    """Length of the intersection of two lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(merged) and j < len(clip):
        a, b = max(merged[i][0], clip[j][0]), min(merged[i][1], clip[j][1])
        total += max(0.0, b - a)
        if merged[i][1] < clip[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_ns(trace: Trace, clip: Sequence[Interval]) -> float:
    """Time in ``clip`` in which some operation ran, averaged over devices."""
    clip = merge(clip)
    per = [overlap(merge((a, b) for a, b, _ in dev), clip)
           for dev in trace.ops]
    return sum(per) / max(1, len(per))


class Clip:
    """Disjoint intervals with a fast length-of-overlap query."""

    def __init__(self, intervals: Iterable[Interval]):
        self.iv = merge(intervals)
        self.starts = [a for a, _ in self.iv]

    def length(self, a: float, b: float) -> float:
        i = max(0, bisect.bisect_right(self.starts, a) - 1)
        total = 0.0
        while i < len(self.iv) and self.iv[i][0] < b:
            lo, hi = self.iv[i]
            total += max(0.0, min(b, hi) - max(a, lo))
            i += 1
        return total


def module_ns(trace: Trace, names: Iterable[str],
              clip: Sequence[Interval]) -> float:
    """Device time of the named programs inside ``clip``, summed over
    devices."""
    names, c = set(names), Clip(clip)
    return sum(c.length(a, b)
               for dev in trace.modules for a, b, n, _ in dev if n in names)


def program_seconds(trace: Trace, clip: Sequence[Interval]) -> Dict[str, float]:
    """Device seconds per program (XLA module) inside ``clip``, summed over
    devices, most first."""
    c = Clip(clip)
    total: Dict[str, float] = collections.Counter()
    for dev in trace.modules:
        for a, b, n, _ in dev:
            total[n] += c.length(a, b) * 1e-9
    return dict(sorted(total.items(), key=lambda kv: -kv[1]))


def leaves(ops: Sequence[Tuple[float, float, str]]):
    """The operations that hold no other: a ``while`` or ``call`` event
    spans the operations of its body, which are events of their own."""
    return [op for op, nxt in zip(ops, list(ops[1:]) + [None])
            if nxt is None or nxt[0] >= op[1]]


def top_ops(trace: Trace, clip: Sequence[Interval],
            n: int = 10) -> List[Tuple[str, float]]:
    """The operations (leaves, see ``leaves``) with most device time in
    ``clip`` (seconds), named ``<program>/<operation>``, summed over
    devices."""
    c = Clip(clip)
    total: Dict[str, float] = collections.Counter()
    for mods, ops in zip(trace.modules, trace.ops):
        i = 0
        for a, b, name in leaves(ops):
            while i < len(mods) and mods[i][1] < a:
                i += 1
            prog = mods[i][2] if i < len(mods) and mods[i][0] <= a else "?"
            t = c.length(a, b)
            if t:
                total[f"{prog}/{name}"] += t
    return [(k, v * 1e-9) for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, clip: Sequence[Interval]) -> List[Interval]:
    """Stretches of ``clip`` in which no operation ran on device 0."""
    busy = merge((a, b) for a, b, _ in trace.ops[0]) if trace.ops else []
    gaps, j = [], 0
    for lo, hi in merge(clip):
        t = lo
        while j < len(busy) and busy[j][1] <= t:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < hi:
            if busy[k][0] > t:
                gaps.append((t, busy[k][0]))
            t = max(t, busy[k][1])
            k += 1
        if t < hi:
            gaps.append((t, hi))
    return gaps


def gaps_by_host(trace: Trace, clip: Sequence[Interval],
                 n: int = 10) -> List[Tuple[str, float]]:
    """Idle device time in ``clip`` (seconds), split by what the host was
    doing: the innermost (latest started) harness span active at each
    moment of a gap."""
    marks = []   # (time, order, kind, payload): ends sort before starts
    for k, (a, b, name) in enumerate(trace.host):
        marks += [(a, 1, "start", (a, k, name)), (b, 0, "end", (a, k, name))]
    for a, b in idle_gaps(trace, clip):
        marks += [(a, 1, "gap", True), (b, 0, "gap", False)]
    marks.sort(key=lambda m: (m[0], m[1]))
    live, heap, in_gap, last = set(), [], False, None
    total: Dict[str, float] = collections.Counter()
    for t, _, kind, payload in marks:
        if in_gap and last is not None and t > last:
            while heap and heap[0][1] not in live:
                heapq.heappop(heap)
            name = heap[0][2][len(PREFIX):] if heap else UNTRACED
            total[name] += t - last
        last = t
        if kind == "gap":
            in_gap = payload
        elif kind == "start":
            live.add(payload[1])
            heapq.heappush(heap, (-payload[0], payload[1], payload[2]))
        else:
            live.discard(payload[1])
    return [(k, v * 1e-9) for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]

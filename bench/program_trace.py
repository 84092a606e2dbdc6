"""The serving program's own marks in a profiler trace: its host spans and
the named scopes of its jitted programs.

``bench/trace.py`` reads the device's programs and operations, the
harness's ``bench.*`` spans and the program's ``serve.*`` spans.  This
module reads what the program marks itself:

- the engine's ``serve.*`` host spans (``repro.obs.ProfilerTracer``), with
  their arguments (the host event's stats): ``serve.step`` per engine
  step, ``serve.decode`` with ``k``, ``rows`` and ``pages_used``,
  ``serve.fetch_tokens`` around each copy of new tokens to the host, and
  so on.  ``call_args`` sums, for each call the harness wraps, the
  arguments of the spans around it;
- the ``jax.named_scope`` of each device operation, one of the scopes
  that the configuration's family names (``SCOPES`` in
  ``bench/families/<family>.py``).  The device trace does not carry it.
  It is read from the compiled text of the executables the engine
  dispatched, keyed by the program run the trace names (``<module>(<id>)``,
  bound to the decode call that dispatched it), and matched to the trace's
  operations by name.

Where the program compiles without full name paths in its locations
(``repro.launch.compile_cache``), an operation's ``op_name`` keeps only
its own primitive, not the scopes around it.  The scopes then come from a
twin: the same jitted callable on the same arguments, compiled again with
full name paths.  XLA names instructions after their locations, so the
twin's names differ; its instructions are lent to the executable that ran
position by position, and only where each one's shape and opcode agree.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import heapq
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import trace as tr
from bench.metrics.decode_step_ms import PROGRAMS as DECODE_PROGRAMS

PREFIX = tr.PROGRAM
UNSCOPED = "unscoped"      # an operation of the program, in no scope
UNMAPPED = "unmapped"      # an operation the scope map does not hold
MIN_COVERAGE = 0.95
# keyed spans that outlive the engine's calls: they name no idle gap and
# hold no call's arguments
RESIDENT = ("serve.queue_wait", "serve.request")

Span = tr.Span
ScopeMap = Dict[Tuple[str, str], Dict[str, str]]   # (module, id) -> op -> scope

# ``%name = <shape> <opcode>(``: the shape may hold spaces (tuples)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*?) ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str, scopes: Iterable[str]) -> str:
    """The first of ``scopes`` on an ``op_name`` path, else ``UNSCOPED``."""
    scopes = set(scopes)
    for part in op_name.split("/"):
        if part in scopes:
            return part
    return UNSCOPED


def instructions(text: str, scopes: Iterable[str]
                 ) -> List[Tuple[str, str, str]]:
    """Each instruction of a compiled HLO text, in order: (name, shape and
    opcode, scope)."""
    scopes = set(scopes)
    out = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            named = _OP_NAME.search(line)
            out.append((m.group(1), f"{m.group(2)} {m.group(3)}",
                        scope_of(named.group(1), scopes) if named
                        else UNSCOPED))
    return out


def executable_scopes(ran: str, named: str, scopes: Iterable[str]
                      ) -> Optional[Dict[str, str]]:
    """Operation -> scope of the executable whose compiled text is ``ran``,
    from ``named``: the same program compiled with full name paths (the
    same text, where the program keeps them).  None where the two are not
    the same program: another count of instructions, or one whose shape or
    opcode differs at the same position."""
    a, b = instructions(ran, scopes), instructions(named, scopes)
    if [x[1] for x in a] != [x[1] for x in b]:
        return None
    return {x[0]: y[2] for x, y in zip(a, b)}


@contextlib.contextmanager
def full_name_paths():
    """Compile with each operation's full name path in its location, and
    with nothing read from or written to the persistent cache, whose key
    leaves locations out.  The in-memory caches are cleared on entry, so
    that callables trace and lower anew, and on exit, so that no later
    call runs what was compiled here."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    full = jax.config.jax_include_full_tracebacks_in_locations
    cached = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_include_full_tracebacks_in_locations", True)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", full)
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()
        jax.clear_caches()


def decode_texts(adapter, engine, ks: Iterable[int]
                 ) -> Dict[int, Tuple[str, str]]:
    """For each fused decode length in ``ks``: the compiled text of the
    executable the engine dispatched, and of its twin with full name
    paths.  Both come from the adapter's own jitted callables (the ones
    its ``decode_multi`` calls) on the engine's own arguments: the page
    pool, the token and position vectors, the page tables.  Run after the
    engine's last call: the first is the executable in JAX's in-memory
    cache, the second is compiled here (``full_name_paths``)."""
    ks = sorted(set(ks))
    fns = {k: adapter._paged_decode1 if k == 1 else adapter._paged_decode_k[k]
           for k in ks}
    args = (adapter.params, engine._tok, engine._pos, engine.cache,
            *adapter._tables())
    ran = {k: fns[k].lower(*args).compile().as_text() for k in ks}
    with full_name_paths():
        named = {k: fns[k].lower(*args).compile().as_text() for k in ks}
    return {k: (ran[k], named[k]) for k in ks}


def decode_runs(trace: tr.Trace) -> List[Tuple[str, str]]:
    """The decode programs' runs on device 0 that start in the trace's
    window, in order: (module, id)."""
    lo, hi = trace.window
    mods = trace.modules[0] if trace.modules else []
    return [(m, i) for a, _, m, i in mods
            if m in DECODE_PROGRAMS and lo <= a < hi]


def scope_map(trace: tr.Trace, decode: Sequence, texts: Dict[int, tuple],
              scopes: Iterable[str]) -> ScopeMap:
    """(module, id) -> operation -> scope, for the decode programs that
    ran in the trace's window.  The n-th decode run is the one the n-th
    decode call of the window (``decode``, the harness's records)
    dispatched, so each run's id is bound to its call's fused length
    ``k``, whose executable's texts ``texts`` holds (``decode_texts``).
    Left out, and so read as unmapped: every run where the runs and the
    calls do not pair up, an id bound to two lengths, and an executable
    that its twin does not match (``executable_scopes``)."""
    runs = decode_runs(trace)
    if len(runs) != len(decode):
        return {}
    bound: Dict[Tuple[str, str], set] = collections.defaultdict(set)
    for run, call in zip(runs, decode):
        bound[run].add(call.k)
    by_k = {k: executable_scopes(ran, named, scopes)
            for k, (ran, named) in texts.items()}
    out: ScopeMap = {}
    for run, ks in bound.items():
        ops = by_k.get(next(iter(ks))) if len(ks) == 1 else None
        if ops is not None:
            out[run] = ops
    return out


def scope_ns(trace: tr.Trace, programs: Iterable[str],
             clip: Sequence[tr.Interval], smap: ScopeMap) -> Dict[str, float]:
    """Device time of the named programs' operations (leaves, see
    ``trace.leaves``) inside ``clip``, by scope, summed over devices;
    ``UNMAPPED`` for operations of a run that ``smap`` does not hold."""
    names, c = set(programs), tr.Clip(clip)
    total: Dict[str, float] = collections.Counter()
    for mods, ops in zip(trace.modules, trace.ops):
        i = 0
        for a, b, op in tr.leaves(ops):
            while i < len(mods) and mods[i][1] < a:
                i += 1
            if i < len(mods) and mods[i][0] <= a and mods[i][2] in names:
                t = c.length(a, b)
                if t:
                    ops_of = smap.get((mods[i][2], mods[i][3]), {})
                    total[ops_of.get(op, UNMAPPED)] += t
    return dict(total)


def coverage(by_scope: Dict[str, float]) -> float:
    """The share of ``scope_ns``'s time that the scope map holds."""
    total = sum(by_scope.values())
    return 1.0 - by_scope.get(UNMAPPED, 0.0) / total if total else 0.0


def scoped_ms(by_scope: Dict[str, float], scopes: Iterable[str],
              steps: int):
    """Device ms in ``scopes`` per step, or None where it would not be
    the program's number: no steps, no time, a map that covers under
    ``MIN_COVERAGE`` of the time, or programs without named scopes."""
    named = any(t for s, t in by_scope.items()
                if s not in (UNSCOPED, UNMAPPED))
    if not steps or coverage(by_scope) < MIN_COVERAGE or not named:
        return None
    return sum(by_scope.get(s, 0.0) for s in scopes) * 1e-6 / steps


def call_args(spans: Sequence[Span], calls: Sequence[tr.Interval]
              ) -> List[Dict[str, float]]:
    """For each call (start, end ns on the trace's clock): the numeric
    arguments of the program's spans around it, summed by name.  Those
    spans are the innermost one that holds the call (but for those in
    ``RESIDENT``) and every span inside that one: for a decode call, the
    engine's ``serve.decode`` and what the adapter opens inside it."""
    own = sorted((s for s in spans if s[2] not in RESIDENT),
                 key=lambda s: (s[0], -s[1]))
    starts = [s[0] for s in own]
    out = []
    for a, b in calls:
        i = bisect.bisect_right(starts, a) - 1
        while i >= 0 and own[i][1] < b:      # the latest start that holds
            i -= 1
        args: Dict[str, float] = collections.Counter()
        end = own[i][1] if i >= 0 else float("-inf")
        for j in range(max(i, 0), len(own)):
            if own[j][0] > end:
                break
            if own[j][1] <= end:
                for name, v in own[j][3].items():
                    if (isinstance(v, (int, float))
                            and not isinstance(v, bool)):
                        args[name] += v
        out.append(dict(args))
    return out


def gaps_by_program(trace: tr.Trace, spans: Sequence[Span],
                    clip: Sequence[tr.Interval],
                    n: int = 12) -> List[Tuple[str, float]]:
    """Idle device time in ``clip`` (seconds), split by what the host was
    doing: the innermost (latest started) ``serve.*`` span active at each
    moment of a gap, but for those in ``RESIDENT``; where there is none,
    the innermost ``bench.*`` span; else ``trace.UNTRACED``."""
    layers = [[(a, b, name) for a, b, name, _ in spans
               if name not in RESIDENT], trace.host]
    marks = []   # (time, order, kind, payload): ends sort before starts
    for rank, layer in enumerate(layers):
        for k, (a, b, name) in enumerate(layer):
            item = (rank, (rank, k), a, name)
            marks += [(a, 1, "start", item), (b, 0, "end", item)]
    for a, b in tr.idle_gaps(trace, clip):
        marks += [(a, 1, "gap", True), (b, 0, "gap", False)]
    marks.sort(key=lambda m: (m[0], m[1]))
    live, heaps, in_gap, last = set(), [[] for _ in layers], False, None
    total: Dict[str, float] = collections.Counter()
    for t, _, kind, payload in marks:
        if in_gap and last is not None and t > last:
            total[_innermost(heaps, live)] += t - last
        last = t
        if kind == "gap":
            in_gap = payload
        elif kind == "start":
            rank, key, a, name = payload
            live.add(key)
            heapq.heappush(heaps[rank], (-a, key, name))
        else:
            live.discard(payload[1])
    return [(k, v * 1e-9) for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def _innermost(heaps, live) -> str:
    for heap in heaps:
        while heap and heap[0][1] not in live:
            heapq.heappop(heap)
        if heap:
            return heap[0][2]
    return tr.UNTRACED


def host_idle_ms_per_step(trace: tr.Trace, spans: Sequence[Span],
                          served: Sequence[tr.Interval]):
    """Device-idle time inside ``served`` per ``serve.step`` span that
    starts in the trace's window, in ms; None without such spans."""
    lo, hi = trace.window
    steps = sum(1 for a, _, name, _ in spans
                if name == PREFIX + "step" and lo <= a < hi)
    if not steps:
        return None
    idle = sum(b - a for a, b in tr.idle_gaps(trace, served))
    return idle * 1e-6 / steps


def decode_scoped_ms_per_step(ctx, scopes: Iterable[str]):
    """A per-layer reader: device ms of the decode programs' operations
    in ``scopes`` per decode step in the window (a fused stretch of k
    steps counts k times), or None (see ``scoped_ms``; a run without a
    scope map reads nothing)."""
    steps = sum(c.k for c in ctx.calls.decode)
    smap = getattr(ctx, "scope_map", None)
    if not steps or not smap:
        return None
    by_scope = scope_ns(ctx.trace, DECODE_PROGRAMS, ctx.window_ns, smap)
    return scoped_ms(by_scope, scopes, steps)

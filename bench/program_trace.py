"""The serving program's own marks in a profiler trace: its host spans and
the named scopes of its jitted programs.

``bench/trace.py`` reads the device's programs and operations and the
harness's ``bench.*`` spans.  This module reads what the program marks
itself:

- the engine's ``serve.*`` host spans (``repro.obs.ProfilerTracer``), with
  their arguments (the host event's stats): ``serve.step`` per engine
  step, ``serve.decode`` with ``k`` and ``rows``, ``serve.fetch_tokens``
  around each copy of new tokens to the host, and so on;
- the ``jax.named_scope`` of each device operation (``embed``,
  ``attention``, ``kv_write``, ``mlp``, ``lm_head``, ``page_gather``,
  ``page_scatter``).  The device trace does not carry it: it is read from
  the compiled program's HLO text, where each instruction's ``op_name``
  metadata holds its scope path, and matched to the trace's operations by
  (program, operation) name.
"""

from __future__ import annotations

import collections
import glob
import heapq
import re
from typing import Dict, Iterable, List, Sequence, Tuple

from bench import trace as tr
from bench.metrics.decode_step_ms import PROGRAMS as DECODE_PROGRAMS

PREFIX = "serve."
SCOPES = ("embed", "attention", "kv_write", "mlp", "lm_head", "page_gather",
          "page_scatter")
KV_SCOPES = ("page_gather", "page_scatter", "kv_write")
UNSCOPED = "unscoped"      # an operation of the program, in no scope
UNMAPPED = "unmapped"      # an operation the compiled programs do not hold
MIN_COVERAGE = 0.95
# keyed spans that outlive the engine's calls: they name no idle gap
RESIDENT = ("serve.queue_wait", "serve.request")

Span = Tuple[float, float, str, dict]

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def load_spans(path: str) -> List[Span]:
    """The ``serve.*`` host spans of one ``.xplane.pb`` (or the only one
    under a directory): (start ns, end ns, name, stats), by start."""
    if not path.endswith(".pb"):
        found = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} traces under {path}")
        path = found[0]
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name, dict(e.stats)))
    return sorted(spans, key=lambda s: (s[0], -s[1]))


def scope_of(op_name: str) -> str:
    """The first of ``SCOPES`` on an ``op_name`` path, else ``UNSCOPED``."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def scope_map(hlo_texts: Dict[str, Iterable[str]]
              ) -> Dict[Tuple[str, str], str]:
    """(program, operation) -> scope, from the compiled HLO text of each
    program.  A program name may hold several texts (one per compiled
    variant, such as each fused decode length); an operation whose
    variants disagree on its scope is left out."""
    out: Dict[Tuple[str, str], str] = {}
    clash = set()
    for module, texts in hlo_texts.items():
        for text in texts:
            for line in text.splitlines():
                m = _INSTRUCTION.match(line)
                if not m:
                    continue
                named = _OP_NAME.search(line)
                scope = scope_of(named.group(1)) if named else UNSCOPED
                if out.setdefault((module, m.group(1)), scope) != scope:
                    clash.add((module, m.group(1)))
    for key in clash:
        del out[key]
    return out


def scope_ns(trace: tr.Trace, programs: Iterable[str],
             clip: Sequence[tr.Interval],
             smap: Dict[Tuple[str, str], str]) -> Dict[str, float]:
    """Device time of the named programs' operations (leaves, see
    ``trace.leaves``) inside ``clip``, by scope, summed over devices;
    ``UNMAPPED`` for operations that ``smap`` does not hold."""
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
                    total[smap.get((mods[i][2], op), UNMAPPED)] += t
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
    if (not steps or coverage(by_scope) < MIN_COVERAGE
            or not any(by_scope.get(s) for s in SCOPES)):
        return None
    return sum(by_scope.get(s, 0.0) for s in scopes) * 1e-6 / steps


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


def decode_hlo_texts(cell, cfg, ks: Iterable[int]) -> Dict[str, List[str]]:
    """The compiled HLO text of the cell's decode programs, one per fused
    length in ``ks``, built from the shapes alone (no device buffers):
    the program's own adapter and step builders, lowered on the same
    argument shapes as the cell's engine.

    Compiled with the persistent cache off: its key leaves out the op
    metadata, so a cache shared with another checkout of the program can
    hand back an executable whose metadata holds other scopes (or none).
    The operations' names do not depend on the metadata."""
    import jax
    import jax.numpy as jnp

    from bench import family
    from bench.common import seed_key
    from repro.models import transformer as T
    from repro.serve.engine import PagedTransformerModel
    from repro.serve.step import make_paged_decode_scan
    from repro.sharding.rules import Rules

    ec = cell.engine_config()
    params = jax.eval_shape(family.load(cell.spec).init_weights(cfg),
                            seed_key(0))
    pool = jax.eval_shape(
        lambda: T.init_cache(cfg, ec.pool_pages + 1, ec.page_size))
    vec = jax.ShapeDtypeStruct((ec.n_slots,), jnp.int32)
    table = jax.ShapeDtypeStruct((ec.n_slots, ec.pages_per_slot), jnp.int32)
    args = (params, vec, vec, pool, table, table)
    rules = Rules.null()
    texts: Dict[str, List[str]] = collections.defaultdict(list)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for k in sorted(set(ks)):
            if k == 1:
                fn = PagedTransformerModel(params, cfg, rules)._paged_decode1
                name = "jit_paged_decode1"
            else:
                fn = jax.jit(make_paged_decode_scan(cfg, rules, k))
                name = "jit_run"
            texts[name].append(fn.lower(*args).compile().as_text())
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
    return dict(texts)


_MAPS: Dict[tuple, dict] = {}


def decode_scope_map(ctx) -> Dict[Tuple[str, str], str]:
    """The scope map of the decode programs a traced run reached:
    ``ctx.scope_map`` where the harness hands one over, else compiled
    here once per cell and set of fused lengths."""
    given = getattr(ctx, "scope_map", None)
    if given is not None:
        return given
    ks = tuple(sorted({k for _, k, _, _ in ctx.calls.decode}))
    key = (ctx.cell.name, ks)
    if key not in _MAPS:
        _MAPS[key] = scope_map(decode_hlo_texts(ctx.cell, ctx.cfg, ks))
    return _MAPS[key]


def decode_scoped_ms_per_step(ctx, scopes: Iterable[str]):
    """A per-layer reader: device ms of the decode programs' operations
    in ``scopes`` per decode step in the window (a fused stretch of k
    steps counts k times), or None (see ``scoped_ms``)."""
    steps = sum(k for _, k, _, _ in ctx.calls.decode)
    if not steps:
        return None
    by_scope = scope_ns(ctx.trace, DECODE_PROGRAMS, ctx.window_ns,
                        decode_scope_map(ctx))
    return scoped_ms(by_scope, scopes, steps)

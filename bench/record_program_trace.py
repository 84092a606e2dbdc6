"""Record the small chip trace that ``tests/bench`` checks
``bench/program_trace.py`` on: the program's own spans and scopes.

    python3 bench/record_program_trace.py --out bench/testdata/trace_program

Serves what ``bench/record_trace.py`` serves (eight short requests through
the paged engine at a small size, with the harness's host annotations)
under the profiler; the engine's ``serve.*`` spans come with it.  Writes
the ``.xplane.pb``, the profiler's Perfetto JSON export of the same trace,
the compiled HLO text of the decode executables that ran (``hlo.json.gz``:
program name -> texts, by fused length) and of their twins compiled with
full name paths (``hlo_named.json.gz``, the same layout; both from
``program_trace.decode_texts``), and ``expected.json``: ``record_trace``'s
numbers, and the numbers ``program_trace`` must give, worked out here from
the Perfetto export and the HLO text by code of its own.

A recording made while the program compiled with full name paths (before
``repro.launch.compile_cache`` turned them off) has no
``hlo_named.json.gz``: its texts carry the paths themselves.
"""

from __future__ import annotations

import argparse
import collections
import gzip
import json
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

DECODE = ("jit_paged_decode1", "jit_run")


def load_texts(path: pathlib.Path):
    """``hlo.json.gz`` and its twins (the same texts where the recording
    has no ``hlo_named.json.gz``)."""
    read = lambda name: json.loads(gzip.decompress(
        (path / name).read_bytes()))
    texts = read("hlo.json.gz")
    named = read("hlo_named.json.gz") if (
        path / "hlo_named.json.gz").is_file() else texts
    return texts, named


def by_length(texts, ks) -> dict:
    """k -> text: ``jit_paged_decode1`` holds k = 1, ``jit_run`` the other
    lengths in ``ks``, ascending."""
    longer = sorted(k for k in set(ks) if k > 1)
    out = {k: t for k, t in zip(longer, texts.get("jit_run", []))}
    if "jit_paged_decode1" in texts:
        out[1] = texts["jit_paged_decode1"][0]
    return out


def op_scopes(ran: str, named: str) -> dict:
    """Operation -> scope or None of the executable whose text is ``ran``,
    each instruction's scope read from the instruction at the same place
    in ``named``, from lines ``%name = ... op_name="a/b/c"``; {} where the
    two texts differ in their count of instructions or in an opcode."""
    from bench import family
    scopes = set(family.load({"family": "dense"}).SCOPES)

    def lines(text):
        out = []
        for line in text.splitlines():
            line = line.strip()
            if line.startswith("ROOT "):
                line = line[5:]
            if not line.startswith("%") or " = " not in line:
                continue
            opcode = re.search(r" ([a-z][\w\-]*)\(", line)
            m = re.search(r'op_name="([^"]*)"', line)
            parts = m.group(1).split("/") if m else []
            out.append((line[1:line.index(" = ")],
                        opcode.group(1) if opcode else None,
                        next((p for p in parts if p in scopes), None)))
        return out
    a, b = lines(ran), lines(named)
    if [x[1] for x in a] != [y[1] for y in b]:
        return {}
    return {x[0]: y[2] for x, y in zip(a, b)}


def program_expected(perfetto, texts, named) -> dict:
    """Scope coverage, device seconds and operations by scope, the two
    scoped metrics per decode step, host idle ms per step, idle seconds by
    program span, from the Perfetto JSON export and the HLO texts.  The
    n-th decode run in the window is bound to the n-th ``serve.decode``
    span's ``k``, and so to that length's texts.  (The
    export keeps picoseconds; the ``.xplane.pb`` reader whole nanoseconds,
    so each operation may read up to 1 ns shorter there.)"""
    events = json.loads(gzip.open(perfetto).read())
    if isinstance(events, dict):
        events = events["traceEvents"]
    proc, thread = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            proc[e["pid"]] = e["args"]["name"]
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            thread[(e["pid"], e["tid"])] = e["args"]["name"]
    dev = [p for p, n in proc.items() if n == "/device:TPU:0"][0]
    span = lambda e: (round(e["ts"] * 1000),
                      round((e["ts"] + e["dur"]) * 1000))
    xs = [e for e in events if e.get("ph") == "X"]
    lo, hi = span([e for e in xs if e["name"] == "bench.window"][0])
    on = lambda kind: [e for e in xs if e["pid"] == dev
                       and thread[(dev, e["tid"])] == kind]
    mods = sorted((span(e), e["name"].split("(")[0], e["name"])
                  for e in on("XLA Modules"))
    ops = sorted((span(e), e["name"].split(" = ")[0].lstrip("%"))
                 for e in on("XLA Ops"))
    serve = [(span(e), e["name"], e.get("args", {})) for e in xs
             if e["name"].startswith("serve.")]
    decode_ks = [int(args["k"]) for (a, _), n, args in
                 sorted(serve, key=lambda x: x[0])
                 if n == "serve.decode" and lo <= a < hi]
    runs = [run for (a, _), prog, run in mods if prog in DECODE
            and lo <= a < hi]
    bound = collections.defaultdict(set)
    for run, k in zip(runs, decode_ks if len(decode_ks) == len(runs)
                      else []):
        bound[run].add(k)
    all_ks = {int(args["k"]) for _, n, args in serve if n == "serve.decode"}
    ran, twin = by_length(texts, all_ks), by_length(named, all_ks)
    scopes = {k: op_scopes(ran[k], twin[k]) for k in ran}
    by_scope, n_ops = collections.Counter(), collections.Counter()
    for i, ((a, b), name) in enumerate(ops):
        holds = i + 1 < len(ops) and ops[i + 1][0][0] < b  # a while or call
        prog = [(n, run) for (s0, s1), n, run in mods if s0 <= a < s1]
        if holds or not prog or prog[0][0] not in DECODE:
            continue
        t = max(0, min(b, hi) - max(a, lo))
        ks = bound.get(prog[0][1], set())
        of = scopes.get(next(iter(ks))) if len(ks) == 1 else None
        scope = ("unmapped" if of is None or name not in of
                 else of[name] or "unscoped")
        by_scope[scope] += t * 1e-9
        n_ops[scope] += t > 0
    total = sum(by_scope.values())

    steps = sum(int(args["k"]) for (a, _), n, args in serve
                if n == "serve.decode" and lo <= a < hi)
    n_step = sum(1 for (a, _), n, _ in serve
                 if n == "serve.step" and lo <= a < hi)
    busy = sorted({(max(a, lo), min(b, hi)) for (a, b), _ in ops
                   if min(b, hi) > max(a, lo)})
    idle, t = [], lo
    for a, b in busy:
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    if t < hi:
        idle.append((t, hi))
    bench = [(s, n) for s, n in ((span(e), e["name"]) for e in xs)
             if n.startswith("bench.") and n != "bench.window"]
    calls = [(s, n) for s, n, _ in serve
             if n not in ("serve.queue_wait", "serve.request")]
    by_span = collections.Counter()
    for g0, g1 in idle:
        cuts = sorted({g0, g1} | {x for (a, b), _ in calls + bench
                                  for x in (a, b) if g0 < x < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            live = ([(s0, n) for (s0, s1), n in calls if s0 <= mid < s1]
                    or [(s0, n) for (s0, s1), n in bench if s0 <= mid < s1])
            name = (max(live)[1] if live
                    else "host outside the harness's spans")
            by_span[name] += (b - a) * 1e-9
    return {
        "scope_coverage": 1 - by_scope["unmapped"] / total,
        "device_scopes_s": dict(by_scope),
        "device_scope_ops": dict(n_ops),
        "decode_steps": steps,
        "kv_cache_ms_per_step": sum(by_scope[s] for s in (
            "page_gather", "page_scatter", "kv_write")) * 1e3 / steps,
        "lm_head_ms_per_step": by_scope["lm_head"] * 1e3 / steps,
        "host_idle_ms_per_step": sum(b - a for a, b in idle) * 1e-6 / n_step,
        "idle_by_program_s": dict(by_span),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(ROOT / "bench/testdata/"
                                         "trace_program"))
    ap.add_argument("--expected-only", action="store_true",
                    help="rewrite expected.json from the files already in "
                         "--out (no chip needed)")
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out)
    from bench import record_trace
    if not args.expected_only:
        from bench import program_trace as pt
        from bench import trace as tr
        run = record_trace.record(out)
        spans = tr.load(str(out / "trace.xplane.pb")).spans
        ks = {int(s["k"]) for _, _, n, s in spans if n == "serve.decode"}
        pairs = pt.decode_texts(run.adapter, run.engine, ks)
        for i, name in enumerate(("hlo.json.gz", "hlo_named.json.gz")):
            texts = collections.defaultdict(list)
            for k in sorted(pairs):
                texts[DECODE[k > 1]].append(pairs[k][i])
            (out / name).write_bytes(
                gzip.compress(json.dumps(texts).encode()))
    texts, named = load_texts(out)
    expected = record_trace.expected_from_perfetto(
        out / "perfetto.trace.json.gz")
    expected.update(program_expected(out / "perfetto.trace.json.gz", texts,
                                     named))
    (out / "expected.json").write_text(json.dumps(expected, indent=1) + "\n")
    print(json.dumps(expected, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

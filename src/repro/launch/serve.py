"""Serving launcher: continuous-batching engine at published widths.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_3b --paged
  PYTHONPATH=src python -m repro.launch.serve --arch llama3_2_3b --demo

Serves a batch of synthetic staggered-arrival prompts through
``serve.engine.ServingEngine`` on the local device and reports prefill
latency (time-to-first-token) separately from decode throughput.
Without ``--demo`` the model is the architecture's published config,
with q heads padded only to the model-axis size (1 on one chip: no
padding); ``--demo`` serves the reduced same-family config instead.
Weights are random, drawn from ``--seed`` by one jitted init that emits
the config's dtype (bf16), so no float32 copy of the weights is ever
held on the device.

``--paged`` switches the KV cache to the paged plane (fixed-size token
pages + per-request page tables; admission gated on free pages) —
outputs are token-identical to the slot plane by construction.
``--oracle`` additionally replays every request through the reference
``greedy_generate`` and verifies the engine reproduced it token-for-token.
``--fleet N`` serves the same workload through N heterogeneous replicas
behind the async fleet front-end (repro.fleet); ``--kill-at T`` kills
one replica at fleet tick T and ``--join-at T`` joins a fresh one — the
oracle check holds under any such schedule (exactly-once requeue).

Fault-domain flags (all tick-addressed, all deterministic):
``--transient-at T`` injects a transient step failure on one replica
(clearing after ``--transient-for`` ticks) to exercise the controller's
retry/backoff path; ``--checkpoint-every N`` snapshots a demo state
dict every N ticks into ``--checkpoint-dir`` and restores it re-sliced
onto the new plan on every kill/join; ``--min-alive K`` sets the
graceful-degradation floor (the front-end rejects with a typed
``FleetDegraded`` + retry-after below it); ``--drain-deadline T``
bounds the drain in ticks so a wedged schedule fails loud, never hangs.

Observability: ``--trace-out`` writes the deterministic step-clock trace
(``obs.Tracer``); ``--profile-dir DIR`` instead runs ``jax.profiler``
around the serve run and writes its trace under DIR, where the engine's
``serve.*`` host spans and the named scopes of its programs (``embed``,
``attention``, ``kv_write``, ``mlp``, ``lm_head``, ``page_gather``,
``page_scatter``) sit on one clock with the device's operations.  With
both flags the engine's spans go to the ``--trace-out`` tracer only.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config, get_reduced
from ..models import transformer as T
from ..models.config import ModelConfig
from ..obs import MetricsRegistry, Tracer, write_chrome_trace
from ..serve import (EngineConfig, EngineReport, PagedTransformerModel,
                     ServingEngine, TransformerModel, greedy_generate)
from ..sharding.rules import Rules
from .compile_cache import use_compile_cache


def _positive_int(flag: str):
    def parse(text: str) -> int:
        try:
            value = int(text)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"{flag} expects an integer, got {text!r}") from None
        if value < 1:
            raise argparse.ArgumentTypeError(
                f"{flag} must be >= 1, got {value} (the engine cannot "
                f"serve an empty batch or generate zero tokens)")
        return value
    return parse


def build_workload(args, vocab_size: int):
    """Synthetic staggered trace: prompt lengths vary below --prompt-len.
    With --prefix-sharing the trace is template-heavy instead (shared
    system-prompt prefixes + random suffixes) so sharing has something
    to share."""
    from ..serve.engine import shared_prefix_workload, synthetic_workload
    if getattr(args, "prefix_sharing", False):
        template_len = max(args.page_size, (args.prompt_len // 2
                                            // args.page_size)
                           * args.page_size)
        suffix_max = max(2, args.prompt_len - template_len)
        # varied decode lengths stagger retirements so same-template
        # requests overlap in flight — a single max_new retires whole
        # admission groups in lockstep and the creator's pages hit
        # refcount zero (index eviction) before the next match arrives
        news = tuple(sorted({max(1, args.max_new // 4),
                             max(1, args.max_new // 2), args.max_new}))
        return shared_prefix_workload(
            args.batch, vocab_size,
            n_templates=max(1, min(4, args.batch // 3)),
            template_len=template_len,
            suffix_lens=tuple(sorted({max(2, suffix_max // 2), suffix_max})),
            news=news, stagger=1.0 / max(1, args.slots), seed=args.seed)
    lens = sorted({max(2, args.prompt_len // 4), max(2, args.prompt_len // 2),
                   max(2, (3 * args.prompt_len) // 4), args.prompt_len})
    return synthetic_workload(args.batch, vocab_size, lens=lens,
                              news=(args.max_new,),
                              stagger=1.0 / max(1, args.slots),
                              seed=args.seed)


def serving_config(arch: str, demo: bool, rules: Rules) -> ModelConfig:
    """The config the launcher serves: the reduced variant under --demo,
    else the published widths with the head-padding quantum set to the
    model-axis size — 1 for single-device rules, so no q head is padded."""
    if demo:
        return get_reduced(arch)
    tp = 1 if rules.mesh is None else int(rules.mesh.shape["model"])
    return dataclasses.replace(get_config(arch), tp=tp)


def param_init(cfg: ModelConfig):
    """Jitted parameter init whose outputs are ``cfg.dtype``.  The float32
    draw of ``T.init_params`` exists only inside the one program, so the
    device never holds a float32 weight tree (6 GiB of bf16 weights for
    llama3_2_3b would be 12 GiB in float32)."""
    dtype = jnp.dtype(cfg.dtype)

    def init(key):
        return jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                      T.init_params(cfg, key))
    return jax.jit(init)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3_2_3b")
    ap.add_argument("--demo", action="store_true",
                    help="serve the reduced same-family config "
                         "(CPU-runnable) instead of the published widths")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the workload")
    ap.add_argument("--batch", type=_positive_int("--batch"), default=4)
    ap.add_argument("--prompt-len", type=_positive_int("--prompt-len"),
                    default=32)
    ap.add_argument("--max-new", type=_positive_int("--max-new"), default=16)
    ap.add_argument("--cache-len", type=_positive_int("--cache-len"),
                    default=None,
                    help="KV positions per slot (default: --prompt-len "
                         "+ --max-new)")
    ap.add_argument("--slots", type=_positive_int("--slots"), default=4,
                    help="continuous-batching cache slots")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV plane: page-table cache, admission "
                         "gated on free pages instead of free slots")
    ap.add_argument("--page-size", type=_positive_int("--page-size"),
                    default=8, help="tokens per KV page (with --paged)")
    ap.add_argument("--pages", type=_positive_int("--pages"), default=None,
                    help="physical page budget (default: slot-equivalent)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="share matching prompt-prefix pages across "
                         "requests (refcounted, copy-on-write; requires "
                         "--paged) and serve a template-heavy workload")
    ap.add_argument("--oracle", action="store_true",
                    help="verify every output against greedy_generate")
    ap.add_argument("--fleet", type=_positive_int("--fleet"), default=None,
                    help="serve through N replicas behind the async "
                         "fleet front-end instead of one engine")
    ap.add_argument("--kill-at", type=_positive_int("--kill-at"),
                    default=None,
                    help="fleet tick at which to kill one replica "
                         "(requires --fleet >= 2)")
    ap.add_argument("--join-at", type=_positive_int("--join-at"),
                    default=None,
                    help="fleet tick at which a fresh replica joins "
                         "(requires --fleet)")
    ap.add_argument("--transient-at", type=_positive_int("--transient-at"),
                    default=None,
                    help="replica tick at which one replica starts "
                         "raising transient step errors (requires "
                         "--fleet; exercises retry/backoff)")
    ap.add_argument("--transient-for",
                    type=_positive_int("--transient-for"), default=2,
                    help="how many replica ticks the transient lasts "
                         "before clearing (with --transient-at)")
    ap.add_argument("--max-retries", type=_positive_int("--max-retries"),
                    default=3,
                    help="transient retries before the controller "
                         "escalates to the kill/requeue path")
    ap.add_argument("--checkpoint-every",
                    type=_positive_int("--checkpoint-every"), default=None,
                    help="fleet ticks between sharded snapshots; also "
                         "enables restore-on-rescale (requires --fleet)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="snapshot directory (default: a temp dir, "
                         "with --checkpoint-every)")
    ap.add_argument("--min-alive", type=_positive_int("--min-alive"),
                    default=1,
                    help="graceful-degradation floor: below this many "
                         "live replicas the front-end rejects with "
                         "FleetDegraded + retry-after")
    ap.add_argument("--drain-deadline",
                    type=_positive_int("--drain-deadline"), default=None,
                    help="max fleet ticks to drain before raising "
                         "FleetDegraded instead of hanging")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(open at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics-registry snapshot as JSON")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="run jax.profiler around the serve run and write "
                         "its trace (device ops, the engine's serve.* "
                         "spans) under DIR")
    args = ap.parse_args(argv)
    if ((args.kill_at or args.join_at or args.transient_at
         or args.checkpoint_every or args.drain_deadline) and not args.fleet):
        ap.error("--kill-at/--join-at/--transient-at/--checkpoint-every/"
                 "--drain-deadline need --fleet")
    if args.kill_at and args.fleet < 2:
        ap.error("--kill-at needs --fleet >= 2 (a survivor must exist)")
    if args.checkpoint_dir and not args.checkpoint_every:
        ap.error("--checkpoint-dir needs --checkpoint-every")
    if args.prefix_sharing and not args.paged:
        ap.error("--prefix-sharing needs --paged (slot rows have no page "
                 "granularity to share)")
    if args.cache_len is not None and (args.cache_len
                                       < args.prompt_len + args.max_new):
        ap.error("--cache-len must hold --prompt-len + --max-new tokens")
    return args


def engine_config(args) -> EngineConfig:
    return EngineConfig(
        n_slots=args.slots, max_prompt_len=args.prompt_len,
        max_new_cap=args.max_new,
        cache_len=args.cache_len or args.prompt_len + args.max_new,
        page_size=args.page_size if args.paged else None,
        n_pages=args.pages if args.paged else None,
        prefix_sharing=args.prefix_sharing)


def load_model(args) -> Tuple[ModelConfig, Rules, Any]:
    """(config, rules, params) for ``args``: weights drawn from --seed."""
    rules = Rules.null()
    cfg = serving_config(args.arch, args.demo, rules)
    params = param_init(cfg)(jax.random.PRNGKey(args.seed))
    return cfg, rules, params


@dataclasses.dataclass
class ServeRun:
    """Everything one single-engine serving run produced."""

    cfg: ModelConfig
    rules: Rules
    params: Any
    workload: List[Tuple[np.ndarray, int, float]]
    engine: ServingEngine
    report: EngineReport
    tracer: Optional[Tracer] = None
    metrics: Optional[MetricsRegistry] = None


def serve(args) -> ServeRun:
    """The launcher's single-engine path: build the model and the
    workload, serve every request, return what came out."""
    cfg, rules, params = load_model(args)
    workload = build_workload(args, cfg.vocab_size)
    model_cls = PagedTransformerModel if args.paged else TransformerModel
    tracer = Tracer() if args.trace_out else None
    metrics = MetricsRegistry() if args.metrics_out else None
    engine = ServingEngine(model_cls(params, cfg, rules), engine_config(args),
                           tracer=tracer, metrics=metrics)
    for prompt, max_new, arrival in workload:
        engine.submit(prompt, max_new, arrival=arrival)
    report = engine.run()
    return ServeRun(cfg, rules, params, workload, engine, report,
                    tracer, metrics)


@dataclasses.dataclass(frozen=True)
class OracleResult:
    """One request against ``greedy_generate``.  ``diverge_at`` is None
    when the tokens are identical; otherwise it is the first differing
    position and ``margin`` the oracle's logit of its own token minus its
    logit of the engine's token there (small = a near-tie)."""

    rid: int
    diverge_at: Optional[int] = None
    margin: Optional[float] = None


def compare_to_oracle(params, cfg: ModelConfig, rules: Rules, workload,
                      completed: Dict[int, np.ndarray]) -> List[OracleResult]:
    out = []
    for rid, (prompt, max_new, _) in enumerate(workload):
        ref, logits = greedy_generate(params, cfg, rules,
                                      np.asarray(prompt)[None],
                                      max_new=max_new, return_logits=True)
        ref = np.asarray(ref)[0]
        got = np.asarray(completed[rid])
        diff = np.flatnonzero(ref != got)
        if diff.size == 0:
            out.append(OracleResult(rid))
            continue
        j = int(diff[0])
        row = np.asarray(logits[0, j], np.float32)
        out.append(OracleResult(rid, j, float(row[ref[j]] - row[got[j]])))
    return out


def main(argv=None):
    args = parse_args(argv)
    use_compile_cache()
    profile = (jax.profiler.trace(args.profile_dir) if args.profile_dir
               else contextlib.nullcontext())
    if args.fleet:
        cfg, rules, params = load_model(args)
        workload = build_workload(args, cfg.vocab_size)
        with profile:
            _serve_fleet(args, params, cfg, rules, workload)
        return
    with profile:
        run = serve(args)
    if args.profile_dir:
        print(f"profile: {args.profile_dir}")
    engine, report, cfg = run.engine, run.report, run.cfg
    _write_obs(args, run.tracer, run.metrics)

    plane = (f"paged(page_size={args.page_size}, "
             f"pages={engine.pool.n_pages})" if args.paged else "slots")
    print(f"arch={cfg.name}  requests={args.batch}  slots={args.slots}  "
          f"max_prompt={args.prompt_len}  new={args.max_new}  "
          f"cache={plane}")
    print(f"prefill: {report.prefill_count} prompts, "
          f"{report.prefill_tokens} tokens in {report.prefill_wall:.2f}s  "
          f"(TTFT mean {report.ttft_mean*1e3:.0f}ms)")
    print(f"decode:  {report.decode_tokens} tokens over "
          f"{report.decode_steps} steps (occupancy {report.occupancy:.2f})")
    print(f"total:   {report.total_tokens} tokens in {report.wall:.2f}s "
          f"({report.tokens_per_sec:.1f} tok/s aggregate)")
    if args.paged:
        print(f"pages:   occupancy {report.page_occupancy:.2f} "
              f"(mean used/total over decode steps)")
    if args.prefix_sharing:
        print(f"sharing: {engine.pool.n_shared_attached} page attaches, "
              f"max refcount {engine.pool.max_refcount}, "
              f"peak pages {engine.pool.peak_used_pages}")
    first = report.completed[0]
    print("generated token ids (first request):",
          list(map(int, first[:16])))

    if args.oracle:
        results = compare_to_oracle(run.params, cfg, run.rules, run.workload,
                                    report.completed)
        bad = [r for r in results if r.diverge_at is not None]
        assert not bad, f"engine != oracle: {bad}"
        print(f"oracle check: {len(results)} requests token-identical")


def _write_obs(args, tracer, metrics):
    """Export the observability artifacts the flags asked for."""
    if tracer is not None:
        print(f"trace:   {write_chrome_trace(tracer, args.trace_out)} "
              f"({len(tracer)} events; open at ui.perfetto.dev)")
    if metrics is not None:
        print(f"metrics: {metrics.write_json(args.metrics_out)}")


def _serve_fleet(args, params, cfg, rules, workload):
    """Serve the workload through N replicas behind the async front-end,
    with optional mid-run kill/join/transient faults, live
    checkpoint-recovery rescale, and graceful-degradation floors."""
    import contextlib
    import tempfile

    from ..fleet import (FaultPlan, FleetController, FleetFrontend, Replica,
                         RetryPolicy)

    tracer = Tracer() if args.trace_out else None
    metrics = MetricsRegistry() if args.metrics_out else None
    ec = engine_config(args)

    def make_model():
        cls = PagedTransformerModel if args.paged else TransformerModel
        return cls(params, cfg, rules)

    # a slot-plane TransformerModel is stateless wrt the cache (it is
    # passed in) so ONE adapter serves every replica — one compilation
    # set for the whole fleet; the paged adapter binds its page pool and
    # needs one instance per replica
    shared = None if args.paged else make_model()
    rates = [1.0, 2.0, 0.5, 1.5]   # heterogeneous fleet, cycled
    # the transient lands on a replica --kill-at does NOT target, so the
    # two faults compose instead of shadowing each other
    transient_on = (f"r{min(1, args.fleet - 1)}"
                    if args.transient_at else None)

    def fault_for(name):
        if name != transient_on:
            return None
        return FaultPlan(transient_at=args.transient_at,
                         transient_for=args.transient_for)

    replicas = [Replica(f"r{i}", shared if shared is not None
                        else make_model(), ec,
                        rate=rates[i % len(rates)],
                        fault=fault_for(f"r{i}"),
                        tracer=tracer, metrics=metrics)
                for i in range(args.fleet)]

    with contextlib.ExitStack() as stack:
        ckpt_dir = ckpt_state = None
        if args.checkpoint_every:
            ckpt_dir = (args.checkpoint_dir or
                        stack.enter_context(
                            tempfile.TemporaryDirectory(prefix="fleet_ckpt_")))
            # a demo state dict sized to the controller's virtual load:
            # partitioned leaves carry one row per virtual-k unit, so
            # restore re-slices them by the new plan's integer shares
            ckpt_state = {
                "w": np.arange(1024 * 4, dtype=np.float32).reshape(1024, 4),
                "bias": np.arange(8, dtype=np.float32),
            }
        controller = FleetController(
            replicas, retry=RetryPolicy(max_retries=args.max_retries),
            min_alive=args.min_alive, checkpoint_dir=ckpt_dir,
            checkpoint_state=ckpt_state,
            checkpoint_every=args.checkpoint_every or 0,
            tracer=tracer, metrics=metrics)
        if args.kill_at:
            controller.schedule_kill("r0", at_tick=args.kill_at)
        if args.join_at:
            controller.schedule_join(
                Replica(f"r{args.fleet}", shared if shared is not None
                        else make_model(), ec, rate=rates[0],
                        fault=FaultPlan(), tracer=tracer, metrics=metrics),
                at_tick=args.join_at)
        frontend = FleetFrontend(controller, max_pending=4 * args.fleet)
        for prompt, max_new, arrival in workload:
            controller.submit(prompt, max_new, arrival=arrival)
        report = asyncio_run_drain(frontend, deadline=args.drain_deadline)
    _write_obs(args, tracer, metrics)

    print(f"arch={cfg.name}  requests={args.batch}  fleet={args.fleet} "
          f"replicas  slots/replica={args.slots}  "
          f"plane={'paged' if args.paged else 'slots'}")
    print(f"ticks={report.ticks}  completed={report.n_completed}  "
          f"requeues={report.requeues}  kills={report.kills}  "
          f"joins={report.joins}")
    if report.retries or report.restores or report.corrupt_shards:
        print(f"faults:  retries={report.retries}  "
              f"recoveries={report.recoveries}  "
              f"restores={report.restores}  "
              f"corrupt_shards_skipped={report.corrupt_shards}")
    for name in sorted(report.occupancy):
        print(f"  {name}: occupancy {report.occupancy[name]:.2f}  "
              f"decode_tokens {report.decode_tokens[name]}")
    if args.oracle:
        bad = [r for r in compare_to_oracle(params, cfg, rules, workload,
                                            report.completed)
               if r.diverge_at is not None]
        assert not bad, f"fleet != oracle: {bad}"
        print(f"oracle check: {len(workload)} requests token-identical "
              f"under the fault schedule")


def asyncio_run_drain(frontend, deadline=None):
    import asyncio
    return asyncio.run(frontend.drain(deadline=deadline))


if __name__ == "__main__":
    main()

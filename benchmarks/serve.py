"""Serving throughput: continuous-batching engine vs the fixed-batch path.

  PYTHONPATH=src python -m benchmarks.serve [--smoke] [--out BENCH_serve.json]

Workload: staggered-arrival requests with mixed prompt/max-new lengths on
the reduced llama3_2_3b config.  The baseline is the pre-engine serving
path — fixed batches of ``slots`` requests, every prompt right-padded to
the longest and every request decoded for the longest max-new in the
workload (that is what a single fixed-shape batch costs).  Both sides are
timed after a warmup pass so jit compilation is excluded; throughput
counts *useful* tokens only (each request's own max_new) on both sides.
(The baseline's actual padded outputs are NOT the per-request greedy
tokens — short rows condition on pad KV, and logits are read at the
common padded last position — but it performs exactly the tensor work a
fixed-shape batch must, which is what the wall-clock comparison
measures; token correctness is the engine's tested property.)

Emits ``BENCH_serve.json``: tokens/sec, batch occupancy, time-to-first-
token for the perf trajectory (CI runs ``--smoke``), plus the
``paged_vs_slot`` section — the paged KV plane timed against the slot
plane on the same workload, with token-identity and fragmentation
evidence (requests spanning non-contiguous pages) as structural gates
for ``benchmarks/check_regression.py`` — and the ``fleet`` section: the
same workload through a 3-replica heterogeneous fleet with one replica
killed mid-decode and one joining later, checked token-identical to the
single engine (requeue counts and per-replica occupancy recorded).
``fleet.chaos`` is the fault-domain smoke: the same workload through a
fixed-seed COMPOSITE fault schedule (kill x transient x contention x
torn-shard x join) with retry/backoff and live checkpoint-recovery on,
reduced to structural verdicts (recoveries == injected transients,
restores == rescales, token identity, zero silent drops) that
``check_regression.py`` gates.  ``prefix_sharing`` is the shared-prefix
capacity smoke: the shared-template workload on the paged plane with
and without ``prefix_sharing``, gated on token identity (vs the private
plane and the greedy oracle), peak pages-in-use strictly below the
private baseline, observed refcounted attaches, and conservation at
drain (see ``docs/serving.md``).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time
from typing import Dict, List, Tuple

import numpy as np

# default artifact location: the repository root, so the perf trajectory
# is tracked across PRs instead of vanishing into /tmp or CI workspaces
DEFAULT_OUT = str(pathlib.Path(__file__).resolve().parents[1]
                  / "BENCH_serve.json")

PROMPT_LENS = (8, 16, 32, 64)
MAX_NEWS = (2, 4, 8, 32)    # heavy-tailed output lengths: the fixed batch
                            # decodes the max for every request, the engine
                            # retires each request at its own length


def make_workload(n: int, seed: int, vocab: int,
                  prompt_lens=PROMPT_LENS, max_news=MAX_NEWS,
                  stagger: float = 0.5
                  ) -> List[Tuple[np.ndarray, int, float]]:
    from repro.serve.engine import synthetic_workload
    return synthetic_workload(n, vocab, lens=prompt_lens, news=max_news,
                              stagger=stagger, seed=seed)


def run_engine(model, workload, slots: int, page_size=None
               ) -> Dict[str, float]:
    from repro.serve import EngineConfig, ServingEngine
    max_len = max(p.shape[0] for p, _, _ in workload)
    max_new = max(m for _, m, _ in workload)
    engine = ServingEngine(model, EngineConfig(
        n_slots=slots, max_prompt_len=max_len, max_new_cap=max_new,
        cache_len=max_len + max_new,
        max_prefill_per_step=max(2, slots // 2),
        page_size=page_size))
    for prompt, m, arrival in workload:
        engine.submit(prompt, m, arrival=arrival)
    rep = engine.run()
    assert len(rep.completed) == len(workload)
    # thin reader: the engine's report derives every metric through
    # obs.metrics.throughput_summary — no bench-side re-derivation
    out = rep.as_dict()
    if page_size is None:
        out.pop("page_occupancy")
    return out


def paged_identity(slot_model, paged_model, workload, slots: int,
                   page_size: int) -> Dict[str, object]:
    """Token-identity + fragmentation evidence for the paged plane: one
    run per plane, outputs compared request-by-request, and the paged
    pool's page history checked for multi-page non-contiguous spans."""
    from repro.serve import EngineConfig, ServingEngine
    max_len = max(p.shape[0] for p, _, _ in workload)
    max_new = max(m for _, m, _ in workload)

    def engine(model, ps):
        eng = ServingEngine(model, EngineConfig(
            n_slots=slots, max_prompt_len=max_len, max_new_cap=max_new,
            cache_len=max_len + max_new,
            max_prefill_per_step=max(2, slots // 2), page_size=ps))
        for prompt, m, arrival in workload:
            eng.submit(prompt, m, arrival=arrival)
        return eng

    slot_eng = engine(slot_model, None)
    paged_eng = engine(paged_model, page_size)
    slot_rep, paged_rep = slot_eng.run(), paged_eng.run()
    identical = all(
        np.array_equal(slot_rep.completed[rid], paged_rep.completed[rid])
        for rid in slot_rep.completed)
    hist = paged_eng.pool.page_history
    multi = sum(len(pages) >= 2 for pages in hist.values())
    frag = sum(any(b != a + 1 for a, b in zip(pages, pages[1:]))
               for pages in hist.values())
    return {
        "token_identical": bool(identical),
        "requests": len(hist),
        "multi_page_requests": int(multi),
        "fragmented_requests": int(frag),
    }


def run_fleet(model, workload, slots: int,
              reference: Dict[int, np.ndarray],
              artifacts_dir=None) -> Dict[str, object]:
    """Elastic-rescale scenario: 3 heterogeneous replicas sharing the
    slot adapter (one compilation set), one killed mid-decode, one
    joining later.  Deterministic by construction (tick clock, seeded
    workload, fixed fault schedule), so everything here is a structural
    gate: the fleet's tokens must equal the single engine's, requests
    must have been requeued by the kill, and nothing may be lost.

    The run is traced (one shared Tracer on the controller's tick axis)
    and metered; ``artifacts_dir`` receives ``trace.json`` (Perfetto)
    and ``metrics.json`` (registry snapshot) — the CI artifacts."""
    from repro.fleet import (FaultPlan, FleetController, FleetFrontend,
                             Replica)
    from repro.obs import MetricsRegistry, Tracer, write_chrome_trace
    from repro.serve import EngineConfig
    max_len = max(p.shape[0] for p, _, _ in workload)
    max_new = max(m for _, m, _ in workload)
    ec = EngineConfig(
        n_slots=slots, max_prompt_len=max_len, max_new_cap=max_new,
        cache_len=max_len + max_new,
        max_prefill_per_step=max(2, slots // 2))
    tracer, metrics = Tracer(), MetricsRegistry()
    replicas = [
        Replica("r0", model, ec, rate=1.0, fault=FaultPlan(kill_at=4),
                tracer=tracer, metrics=metrics),
        Replica("r1", model, ec, rate=2.0, tracer=tracer, metrics=metrics),
        Replica("r2", model, ec, rate=0.5, tracer=tracer, metrics=metrics),
    ]
    # stealing is ON but must stay invisible: this scenario injects
    # kill/join faults, never contention, so the drift corrector's
    # hysteresis has to hold at zero steals (gated by check_regression)
    controller = FleetController(replicas, miss_threshold=3, steal=True,
                                 tracer=tracer, metrics=metrics)
    controller.schedule_join(Replica("r3", model, ec, rate=1.5,
                                     tracer=tracer, metrics=metrics),
                             at_tick=8)
    frontend = FleetFrontend(controller, max_pending=2 * slots)
    report = frontend.serve(workload)
    identical = (set(report.completed) == set(reference)
                 and all(np.array_equal(reference[rid],
                                        report.completed[rid])
                         for rid in reference))
    # exercise the admission-rejection path end to end: an over-budget
    # prompt must be refused by a live engine and counted by reason
    from repro.serve.engine.queue import AdmissionError
    survivor = controller.replicas[controller.alive_names()[0]]
    try:
        survivor.engine.submit(np.zeros(max_len + max_new + 1, np.int32), 1)
    except AdmissionError:
        pass
    if artifacts_dir is not None:
        d = pathlib.Path(artifacts_dir)
        d.mkdir(parents=True, exist_ok=True)
        write_chrome_trace(tracer, d / "trace.json")
        metrics.write_json(d / "metrics.json")
    return {
        "token_identical": bool(identical),
        "completed": int(report.n_completed),
        "requeued": int(report.requeues),
        "kills": len(report.kills),
        "joins": len(report.joins),
        "steals": int(report.steals),
        "ticks": int(report.ticks),
        "replica_occupancy": {n: round(float(v), 4)
                              for n, v in sorted(
                                  report.occupancy.items())},
        "replica_decode_tokens": {n: int(v) for n, v in sorted(
            report.decode_tokens.items())},
        # the metrics-snapshot structural gates (check_regression):
        # counted requeues must match the report, rejections must be
        # counted by reason
        "metrics": {
            "requeues": int(metrics.counter_value("requeues")),
            "admission_rejections": int(
                metrics.counter_total("admission_rejections")),
            "heartbeat_misses": int(
                metrics.counter_value("heartbeat_misses")),
            "steals": int(metrics.counter_value("steals")),
            "trace_events": len(tracer),
        },
    }


def run_chaos_scenario(model, workload, slots: int,
                       reference: Dict[int, np.ndarray],
                       artifacts_dir=None) -> Dict[str, object]:
    """Chaos smoke: one fixed-seed COMPOSITE fault schedule through the
    shared chaos harness — kill + transient(retry/backoff) + contention
    + torn checkpoint shards + join, with live checkpoint-recovery on.
    Tick-driven and fully fault-scheduled, so every emitted number is a
    structural verdict for check_regression: recoveries must equal the
    injected transients, every rescale must restore the checkpointed
    state (falling back past the torn snapshots), tokens must equal the
    single-engine reference, and nothing may be silently dropped."""
    import tempfile
    from repro.fleet import (ChaosReplicaSpec, ChaosSchedule, FaultPlan,
                             Replica, RetryPolicy, chaos_verdicts,
                             run_chaos)
    from repro.obs import MetricsRegistry, Tracer, write_chrome_trace
    from repro.serve import EngineConfig
    max_len = max(p.shape[0] for p, _, _ in workload)
    max_new = max(m for _, m, _ in workload)
    ec = EngineConfig(
        n_slots=slots, max_prompt_len=max_len, max_new_cap=max_new,
        cache_len=max_len + max_new,
        max_prefill_per_step=max(2, slots // 2))
    tracer, metrics = Tracer(), MetricsRegistry()

    def mk(name, rate, fault):
        return Replica(name, model, ec, rate=rate, fault=fault,
                       tracer=tracer, metrics=metrics)

    schedule = ChaosSchedule(
        replicas=(
            ChaosReplicaSpec("c0", 1.0, FaultPlan(kill_at=6)),
            ChaosReplicaSpec("c1", 2.0, FaultPlan(transient_at=3,
                                                  transient_for=2)),
            # contended AND tearing its shard of every snapshot from its
            # step 2 on — restores must fall back to an intact epoch
            ChaosReplicaSpec("c2", 1.0, FaultPlan(slow_at=2, slow_factor=2,
                                                  torn_shard_at=2)),
        ),
        join_at=10, join_name="c3", join_rate=1.5, checkpoint_every=4)
    # the co-hosted LBP state the controller snapshots/restores: one
    # load-sized leaf (sharded by the rebalance plan) + one replicated
    state = {"w": np.arange(1024 * 4, dtype=np.float32).reshape(1024, 4),
             "bias": np.arange(8, dtype=np.float32)}
    with tempfile.TemporaryDirectory() as ckpt_dir:
        ctrl, report = run_chaos(
            schedule, mk, workload,
            retry=RetryPolicy(max_retries=3, backoff_base=1, backoff_cap=8),
            checkpoint_dir=ckpt_dir, checkpoint_state=state,
            tracer=tracer, metrics=metrics)
    v = chaos_verdicts(schedule, report, workload, reference)
    if artifacts_dir is not None:
        d = pathlib.Path(artifacts_dir)
        d.mkdir(parents=True, exist_ok=True)
        write_chrome_trace(tracer, d / "chaos_trace.json")
    v["metrics"] = {
        "retries": int(metrics.counter_value("retries")),
        "recoveries": int(metrics.counter_value("recoveries")),
        "restores": int(metrics.counter_value("restores")),
        "corrupt_shards": int(metrics.counter_value("corrupt_shards")),
        "checkpoints": int(metrics.counter_value("checkpoints")),
        "trace_events": len(tracer),
    }
    return v


def run_prefix_sharing(paged_model, params, cfg, rules,
                       smoke: bool) -> Dict[str, object]:
    """Prefix-sharing capacity smoke: the shared-template workload
    served twice on the paged plane — worst-case private reservation vs
    ``prefix_sharing`` — reduced to structural verdicts for
    ``check_regression.py``: token identity vs the non-sharing plane AND
    (spot-checked, one request per template) vs ``greedy_generate``,
    peak pages-in-use strictly below the private baseline, refcounted
    attaches actually observed, and conservation at drain."""
    from repro.serve import EngineConfig, ServingEngine, greedy_generate
    from repro.serve.engine import shared_prefix_workload
    n, n_templates = (16, 2) if smoke else (32, 4)
    wl = shared_prefix_workload(n, cfg.vocab_size, n_templates=n_templates,
                                template_len=16, suffix_lens=(4, 8, 12),
                                news=(4, 8, 12, 16), stagger=0.5)

    def run(sharing):
        eng = ServingEngine(paged_model, EngineConfig(
            n_slots=8, max_prompt_len=28, max_new_cap=16, cache_len=44,
            page_size=4, prefix_sharing=sharing))
        for prompt, m, arrival in wl:
            eng.submit(prompt, m, arrival=arrival)
        return eng, eng.run()

    eng_off, rep_off = run(False)
    eng_on, rep_on = run(True)
    identical = all(np.array_equal(rep_off.completed[rid],
                                   rep_on.completed[rid])
                    for rid in rep_off.completed)
    oracle_ok = True
    for rid in range(n_templates):            # one request per template
        prompt, m, _ = wl[rid]
        ref = np.asarray(greedy_generate(params, cfg, rules,
                                         np.asarray(prompt)[None],
                                         max_new=m))[0]
        oracle_ok = oracle_ok and np.array_equal(ref, rep_on.completed[rid])
    pool_on, pool_off = eng_on.pool, eng_off.pool
    return {
        "requests": n, "templates": n_templates,
        "token_identical_vs_private": bool(identical),
        "token_identical_vs_oracle": bool(oracle_ok),
        "peak_used_pages_private": int(pool_off.peak_used_pages),
        "peak_used_pages_shared": int(pool_on.peak_used_pages),
        "capacity_ratio": (pool_off.peak_used_pages
                           / max(pool_on.peak_used_pages, 1)),
        "shared_attaches": int(pool_on.n_shared_attached),
        "max_refcount": int(pool_on.max_refcount),
        "refcount_conserved": bool(
            pool_on.n_allocated == pool_on.n_freed
            and len(pool_on.prefix_index) == 0
            and pool_on.free_page_count == pool_on.n_pages),
    }


def run_fixed_batch(params, cfg, rules, workload, slots: int
                    ) -> Dict[str, float]:
    """The seed serving path: fixed batches, padded to the workload max."""
    import jax.numpy as jnp
    from repro.serve import cached_decode_step, cached_prefill_step
    from repro.models import transformer as T
    Smax = max(p.shape[0] for p, _, _ in workload)
    new_max = max(m for _, m, _ in workload)
    prefill = cached_prefill_step(cfg, rules)
    decode = cached_decode_step(cfg, rules)
    useful = sum(m for _, m, _ in workload)

    t0 = time.perf_counter()
    ttfts = []
    for g in range(0, len(workload), slots):
        group = workload[g:g + slots]
        batch = np.zeros((slots, Smax), np.int32)   # pad rows + dummy reqs
        for b, (p, _, _) in enumerate(group):
            batch[b, :p.shape[0]] = p
        cache = T.init_cache(cfg, slots, Smax + new_max)
        cache, logits = prefill(params, jnp.asarray(batch), cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        tok.block_until_ready()
        ttfts += [time.perf_counter() - t0] * len(group)
        pos = jnp.full((slots,), Smax, jnp.int32)
        for _ in range(new_max - 1):
            nxt, _, cache = decode(params, tok, pos, cache)
            tok = nxt[:, None]
            pos = pos + 1
        tok.block_until_ready()
    wall = time.perf_counter() - t0
    n_groups = (len(workload) + slots - 1) // slots
    raw = n_groups * slots * new_max
    decode_steps = n_groups * (new_max - 1)
    # same derivation as the engine report (obs.metrics.throughput_summary):
    # the fixed batch contributes its useful fraction once per decode step
    from repro.obs import throughput_summary
    return throughput_summary(
        useful_tokens=useful, wall_s=wall, ttfts_s=ttfts,
        occupancy_sum=(useful / raw) * decode_steps,
        decode_steps=decode_steps)


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload for CI (16 requests, 4 slots)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page for the paged-plane side")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=5,
                    help="measured repetitions; best wall per side is kept "
                         "(shared CI runners swing several-fold run to run)")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args(argv)

    import jax
    from repro.configs import get_reduced
    from repro.models import transformer as T
    from repro.serve import TransformerModel
    from repro.sharding.rules import Rules

    n, slots = (16, 4) if args.smoke else (args.requests, args.slots)
    lens, news = ((8, 16), (2, 16)) if args.smoke else (PROMPT_LENS, MAX_NEWS)
    page_size = args.page_size
    cfg = get_reduced("llama3_2_3b")
    rules = Rules.null()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    workload = make_workload(n, args.seed, cfg.vocab_size, lens, news)
    from repro.serve import PagedTransformerModel
    model = TransformerModel(params, cfg, rules)
    paged_model = PagedTransformerModel(params, cfg, rules)

    # warmup: compile every shape all three paths will touch
    run_engine(model, workload, slots)
    run_engine(paged_model, workload, slots, page_size=page_size)
    run_fixed_batch(params, cfg, rules, workload, slots)

    eng = min((run_engine(model, workload, slots)
               for _ in range(args.reps)), key=lambda r: r["wall_s"])
    paged = min((run_engine(paged_model, workload, slots,
                            page_size=page_size)
                 for _ in range(args.reps)), key=lambda r: r["wall_s"])
    base = min((run_fixed_batch(params, cfg, rules, workload, slots)
                for _ in range(args.reps)), key=lambda r: r["wall_s"])
    identity = paged_identity(model, paged_model, workload, slots,
                              page_size)

    # fleet oracle reference: the single engine's tokens (themselves
    # oracle-tested against greedy_generate in tier-1)
    from repro.serve import EngineConfig, ServingEngine
    max_len = max(p.shape[0] for p, _, _ in workload)
    max_new = max(m for _, m, _ in workload)
    ref_eng = ServingEngine(model, EngineConfig(
        n_slots=slots, max_prompt_len=max_len, max_new_cap=max_new,
        cache_len=max_len + max_new,
        max_prefill_per_step=max(2, slots // 2)))
    for prompt, m, arrival in workload:
        ref_eng.submit(prompt, m, arrival=arrival)
    reference = ref_eng.run().completed
    # trace.json / metrics.json land beside the BENCH artifact (CI
    # uploads the whole directory)
    fleet = run_fleet(model, workload, slots, reference,
                      artifacts_dir=pathlib.Path(args.out).parent)
    fleet["chaos"] = run_chaos_scenario(
        model, workload, slots, reference,
        artifacts_dir=pathlib.Path(args.out).parent)
    sharing = run_prefix_sharing(paged_model, params, cfg, rules,
                                 smoke=args.smoke)
    result = {
        "workload": {"requests": n, "slots": slots, "seed": args.seed,
                     "prompt_lens": list(lens), "max_news": list(news),
                     "page_size": page_size,
                     "arch": cfg.name, "smoke": bool(args.smoke)},
        "engine": eng,
        "paged": paged,
        "fixed_batch": base,
        "speedup": eng["tokens_per_sec"] / base["tokens_per_sec"],
        "paged_vs_slot": {
            "tokens_per_sec_ratio": (paged["tokens_per_sec"]
                                     / eng["tokens_per_sec"]),
            "occupancy_delta": paged["occupancy"] - eng["occupancy"],
            "page_occupancy": paged["page_occupancy"],
            **identity,
        },
        "fleet": fleet,
        "prefix_sharing": sharing,
    }
    print(f"\nworkload: {n} staggered requests, {slots} slots, {cfg.name}")
    print(f"engine:      {eng['tokens_per_sec']:8.1f} tok/s  "
          f"occupancy {eng['occupancy']:.2f}  "
          f"ttft {eng['ttft_mean_s']*1e3:.0f}ms")
    print(f"paged:       {paged['tokens_per_sec']:8.1f} tok/s  "
          f"occupancy {paged['occupancy']:.2f}  "
          f"page-occ {paged['page_occupancy']:.2f}  "
          f"(page_size={page_size})")
    print(f"fixed batch: {base['tokens_per_sec']:8.1f} tok/s  "
          f"useful-fraction {base['occupancy']:.2f}  "
          f"ttft {base['ttft_mean_s']*1e3:.0f}ms")
    print(f"speedup:     {result['speedup']:.2f}x   paged/slot "
          f"{result['paged_vs_slot']['tokens_per_sec_ratio']:.2f}x  "
          f"identical={identity['token_identical']}  "
          f"fragmented {identity['fragmented_requests']}"
          f"/{identity['requests']}")
    print(f"fleet:       {fleet['completed']} completed in "
          f"{fleet['ticks']} ticks, {fleet['kills']} kill / "
          f"{fleet['joins']} join, requeued {fleet['requeued']}, "
          f"steals {fleet['steals']}, "
          f"identical={fleet['token_identical']}")
    ch = fleet["chaos"]
    print(f"chaos:       {ch['completed']} completed under composite "
          f"faults: {ch['retries']} retries -> {ch['recoveries']} "
          f"recovered, {ch['kills']} kill / {ch['joins']} join -> "
          f"{ch['restores']} restores ({ch['corrupt_shards']} torn "
          f"snapshots skipped), identical={ch['token_identical']}, "
          f"gates={'all pass' if all(ch['gates'].values()) else ch['gates']}")
    print(f"sharing:     {sharing['requests']} reqs / "
          f"{sharing['templates']} templates: peak pages "
          f"{sharing['peak_used_pages_private']} -> "
          f"{sharing['peak_used_pages_shared']} "
          f"({sharing['capacity_ratio']:.2f}x), "
          f"{sharing['shared_attaches']} attaches, max refcount "
          f"{sharing['max_refcount']}, "
          f"identical={sharing['token_identical_vs_private']}"
          f"/oracle={sharing['token_identical_vs_oracle']}, "
          f"conserved={sharing['refcount_conserved']}")
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {args.out}")
    return result


if __name__ == "__main__":
    main()

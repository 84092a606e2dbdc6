"""Continuous-batching serving engine.

One engine iteration = (retire, admit+prefill, one slot-batched decode
step).  Prefill runs per request at its exact prompt length (B=1, no
padding) and the resulting cache row is spliced into the slot pool;
decode runs once per iteration over the *whole* slot batch with per-row
token/position vectors, so requests at different depths share the step.
Inactive slots decode garbage rows that are simply never read — the jit
cost of a fixed batch shape buys a single decode compilation for the
engine's lifetime.

The decode loop never syncs with the device: per-slot token/position
state stays on device (inactive slots carry garbage that admission
overwrites), each step's next-token vector is appended to a trace, and
completion is detected by *count* (a request joins every decode batch
from admission until it has max_new tokens, so its tokens are consecutive
trace rows).  The trace is materialized once at drain — host round-trips
per served token would otherwise dominate small-model serving.

Under greedy decoding the engine is token-identical to per-request
``serve.step.greedy_generate`` (the reference oracle): decode attention
masks cache positions beyond each request's own depth, so neither the
shared (longer) cache length nor the co-batched neighbours change a
request's logits' argmax.

The engine is model-agnostic: anything with ``init_pool`` / ``prefill``
/ ``decode`` (see ``TransformerModel``) can serve, which is how the
scheduling-invariant property tests run against a tensor-free fake.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...models import transformer as T
from ...models.config import ModelConfig
from ...obs import clock as obs_clock
from ...obs.metrics import MetricsRegistry, throughput_summary
from ...obs.trace import NullTracer, ProfilerTracer
from ...sharding.rules import Rules
from .cache_pool import PagedCachePool, SlotCachePool, write_slot
from .queue import AdmissionError, AdmissionLimits, RequestQueue
from .request import Request
from .scheduler import Scheduler

# fixed deterministic bucket edges for the TTFT histogram (seconds) —
# fixed edges keep per-replica histograms mergeable order-invariantly
TTFT_EDGES = (0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0)


class TransformerModel:
    """Adapter binding the engine to ``models.transformer`` serving steps.

    Every engine operation is ONE jitted dispatch — serving small models
    is dispatch-bound, so prefill fuses cache init + forward + argmax +
    slot splice + token-state update into a single call (compiled once
    per distinct prompt length; the slot index is traced), and decode
    fuses the position advance.  ``decode_multi`` runs k decode steps in
    one ``lax.scan`` dispatch (compiled once per k) for the drain phase.
    """

    def __init__(self, params, cfg: ModelConfig, rules: Rules):
        if cfg.family == "ssm":
            raise NotImplementedError(
                "ssm caches mix batch axes; the slot pool assumes batch "
                "axis 1 on every cache leaf")
        from ..step import make_decode_step
        self.params = params
        self.cfg = cfg
        self.rules = rules
        # host spans of the adapter's own work (the engine hands over its
        # tracer when it is built around this adapter)
        self.tracer = NullTracer()
        self._decode_step = make_decode_step(cfg, rules)

        def group_prefill(cache_len, params, tokens, lengths, slots, pool,
                          tok_vec, pos_vec):
            """Prefill B requests right-padded to one length, splice each
            row into its slot.  Valid because causal attention keeps pad
            positions out of real rows, and decode overwrites each pad
            cache entry before the position mask exposes it.

            ``cache_len`` is static (the pool's time length, recorded by
            init_pool) — it cannot be sniffed from pool leaf shapes, which
            for hybrid caches lead with the conv-state width."""
            B = tokens.shape[0]
            batch = T.init_cache(cfg, B, cache_len)
            batch, logits = T.prefill(params, cfg, rules, tokens, batch,
                                      last_index=lengths - 1)
            with jax.named_scope("lm_head"):
                firsts = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            for b in range(B):   # static unroll: B is a compile-time const
                row = jax.tree_util.tree_map(
                    lambda c: jax.lax.dynamic_slice_in_dim(c, b, 1, axis=1),
                    batch)
                pool = write_slot(pool, row, slots[b])
                tok_vec = jax.lax.dynamic_update_slice(
                    tok_vec, firsts[b:b + 1], (slots[b],))
                pos_vec = jax.lax.dynamic_update_slice(
                    pos_vec, lengths[b:b + 1], (slots[b],))
            return pool, firsts, tok_vec, pos_vec

        def decode1(params, tok, pos, cache):
            nxt, _, cache = self._decode_step(params, tok[:, None], pos,
                                              cache)
            return cache, nxt, nxt, pos + 1

        def decode_k(k):
            def run(params, tok, pos, cache):
                def body(carry, _):
                    tok, pos, cache = carry
                    nxt, _, cache = self._decode_step(params, tok[:, None],
                                                      pos, cache)
                    return (nxt, pos + 1, cache), nxt

                (tok, pos, cache), stack = jax.lax.scan(
                    body, (tok, pos, cache), None, length=k)
                return cache, stack, tok, pos
            return run

        self._group_prefill = jax.jit(group_prefill, static_argnums=0)
        self._cache_len = None            # recorded by init_pool
        self._decode1 = jax.jit(decode1)
        self._decode_k = {}
        self._decode_k_builder = decode_k
        # right-padded grouped prefill needs a purely causal stack: any
        # recurrent state (hybrid/ssm) or ring-windowed cache would absorb
        # the pad tokens, so those families prefill one request at a time.
        self.can_group_prefill = (cfg.family in ("dense", "moe")
                                  and cfg.window == 0)

    def init_pool(self, n_slots: int, cache_len: int):
        self._cache_len = int(cache_len)
        return T.init_cache(self.cfg, n_slots, cache_len)

    def token_state(self, n_slots: int):
        """Initial per-slot (token, position) decode inputs (on device)."""
        return jnp.zeros(n_slots, jnp.int32), jnp.zeros(n_slots, jnp.int32)

    def prefill(self, pool, prompts, slots, tok, pos):
        """Prefill a group of requests into their slots in ONE dispatch
        (right-padded to the group max; compiled once per (B, max_len)).

        Returns (pool, firsts (B,) device array, tok, pos) with every
        slot's token-state entries updated — no host sync.  Families that
        cannot pad (recurrent state) fall back to per-request calls.
        """
        if not self.can_group_prefill and len(prompts) > 1:
            firsts = []
            for prompt, slot in zip(prompts, slots):
                pool, f, tok, pos = self.prefill(pool, [prompt], [slot],
                                                 tok, pos)
                firsts.append(f)
            return pool, jnp.concatenate(firsts), tok, pos
        assert self._cache_len is not None, "init_pool must run first"
        B = len(prompts)
        lengths = np.array([p.shape[0] for p in prompts], np.int32)
        smax = int(lengths.max())
        batch = np.zeros((B, smax), np.int32)
        for b, p in enumerate(prompts):
            batch[b, :p.shape[0]] = p
        return self._group_prefill(self._cache_len, self.params,
                                   jnp.asarray(batch), jnp.asarray(lengths),
                                   jnp.asarray(np.asarray(slots, np.int32)),
                                   pool, tok, pos)

    def decode(self, pool, tok, pos):
        """One decode step over the full slot batch.

        Returns (pool, next (n_slots,), tok, pos) — the position advance
        is fused; nothing syncs with the host.
        """
        return self._decode1(self.params, tok, pos, pool)

    def decode_multi(self, pool, tok, pos, k: int):
        """k fused decode steps in one dispatch; next tokens stacked
        (k, n_slots).  Compiles once per distinct k (the engine buckets
        k to powers of two)."""
        if k == 1:
            pool, nxt, tok, pos = self.decode(pool, tok, pos)
            return pool, nxt[None], tok, pos
        if k not in self._decode_k:
            self._decode_k[k] = jax.jit(self._decode_k_builder(k))
        return self._decode_k[k](self.params, tok, pos, pool)


class PagedTransformerModel(TransformerModel):
    """Transformer adapter for the paged KV plane.

    Same dispatch discipline as the slot adapter — grouped prefill and
    every decode stretch are ONE jitted call — but the cache pytree is a
    physical page pool (``n_pages + 1`` pages of ``page_size`` token rows
    per layer; the extra page is the trash page) and every dispatch takes
    the host-maintained page tables as arguments.  Decode reads K/V
    through the READ table and writes each new row through the WRITE
    table *inside* the jit (serve.step paged builders), so the paged plane
    adds zero dispatches over the slot plane.

    Every program that writes the pool donates it, so it updates the pool
    in place.  A call (``prefill`` or ``decode``) consumes the pool it is
    given only if that pool is the one the adapter last returned, and if
    a pool this call returned has come back before: its caller threads
    the pool through instead of keeping it.  That is the engine's own
    chain, where the cache is always the previous call's output.  Any
    other pool is first copied on the device, and the copy is donated, so
    the caller's pool stays intact: the initial pool, one a caller keeps
    and passes again (the benchmark's warm-up), and the input of a call
    whose outputs never come back (a caller that throws them away).
    ``kv_pool_in_place`` and ``kv_pool_copied`` count the two cases; an
    engine copies twice, at its first prefill and its first decode.

    Restricted to purely-causal attention caches (dense/moe, no window):
    recurrent state mixes batch axes and ring-windowed caches wrap
    positions mod the window, neither of which pages cleanly.
    """

    def __init__(self, params, cfg: ModelConfig, rules: Rules):
        super().__init__(params, cfg, rules)
        if not self.can_group_prefill:
            raise NotImplementedError(
                "paged KV serving supports purely-causal attention caches "
                "(dense/moe families, window == 0); recurrent and "
                "windowed caches do not page cleanly")
        from ..step import make_paged_decode_step
        from .cache_pool import scatter_page_view
        self._paged: Optional[PagedCachePool] = None
        # the engine hands over its registry, as it does its tracer
        self.metrics = MetricsRegistry()
        # the pool the last pool-writing call returned (the object itself:
        # an id can be recycled) and that call; the calls whose pools have
        # come back, whose callers therefore thread the pool through
        self._last_pool, self._last_call = None, None
        self._threaded = set()

        def paged_group_prefill(view_len, params, tokens, lengths, slots,
                                tables, pool, tok_vec, pos_vec):
            """Prefill B requests right-padded to one length, scatter each
            row through its page table.  Unclaimed logical pages map to
            the trash page; claimed pages receive the freshly-initialized
            row (zero tail included), so no stale bytes from a previous
            page owner are ever visible below a request's depth."""
            B = tokens.shape[0]
            batch = T.init_cache(self.cfg, B, view_len)
            batch, logits = T.prefill(params, self.cfg, self.rules, tokens,
                                      batch, last_index=lengths - 1)
            with jax.named_scope("lm_head"):
                firsts = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            for b in range(B):   # static unroll: B is a compile-time const
                row = jax.tree_util.tree_map(
                    lambda c: jax.lax.dynamic_slice_in_dim(c, b, 1, axis=1),
                    batch)
                pool = scatter_page_view(pool, row, tables[b:b + 1])
                tok_vec = jax.lax.dynamic_update_slice(
                    tok_vec, firsts[b:b + 1], (slots[b],))
                pos_vec = jax.lax.dynamic_update_slice(
                    pos_vec, lengths[b:b + 1], (slots[b],))
            return pool, firsts, tok_vec, pos_vec

        step1 = make_paged_decode_step(self.cfg, rules)

        def paged_decode1(params, tok, pos, pool, table, write_table):
            nxt, _, pool = step1(params, tok[:, None], pos, pool, table,
                                 write_table)
            return pool, nxt, nxt, pos + 1

        self._paged_prefill = jax.jit(paged_group_prefill, static_argnums=0,
                                      donate_argnames="pool")
        self._paged_decode1 = jax.jit(paged_decode1, donate_argnames="pool")
        self._paged_decode_k = {}
        self._copy_pool = jax.jit(
            lambda pool: jax.tree_util.tree_map(jnp.copy, pool))

    def init_paged_pool(self, pool: PagedCachePool):
        """Bind the page allocator and build the device-side page pool:
        one batch row per physical page (+ the trash page)."""
        self._paged = pool
        self._last_pool, self._last_call = None, None
        self._threaded = set()
        return T.init_cache(self.cfg, pool.n_pages + 1, pool.page_size)

    def paged_decode_scan(self, k: int):
        """The jitted k-step decode scan, built once per k; it donates the
        pool like the one-step program."""
        if k not in self._paged_decode_k:
            from ..step import make_paged_decode_scan
            self._paged_decode_k[k] = jax.jit(
                make_paged_decode_scan(self.cfg, self.rules, k),
                donate_argnames="pool")
        return self._paged_decode_k[k]

    def _consumable(self, pool, call: str):
        """What ``call``'s donating program may consume: ``pool`` itself if
        it is the adapter's last output and ``call`` is threaded, else a
        device copy of it."""
        if pool is self._last_pool:
            self._threaded.add(self._last_call)
            if call in self._threaded:
                self.metrics.counter("kv_pool_in_place").inc()
                return pool
        # let the last output go before the copy is made, so that no more
        # pools are alive at once than without donation
        self._last_pool = None
        self.metrics.counter("kv_pool_copied").inc()
        return self._copy_pool(pool)

    def _returned(self, out, call: str):
        self._last_pool, self._last_call = out[0], call
        return out

    def _tables(self):
        # snapshots, never aliases: on CPU jnp.asarray can be ZERO-COPY
        # over the host numpy buffer, and the allocator mutates the page
        # maps in place while the previous async dispatch may still be
        # reading them — without the copies the maps race the device
        with self.tracer.span("page_tables", lane="engine"):
            return (jnp.asarray(self._paged.table.copy()),
                    jnp.asarray(self._paged.write_table.copy()))

    def prefill(self, pool, prompts, slots, tok, pos):
        assert self._paged is not None, "init_paged_pool must run first"
        B = len(prompts)
        lengths = np.array([p.shape[0] for p in prompts], np.int32)
        batch = np.zeros((B, int(lengths.max())), np.int32)
        for b, p in enumerate(prompts):
            batch[b, :p.shape[0]] = p
        slots_np = np.asarray(slots, np.int32)
        # prefill scatters through the WRITE map: attached shared-prefix
        # pages are trash there, so a follower's recomputed prefix KV is
        # discarded and the creator's pages are never overwritten (the
        # fancy index copies — no alias of the live host map)
        with self.tracer.span("page_tables", lane="engine"):
            tables = jnp.asarray(self._paged.write_table[slots_np])
        return self._returned(self._paged_prefill(
            self._paged.view_len, self.params, jnp.asarray(batch),
            jnp.asarray(lengths), jnp.asarray(slots_np), tables,
            self._consumable(pool, "prefill"), tok, pos), "prefill")

    def decode(self, pool, tok, pos):
        table, write_table = self._tables()
        return self._returned(self._paged_decode1(
            self.params, tok, pos, self._consumable(pool, "decode"), table,
            write_table), "decode")

    def decode_multi(self, pool, tok, pos, k: int):
        if k == 1:
            pool, nxt, tok, pos = self.decode(pool, tok, pos)
            return pool, nxt[None], tok, pos
        scan = self.paged_decode_scan(k)
        table, write_table = self._tables()
        return self._returned(scan(self.params, tok, pos,
                                   self._consumable(pool, "decode"), table,
                                   write_table), "decode")


class ManualClock:
    """Deterministic injectable clock for wall-clock arrival replay in
    tests: ``clock()`` reads the time, ``sleep`` advances it (the engine
    calls ``sleep`` when idle until the next arrival)."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += float(dt)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 8
    max_prompt_len: int = 64
    max_new_cap: int = 64
    max_queue: int = 4096
    max_prefill_per_step: int = 2
    cache_len: Optional[int] = None   # default: max_prompt_len + max_new_cap
    # paged KV plane: set page_size to gate admission on free pages
    # instead of free slots (n_slots then only caps decode-batch width)
    page_size: Optional[int] = None
    n_pages: Optional[int] = None     # default: n_slots * pages_per_slot
    # prefix sharing (paged plane only): requests whose prompts agree on
    # leading FULL pages share those physical pages (refcounted, CoW);
    # admission reserves shared + private instead of the worst case
    prefix_sharing: bool = False
    # arrival units: "steps" (engine iterations, the default) or
    # "seconds" (wall-clock replay against a monotonic clock)
    arrival_mode: str = "steps"

    @property
    def pool_len(self) -> int:
        return (self.cache_len if self.cache_len is not None
                else self.max_prompt_len + self.max_new_cap)

    @property
    def paged(self) -> bool:
        return self.page_size is not None

    @property
    def pages_per_slot(self) -> int:
        assert self.page_size is not None
        return -(-self.pool_len // self.page_size)

    @property
    def pool_pages(self) -> int:
        """Physical page budget (default: slot-pool-equivalent memory)."""
        return (self.n_pages if self.n_pages is not None
                else self.n_slots * self.pages_per_slot)


@dataclasses.dataclass
class EngineReport:
    completed: Dict[int, np.ndarray]       # rid -> generated tokens
    steps: int
    decode_steps: int
    prefill_count: int
    decode_tokens: int
    prefill_tokens: int
    occupancy: float                       # mean active/n_slots over decode steps
    ttft: Dict[int, float]                 # rid -> seconds to first token
    wall: float
    prefill_wall: float
    page_occupancy: float = 0.0            # mean used/total pages (paged only)

    @property
    def total_tokens(self) -> int:
        # every completed request's first token came from its prefill
        return self.decode_tokens + len(self.completed)

    @property
    def tokens_per_sec(self) -> float:
        return self.total_tokens / max(self.wall, 1e-9)

    @property
    def ttft_mean(self) -> float:
        return float(np.mean(list(self.ttft.values()))) if self.ttft else 0.0

    def as_dict(self) -> Dict[str, float]:
        """Benchmark-facing view via the ONE metric derivation
        (``obs.metrics.throughput_summary``) — benchmarks read this dict
        instead of re-deriving tok/s / TTFT / occupancy themselves, so
        bench-vs-engine metric skew is impossible by construction."""
        out = throughput_summary(
            useful_tokens=self.total_tokens, wall_s=self.wall,
            ttfts_s=self.ttft.values(),
            occupancy_sum=self.occupancy * self.decode_steps,
            decode_steps=self.decode_steps)
        out.update(steps=self.steps, prefill_count=self.prefill_count,
                   n_completed=len(self.completed),
                   page_occupancy=self.page_occupancy)
        return out


class ServingEngine:
    def __init__(self, model, config: EngineConfig = EngineConfig(),
                 clock=None, tracer=None, metrics=None,
                 name: str = "engine"):
        if config.arrival_mode not in ("steps", "seconds"):
            raise ValueError(
                f"arrival_mode must be 'steps' or 'seconds', got "
                f"{config.arrival_mode!r}")
        self.model = model
        self.config = config
        self.name = name
        # observability plane (host-side only — hooks never add a jitted
        # dispatch; the ProfilerTracer default makes every hook one
        # inactive TraceMe unless a jax.profiler session is running)
        self.tracer = tracer if tracer is not None else ProfilerTracer()
        if hasattr(model, "tracer"):
            model.tracer = self.tracer
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        if hasattr(model, "metrics"):
            model.metrics = self.metrics
        self.queue = RequestQueue(AdmissionLimits(
            max_prompt_len=config.max_prompt_len,
            max_new_cap=config.max_new_cap,
            max_queue=config.max_queue,
            max_total_len=config.pool_len))
        if config.paged:
            if not hasattr(model, "init_paged_pool"):
                raise TypeError(
                    "page_size is set but the model adapter has no "
                    "init_paged_pool — use PagedTransformerModel (or a "
                    "paged-capable fake) for the paged KV plane")
            self.pool = PagedCachePool(
                n_pages=config.pool_pages, page_size=config.page_size,
                n_slots=config.n_slots,
                pages_per_slot=config.pages_per_slot,
                share_prefixes=config.prefix_sharing)
            self.cache = model.init_paged_pool(self.pool)
        elif config.prefix_sharing:
            raise ValueError(
                "prefix_sharing requires the paged KV plane — set "
                "page_size (slot rows have no page granularity to share)")
        else:
            self.pool = SlotCachePool(config.n_slots)
            self.cache = model.init_pool(config.n_slots, config.pool_len)
        self.scheduler = Scheduler(self.queue, self.pool,
                                   config.max_prefill_per_step,
                                   metrics=self.metrics)
        self._tok, self._pos = model.token_state(config.n_slots)
        self._trace = []                  # (k_i, n_slots) next-token blocks
        self._rows = 0                    # total trace rows so far
        self.completed: Dict[int, Request] = {}
        # incremental drain state (the fleet plane's step-callable surface):
        # host-side copies of the trace, fetched block-by-block on demand
        self.results: Dict[int, np.ndarray] = {}   # harvested tokens
        self._host_trace = np.zeros((0, config.n_slots), np.int32)
        self._fetched_blocks = 0
        self._firsts_cache: Dict[int, np.ndarray] = {}
        self.steps = 0
        self.clock = 0.0
        # wall-clock arrival replay: arrivals are seconds on an injectable
        # monotonic clock (tests pass ManualClock; the default comes from
        # obs.clock, the one sanctioned home of wall-clock reads)
        self._wall_arrivals = config.arrival_mode == "seconds"
        self._clock_fn = clock if clock is not None else obs_clock.monotonic
        self._clock_t0: Optional[float] = None
        # timeline adoption: if the tracer has no clock yet, this engine's
        # arrival clock becomes the timeline (a fleet controller built
        # later overrides it with its tick counter — last owner wins)
        if self.tracer.enabled and self.tracer.clock is None:
            self.tracer.use_clock(lambda: self.clock)
        self._stats = dict(decode_steps=0, prefill_count=0, decode_tokens=0,
                           prefill_tokens=0, occupancy_sum=0.0,
                           prefill_wall=0.0, page_occupancy_sum=0.0)

    def _now(self) -> float:
        """Engine time in arrival units (seconds since run start in
        wall-clock mode; the iteration counter otherwise)."""
        if self._clock_t0 is None:
            self._clock_t0 = self._clock_fn()
        return self._clock_fn() - self._clock_t0

    def _sleep(self, dt: float) -> None:
        sleep = getattr(self._clock_fn, "sleep", time.sleep)
        sleep(dt)

    def submit(self, prompt, max_new: int, arrival: float = 0.0) -> int:
        try:
            req = self.queue.submit(prompt, max_new, arrival)
        except AdmissionError as e:
            self.metrics.counter("admission_rejections",
                                 reason=e.reason).inc()
            raise
        self.metrics.counter("requests_submitted").inc()
        # queue-wait span: opened at submit, closed when the scheduler
        # admits the request (a keyed cross-step span).  Keys carry the
        # engine name: fleet replicas share one tracer and local rids
        # collide across engines.
        self.tracer.begin("queue_wait", track=self.name,
                          lane=f"req:{req.rid}",
                          key=("qw", self.name, req.rid),
                          rid=req.rid, arrival=req.arrival)
        return req.rid

    @property
    def has_work(self) -> bool:
        """Anything queued or in flight (``step()`` would do work)."""
        return self.scheduler.has_work

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One engine iteration; returns False when fully drained."""
        if not self.scheduler.has_work:
            return False
        self.steps += 1
        shape = {}
        key = self.tracer.begin("step", track=self.name, lane="engine",
                                step=self.steps)
        try:
            shape = self._iterate()
        finally:
            self.tracer.end(key, **shape)
        return True

    def _iterate(self) -> Dict[str, int]:
        """The body of ``step``; returns what it ran (admitted requests,
        live decode rows, fused decode steps) for the step's span."""
        if self._wall_arrivals:
            self.clock = self._now()
        now, wall = self.clock, time.perf_counter()
        self.queue.mark_eligible(now, wall)
        with self.tracer.span("plan", track=self.name, lane="engine"):
            plan = self.scheduler.plan(now)
        if not (plan.retired or plan.admit or self.scheduler.active):
            # nothing in flight and nothing eligible: fast-forward the
            # clock to the next arrival instead of spinning no-op steps
            # (in wall-clock mode: actually wait on the injected clock)
            nxt = self.queue.next_arrival()
            if nxt is not None and nxt > self.clock:
                if self._wall_arrivals:
                    self._sleep(nxt - self.clock)
                    self.clock = self._now()
                else:
                    self.clock = float(nxt)
                return dict(n_admit=0, n_live=0, k=0)
        for r in plan.retired:
            r.finish_wall = r.finish_wall or wall
            self.completed[r.rid] = r
            # close the residency span opened at admit
            self.tracer.end(("req", self.name, r.rid), tokens=r.max_new)
            self.tracer.event("retire", track=self.name,
                              lane=f"req:{r.rid}", rid=r.rid)

        if plan.admit:
            for r in plan.admit:
                self.tracer.end(("qw", self.name, r.rid))
                self.tracer.begin("request", track=self.name,
                                  lane=f"req:{r.rid}",
                                  key=("req", self.name, r.rid),
                                  rid=r.rid, prompt_len=r.prompt_len,
                                  max_new=r.max_new, slot=r.slot)
            prompts = [r.prompt for r in plan.admit]
            t0 = time.perf_counter()
            with self.tracer.span("prefill", track=self.name, lane="engine",
                                  n=len(prompts),
                                  tokens=sum(len(p) for p in prompts),
                                  padded_len=max(len(p) for p in prompts)):
                self.cache, firsts, self._tok, self._pos = self.model.prefill(
                    self.cache, prompts, [r.slot for r in plan.admit],
                    self._tok, self._pos)
            if hasattr(firsts, "block_until_ready"):
                with self.tracer.span("prefill_wait", track=self.name,
                                      lane="engine"):
                    # TTFT is a real latency metric
                    firsts.block_until_ready()
            t1 = time.perf_counter()
            for b, r in enumerate(plan.admit):
                r.first_token = (firsts, b)   # sliced lazily at drain
                r.n_generated = 1
                r.trace_start = self._rows
                r.trace_slot = r.slot
                r.eligible_wall = (t0 if r.eligible_wall is None
                                   else r.eligible_wall)
                r.first_token_wall = t1
                self._stats["prefill_tokens"] += r.prompt_len
                # TTFT lands in the metrics plane as an observed value
                # (wall seconds never enter the trace timeline)
                self.metrics.histogram("ttft_s", TTFT_EDGES).observe(
                    r.first_token_wall - r.eligible_wall)
            self._stats["prefill_count"] += len(plan.admit)
            self._stats["prefill_wall"] += t1 - t0
            self.metrics.counter("prefill_tokens").inc(
                sum(r.prompt_len for r in plan.admit))
            # the prefill dispatch above wrote these requests' prompt
            # pages: publish the shareable ones (materialize their index
            # entries and write-protect them) BEFORE any decode runs —
            # from the next scheduler step on, followers attach instead
            # of claiming.  No-op without prefix sharing / on slot pools.
            self.pool.seal_prefilled(plan.admit)

        # the decode batch was planned BEFORE prefill handed max_new == 1
        # admits their first (and only) token — drop the already-done ones
        # so budget math (k, page growth, token accounting) can't overshoot
        live = [r for r in plan.decode if not r.done]
        k = 0
        if live:
            # decode fusion: when nothing was admitted this step AND no
            # admission can happen before the next retirement (queue empty,
            # or every slot busy), the next k iterations are pure decode —
            # run them as ONE dispatch.  k is the smallest remaining budget
            # among in-flight requests (nobody overshoots and the next
            # retirement lands exactly at the call boundary), bucketed to
            # a power of two to bound compilations.
            k = 1
            if not plan.admit and (len(self.queue) == 0
                                   or self.pool.free_count == 0):
                k = min(r.max_new - r.n_generated for r in live)
                k = 1 << max(0, k.bit_length() - 1)
            # paged plane: claim every page the next k steps will write
            # BEFORE the dispatch (the page map is an argument of the
            # fused call); reservations make the claims infallible
            with self.tracer.span("prepare_decode", track=self.name,
                                  lane="engine", k=k, rows=len(live)):
                self.pool.prepare_decode(live, k)
            paged = isinstance(self.pool, PagedCachePool)
            # kv_read: where decode attention reads K/V — the page pool
            # in place ("pages") or the per-slot cache rows ("slots")
            dk_key = self.tracer.begin(
                "decode", track=self.name, lane="engine", k=k,
                rows=len(live),
                depth_sum=sum(r.prompt_len + r.n_generated for r in live),
                pages_used=self.pool.used_pages if paged else 0,
                kv_read="pages" if paged else "slots")
            self.cache, rows, self._tok, self._pos = self.model.decode_multi(
                self.cache, self._tok, self._pos, k)
            self._trace.append(rows)       # (k, n_slots)
            self._rows += k
            for r in live:
                r.n_generated += k
            self._stats["decode_steps"] += k
            self._stats["decode_tokens"] += k * len(live)
            self._stats["occupancy_sum"] += (k * len(live)
                                             / self.config.n_slots)
            if paged:
                self._stats["page_occupancy_sum"] += (
                    k * self.pool.used_pages / self.pool.n_pages)
            self.metrics.counter("decode_tokens").inc(k * len(live))
        if not self._wall_arrivals:   # wall mode reads the clock per step
            self.clock += float(max(k, 1) if live else 1)
        if live:
            # close after the clock advance so a fused k-step decode spans
            # k ticks on the trace timeline
            self.tracer.end(dk_key)
        # end-of-step gauges: queue depth + pool occupancy (host state the
        # loop already owns — no device sync, no extra dispatch)
        self.metrics.gauge("queue_depth").set(len(self.queue))
        self.metrics.gauge("pool_occupancy").set(self.pool.occupancy)
        self.tracer.counter("queue_depth", len(self.queue), track=self.name)
        self.tracer.counter("pool_occupancy", self.pool.occupancy,
                            track=self.name)
        return dict(n_admit=len(plan.admit), n_live=len(live), k=k)

    # -- host materialization (incremental: the fleet drain surface) ----
    def _trace_upto(self, rows: int) -> np.ndarray:
        """Host trace covering at least ``rows`` rows: fetch every
        still-on-device block in ONE transfer when the prefix is short
        (blocks are append-only, so earlier fetches stay valid)."""
        if self._host_trace.shape[0] < rows:
            pend = self._trace[self._fetched_blocks:]
            if pend:
                block = pend[0]
                if len(pend) > 1:
                    with self.tracer.span("join_tokens", track=self.name,
                                          lane="engine", blocks=len(pend)):
                        block = jnp.concatenate(pend)
                with self.tracer.span("fetch_tokens", track=self.name,
                                      lane="engine", blocks=len(pend),
                                      rows=block.shape[0]):
                    got = np.asarray(jax.device_get(block))
                with self.tracer.span("join_tokens", track=self.name,
                                      lane="engine", blocks=len(pend)):
                    self._host_trace = np.concatenate([self._host_trace,
                                                       got])
                self._fetched_blocks = len(self._trace)
        return self._host_trace

    def _firsts(self, r: Request) -> np.ndarray:
        """The request's prefill token, from its admit group's argmax
        vector (one transfer per group, cached for the engine's life —
        the group array stays referenced by its requests, so ``id`` keys
        cannot be recycled under us)."""
        firsts, b = r.first_token
        group = self._firsts_cache.get(id(firsts))
        if group is None:
            with self.tracer.span("fetch_firsts", track=self.name,
                                  lane="engine"):
                group = self._firsts_cache[id(firsts)] = np.asarray(
                    jax.device_get(firsts))
        return group[b:b + 1]

    def harvest(self) -> Dict[int, np.ndarray]:
        """Materialize tokens of requests completed since the last call.

        The fleet controller's per-tick drain: only newly completed
        requests are sliced (and only the trace blocks they need are
        fetched), results accumulate in ``self.results``, and the return
        value carries just the NEW ones — calling this every tick costs
        nothing when nothing finished.
        """
        out: Dict[int, np.ndarray] = {}
        key = self.tracer.begin("harvest", track=self.name, lane="engine")
        for rid, r in self.completed.items():
            if rid in self.results:
                continue
            trace = self._trace_upto(r.trace_start + r.max_new - 1)
            dec = trace[r.trace_start:r.trace_start + r.max_new - 1,
                        r.trace_slot]
            assert dec.shape[0] == r.max_new - 1, (rid, dec.shape, r.max_new)
            r.tokens = np.concatenate([self._firsts(r), dec]).astype(np.int32)
            out[rid] = r.tokens
        self.results.update(out)
        self.tracer.end(key, n_done=len(out))
        return out

    def tokens_so_far(self, rid: int) -> np.ndarray:
        """Host view of what ``rid`` has generated so far (the streaming
        surface; syncs with the device up to the request's depth).
        Empty for queued/unknown rids."""
        r = self.completed.get(rid)
        if r is None:
            r = self.scheduler.active.get(rid)
        if r is None or r.first_token is None:
            return np.zeros(0, np.int32)
        if r.tokens is not None:
            return r.tokens
        n_dec = min(r.n_generated, r.max_new) - 1
        trace = self._trace_upto(r.trace_start + n_dec)
        dec = trace[r.trace_start:r.trace_start + n_dec, r.trace_slot]
        return np.concatenate([self._firsts(r), dec]).astype(np.int32)

    def shed_queued(self, n: int) -> List[Request]:
        """Give up ``n`` still-QUEUED requests, latest-arrival first — the
        work-stealing shed surface.  Only un-admitted requests are
        sheddable: they have generated zero tokens, so requeuing them on
        another replica preserves the greedy oracle byte-for-byte.  Their
        queue-wait spans are closed ``outcome="stolen"`` (the thief opens
        a fresh one on its own track)."""
        victims = self.queue.steal_latest(n)
        for r in victims:
            self.tracer.end(("qw", self.name, r.rid), outcome="stolen")
        return victims

    def outstanding(self) -> List[Request]:
        """Every request whose tokens are NOT yet harvested to the host:
        queued, in flight, and completed-but-unharvested, in rid order.
        This is the failover set — what a dead replica still owes."""
        queued = self.queue.pending()
        active = [self.scheduler.active[rid]
                  for rid in sorted(self.scheduler.active)]
        unharvested = [r for rid, r in sorted(self.completed.items())
                       if rid not in self.results]
        return sorted(queued + active + unharvested, key=lambda r: r.rid)

    def progress(self) -> Dict[str, float]:
        """Cheap host-side stats snapshot (fleet replicas never ``run()``
        to completion, so occupancy must be readable mid-flight)."""
        s = self._stats
        occ = (s["occupancy_sum"] / s["decode_steps"]
               if s["decode_steps"] else 0.0)
        return dict(steps=self.steps, decode_steps=s["decode_steps"],
                    decode_tokens=s["decode_tokens"],
                    prefill_count=s["prefill_count"], occupancy=occ,
                    n_queued=len(self.queue),
                    n_active=len(self.scheduler.active),
                    n_completed=len(self.completed),
                    n_rejected=self.queue.n_rejected,
                    pool_occupancy=self.pool.occupancy)

    def _materialize(self) -> Dict[int, np.ndarray]:
        """Pull the step trace from device and slice per request."""
        self.harvest()
        return dict(self.results)

    def run(self, max_steps: Optional[int] = None) -> EngineReport:
        """Drive until drained; returns the report for this run."""
        t_start = time.perf_counter()
        n = 0
        while self.step():
            n += 1
            if max_steps is not None and n >= max_steps:
                break
        completed = self._materialize()   # blocks on all in-flight work
        wall = time.perf_counter() - t_start
        if max_steps is None:
            assert self.pool.drained, "drained engine still holds slots"
            assert self.pool.n_allocated == self.pool.n_freed, (
                self.pool.n_allocated, self.pool.n_freed)
        s = self._stats
        ttft = {r.rid: (r.first_token_wall - r.eligible_wall)
                for r in self.completed.values()
                if r.first_token_wall is not None
                and r.eligible_wall is not None}
        occ = (s["occupancy_sum"] / s["decode_steps"]
               if s["decode_steps"] else 0.0)
        pocc = (s["page_occupancy_sum"] / s["decode_steps"]
                if s["decode_steps"] else 0.0)
        return EngineReport(
            completed=completed,
            steps=n, decode_steps=s["decode_steps"],
            prefill_count=s["prefill_count"],
            decode_tokens=s["decode_tokens"],
            prefill_tokens=s["prefill_tokens"],
            occupancy=occ, ttft=ttft, wall=wall,
            prefill_wall=s["prefill_wall"], page_occupancy=pocc)


def serve_requests(params, cfg: ModelConfig, rules: Rules, requests,
                   n_slots: int = 8, max_prefill_per_step: int = 2,
                   page_size: Optional[int] = None,
                   n_pages: Optional[int] = None,
                   prefix_sharing: bool = False) -> EngineReport:
    """Convenience one-shot: serve [(prompt, max_new, arrival), ...].

    ``page_size`` switches to the paged KV plane (``n_pages`` defaults to
    slot-pool-equivalent memory); ``prefix_sharing`` additionally maps
    matching prompt prefixes onto shared pages — outputs must be
    token-identical in every mode.
    """
    reqs = [(np.asarray(p, np.int32).reshape(-1), int(m), float(a))
            for p, m, a in requests]
    max_len = max(p.shape[0] + m for p, m, _ in reqs)
    ec = EngineConfig(n_slots=n_slots,
                      max_prompt_len=max(p.shape[0] for p, _, _ in reqs),
                      max_new_cap=max(m for _, m, _ in reqs),
                      cache_len=max_len,
                      max_prefill_per_step=max_prefill_per_step,
                      page_size=page_size, n_pages=n_pages,
                      prefix_sharing=prefix_sharing)
    model_cls = PagedTransformerModel if ec.paged else TransformerModel
    # engines are built through the fleet plane's factory (CI grep-gates
    # direct ServingEngine construction outside repro.fleet and launch/);
    # imported lazily because fleet imports this module
    from ...fleet.replica import build_engine
    eng = build_engine(model_cls(params, cfg, rules), ec)
    for p, m, a in reqs:
        eng.submit(p, m, arrival=a)
    return eng.run()

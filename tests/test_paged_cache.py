"""Paged KV-cache plane: allocator invariants, page-budget admission,
and token-identity of the paged engine against the greedy oracle AND the
slot-pool engine.

Allocator properties (hypothesis, deterministic shim fallback):
  * conservation — pages allocated == pages freed once drained;
  * exclusivity — no physical page is held by two live requests, under
    arbitrary admit/grow/release interleavings (fragmentation);
  * bounded growth — grow-on-decode can never exceed the admission-time
    reservation (preemption-freedom is structural).

The acceptance check runs the 32-request heavy-tailed staggered workload
with a page size small enough that EVERY request spans >= 2 physical
pages with at least one non-contiguous jump — the paged plane must still
be token-identical to per-request ``greedy_generate`` and to the slot
engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_reduced
from repro.models import transformer as T
from repro.serve import greedy_generate, serve_requests
from repro.serve.engine import (EngineConfig, PagedCachePool,
                                PagedTransformerModel, Request, ServingEngine,
                                SlotCachePool, shared_prefix_workload,
                                synthetic_workload)
from repro.sharding.rules import Rules

RULES = Rules.null()


def _req(rid, prompt_len, max_new):
    return Request(rid=rid, prompt=np.arange(1, prompt_len + 1),
                   max_new=max_new)


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

def test_paged_pool_admit_claim_release_roundtrip():
    pool = PagedCachePool(n_pages=8, page_size=4, n_slots=2,
                          pages_per_slot=4)
    r = _req(0, prompt_len=5, max_new=8)
    assert pool.pages_needed(5, 8) == 3      # 12 tokens / 4 per page
    assert pool.can_admit(r)
    slot = pool.admit(r)
    assert pool.live_pages(0) == (0, 1)      # prefill: ceil(5/4) pages
    assert pool.reserved_pages == 3
    # grow to cover 9 tokens -> third page
    pool.grow_to(0, 9)
    assert pool.live_pages(0) == (0, 1, 2)
    # table row mirrors the claims; tail stays trash
    np.testing.assert_array_equal(
        pool.table[slot], [0, 1, 2, pool.trash_page])
    r.slot = slot
    pool.release(r)
    assert pool.drained and pool.n_allocated == pool.n_freed == 3
    assert np.all(pool.table == pool.trash_page)
    assert pool.page_history[0] == (0, 1, 2)


def test_paged_pool_grow_past_reservation_raises():
    pool = PagedCachePool(n_pages=8, page_size=4, n_slots=2,
                          pages_per_slot=4)
    pool.admit(_req(0, prompt_len=4, max_new=4))   # reserve ceil(7/4) = 2
    pool.grow_to(0, 7)
    with pytest.raises(RuntimeError, match="reservation"):
        pool.grow_to(0, 9)                          # needs a 3rd page


def test_paged_pool_admission_gated_on_pages_not_rows():
    # 2 rows but only enough unreserved pages for one worst-case request
    pool = PagedCachePool(n_pages=4, page_size=4, n_slots=2,
                          pages_per_slot=3)
    a = _req(0, prompt_len=8, max_new=5)            # reserve 3 pages
    assert pool.can_admit(a)
    a.slot = pool.admit(a)
    b = _req(1, prompt_len=8, max_new=5)
    assert not pool.can_admit(b)                    # row free, pages not
    pool.release(a)
    assert pool.can_admit(b)


def test_paged_pool_fragmentation_reuses_freed_pages():
    """Interleaved release/claim fragments the pool: a later request's
    pages span a freed hole plus the tail — non-contiguous — and no page
    is ever aliased.  Freed pages come back LIFO (the free list is a
    stack, not a sorted heap), so the hole is reused before the tail."""
    pool = PagedCachePool(n_pages=8, page_size=2, n_slots=4,
                          pages_per_slot=3)
    a, b, c = (_req(i, prompt_len=4, max_new=1) for i in range(3))
    for r in (a, b, c):
        r.slot = pool.admit(r)                      # a:{0,1} b:{2,3} c:{4,5}
    pool.release(b)                                 # hole at {2,3}
    d = _req(3, prompt_len=2, max_new=5)            # reserve 3, claim 1
    d.slot = pool.admit(d)
    assert pool.live_pages(3) == (2,)     # top of the LIFO stack = b's
    # first page (releases push a request's pages reversed)
    pool.grow_to(3, 3)
    pool.grow_to(3, 5)
    # d spans the freed hole {2,3} then jumps the live c to page 6
    assert pool.live_pages(3) == (2, 3, 6)
    flat = [p for r in (a, c, d) for p in pool.live_pages(r.rid)]
    assert len(flat) == len(set(flat))              # no aliasing


def test_paged_pool_free_pages_are_a_lifo_stack():
    """Pin the allocator discipline: page claims pop the most recently
    freed page first (O(1) stack, no ordering guarantee beyond LIFO),
    and a fresh pool hands out ascending ids.  Page identity is
    interchangeable through the table indirection, so the ONLY contract
    is exclusivity + LIFO reuse — anything asserting globally-lowest-
    first would be over-pinning."""
    pool = PagedCachePool(n_pages=6, page_size=2, n_slots=3,
                          pages_per_slot=3)
    a = _req(0, prompt_len=4, max_new=1)            # claims {0, 1}
    b = _req(1, prompt_len=4, max_new=1)            # claims {2, 3}
    a.slot = pool.admit(a)
    b.slot = pool.admit(b)
    assert pool.live_pages(0) == (0, 1)             # fresh pool: ascending
    assert pool.live_pages(1) == (2, 3)
    pool.release(a)                                 # stack top: 0, then 1
    c = _req(2, prompt_len=6, max_new=1)
    c.slot = pool.admit(c)
    # c reuses a's pages in a's original order, THEN falls through to the
    # untouched tail — LIFO, not lowest-id-first across the whole pool
    assert pool.live_pages(2) == (0, 1, 4)
    assert pool.free_page_count == 1


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), n_pages=st.integers(4, 24),
       page_size=st.integers(1, 5))
def test_paged_pool_conservation_and_exclusivity(seed, n_pages, page_size):
    """Random admit/grow/release interleavings: live pages are always
    exclusive, claims never pass reservations, and the drained pool
    conserves pages."""
    rng = np.random.default_rng(seed)
    pages_per_slot = max(2, n_pages // 2)
    pool = PagedCachePool(n_pages=n_pages, page_size=page_size,
                          n_slots=4, pages_per_slot=pages_per_slot)
    live = {}
    next_rid = 0
    for _ in range(60):
        op = rng.integers(0, 3)
        if op == 0:   # admit
            plen = int(rng.integers(1, 2 * page_size + 1))
            cap = pages_per_slot * page_size - plen
            if cap < 1:
                continue
            mn = int(rng.integers(1, cap + 1))
            r = _req(next_rid, plen, mn)
            if pool.can_admit(r):
                r.slot = pool.admit(r)
                live[next_rid] = r
                next_rid += 1
        elif op == 1 and live:   # grow a random live request one token
            rid = int(rng.choice(list(live)))
            r = live[rid]
            if r.n_generated < r.max_new:
                r.n_generated += 1
                pool.grow_to(rid, r.prompt_len + r.n_generated - 1)
        elif op == 2 and live:   # release a random live request
            rid = int(rng.choice(list(live)))
            pool.release(live.pop(rid))
        # exclusivity + reservation bound at every step
        flat = []
        for rid in live:
            pages = pool.live_pages(rid)
            assert len(pages) <= pool.pages_needed(
                live[rid].prompt_len, live[rid].max_new)
            flat.extend(pages)
        assert len(flat) == len(set(flat)), "page aliased by two requests"
        assert all(0 <= p < n_pages for p in flat)
        # table mirrors the claims
        for rid in live:
            row = pool.table[live[rid].slot]
            claimed = pool.live_pages(rid)
            np.testing.assert_array_equal(row[:len(claimed)], claimed)
            assert np.all(row[len(claimed):] == pool.trash_page)
    for r in list(live.values()):
        pool.release(r)
    assert pool.drained
    assert pool.n_allocated == pool.n_freed
    assert pool.free_page_count == n_pages


# ---------------------------------------------------------------------------
# engine-level scheduling on the paged plane (tensor-light fake)
# ---------------------------------------------------------------------------

class FakePagedModel:
    """The FakeModel dynamics (next = (prev * 31 + pos) % V) behind the
    paged adapter surface — pool tensors unused, so this exercises pure
    scheduling/allocation behaviour."""

    V = 97

    def init_paged_pool(self, pool):
        return {"pages": jnp.zeros((1, pool.n_pages + 1, pool.page_size),
                                   jnp.int32)}

    def token_state(self, n_slots):
        return jnp.zeros(n_slots, jnp.int32), jnp.zeros(n_slots, jnp.int32)

    def first_token(self, prompt):
        return int(np.sum(prompt) % self.V)

    def prefill(self, pool, prompts, slots, tok, pos):
        firsts = []
        for prompt, slot in zip(prompts, slots):
            first = self.first_token(prompt)
            firsts.append(first)
            tok = tok.at[slot].set(first)
            pos = pos.at[slot].set(prompt.shape[0])
        return pool, jnp.asarray(firsts, jnp.int32), tok, pos

    def decode_multi(self, pool, tok, pos, k):
        rows = []
        for _ in range(k):
            tok = (tok * 31 + pos) % self.V
            pos = pos + 1
            rows.append(tok)
        return pool, jnp.stack(rows), tok, pos

    def decode(self, pool, tok, pos):
        pool, rows, tok, pos = self.decode_multi(pool, tok, pos, 1)
        return pool, rows[0], tok, pos

    def oracle(self, prompt, max_new):
        out = [self.first_token(prompt)]
        tok, pos = out[0], prompt.shape[0]
        for _ in range(max_new - 1):
            tok = (tok * 31 + pos) % self.V
            pos += 1
            out.append(tok)
        return np.asarray(out, np.int32)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 16),
       page_size=st.integers(1, 4), budget=st.integers(0, 8))
def test_paged_engine_conservation_and_no_starvation(seed, n, page_size,
                                                     budget):
    """Random workloads against a page-budget-constrained pool: every
    request completes with exactly the fake-oracle tokens, and the pool
    conserves pages at drain — even when the page budget (not the slot
    count) is the binding admission constraint."""
    rng = np.random.default_rng(seed)
    pages_per_slot = -(-18 // page_size)
    ec = EngineConfig(n_slots=3, max_prompt_len=12, max_new_cap=6,
                      cache_len=18, max_prefill_per_step=2,
                      page_size=page_size,
                      n_pages=pages_per_slot + budget)
    eng = ServingEngine(FakePagedModel(), ec)
    want = {}
    for _ in range(n):
        prompt = rng.integers(0, 50, rng.integers(1, 13))
        max_new = int(rng.integers(1, 7))
        arrival = float(rng.integers(0, 8))
        rid = eng.submit(prompt, max_new, arrival=arrival)
        want[rid] = (prompt, max_new)
    rep = eng.run()
    assert set(rep.completed) == set(want)
    assert eng.pool.drained
    assert eng.pool.n_allocated == eng.pool.n_freed
    fake = FakePagedModel()
    for rid, (prompt, max_new) in want.items():
        np.testing.assert_array_equal(
            rep.completed[rid],
            fake.oracle(np.asarray(prompt, np.int32), max_new))
    # every request's final page count stayed within its reservation
    for rid, pages in eng.pool.page_history.items():
        prompt, max_new = want[rid]
        assert len(pages) <= eng.pool.pages_needed(prompt.shape[0], max_new)


def test_paged_engine_page_budget_limits_concurrency():
    """With pages for only one worst-case request, requests serve
    sequentially (admission by page budget) yet all complete."""
    ec = EngineConfig(n_slots=4, max_prompt_len=8, max_new_cap=4,
                      cache_len=12, page_size=4, n_pages=3)
    eng = ServingEngine(FakePagedModel(), ec)
    for i in range(3):
        eng.submit(np.arange(1, 9), 4, arrival=0.0)
    rep = eng.run()
    assert len(rep.completed) == 3
    # one request's reservation (3 pages) fills the pool: occupancy over
    # n_slots=4 can never exceed 1/4
    assert rep.occupancy <= 0.25 + 1e-9


# ---------------------------------------------------------------------------
# oracle identity on the real model (acceptance workload)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_lm():
    cfg = get_reduced("llama3_2_3b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_paged_engine_acceptance_fragmented_oracle_identity(small_lm):
    """THE acceptance check: 32 heavy-tailed staggered requests, page
    size 4 so every request spans >= 2 physical pages with at least one
    non-contiguous jump; paged output must be token-identical to
    per-request greedy_generate AND to the slot engine."""
    cfg, params = small_lm
    workload = synthetic_workload(32, cfg.vocab_size,
                                  lens=(5, 9, 13, 17), news=(6, 12, 16),
                                  stagger=0.5, seed=0)
    max_len = max(p.shape[0] + m for p, m, _ in workload)
    ec = EngineConfig(n_slots=8, max_prompt_len=17, max_new_cap=16,
                      cache_len=max_len, max_prefill_per_step=4,
                      page_size=4)
    eng = ServingEngine(PagedTransformerModel(params, cfg, RULES), ec)
    for p, m, a in workload:
        eng.submit(p, m, arrival=a)
    rep = eng.run()
    assert len(rep.completed) == 32

    slot_rep = serve_requests(params, cfg, RULES, workload, n_slots=8,
                              max_prefill_per_step=4)
    for rid, (prompt, max_new, _) in enumerate(workload):
        ref = np.asarray(greedy_generate(
            params, cfg, RULES, np.asarray(prompt)[None],
            max_new=max_new))[0]
        np.testing.assert_array_equal(rep.completed[rid], ref,
                                      err_msg=f"vs greedy, rid {rid}")
        np.testing.assert_array_equal(rep.completed[rid],
                                      slot_rep.completed[rid],
                                      err_msg=f"vs slot engine, rid {rid}")
    # fragmentation evidence: every request held >= 2 pages and took at
    # least one non-contiguous jump through the physical pool
    assert set(eng.pool.page_history) == set(range(32))
    for rid, pages in eng.pool.page_history.items():
        assert len(pages) >= 2, (rid, pages)
        assert any(b != a + 1 for a, b in zip(pages, pages[1:])), \
            (rid, pages)
    assert eng.pool.drained
    assert eng.pool.n_allocated == eng.pool.n_freed
    assert rep.page_occupancy > 0.0


def test_paged_engine_single_request_exact(small_lm):
    """Degenerate case: one request, page growth across many pages."""
    cfg, params = small_lm
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, 13).astype(np.int32)
    ref = np.asarray(greedy_generate(params, cfg, RULES, prompt[None],
                                     max_new=16))[0]
    rep = serve_requests(params, cfg, RULES, [(prompt, 16, 0.0)],
                         n_slots=1, page_size=4)
    np.testing.assert_array_equal(rep.completed[0], ref)


def _pool_writes(model):
    """Record each of the adapter's pool-writing calls and the pool it was
    given (``decode`` is reached through ``decode_multi``)."""
    given = []
    for name in ("prefill", "decode_multi"):
        def call(pool, *a, _fn=getattr(model, name), _name=name):
            given.append((_name, pool))
            return _fn(pool, *a)
        setattr(model, name, call)
    return given


def _small_paged_engine(small_lm, n=6):
    cfg, params = small_lm
    workload = synthetic_workload(n, cfg.vocab_size, lens=(5, 9, 13),
                                  news=(4, 9), stagger=0.5, seed=2)
    ec = EngineConfig(n_slots=3, max_prompt_len=13, max_new_cap=9,
                      cache_len=22, max_prefill_per_step=2, page_size=4)
    model = PagedTransformerModel(params, cfg, RULES)
    return model, ServingEngine(model, ec), workload


def _assert_greedy(small_lm, completed, workload):
    cfg, params = small_lm
    for rid, (prompt, max_new, _) in enumerate(workload):
        ref = np.asarray(greedy_generate(
            params, cfg, RULES, np.asarray(prompt)[None],
            max_new=max_new))[0]
        np.testing.assert_array_equal(completed[rid], ref,
                                      err_msg=f"rid {rid}")


def _deleted(pool):
    return [leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(pool)]


def _first_of_each(given):
    names = [name for name, _ in given]
    return {names.index("prefill"), names.index("decode_multi")}


def test_paged_engine_updates_pool_in_place(small_lm):
    """On the engine's own chain the first prefill and the first decode
    copy the pool they are given (the initial pool; prefill's first
    output, before any decode output has come back), and every other
    dispatch consumes its pool, the previous dispatch's output, in place.
    Tokens stay greedy's."""
    model, eng, workload = _small_paged_engine(small_lm)
    given = _pool_writes(model)
    for p, m, a in workload:
        eng.submit(p, m, arrival=a)
    rep = eng.run()
    n = len(given)
    assert n > 3
    copied = _first_of_each(given)
    assert copied == {0, 1}
    for i, (_, pool) in enumerate(given):
        assert _deleted(pool) == [i not in copied] * 2, i
    assert not any(_deleted(eng.cache))
    counters = eng.metrics.snapshot()["counters"]
    assert counters["kv_pool_copied"] == 2
    assert counters["kv_pool_in_place"] == n - 2
    _assert_greedy(small_lm, rep.completed, workload)


@pytest.mark.parametrize("dropped", ["prefill", "decode_multi"])
def test_paged_adapter_keeps_the_pool_of_a_caller_that_drops_outputs(
        small_lm, dropped):
    """A caller that throws one call's pools away and hands back the pool
    it was given (a wrapper around the engine's calls) never has that pool
    consumed: the run completes, every pool handed back is intact, and the
    other call still runs in place once its own pools come back."""
    model, eng, workload = _small_paged_engine(small_lm)
    fn = getattr(model, dropped)
    handed_back = []

    def keep(pool, *a):
        out = fn(pool, *a)
        handed_back.append(pool)
        return (pool,) + tuple(out[1:])
    setattr(model, dropped, keep)
    for p, m, a in workload:
        eng.submit(p, m, arrival=a)
    rep = eng.run()
    assert len(rep.completed) == len(workload)
    assert handed_back
    for pool in handed_back:
        assert not any(_deleted(pool))
    in_place = eng.metrics.counter_value("kv_pool_in_place")
    assert (in_place > 0) == (dropped == "prefill")


def _warm_up_calls(model, pool, n_slots):
    """The adapter's calls as the benchmark's warm-up makes them: on a
    pool the caller keeps, outputs thrown away."""
    tok, pos = model.token_state(n_slots)
    prompts = [np.arange(1, 10, dtype=np.int32)] * 2
    return {"prefill": lambda: model.prefill(pool, prompts, [0, 1], tok, pos),
            "decode1": lambda: model.decode_multi(pool, tok, pos, 1),
            "scan4": lambda: model.decode_multi(pool, tok, pos, 4)}


@pytest.mark.parametrize("call", ["prefill", "decode1", "scan4"])
def test_paged_adapter_leaves_a_kept_pool_intact(small_lm, call):
    """A call repeated on a pool the caller keeps copies it each time:
    the pool stays intact and unchanged, and both calls give the same
    outputs."""
    model, eng, _ = _small_paged_engine(small_lm)
    kept = eng.cache
    before = jax.tree_util.tree_map(np.asarray, kept)
    run = _warm_up_calls(model, kept, eng.config.n_slots)[call]
    first, again = run(), run()
    for a, b in zip(jax.tree_util.tree_leaves(first),
                    jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not any(_deleted(kept))
    for name, leaf in before.items():
        np.testing.assert_array_equal(np.asarray(kept[name]), leaf)
    assert eng.metrics.counter_value("kv_pool_copied") == 2
    assert eng.metrics.counter_value("kv_pool_in_place") == 0


def test_paged_engine_serves_after_warm_up(small_lm):
    """Warm-up calls on the engine's pool, then the engine serves from
    that pool: its first prefill and first decode copy, every other
    dispatch runs in place, and the tokens are greedy's."""
    model, eng, workload = _small_paged_engine(small_lm)
    calls = _warm_up_calls(model, eng.cache, eng.config.n_slots)
    for run in calls.values():
        run()
    given = _pool_writes(model)
    for p, m, a in workload:
        eng.submit(p, m, arrival=a)
    rep = eng.run()
    assert eng.metrics.counter_value("kv_pool_copied") == len(calls) + 2
    assert eng.metrics.counter_value("kv_pool_in_place") == len(given) - 2
    _assert_greedy(small_lm, rep.completed, workload)


def test_paged_adapter_serves_engines_in_turn(small_lm):
    """One adapter around two engines in turn (binding a page pool starts
    a new chain): the second engine copies as a new one does, and never
    consumes the first engine's last pool."""
    model, first, workload = _small_paged_engine(small_lm)
    for p, m, a in workload:
        first.submit(p, m, arrival=a)
    first.run()
    second = ServingEngine(model, first.config)
    for p, m, a in workload:
        second.submit(p, m, arrival=a)
    rep = second.run()
    assert not any(_deleted(first.cache))
    assert second.metrics.counter_value("kv_pool_copied") == 2
    _assert_greedy(small_lm, rep.completed, workload)


def test_paged_rejects_recurrent_families():
    cfg = get_reduced("recurrentgemma_9b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="paged"):
        PagedTransformerModel(params, cfg, RULES)


def test_paged_engine_requires_paged_adapter(small_lm):
    cfg, params = small_lm
    from repro.serve import TransformerModel
    with pytest.raises(TypeError, match="init_paged_pool"):
        ServingEngine(TransformerModel(params, cfg, RULES),
                      EngineConfig(n_slots=2, page_size=4))


def test_slot_pool_interface_unchanged():
    """The slot pool keeps its direct allocate/free surface AND serves
    the shared admission interface the scheduler uses."""
    pool = SlotCachePool(2)
    r = _req(0, 4, 2)
    assert pool.can_admit(r)
    r.slot = pool.admit(r)
    pool.release(r)
    assert pool.drained and pool.n_allocated == pool.n_freed == 1


# ---------------------------------------------------------------------------
# prefix sharing + copy-on-write
# ---------------------------------------------------------------------------

TPL = np.arange(100, 108, dtype=np.int32)       # two FULL pages at size 4


def _tpl_req(rid, suffix, max_new, template=TPL):
    return Request(rid=rid,
                   prompt=np.concatenate(
                       [template, np.asarray(suffix, np.int32)]),
                   max_new=max_new)


def _shared_pool(**kw):
    args = dict(n_pages=12, page_size=4, n_slots=4, pages_per_slot=4,
                share_prefixes=True)
    args.update(kw)
    return PagedCachePool(**args)


def test_prefix_follower_attaches_after_seal():
    """Creator claims + registers; after seal_prefilled a same-template
    follower attaches to the creator's pages (refcount 2) and reserves
    only its private tail — the shared + private admission math."""
    pool = _shared_pool()
    a = _tpl_req(0, [1, 2], max_new=3)          # 10 prompt tokens, 3 pages
    a.slot = pool.admit(a)
    assert pool.live_pages(0) == (0, 1, 2)
    assert pool.reserved_pages == 3
    pool.seal_prefilled([a])                    # prefill dispatch landed
    b = _tpl_req(1, [3, 4], max_new=3)
    assert pool.can_admit(b)
    b.slot = pool.admit(b)
    assert pool.shared_pages(1) == (0, 1)       # attached, not copied
    assert pool.refcount(0) == pool.refcount(1) == 2
    assert pool.refcount(2) == 1                # a's partial page: private
    assert pool.live_pages(1) == (0, 1, 3)      # CoW: own partial page
    assert pool.reserved_pages == 4             # 4 claimed + 0 future
    assert pool.n_shared_attached == 2 and pool.max_refcount == 2
    # the creator can retire first: pages survive for the follower
    pool.release(a)
    assert pool.refcount(0) == pool.refcount(1) == 1
    assert pool.refcount(2) == 0                # freed with a
    pool.release(b)
    assert pool.drained and pool.n_allocated == pool.n_freed == 4
    assert len(pool.prefix_index) == 0          # evicted at refcount zero


def test_prefix_cow_write_table_masks_shared_pages():
    """No request ever writes a page with refcount > 1: attached pages
    AND sealed creator pages are the trash page in write_table, while
    the read table still maps them — the page-granular copy-on-write."""
    pool = _shared_pool()
    a = _tpl_req(0, [1, 2], max_new=3)
    a.slot = pool.admit(a)
    # before seal the creator's own prefill must be able to write them
    np.testing.assert_array_equal(pool.write_table[a.slot, :3], [0, 1, 2])
    pool.seal_prefilled([a])
    np.testing.assert_array_equal(
        pool.write_table[a.slot], [pool.trash_page, pool.trash_page, 2,
                                   pool.trash_page])
    b = _tpl_req(1, [3, 4], max_new=3)
    b.slot = pool.admit(b)
    np.testing.assert_array_equal(
        pool.write_table[b.slot], [pool.trash_page, pool.trash_page, 3,
                                   pool.trash_page])
    np.testing.assert_array_equal(pool.table[b.slot, :3], [0, 1, 3])
    # global exclusivity: every non-trash write entry appears exactly once
    writable = pool.write_table[pool.write_table != pool.trash_page]
    assert len(writable) == len(set(writable.tolist()))
    for page in set(writable.tolist()):
        assert pool.refcount(page) == 1


def test_prefix_same_step_co_admits_stay_private():
    """Two creators of one template admitted BEFORE any seal: the second
    register loses and claims private copies — nobody attaches to an
    unwritten page (materialize-after-prefill ordering)."""
    pool = _shared_pool(n_pages=16)
    a = _tpl_req(0, [1, 2], max_new=3)
    b = _tpl_req(1, [3, 4], max_new=3)
    a.slot = pool.admit(a)
    b.slot = pool.admit(b)                      # same step: no seal yet
    assert pool.shared_pages(0) == pool.shared_pages(1) == ()
    assert all(pool.refcount(p) == 1
               for p in pool.live_pages(0) + pool.live_pages(1))
    pool.seal_prefilled([a, b])                 # only a's keys indexed
    c = _tpl_req(2, [5, 6], max_new=3)
    c.slot = pool.admit(c)
    assert pool.shared_pages(2) == pool.live_pages(0)[:2]
    pool.release(a)                             # c still holds a's pages
    pool.release(b)
    pool.release(c)
    assert pool.drained and pool.n_allocated == pool.n_freed


def test_prefix_sharing_off_is_bitwise_private():
    """share_prefixes=False: write_table always equals table and every
    page has refcount 1 — the old plane, bit for bit."""
    pool = PagedCachePool(n_pages=12, page_size=4, n_slots=4,
                          pages_per_slot=4)
    a = _tpl_req(0, [1, 2], max_new=3)
    a.slot = pool.admit(a)
    pool.seal_prefilled([a])                    # engine calls it anyway
    b = _tpl_req(1, [3, 4], max_new=3)
    b.slot = pool.admit(b)
    np.testing.assert_array_equal(pool.table, pool.write_table)
    assert pool.n_shared_attached == 0
    assert pool.reserved_pages == 6             # full private worst case


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), n_pages=st.integers(8, 24),
       page_size=st.integers(1, 4))
def test_prefix_refcount_conservation_and_cow_exclusivity(seed, n_pages,
                                                          page_size):
    """Mixed shared/private churn with delayed seals: refcounts always
    equal the live holder count, no page with refcount > 1 is ever
    writable anywhere, non-trash write entries stay globally exclusive,
    and the drained pool conserves pages with an empty index (every
    shared page's refcount hit zero)."""
    rng = np.random.default_rng(seed)
    pages_per_slot = max(3, n_pages // 2)
    pool = PagedCachePool(n_pages=n_pages, page_size=page_size, n_slots=4,
                          pages_per_slot=pages_per_slot,
                          share_prefixes=True)
    templates = [rng.integers(0, 50, 2 * page_size),
                 rng.integers(0, 50, page_size)]
    live, pending, next_rid = {}, [], 0
    for _ in range(80):
        op = int(rng.integers(0, 4))
        if op == 0:   # admit: template-headed (shared) or random private
            if rng.random() < 0.6:
                t = templates[int(rng.integers(0, len(templates)))]
                sfx = rng.integers(0, 50, int(rng.integers(1,
                                                           page_size + 1)))
                prompt = np.concatenate([t, sfx]).astype(np.int32)
            else:
                prompt = rng.integers(
                    0, 50, int(rng.integers(1, 2 * page_size + 1))
                ).astype(np.int32)
            cap = pages_per_slot * page_size - prompt.shape[0]
            if cap < 1:
                continue
            r = Request(rid=next_rid, prompt=prompt,
                        max_new=int(rng.integers(1, cap + 1)))
            if pool.can_admit(r):
                r.slot = pool.admit(r)
                live[next_rid] = r
                pending.append(r)
                next_rid += 1
        elif op == 1 and pending:   # the prefill dispatch lands
            pool.seal_prefilled(pending)
            pending = []
        elif op == 2 and live:      # grow a live request one token
            rid = int(rng.choice(list(live)))
            r = live[rid]
            if r.n_generated < r.max_new:
                r.n_generated += 1
                pool.grow_to(rid, r.prompt_len + r.n_generated - 1)
        elif op == 3 and live:      # release (kill/retire, maybe unsealed)
            rid = int(rng.choice(list(live)))
            r = live.pop(rid)
            pool.release(r)
            pending = [p for p in pending if p.rid != rid]
        # --- invariants at every step --------------------------------
        holders = {}
        for rid, r in live.items():
            for p in pool.live_pages(rid):
                holders[p] = holders.get(p, 0) + 1
        for p, n in holders.items():
            assert pool.refcount(p) == n, (p, n)
        writable = pool.write_table[pool.write_table != pool.trash_page]
        assert len(writable) == len(set(writable.tolist()))
        for p in set(writable.tolist()):
            assert pool.refcount(p) == 1, "writable page is shared"
        for rid, r in live.items():
            row = pool.table[r.slot]
            claimed = pool.live_pages(rid)
            np.testing.assert_array_equal(row[:len(claimed)], claimed)
            assert np.all(row[len(claimed):] == pool.trash_page)
    for r in list(live.values()):
        pool.release(r)
    assert pool.drained
    assert pool.n_allocated == pool.n_freed
    assert pool.free_page_count == n_pages
    assert len(pool.prefix_index) == 0
    assert all(pool.refcount(p) == 0 for p in range(n_pages))


def test_prefix_sharing_fake_engine_scheduling():
    """Engine loop with sharing on, tensor-free fake: oracle tokens,
    conservation at drain, and real attach evidence (the fake's decode
    never touches pages, so this isolates scheduling + allocation)."""
    ec = EngineConfig(n_slots=3, max_prompt_len=12, max_new_cap=6,
                      cache_len=18, max_prefill_per_step=2, page_size=4,
                      n_pages=8, prefix_sharing=True)
    eng = ServingEngine(FakePagedModel(), ec)
    tpl = np.arange(60, 68)                      # two full pages
    want = {}
    rng = np.random.default_rng(3)
    for i in range(12):
        prompt = np.concatenate([tpl, rng.integers(0, 50, 1 + i % 3)])
        rid = eng.submit(prompt, 2 + i % 4, arrival=float(i % 5))
        want[rid] = (prompt.astype(np.int32), 2 + i % 4)
    rep = eng.run()
    assert set(rep.completed) == set(want)
    fake = FakePagedModel()
    for rid, (prompt, max_new) in want.items():
        np.testing.assert_array_equal(rep.completed[rid],
                                      fake.oracle(prompt, max_new))
    assert eng.pool.drained
    assert eng.pool.n_allocated == eng.pool.n_freed
    assert eng.pool.n_shared_attached > 0 and eng.pool.max_refcount > 1


def test_prefix_sharing_requires_paged_plane():
    with pytest.raises(ValueError, match="prefix_sharing"):
        ServingEngine(FakePagedModel(),
                      EngineConfig(n_slots=2, prefix_sharing=True))


def test_prefix_sharing_acceptance_oracle_identity(small_lm):
    """THE sharing acceptance check: 32 requests over 4 shared templates;
    the sharing engine is token-identical to greedy_generate AND to the
    non-sharing paged engine, while peak pages-in-use stays strictly
    below the private-reservation baseline."""
    cfg, params = small_lm
    wl = shared_prefix_workload(32, cfg.vocab_size, n_templates=4,
                                template_len=16, suffix_lens=(4, 8, 12),
                                news=(6, 12, 16), stagger=0.5, seed=0)
    max_len = max(p.shape[0] + m for p, m, _ in wl)

    def run(sharing):
        ec = EngineConfig(n_slots=8, max_prompt_len=28, max_new_cap=16,
                          cache_len=max_len, max_prefill_per_step=4,
                          page_size=4, prefix_sharing=sharing)
        eng = ServingEngine(PagedTransformerModel(params, cfg, RULES), ec)
        for p, m, a in wl:
            eng.submit(p, m, arrival=a)
        return eng, eng.run()

    eng_off, rep_off = run(False)
    eng_on, rep_on = run(True)
    assert len(rep_on.completed) == 32
    for rid, (prompt, max_new, _) in enumerate(wl):
        ref = np.asarray(greedy_generate(
            params, cfg, RULES, np.asarray(prompt)[None],
            max_new=max_new))[0]
        np.testing.assert_array_equal(rep_on.completed[rid], ref,
                                      err_msg=f"vs greedy, rid {rid}")
        np.testing.assert_array_equal(rep_on.completed[rid],
                                      rep_off.completed[rid],
                                      err_msg=f"vs non-sharing, rid {rid}")
    # capacity evidence: sharing held strictly fewer pages at peak, with
    # real attaches, and still conserved everything at drain
    assert eng_on.pool.peak_used_pages < eng_off.pool.peak_used_pages
    assert eng_on.pool.n_shared_attached > 0
    assert eng_on.pool.max_refcount > 1
    assert eng_on.pool.drained
    assert eng_on.pool.n_allocated == eng_on.pool.n_freed
    assert len(eng_on.pool.prefix_index) == 0


def test_prefix_sharing_fleet_kill_requeue_oracle(small_lm):
    """Sharing survives the fault domain: a 2-replica sharing fleet with
    one replica killed mid-flight requeues its work onto the survivor,
    which re-matches or re-creates the shared pages — outputs stay
    token-identical to greedy_generate."""
    from repro.fleet import FaultPlan, FleetController, FleetFrontend, \
        Replica
    cfg, params = small_lm
    rules = RULES
    wl = shared_prefix_workload(16, cfg.vocab_size, n_templates=2,
                                template_len=12, suffix_lens=(4, 8),
                                news=(3, 6, 9), stagger=0.5, seed=1)
    max_len = max(p.shape[0] + m for p, m, _ in wl)
    ec = EngineConfig(n_slots=4, max_prompt_len=20, max_new_cap=9,
                      cache_len=max_len, max_prefill_per_step=2,
                      page_size=4, prefix_sharing=True)
    # the paged adapter binds its page pool: one instance per replica
    reps = [Replica("r0", PagedTransformerModel(params, cfg, rules), ec,
                    rate=1.0, fault=FaultPlan(kill_at=4)),
            Replica("r1", PagedTransformerModel(params, cfg, rules), ec,
                    rate=2.0)]
    ctrl = FleetController(reps, miss_threshold=3)
    fe = FleetFrontend(ctrl, max_pending=8)
    report = fe.serve(wl)
    assert report.n_completed == 16
    assert [n for _, n in report.kills] == ["r0"]
    assert report.requeues >= 1, "the kill must have caught work in flight"
    for rid, (prompt, max_new, _) in enumerate(wl):
        ref = np.asarray(greedy_generate(
            params, cfg, rules, np.asarray(prompt)[None],
            max_new=max_new))[0]
        np.testing.assert_array_equal(report.completed[rid], ref,
                                      err_msg=f"rid {rid}")
    # the survivor actually shared (requeued + native traffic both hit
    # its index); the dead pool is abandoned whole, never drained
    assert ctrl.replicas["r1"].engine.pool.n_shared_attached > 0


# ---------------------------------------------------------------------------
# in-place paged decode against the view composition it replaced
# ---------------------------------------------------------------------------

PAGE, PPS, N_PAGES = 4, 5, 24          # view of 20 positions, trash page 24


def _old_paged_decode(cfg, k):
    """The view composition: gather the per-slot view through the READ
    map, run the slot plane's decode on it, scatter it back through the
    WRITE map (kept here as the oracle of the in-place path)."""
    from repro.serve.engine import gather_page_view, scatter_page_view
    from repro.serve.step import make_decode_step
    base = make_decode_step(cfg, RULES)

    def run(params, tok, pos, pool, table, write_table):
        view = gather_page_view(pool, table)
        toks, logits = [], []
        for _ in range(k):
            tok, lg, view = base(params, tok[:, None], pos, view)
            toks.append(tok)
            logits.append(lg[:, -1])
            pos = pos + 1
        pool = scatter_page_view(pool, view, write_table)
        return pool, jnp.stack(toks), jnp.stack(logits)
    return jax.jit(run)


def _new_paged_decode(cfg, k):
    from repro.serve.step import make_paged_decode_step
    step = make_paged_decode_step(cfg, RULES)

    def run(params, tok, pos, pool, table, write_table):
        toks, logits = [], []
        for _ in range(k):
            tok, lg, pool = step(params, tok[:, None], pos, pool, table,
                                 write_table)
            toks.append(tok)
            logits.append(lg[:, -1])
            pos = pos + 1
        return pool, jnp.stack(toks), jnp.stack(logits)
    return jax.jit(run)


def _paged_state(cfg, k, seed=0):
    """A fragmented pool with random K/V on every real page, the trash
    page zero, and six rows: depth 0, a page boundary, the view's last
    position (k = 1; the last k positions for k > 1), two rows sharing
    their first two pages (write-protected in the WRITE map) and an idle
    row (all trash, a stale position)."""
    rng = np.random.default_rng(seed)
    pool = T.init_cache(cfg, N_PAGES + 1, PAGE)
    pool = {n: jnp.asarray(rng.standard_normal(leaf.shape), leaf.dtype)
            .at[:, N_PAGES].set(0) for n, leaf in pool.items()}
    view = PAGE * PPS
    pos = np.array([0, PAGE, view - k, 2 * PAGE + 1, 3 * PAGE - 1, 3],
                   np.int32)
    pages = iter(rng.permutation(N_PAGES).tolist())
    table = np.full((6, PPS), N_PAGES, np.int32)
    shared = [next(pages), next(pages)]
    for row in range(5):
        last = (int(pos[row]) + k - 1) // PAGE
        for col in range(last + 1):
            table[row, col] = (shared[col] if row in (3, 4) and col < 2
                               else next(pages))
    write_table = table.copy()
    write_table[3:5, :2] = N_PAGES
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, 6), jnp.int32)
    return (tok, jnp.asarray(pos), pool, jnp.asarray(table),
            jnp.asarray(write_table))


@pytest.mark.parametrize("arch", ["llama3_2_3b", "qwen3_14b"])
@pytest.mark.parametrize("k", [1, 4])
def test_paged_decode_in_place_matches_view_composition(arch, k):
    """The in-place paged decode (one step and the fused k-step stretch)
    against gather -> slot decode -> scatter: the same next tokens on
    every live row, logits within bf16 rounding, the same bytes on every
    real page, and a trash page that stays all zeros."""
    cfg = get_reduced(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    tok, pos, pool, table, write_table = _paged_state(cfg, k)
    old_pool, old_tok, old_lg = _old_paged_decode(cfg, k)(
        params, tok, pos, pool, table, write_table)
    new_pool, new_tok, new_lg = _new_paged_decode(cfg, k)(
        params, tok, pos, pool, table, write_table)
    live = slice(0, 5)              # row 5 is idle: its output is unused
    np.testing.assert_array_equal(np.asarray(new_tok)[:, live],
                                  np.asarray(old_tok)[:, live])
    np.testing.assert_allclose(np.asarray(new_lg)[:, live],
                               np.asarray(old_lg)[:, live],
                               rtol=2e-2, atol=2e-2)
    for name in ("k", "v"):
        new, old = np.asarray(new_pool[name]), np.asarray(old_pool[name])
        np.testing.assert_array_equal(new[:, :N_PAGES], old[:, :N_PAGES])
        assert not new[:, N_PAGES].any()
        # the write landed: every live row's new positions differ from
        # the random bytes they held, shared pages did not move
        before = np.asarray(pool[name])
        assert (new[:, table[0, 0], 0] != before[:, table[0, 0], 0]).any()
        shared = np.asarray(table)[3, :2]
        np.testing.assert_array_equal(new[:, shared], before[:, shared])


@pytest.mark.parametrize("k", [1, 4])
def test_paged_decode_programs_hold_no_view(small_lm, k):
    """Structural guard: the compiled paged decode programs (the adapter's
    one-step program and the fused scan) hold no array of the per-slot
    view's shape (L, n_slots, view_len, KV, hd) and no operation in a
    page_gather or page_scatter scope."""
    cfg, params = small_lm
    tok, pos, pool, table, write_table = _paged_state(cfg, k)
    model = PagedTransformerModel(params, cfg, RULES)
    fn = model._paged_decode1 if k == 1 else model.paged_decode_scan(k)
    text = fn.lower(params, tok, pos, pool, table,
                    write_table).compile().as_text()
    L, _, _, KV, hd = pool["k"].shape
    view = f"[{L},6,{PAGE * PPS},{KV},{hd}]"
    assert view not in text
    assert "page_gather" not in text and "page_scatter" not in text
    assert "kv_write" in text and "attention" in text

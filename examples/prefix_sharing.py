"""Prefix sharing + copy-on-write on the paged KV plane.

    PYTHONPATH=src python examples/prefix_sharing.py [--arch llama3_2_3b]

Serves 32 requests drawn from 4 shared system-prompt templates through
the paged serving engine twice — once with worst-case private page
reservation, once with ``prefix_sharing`` — and shows the capacity win:
matching prompts attach to the SAME physical prompt pages (refcounted),
each request privately claims only its divergent suffix + decode pages
(the copy-on-write), and peak pages-in-use drops while every token stays
identical to the non-sharing plane AND to the ``greedy_generate``
oracle.  The contract behind this demo is documented in
``docs/serving.md``.
"""

import argparse

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_reduced
from repro.models import transformer as T
from repro.serve import (EngineConfig, PagedTransformerModel,
                         ServingEngine, greedy_generate)
from repro.serve.engine import shared_prefix_workload
from repro.sharding.rules import Rules

PAGE_SIZE = 4


def run(params, cfg, rules, workload, *, sharing):
    eng = ServingEngine(
        PagedTransformerModel(params, cfg, rules),
        EngineConfig(n_slots=8, max_prompt_len=28, max_new_cap=16,
                     cache_len=44, page_size=PAGE_SIZE,
                     prefix_sharing=sharing))
    for prompt, max_new, arrival in workload:
        eng.submit(prompt, max_new, arrival=arrival)
    return eng, eng.run()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3_2_3b")
    args = ap.parse_args(argv)

    cfg = get_reduced(args.arch)
    rules = Rules.null()
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    # 32 requests over 4 templates: each prompt = 16-token template
    # (4 full shareable pages) + a private 4..12-token suffix.
    workload = shared_prefix_workload(32, cfg.vocab_size, n_templates=4,
                                      template_len=16, suffix_lens=(4, 8, 12),
                                      news=(4, 8, 12, 16), stagger=0.5)
    print(f"{cfg.name}: 32 requests over 4 shared {16}-token templates "
          f"(page_size={PAGE_SIZE})")

    eng_off, rep_off = run(params, cfg, rules, workload, sharing=False)
    eng_on, rep_on = run(params, cfg, rules, workload, sharing=True)

    print(f"\n  {'':22s}{'sharing off':>12s}{'sharing on':>12s}")
    print(f"  {'peak pages in use':22s}{eng_off.pool.peak_used_pages:>12d}"
          f"{eng_on.pool.peak_used_pages:>12d}")
    print(f"  {'pages allocated':22s}{eng_off.pool.n_allocated:>12d}"
          f"{eng_on.pool.n_allocated:>12d}")
    print(f"  {'shared attaches':22s}{eng_off.pool.n_shared_attached:>12d}"
          f"{eng_on.pool.n_shared_attached:>12d}")
    print(f"  {'max refcount':22s}{eng_off.pool.max_refcount:>12d}"
          f"{eng_on.pool.max_refcount:>12d}")
    ratio = eng_off.pool.peak_used_pages / max(eng_on.pool.peak_used_pages, 1)
    print(f"  capacity ratio (peak off / peak on): {ratio:.2f}x")

    # token identity: sharing vs non-sharing, and both vs the oracle
    identical = all(np.array_equal(rep_off.completed[rid],
                                   rep_on.completed[rid])
                    for rid in rep_off.completed)
    print(f"\n  sharing token-identical to non-sharing plane: {identical}")
    assert identical
    for rid in (0, 15, 31):
        prompt, max_new, _ = workload[rid]
        ref = np.asarray(greedy_generate(params, cfg, rules,
                                         np.asarray(prompt)[None],
                                         max_new=max_new))[0]
        assert np.array_equal(ref, rep_on.completed[rid]), rid
    print("  oracle spot-check (rids 0/15/31): token-identical")

    assert eng_on.pool.n_shared_attached > 0
    assert eng_on.pool.peak_used_pages < eng_off.pool.peak_used_pages
    assert eng_on.pool.n_allocated == eng_on.pool.n_freed
    print("  drained clean: n_allocated == n_freed, prefix index empty "
          f"({len(eng_on.pool.prefix_index)} entries)")


if __name__ == "__main__":
    main()

"""Whether the timed window served the right tokens.

Once the window has closed and the program's state is freed, a sample of
the delivered requests, drawn from the seed and holding the request with
the most served tokens, runs through the plain reference of the
configuration's family (``bench.family``): each prompt followed by its
served tokens, in one packed forward pass.  At each served token the gap
is the reference's best logit minus its logit of the served token.  Two
numbers are compared (``numbers``): the widest gap, and the mean gap over
every checked token, which grows with the square of the logits' error and
so also catches a lower precision in the weights alone.  Each limit sits
between what sound runs of the program read and what the controls read
(the reference one precision step lower: ``quant``, see
``bench.common``), as ``PERF.md`` records.  A delivered request of the
wrong length or with an id outside the vocabulary is wrong whatever its
logits.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import family
from .common import Q_CHUNK, served_rows


def budget(cell, scale: int = 1) -> Tuple[int, int]:
    """(packed positions, logit rows) one reference pass holds: two of the
    longest requests, and four of the longest outputs (``scale`` times
    that, for readings of other budgets)."""
    span = cell.max_prompt + cell.max_output
    return (-(-2 * scale * span // Q_CHUNK) * Q_CHUNK,
            4 * scale * cell.max_output)


def sample(delivered: Dict[int, Tuple[np.ndarray, np.ndarray]], seed: int,
           positions: int, rows: int) -> List[int]:
    """Keys of ``delivered`` (key -> (prompt, served)) to check: the one
    with the most served tokens, then others in a seeded order while the
    budget holds."""
    if not delivered:
        return []
    size = lambda k: (len(delivered[k][0]) + len(delivered[k][1]) - 1,
                      len(delivered[k][1]))
    keys = sorted(delivered)
    first = max(keys, key=lambda k: (size(k)[1], size(k)[0], -k))
    rng = np.random.default_rng([int(seed) % (1 << 64), 5])
    chosen, used, n_rows = [first], size(first)[0], size(first)[1]
    for k in rng.permutation([k for k in keys if k != first]):
        p, r = size(int(k))
        if used + p <= positions and n_rows + r <= rows:
            chosen.append(int(k))
            used, n_rows = used + p, n_rows + r
    return chosen


def gaps(spec: dict, cfg, seed: int, prompts: Sequence[np.ndarray],
         served: Sequence[np.ndarray], positions: int, rows: int,
         quants: Sequence[Optional[str]] = ()) -> Dict[str, np.ndarray]:
    """Per checked token, the gap under the float32 reference of the served
    token (``"served"``) and, for each ``quant``, of the token that the
    lower precision would put first at the same position.  ``spec`` is
    the configuration file, whose family gives the reference."""
    import jax.numpy as jnp
    logits = family.load(spec).reference_logits
    seqs, where = served_rows(prompts, served)
    idx = np.concatenate(where)
    tokens = np.concatenate(served).astype(np.int32)
    n = len(idx)
    pad = np.zeros(rows, np.int32)
    pad[:n] = idx
    ref = logits(cfg, seed, seqs, pad, positions)[:n]
    best = ref.max(axis=-1)
    pick = lambda toks: ref[jnp.arange(n), jnp.asarray(toks)]
    out = {"served": np.asarray(best - pick(tokens), np.float64)}
    for q in quants:
        low = logits(cfg, seed, seqs, pad, positions, quant=q)[:n]
        out[q] = np.asarray(best - pick(low.argmax(axis=-1)), np.float64)
    return out


def numbers(gap: np.ndarray) -> Dict[str, float]:
    """The numbers compared, from the gaps of the checked tokens (none
    checked: every number is infinite)."""
    if not gap.size:
        return {"max_logit_gap": float("inf"), "mean_logit_gap": float("inf")}
    return {"max_logit_gap": float(gap.max()),
            "mean_logit_gap": float(gap.mean())}


def judge(values: Dict[str, float], limits: Dict[str, float]):
    """Each number that has a limit, beside it; and whether all hold."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def bad_outputs(records, vocab: int) -> int:
    """Delivered requests whose tokens are not ``max_new`` ids in range."""
    bad = 0
    for r in records:
        t = r.tokens
        if t is not None and (t.shape != (r.max_new,)
                              or (t < 0).any() or (t >= vocab).any()):
            bad += 1
    return bad

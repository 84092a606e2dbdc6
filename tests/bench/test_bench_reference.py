"""The benchmark's weights and its plain float32 reference, on the CPU.

The dense family's reference (``bench/families/dense.py``) imports nothing
of the program; here it is held against the program's own prefill and
cached decode at a small size, with and without per-head q/k RMSNorm.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import common, family
from smallcell import small_spec

dense = family.load(small_spec())

# bf16 activations through two layers move the program's logits by at most
# 0.0111 (no q/k norm) and 0.0101 (q/k norm) from the float32 reference;
# the same forward with fp8 operands moves them by 0.14-0.15.  0.03 sits
# between, nearer the program.
LOGIT_TOL = 0.03


def test_one_layer_draw_equals_the_stacked_init():
    cfg = dense.model_config(small_spec(qk_norm=True, layers=3))
    key = common.seed_key(2**33 + 7)
    full = dense.init_weights(cfg)(key)
    for layer in range(cfg.n_layers):
        one = jax.jit(dense.layer_weights, static_argnums=1)(key, cfg, layer)
        for name, w in one.items():
            np.testing.assert_array_equal(
                np.asarray(full["blocks"][name][layer]), np.asarray(w))
    top = dense.top_weights(key, cfg)
    np.testing.assert_array_equal(np.asarray(full["embed"]),
                                  np.asarray(top["embed"]))


@pytest.mark.parametrize("qk_norm", [False, True])
def test_weights_fill_the_programs_parameter_tree(qk_norm):
    from repro.models import transformer as T
    cfg = dense.model_config(small_spec(qk_norm=qk_norm))
    key = jax.random.PRNGKey(0)
    ours = jax.eval_shape(dense.init_weights(cfg), key)
    theirs = jax.eval_shape(lambda k: T.init_params(cfg, k), key)
    assert (jax.tree_util.tree_structure(ours)
            == jax.tree_util.tree_structure(theirs))
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        assert a.shape == b.shape and a.dtype == jnp.bfloat16


def _program_logits(cfg, params, toks, prompt_len):
    """Prefill then cached decode through the program, one token at a
    time: the logits that picked each next token."""
    from repro.models import transformer as T
    from repro.sharding.rules import Rules
    rules = Rules.null()
    cache = T.init_cache(cfg, 1, 64)
    cache, lg = T.prefill(params, cfg, rules,
                          jnp.asarray(toks[None, :prompt_len]), cache)
    out = [lg[0]]
    for t in range(prompt_len, len(toks)):
        lg, cache = T.decode_step(params, cfg, rules,
                                  jnp.asarray(toks[None, t:t + 1]),
                                  jnp.asarray([t], jnp.int32), cache)
        out.append(lg[0, 0])
    return np.asarray(jnp.stack(out), np.float32)


@pytest.mark.parametrize("qk_norm", [False, True])
def test_reference_matches_prefill_then_cached_decode(qk_norm):
    cfg = dense.model_config(small_spec(qk_norm=qk_norm))
    seed, prompt_len, n = 5, 40, 6
    params = dense.init_weights(cfg)(common.seed_key(seed))
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                             prompt_len + n).astype(np.int32)
    got = _program_logits(cfg, params, toks, prompt_len)
    rows = np.zeros(16, np.int32)
    rows[:n + 1] = prompt_len - 1 + np.arange(n + 1)
    ref = np.asarray(dense.reference_logits(cfg, seed, [toks], rows,
                                            256))[:n + 1]
    assert np.abs(got - ref).max() <= LOGIT_TOL
    low = np.asarray(dense.reference_logits(cfg, seed, [toks], rows, 256,
                                            quant="fp8"))[:n + 1]
    assert np.abs(low - ref).max() > LOGIT_TOL


def test_packed_sequences_do_not_see_each_other():
    cfg = dense.model_config(small_spec())
    rng = np.random.default_rng(1)
    a = rng.integers(0, cfg.vocab_size, 30).astype(np.int32)
    b = rng.integers(0, cfg.vocab_size, 50).astype(np.int32)
    rows = np.zeros(8, np.int32)
    rows[:2] = [29, 30 + 49]
    both = np.asarray(dense.reference_logits(cfg, 3, [a, b], rows, 256))
    rows_b = np.zeros(8, np.int32)
    rows_b[0] = 49
    alone = np.asarray(dense.reference_logits(cfg, 3, [b], rows_b, 256))
    np.testing.assert_allclose(both[1], alone[0], atol=1e-5)


def test_served_rows_point_at_the_logits_that_picked_each_token():
    prompts = [np.arange(5), np.arange(3)]
    served = [np.array([7, 8, 9]), np.array([4])]
    seqs, rows = common.served_rows(prompts, served)
    assert [len(s) for s in seqs] == [7, 3]
    assert rows[0].tolist() == [4, 5, 6] and rows[1].tolist() == [9]

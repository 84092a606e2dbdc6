"""The serving launcher's own path: config, weights, oracle check, cache
placement, and the chip smoke test's refusal to run without a chip."""

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import serve as launcher
from repro.launch.compile_cache import DEFAULT_DIR
from repro.models import transformer as T
from repro.sharding.rules import Rules

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(extra)
    return env


def test_serving_config_keeps_published_widths():
    cfg = launcher.serving_config("llama3_2_3b", demo=False,
                                  rules=Rules.null())
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.hd, cfg.d_ff, cfg.vocab_size) == (
        28, 3072, 24, 8, 128, 8192, 128256)
    assert cfg.tp == 1 and cfg.h_padded == 24 and cfg.kv_param == 8
    demo = launcher.serving_config("llama3_2_3b", demo=True,
                                   rules=Rules.null())
    assert demo.name == "llama3_2_3b-reduced" and demo.d_model == 64


def test_param_init_casts_the_reference_draw():
    cfg = launcher.serving_config("llama3_2_3b", demo=True,
                                  rules=Rules.null())
    key = jax.random.PRNGKey(3)
    got = launcher.param_init(cfg)(key)
    ref = T.init_params(cfg, key)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(r.astype(jnp.bfloat16)))


def test_cache_len_must_hold_prompt_and_new_tokens():
    args = launcher.parse_args(["--prompt-len", "8", "--max-new", "4",
                                "--cache-len", "32"])
    assert launcher.engine_config(args).pool_len == 32
    assert launcher.engine_config(
        launcher.parse_args(["--prompt-len", "8", "--max-new", "4"])
    ).pool_len == 12
    with pytest.raises(SystemExit):
        launcher.parse_args(["--prompt-len", "8", "--max-new", "4",
                             "--cache-len", "11"])


@pytest.fixture(scope="module")
def demo_run():
    args = launcher.parse_args([
        "--demo", "--paged", "--page-size", "4", "--slots", "3",
        "--batch", "5", "--prompt-len", "16", "--max-new", "6",
        "--cache-len", "32", "--seed", "1"])
    return launcher.serve(args)


def test_serve_paged_demo_matches_oracle(demo_run):
    assert len(demo_run.report.completed) == 5
    assert demo_run.engine.pool.view_len == 32
    results = launcher.compare_to_oracle(
        demo_run.params, demo_run.cfg, demo_run.rules, demo_run.workload,
        demo_run.report.completed)
    assert [r.diverge_at for r in results] == [None] * 5


def test_oracle_reports_first_divergence_and_margin(demo_run):
    completed = {rid: np.array(t) for rid, t in
                 demo_run.report.completed.items()}
    vocab = demo_run.cfg.vocab_size
    completed[2][3] = (completed[2][3] + 1) % vocab
    results = launcher.compare_to_oracle(
        demo_run.params, demo_run.cfg, demo_run.rules, demo_run.workload,
        completed)
    bad = [r for r in results if r.diverge_at is not None]
    assert len(bad) == 1 and bad[0].rid == 2 and bad[0].diverge_at == 3
    # the oracle picked the argmax, so its own token scores at least as
    # high as the engine's
    assert bad[0].margin >= 0.0


def test_compile_cache_uses_the_variable_when_set(tmp_path):
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "print(use_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(3)).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    before = set(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.exists() else set()
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env=_cpu_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path)] * 2
    assert list(tmp_path.iterdir())               # entries landed here...
    after = set(os.listdir(DEFAULT_DIR)) if DEFAULT_DIR.exists() else set()
    assert after == before                        # ...and nowhere else


def test_compile_cache_defaults_to_the_repo_directory():
    code = ("import jax\n"
            "from repro.launch.compile_cache import use_compile_cache\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(use_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=_cpu_env())
    assert r.returncode == 0, r.stderr
    unset, chosen, now = r.stdout.split("\n")[:3]
    assert unset == "None"        # importing the helper sets nothing
    assert chosen == now == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=_cpu_env(), cwd=str(ROOT))
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_compile_cache_key_of_a_kernel_ignores_its_caller():
    """Once the cache is in use, a program holding the paged decode kernel
    has one persistent-cache key whatever script and line traced it."""
    code = (
        "import jax, jax.numpy as jnp, hashlib\n"
        "from jax._src import cache_key\n"
        "from repro.launch.compile_cache import use_compile_cache\n"
        "from repro.kernels.paged_decode_attention_kernel import "
        "paged_decode_attention_pallas as f\n"
        "use_compile_cache()\n"
        "pool = jax.ShapeDtypeStruct((2, 5, 4, 2, 128), jnp.bfloat16)\n"
        "i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)\n"
        "args = (jax.ShapeDtypeStruct((3, 2, 4, 128), jnp.bfloat16), pool,"
        " pool, i32(), i32(3, 2), i32(3))\n"
        "{pad}m = jax.jit(lambda *a: f(*a, interpret=False)).trace(*args)"
        ".lower(lowering_platforms=('tpu',)).compiler_ir()\n"
        "print(hashlib.sha256(cache_key._canonicalize_ir("
        "m, cache_key.IgnoreCallbacks.NO)).hexdigest())\n")
    keys = []
    for pad in ("", "pass\n\n\n"):        # the tracing line moves
        r = subprocess.run([sys.executable, "-c", code.format(pad=pad)],
                           capture_output=True, text=True, timeout=120,
                           env=_cpu_env())
        assert r.returncode == 0, r.stderr
        keys.append(r.stdout.split()[-1])
    assert keys[0] == keys[1]

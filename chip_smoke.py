"""Smoke test on the chip: the system's main path at published widths.

    python chip_smoke.py             # one TPU chip: serve llama3_2_3b
    python chip_smoke.py --chips 4   # four chips: the LBP matmul only

One chip: eight requests (prompts of 128-512 tokens, 32 new tokens each)
go through the serving launcher's own path (``repro.launch.serve.serve``)
at llama3_2_3b's published widths with random bf16 weights drawn from
``--seed``: paged KV plane, 16-token pages, 8 slots, 1024 cache positions
per slot.  Every request is then replayed through ``greedy_generate``.
Where the tokens differ, the check prints the oracle's logit margin at
the first difference, and passes only a near-tie: a margin that the
summation order alone could close (see ``shape_shift``).

Four chips: the paper's k-sharded matmul (``lbp_matmul`` in the layers,
allreduce and scatter modes, and ``lbp_matmul_heterogeneous`` with an
uneven split) on a mesh of the four local chips, at the llama
down-projection shape, against ``lbp_matmul_reference`` on one chip.

Times printed are from this smoke run and include compilation; they are
not benchmark numbers.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The script
exits non-zero without that line when JAX finds no TPU, or when any
check fails.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

SERVE_ARGV = ["--arch", "llama3_2_3b", "--paged", "--page-size", "16",
              "--slots", "8", "--batch", "8", "--prompt-len", "512",
              "--max-new", "32", "--cache-len", "1024"]

DOWN_PROJ = (4096, 8192, 3072)   # x (M, K) @ w (K, F): llama's w_down
UNEVEN_K = (3072, 2560, 1536, 1024)


def tpu_devices(n_chips: int):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{platform!r}")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips; JAX found "
                 f"{len(devices)}")
    return devices


class CompileClock:
    """Sums XLA backend compile time (and persistent-cache reads) as JAX
    reports them."""

    def __init__(self):
        import jax
        self.compile_s = self.cache_read_s = 0.0
        self.compiles = self.cache_reads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_read_s += duration
            self.cache_reads += 1

    def __str__(self):
        return (f"{self.compiles} XLA compiles in {self.compile_s:.1f}s, "
                f"{self.cache_reads} persistent-cache reads in "
                f"{self.cache_read_s:.1f}s")


def shape_shift(run, rid: int, j: int) -> float:
    """How far the reference logits at generated position ``j`` of request
    ``rid`` move, at most over the vocabulary, when the same tokens (the
    prompt, then the engine's first ``j`` tokens) run at the engine's
    shapes instead of the oracle's.  The oracle's: one row, the exact
    prompt, an exact-length cache.  The engine's: a prefill batch as wide
    as its prefill group and a decode batch as wide as its slots (copies
    of this request), the prompt right-padded to the longest prompt, and
    a cache as long as the engine's.  No pages, same weights: only the
    order in which bf16 sums are taken differs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T
    from repro.serve.step import cached_decode_step

    cfg, rules, params = run.cfg, run.rules, run.params
    prompt, max_new, _ = run.workload[rid]
    fed = np.asarray(run.report.completed[rid][:j], np.int32)
    S = len(prompt)
    prefill = jax.jit(T.prefill, static_argnums=(1, 2))
    step = cached_decode_step(cfg, rules)

    def logits(prefill_rows, decode_rows, width, cache_len):
        tokens = np.zeros((prefill_rows, width), np.int32)
        tokens[:, :S] = prompt
        cache, out = prefill(params, cfg, rules, jnp.asarray(tokens),
                             T.init_cache(cfg, prefill_rows, cache_len),
                             last_index=jnp.full((prefill_rows,), S - 1))
        cache = jax.tree_util.tree_map(
            lambda c: jnp.repeat(c[:, :1], decode_rows, axis=1), cache)
        pos = jnp.full((decode_rows,), S, jnp.int32)
        for t in fed:
            _, out, cache = step(params,
                                 jnp.full((decode_rows, 1), t, jnp.int32),
                                 pos, cache)
            out, pos = out[:, 0], pos + 1
        return np.asarray(out[0], np.float32)

    ec = run.engine.config
    return float(np.abs(logits(1, 1, S, S + max_new) - logits(
        ec.max_prefill_per_step, ec.n_slots, ec.max_prompt_len,
        ec.pool_len)).max())


def serve_phase(argv) -> bool:
    """Serve through the launcher and check against the oracle."""
    import numpy as np
    from repro.launch import serve as launcher

    args = launcher.parse_args(argv)
    t0 = time.perf_counter()
    run = launcher.serve(args)
    wall = time.perf_counter() - t0
    cfg, rep = run.cfg, run.report
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} (in params: {cfg.h_padded}) "
          f"kv_heads={cfg.kv_param} head_dim={cfg.hd} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}")
    wq = run.params["blocks"]["wq"]
    ok = (cfg.h_padded == cfg.n_heads
          and wq.shape == (cfg.n_layers, cfg.d_model, cfg.n_heads * cfg.hd)
          and str(wq.dtype) == cfg.dtype)
    print(f"smoke run, not a benchmark (times include compilation): "
          f"{rep.prefill_count} prompts / {rep.prefill_tokens} tokens "
          f"prefilled in {rep.prefill_wall:.2f}s, {rep.decode_tokens} "
          f"tokens decoded in {rep.decode_wall:.2f}s over "
          f"{rep.decode_steps} steps, serve() took {wall:.2f}s")

    for rid, (prompt, max_new, _) in enumerate(run.workload):
        toks = np.asarray(rep.completed.get(rid, []))
        if toks.shape != (max_new,) or not (
                (toks >= 0) & (toks < cfg.vocab_size)).all():
            print(f"request {rid}: bad output {toks!r}")
            ok = False
    print(f"served {len(rep.completed)}/{len(run.workload)} requests, "
          f"prompt lengths {sorted({len(p) for p, _, _ in run.workload})}")

    t0 = time.perf_counter()
    results = launcher.compare_to_oracle(run.params, cfg, run.rules,
                                         run.workload, rep.completed)
    same = sum(r.diverge_at is None for r in results)
    print(f"oracle: {same}/{len(results)} requests token-identical to "
          f"greedy_generate ({time.perf_counter() - t0:.1f}s)")
    for r in results:
        if r.diverge_at is None:
            continue
        # two logits that each move by up to `shift` can swap order when
        # they lie within 2 * shift of each other
        shift = shape_shift(run, r.rid, r.diverge_at)
        tie = 0.0 <= r.margin <= 2 * shift
        print(f"  request {r.rid}: first differs at token {r.diverge_at}, "
              f"oracle logit margin {r.margin:.6f}, logit shift from the "
              f"engine's shapes {shift:.6f} -> "
              f"{'near-tie' if tie else 'NOT a tie'}")
        ok = ok and tie
    return ok


def lbp_phase(devices, shape=DOWN_PROJ, uneven_k=UNEVEN_K,
              seed: int = 0) -> bool:
    """The k-sharded matmul across ``devices`` vs one device."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import make_mesh
    from repro.core.lbp_matmul import (lbp_matmul, lbp_matmul_heterogeneous,
                                       lbp_matmul_reference)
    from repro.core.partition import LayerAssignment

    M, K, F = shape
    p = len(devices)
    mesh = make_mesh((p,), ("model",), devices=devices)
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    with jax.default_device(devices[0]):
        x = jax.random.normal(kx, (M, K), jnp.bfloat16)
        w = (jax.random.normal(kw, (K, F), jnp.float32)
             * K ** -0.5).astype(jnp.bfloat16)
        ref = np.asarray(jax.jit(lbp_matmul_reference)(x, w), np.float32)
    rms = float(np.sqrt(np.mean(ref ** 2)))
    eps = 2.0 ** -8                    # bf16 unit roundoff
    # p partial layers, each rounded to bf16, then summed: a few roundings
    # of values up to |ref| each
    tol = 4 * p * eps * (np.abs(ref) + rms)

    xs = jax.device_put(x, NamedSharding(mesh, P(None, "model")))
    ws = jax.device_put(w, NamedSharding(mesh, P("model", None)))
    # the uneven split repacks whole operands: hand it a copy on every chip
    xr, wr = (jax.device_put(a, NamedSharding(mesh, P())) for a in (x, w))
    assign = LayerAssignment(np.asarray(uneven_k), quantum=128)
    runs = {mode: jax.jit(functools.partial(lbp_matmul, mesh=mesh, mode=mode))
            for mode in ("layers", "allreduce", "scatter")}
    runs["heterogeneous"] = jax.jit(
        lambda x, w: lbp_matmul_heterogeneous(x, w, assign, mesh))
    ok = True
    for name, fn in runs.items():
        args = (xr, wr) if name == "heterogeneous" else (xs, ws)
        got = np.asarray(fn(*args), np.float32)
        if name == "layers":   # one full-shape layer per chip
            got = got.reshape(p, M, F).sum(0)
        err = np.abs(got - ref)
        good = got.shape == ref.shape and bool((err <= tol).all())
        ok = ok and good
        print(f"lbp_matmul {name:13s}: x{(M, K)} @ w{(K, F)} bf16 on {p} "
              f"chips, max |err| {err.max():.5f} (ref rms {rms:.4f}, "
              f"worst err/tol {float((err / tol).max()):.3f}) "
              f"{'ok' if good else 'MISMATCH'}")
    print(f"uneven split k = {list(uneven_k)}")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve llama3_2_3b on one chip; 4: the LBP "
                         "matmul across four chips vs one")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = tpu_devices(args.chips)
    import jax

    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()
    dev = devices[0]
    print(f"device: {dev.device_kind} ({dev.platform}), {len(devices)} "
          f"visible, using {args.chips}")
    if args.chips == 1:
        ok = serve_phase(SERVE_ARGV + ["--seed", str(args.seed)])
    else:
        ok = lbp_phase(devices[:args.chips], seed=args.seed)
    print(f"compile: {clock}")
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')} "
          f"of bytes_limit {stats.get('bytes_limit', 'n/a')}")
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

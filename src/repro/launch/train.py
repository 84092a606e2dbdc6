"""Training launcher.

Full-scale (dry-run container: compiles only; real pod: runs):
  PYTHONPATH=src python -m repro.launch.train --arch llama3_2_3b --demo

``--demo`` runs an actual reduced-config training on the local devices
(the end-to-end driver required by deliverable (b)): synthetic pipeline,
AdamW, async checkpoints, resume, loss printed per step.
"""

from __future__ import annotations

import argparse

import jax

from ..configs import ARCH_IDS, get_config, get_reduced
from ..runtime.trainer import Trainer, TrainerConfig
from ..sharding.rules import Rules, make_rules
from .compile_cache import use_compile_cache
from .mesh import make_production_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3_2_3b")
    ap.add_argument("--demo", action="store_true",
                    help="reduced config on local devices (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped layer-streaming plane: explicit "
                         "shard_map LBP with stream_* aggregation "
                         "(sequence-parallel train_sp profile)")
    ap.add_argument("--bidir", action="store_true",
                    help="bidirectional half-rings on the streamed "
                         "plane (stream_*_bidir modes: same bytes, "
                         "ceil((p-1)/2) sequential hops); implies "
                         "--overlap")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the "
                         "training run (open at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics-registry snapshot as JSON")
    args = ap.parse_args()
    use_compile_cache()

    if args.bidir:
        args.overlap = True
    if args.overlap:
        from ..models.tuning import set_tuning
        set_tuning(explicit_lbp_scatter=True, overlap_streaming=True,
                   overlap_bidir=args.bidir)

    if args.demo:
        from ..obs import MetricsRegistry, Tracer, write_chrome_trace
        cfg = get_reduced(args.arch)
        rules = Rules.null()
        if not args.resume:
            import shutil
            shutil.rmtree(args.ckpt_dir, ignore_errors=True)
        tracer = Tracer() if args.trace_out else None
        metrics = MetricsRegistry() if args.metrics_out else None
        tr = Trainer(cfg, rules,
                     TrainerConfig(total_steps=args.steps,
                                   checkpoint_dir=args.ckpt_dir,
                                   grad_accum=args.grad_accum,
                                   checkpoint_every=10),
                     batch_size=args.batch, seq_len=args.seq,
                     tracer=tracer, metrics=metrics)
        hist = tr.run()
        if tracer is not None:
            print(f"trace:   {write_chrome_trace(tracer, args.trace_out)}")
        if metrics is not None:
            print(f"metrics: {metrics.write_json(args.metrics_out)}")
        for m in hist:
            if m["step"] % 5 == 0 or m["step"] == len(hist) - 1:
                print(f"step {m['step']:4d}  loss {m['loss']:.4f}  "
                      f"gnorm {m['grad_norm']:.3f}  lr {m['lr']:.2e}  "
                      f"{m['dt']*1e3:.0f} ms")
        first, last = hist[0]["loss"], hist[-1]["loss"]
        print(f"loss: {first:.4f} -> {last:.4f} "
              f"({'DOWN' if last < first else 'FLAT'})")
        return

    # production path: build the pod mesh and compile the step
    mesh = make_production_mesh()
    rules = make_rules("train_sp" if args.overlap else "train", mesh)
    cfg = get_config(args.arch)
    print(f"arch={cfg.name}  N={cfg.n_params()/1e9:.2f}B  mesh={mesh.shape}")
    print("production launch requires a real pod; use launch.dryrun to "
          "verify the compiled step on this host.")


if __name__ == "__main__":
    main()

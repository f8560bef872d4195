"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b \
        --steps 100 --ckpt-dir /tmp/ckpt [--smoke] [--devices 8]

``--smoke`` uses the arch's reduced config (runs on CPU); the full config
is only practical on real accelerators — the multi-pod configuration is
exercised via launch/dryrun.py.  Device simulation (``--devices``) must be
set before jax initializes, which is why this module parses argv before
importing jax.
"""
import argparse
import os
import sys


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--devices", type=int, default=0,
                    help="simulate N host devices (CPU)")
    ap.add_argument("--data", type=int, default=0, help="data-axis size")
    ap.add_argument("--model", type=int, default=0, help="model-axis size")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--no-compress", action="store_true")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable backward-pipelined bucket sync "
                         "(BucketSpec.overlap); keeps the post-backward "
                         "reference schedule")
    return ap.parse_args()


def main():
    args = _parse()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    from repro.configs.base import SHAPES, RunConfig, ShapeSpec
    from repro.configs.registry import (get_config, get_run_config,
                                        smoke_config)
    from repro.core import types as core_types
    from repro.optim.optimizers import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    n = jax.device_count()
    data = args.data or max(1, n // max(1, args.model or 1))
    model = args.model or (n // data)
    mesh = jax.make_mesh((data, model), ("data", "model"))

    if args.smoke:
        cfg = smoke_config(args.arch)
        shape = ShapeSpec("cli", "train", args.seq, args.batch)
        comp = (core_types.CompressionConfig(mode="none") if args.no_compress
                else core_types.CompressionConfig(
                    encoder=core_types.EncoderSpec(kind="fixed_k",
                                                   fraction=1 / 16),
                    mode="shared_support", axes=("data",),
                    min_compress_size=1024, error_feedback=True))
        run = RunConfig(microbatches=1, model_parallel=model > 1,
                        seq_shard=model > 1, attn_chunk_q=min(128, args.seq),
                        attn_chunk_k=min(128, args.seq), remat=False,
                        compression=comp)
    else:
        cfg = get_config(args.arch)
        shape = SHAPES[args.shape]
        run = get_run_config(args.arch, args.shape)
    if args.no_overlap:
        import dataclasses
        comp = run.compression
        run = dataclasses.replace(
            run, compression=dataclasses.replace(
                comp, bucket=dataclasses.replace(comp.bucket, overlap=False)))

    tcfg = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every,
                         log_every=max(1, args.steps // 20))
    tr = Trainer(mesh, cfg, run, shape, tcfg,
                 AdamWConfig(lr=args.lr, total_steps=args.steps))
    _, _, hist = tr.fit()
    for h in hist:
        print(f"step {h['step']:5d}  loss {h['loss']:.4f}  "
              f"gnorm {h['grad_norm']:.3f}  lr {h['lr']:.2e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

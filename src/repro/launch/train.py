"""Training launcher.

CPU-scale end-to-end entry point (examples/train_lm.py wraps this) and the
production shape: on a real pod the same code runs under
``jax.distributed.initialize`` with the production mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 50 --data /tmp/corpus.rntj --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
from pathlib import Path

import jax

from repro.configs import ARCHS, get_arch, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models.registry import build
from repro.pipeline import PackedLoader, ingest_corpus, synth_corpus
from repro.train import LoopConfig, TrainLoop, make_optimizer


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data", default="/tmp/repro_corpus.rntj")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 production mesh (needs 256 devices)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_arch(args.arch)
    bundle = build(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())

    if not Path(args.data).exists():
        print(f"ingesting synthetic corpus -> {args.data}")
        ingest_corpus(
            synth_corpus(2000, mean_len=256, vocab=cfg.vocab_size),
            args.data, n_workers=4,
        )
    loader = PackedLoader(args.data, batch=args.batch, seq_len=args.seq)

    loop = TrainLoop(
        bundle, mesh, loader, args.ckpt_dir,
        config=LoopConfig(
            steps=args.steps, ckpt_every=args.ckpt_every,
            grad_compression=args.grad_compression,
            microbatches=args.microbatches,
        ),
        optimizer=make_optimizer(peak_lr=args.lr, warmup=20, total=args.steps),
    )
    if loop.step:
        print(f"restored from checkpoint at step {loop.step}")
    history = loop.run()
    print(f"done: step {loop.step}, "
          f"loss {history[0].loss:.3f} -> {history[-1].loss:.3f}")


if __name__ == "__main__":
    main()

"""Regenerate the packaged board768 net (fishnet_tpu/assets/).

Distills the classical handcrafted evaluation (material + PST + mobility,
models/train.py classical_eval_target) into the board768 net the TPU
engine ships with — the reference instead ships externally trained
Stockfish nets (reference: build.rs:8-9); this is the in-framework
bootstrap equivalent.

Usage: python tools/train_default_net.py [--steps N] [--samples N]
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    ap = argparse.ArgumentParser()
    # defaults reproduce the shipped net (docs/strength.md: the r2 net's
    # 4k steps badly underfit — evals compressed to ±200 cp and it LOST
    # to a material searcher; 24k steps/150k positions fits the full
    # material scale and scores 0.94 against the same opponent)
    ap.add_argument("--steps", type=int, default=24_000)
    ap.add_argument("--samples", type=int, default=150_000)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--l1", type=int, default=64)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax  # noqa: F401  (after env setup)

    from fishnet_tpu.assets import ASSET_DIR, DEFAULT_NETS
    from fishnet_tpu.models import nnue
    from fishnet_tpu.models.train import (
        diverse_position_dataset,
        train_material_net,
    )

    print(f"generating {args.samples} positions ...", flush=True)
    dataset = diverse_position_dataset(args.samples, seed=args.seed)
    print("training ...", flush=True)
    params, loss = train_material_net(
        l1=args.l1, steps=args.steps, batch=args.batch, seed=args.seed,
        dataset=dataset, lr=args.lr,
    )
    out = args.out or (ASSET_DIR / DEFAULT_NETS["board768"])
    nnue.save_params(params, out)
    print(f"saved {out} (final loss {loss:.4f})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

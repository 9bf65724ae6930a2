"""Train the next board768 net against DEEPER SEARCH labels.

VERDICT r2 #5: the shipped net is distilled from a handcrafted
material+PST+mobility target; the next step is self-distillation from
search — label positions with the device search's depth-d backed-up score
of the CURRENT net (TD-leaf style), and fit a fresh net to those labels.
Search backups see tactics the static eval misses, so the fitted eval
absorbs one tempo of tactics per iteration.

Labeling runs the batched lockstep search itself (lanes are cheap — the
same property the engine exploits), so 30k labels cost ~120 dispatches.

Usage:
  python tools/train_search_net.py --samples 20000 --depth 2 \
      --out /tmp/net-candidate.npz
  python tools/strength_ab.py --net /tmp/net-candidate.npz ...  # then A/B
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--base", default="fishnet_tpu/assets/nnue-board768-64.npz",
                    help="net whose search produces the labels")
    ap.add_argument("--samples", type=int, default=20_000)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--budget", type=int, default=20_000)
    ap.add_argument("--lanes", type=int, default=256)
    ap.add_argument("--steps", type=int, default=3000)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--warm-start", action="store_true",
                    help="initialize from --base instead of fresh")
    ap.add_argument("--out", default="/tmp/net-search-distilled.npz")
    ap.add_argument("--device", action="store_true",
                    help="label AND train on the real accelerator "
                         "(default: force CPU, the historical mode)")
    ap.add_argument("--classical-mix", type=float, default=0.25,
                    help="regularizer weight L: train against "
                         "(search + L*classical)/(1+L) — for MSE this "
                         "IS the sum-of-losses regularizer (identical "
                         "gradients up to scale); docs/strength.md "
                         "recipe (b) against label-noise memorization")
    ap.add_argument("--holdout", type=float, default=0.05,
                    help="fraction of labels held out; training stops "
                         "when held-out loss stops improving "
                         "(docs/strength.md recipe (c))")
    ap.add_argument("--patience", type=int, default=6,
                    help="early-stop after this many 250-step windows "
                         "without a held-out improvement")
    args = ap.parse_args()

    if not args.device:
        from tools import force_cpu  # noqa: F401  (JAX_PLATFORMS=cpu)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from fishnet_tpu.models import nnue
    from fishnet_tpu.models.train import (
        diverse_position_dataset,
        make_train_step,
    )
    from fishnet_tpu.ops.board import Board, stack_boards
    from fishnet_tpu.ops.search import MATE, search_batch_jit
    from fishnet_tpu.utils import enable_compile_cache

    enable_compile_cache()
    base = nnue.load_params(args.base)

    print(f"generating {args.samples} positions ...", flush=True)
    boards, stms, classical = diverse_position_dataset(
        args.samples, seed=args.seed
    )

    print(f"labeling with depth-{args.depth} search of the base net ...",
          flush=True)
    B = args.lanes
    labels = np.zeros(args.samples, np.float32)
    t0 = time.time()
    for off in range(0, args.samples, B):
        sl = slice(off, min(off + B, args.samples))
        n = sl.stop - sl.start
        bb = np.zeros((B, 64), np.int32)
        ss = np.zeros((B,), np.int32)
        bb[:n] = boards[sl]
        ss[:n] = stms[sl]
        roots = Board(
            board=jnp.asarray(bb), stm=jnp.asarray(ss),
            ep=jnp.full((B,), -1, jnp.int32),
            castling=jnp.full((B, 4), -1, jnp.int32),
            halfmove=jnp.zeros((B,), jnp.int32),
            extra=jnp.zeros((B, 12), jnp.int32),
        )
        # max_steps caps the worst batch: random-material monsters (200+
        # moves/node) can spend millions of lockstep steps unwinding
        # after budget exhaustion (a 200k-label run stalled ~40 min on
        # one such batch); lanes cut off report done=False and fall back
        # to their classical target below — sane labels either way
        out = search_batch_jit(
            base, roots, args.depth, args.budget, max_ply=args.depth + 2,
            max_steps=250_000,
        )
        sc = np.asarray(out["score"])[:n].astype(np.float32)
        ok = np.asarray(out["done"])[:n]
        sc = np.where(ok, sc, classical[sl].astype(np.float32))
        # mate-range backups would dominate the regression loss; clamp to
        # the same range the eval itself lives in
        labels[sl] = np.clip(sc, -3000, 3000)
        if (off // B) % 10 == 0:
            done = sl.stop
            rate = done / max(time.time() - t0, 1e-9)
            print(f"  {done}/{args.samples} ({rate:,.0f} pos/s)", flush=True)

    # recipe (b): classical-target regularizer via label blending — for
    # MSE, min over p of (p-s)^2 + L*(p-c)^2 has the same gradients as
    # (1+L) * (p - (s+L*c)/(1+L))^2, so blending IS the regularizer
    lam = args.classical_mix
    labels = (labels + lam * classical.astype(np.float32)) / (1.0 + lam)

    # recipe (c): held-out split, early stop on held-out loss (cap so a
    # tiny --samples smoke run keeps a non-empty training split)
    n_hold = min(
        max(int(args.samples * args.holdout), args.batch),
        args.samples // 2,
    )
    rng = np.random.default_rng(args.seed)
    perm = rng.permutation(args.samples)
    hold, tr = perm[:n_hold], perm[n_hold:]
    hb, hs, hl = (jnp.asarray(boards[hold]), jnp.asarray(stms[hold]),
                  jnp.asarray(labels[hold]))

    print(f"training ({len(tr)} train / {n_hold} held out, "
          f"classical mix {lam}) ...", flush=True)
    if args.warm_start:
        params = base
    else:
        params = nnue.init_params(
            jax.random.PRNGKey(args.seed), l1=base.l1, feature_set="board768"
        )
    # cosine decay: the first self-distillation attempt diverged late on
    # a flat lr (docs/strength.md) — search-backup labels are noisy
    optimizer = optax.adam(
        optax.cosine_decay_schedule(args.lr, args.steps)
    )
    opt_state = optimizer.init(params)
    step = make_train_step(optimizer)
    from fishnet_tpu.models.train import loss_fn

    val_loss = jax.jit(loss_fn)
    loss = None
    best = (float("inf"), params, -1)
    stale = 0
    for i in range(args.steps):
        idx = tr[rng.integers(0, len(tr), size=args.batch)]
        params, opt_state, loss = step(
            params, opt_state,
            jnp.asarray(boards[idx]), jnp.asarray(stms[idx]),
            jnp.asarray(labels[idx]),
        )
        if i % 250 == 0:
            v = float(val_loss(params, hb, hs, hl))
            mark = ""
            if v < best[0] - 1e-4:
                best = (v, params, i)
                stale = 0
                mark = " *"
            else:
                stale += 1
            print(f"  step {i}: loss {float(loss):.4f} "
                  f"held-out {v:.4f}{mark}", flush=True)
            if stale >= args.patience:
                print(f"  early stop at step {i} (best held-out "
                      f"{best[0]:.4f} @ step {best[2]})", flush=True)
                break
    params = best[1]
    nnue.save_params(params, args.out)
    print(f"saved {args.out} (best held-out loss {best[0]:.4f} "
          f"@ step {best[2]})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

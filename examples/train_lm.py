"""End-to-end driver: decentralized LM training with NetMax-DP.

Trains a reduced tinyllama-family model (~100M-class scaled down for CPU;
pass --scale 100m on real hardware) through the launcher's training loop
(``repro.launch.train.train``) with:
  * M worker replicas (stacked leading dim — same code path the 512-chip
    dry-run lowers),
  * the Network Monitor refreshing (P, rho) from measured round times,
  * checkpoint/restart every N rounds (kill it and rerun: it resumes).

    PYTHONPATH=src python examples/train_lm.py --rounds 60 --workers 4
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--scale", default="cpu", choices=["cpu", "100m"])
    ap.add_argument("--ckpt", default="artifacts/train_lm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--gossip", default="gather", choices=["gather", "masked_psum", "none"])
    ap.add_argument("--lr", type=float, default=0.02)
    args = ap.parse_args()

    from dataclasses import replace

    from repro.configs.base import get_arch
    from repro.launch.train import train

    base = get_arch("tinyllama-1.1b")
    if args.scale == "cpu":
        cfg = replace(
            base.reduced(), n_layers=4, d_model=128, n_heads=4, n_kv_heads=2,
            d_ff=512, vocab_size=2048, head_dim=32,
        )
        seq, bsz = 128, 8
    else:  # ~100M: tinyllama dims cut to 12 layers / 768 wide
        cfg = replace(base, n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                      d_ff=2048, vocab_size=32000, dtype="float32", remat=False)
        seq, bsz = 512, 8

    run = train(
        cfg, workers=args.workers, rounds=args.rounds, seq=seq,
        batch_per_worker=bsz, lr=args.lr,
        algo="local" if args.gossip == "none" else "netmax",
        gossip="gather" if args.gossip == "none" else args.gossip,
        ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every, log_every=5,
    )
    params = run.params

    print("\nConsensus check (replica max-deviation per leaf, should be small):")
    dev = max(
        float(jnp.abs(l - l.mean(axis=0, keepdims=True)).max())
        for l in jax.tree_util.tree_leaves(params)
    )
    print(f"  max |x_i - mean| = {dev:.5f}")


if __name__ == "__main__":
    main()

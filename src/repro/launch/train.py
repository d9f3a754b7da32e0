"""Training CLI.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
        --steps 200 --seq-len 128 --batch 8 --ckpt-dir /tmp/ck

Full-size configs target the production mesh (see dryrun.py); ``--reduced``
shrinks to a same-family config that trains on this host.
"""
from __future__ import annotations

import argparse

from repro.configs import get_config
from repro.configs.reduce import reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models import RuntimeOptions
from repro.optim import AdamWConfig
from repro.train import TrainConfig, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg, d_model=args.d_model, n_layers=args.layers)
    tcfg = TrainConfig(
        steps=args.steps, seq_len=args.seq_len, global_batch=args.batch,
        n_micro=args.n_micro, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
        optimizer=AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                              total_steps=args.steps))
    out = train(cfg, tcfg, RuntimeOptions(dtype=args.dtype))
    print(f"[train] done: steps={out['last_step']} "
          f"loss {out['losses'][0]:.3f} -> {out['final_loss']:.3f}")


if __name__ == "__main__":
    main()

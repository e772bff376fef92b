"""Training launcher.

Drives ``train_loop`` over the synthetic pipeline on one device: the CPU
(Pallas kernels in interpret mode) or one TPU chip.  It builds no mesh; the
data-parallel path over several chips goes through
``make_train_step(cfg, mesh=...)`` (see ``chip_smoke.py --chips 4``).

  python -m repro.launch.train --arch granite-3-2b --smoke --steps 20
  python -m repro.launch.train --arch bert-large --optimizer vr_lamb \
      --batch 256 --seq 128 --steps 100
"""
from __future__ import annotations

import argparse
import dataclasses
import json

from repro.configs import ARCH_MODULES, get_config, get_smoke
from repro.data import lm_batches
from repro.launch.cache import enable_compile_cache
from repro.train import train_loop


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCH_MODULES))
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--optimizer", default="")
    ap.add_argument("--lr", type=float, default=0.0)
    ap.add_argument("--k", type=int, default=0)
    ap.add_argument("--gamma", type=float, default=-1.0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.batch:
        cfg = cfg.replace(global_batch=args.batch)
    if args.seq:
        cfg = cfg.replace(seq_len=args.seq)
    opt = cfg.optimizer
    kw = {"total_steps": args.steps}
    if args.optimizer:
        kw["name"] = args.optimizer
    if args.lr:
        kw["lr"] = args.lr
    if args.k:
        kw["k"] = args.k
    if args.gamma >= 0:
        kw["gamma"] = args.gamma
    cfg = cfg.replace(optimizer=dataclasses.replace(opt, **kw))

    extra = {}
    m = cfg.model
    if m.n_image_tokens:
        extra["image"] = (m.n_image_tokens, m.d_model)
    if m.encoder is not None:
        extra["frames"] = (m.encoder.n_frames, m.d_model)
    stream = lm_batches(m.vocab_size, cfg.global_batch, cfg.seq_len, extra=extra or None)
    print(f"training {m.name} opt={cfg.optimizer.name} k={cfg.optimizer.k} "
          f"gamma={cfg.optimizer.gamma} batch={cfg.global_batch} seq={cfg.seq_len}")
    _state, hist = train_loop(
        cfg, stream, steps=args.steps, log_every=args.log_every, log_gsnr=cfg.optimizer.is_vr
    )
    if args.out:
        with open(args.out, "w") as f:
            json.dump(hist, f, indent=1)


if __name__ == "__main__":
    main()

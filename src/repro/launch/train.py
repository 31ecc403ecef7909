"""Training launcher.

Runs the paper's router-training recipe end-to-end on real devices
(CPU-scale here; the same code path lowers for the production mesh —
dryrun.py proves it).  Example:

    PYTHONPATH=src python -m repro.launch.train \
        --arch phi3-mini-3.8b --smoke --steps 100 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import numpy as np

from repro.configs import ALL_ARCHS, get_config, smoke_variant
from repro.data import mixture_iterator
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as MD
from repro.train import PretrainTrainer, RouterTrainer, checkpoint


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--pretrain-steps", type=int, default=0,
                    help="backbone pretraining steps before router "
                         "training (0 = random backbone)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="artifacts/train")
    ap.add_argument("--load", default=None,
                    help="checkpoint to initialize the backbone from")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    params = MD.init_params(jax.random.key(args.seed), cfg)
    if args.load:
        params = checkpoint.load(args.load, params)
    data = mixture_iterator(cfg.vocab_size, args.batch, args.seq,
                            seed=args.seed)

    history = {}
    if args.pretrain_steps:
        pt = PretrainTrainer(cfg, total_steps=args.pretrain_steps)
        st = pt.init(params)
        st, history["pretrain"] = pt.run(st, data, args.pretrain_steps)
        params = st["params"]

    if cfg.routable_layers() and cfg.flux.enabled:
        rt = RouterTrainer(cfg, total_steps=args.steps)
        state = rt.init(params, jax.random.key(args.seed + 1))
        state, history["router"] = rt.run(state, data, args.steps)
        params = rt.params(state)
    else:
        print(f"{cfg.name}: no routable attention layers — router "
              "training skipped (DESIGN.md §Arch-applicability)")

    os.makedirs(args.out, exist_ok=True)
    ck = os.path.join(args.out, f"{cfg.name}.msgpack")
    checkpoint.save(ck, params)
    with open(os.path.join(args.out, f"{cfg.name}_history.json"),
              "w") as f:
        json.dump(history, f, indent=2)
    print(f"saved {ck}")


if __name__ == "__main__":
    main()

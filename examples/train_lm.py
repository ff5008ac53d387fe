"""End-to-end LM training driver: an assigned-architecture family variant
trained for a few hundred steps through the full production path — sharded
train step (FSDPxTP mesh over the host devices), OAC-FAIR-k server phase,
checkpointing, loss curve.

Default is a ~15M-parameter qwen-family variant sized for a CPU container;
``--size 100m`` builds a ~100M variant (same code path, longer wall-time).

  PYTHONPATH=src python examples/train_lm.py --steps 200 --arch qwen2.5-32b
"""

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro import checkpoint
from repro.configs import ARCHS, get_config
from repro.configs.base import InputShape
from repro.data.tokens import lm_batch
from repro.launch.mesh import make_mesh
from repro.launch.steps import (OacServerConfig, init_server_state,
                                make_train_step)
from repro.models import transformer as tr
from repro.optim import make_optimizer


def sized_config(arch: str, size: str):
    cfg = get_config(arch, reduced_variant=True)
    if size == "100m":
        cfg = dataclasses.replace(
            cfg, name=cfg.name + "-100m", n_layers=8 * cfg.scan_block,
            d_model=512, n_heads=8 if cfg.n_heads else 0,
            n_kv_heads=2 if cfg.n_heads else 0,
            head_dim=64 if cfg.n_heads else 0,
            d_ff=2048 if cfg.d_ff else 0, vocab=32768)
    return cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen2.5-32b")
    ap.add_argument("--size", choices=("small", "100m"), default="small")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--rho", type=float, default=0.1)
    ap.add_argument("--noise", type=float, default=0.0,
                    help="channel noise sigma_z (scaled by 1/N_clients)")
    ap.add_argument("--no-oac", dest="oac", action="store_false",
                    default=True)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    cfg = sized_config(args.arch, args.size)
    n_dev = len(jax.devices())
    mesh = make_mesh((1, n_dev), ("data", "model"))
    shape = InputShape("custom", args.seq, args.batch, "train")
    oac = (OacServerConfig(rho=args.rho, noise_std=args.noise)
           if args.oac else None)
    bundle = make_train_step(cfg, shape, mesh, n_micro=1, oac=oac,
                             opt_name="adamw", lr=args.lr)

    params = tr.init_lm(jax.random.PRNGKey(0), cfg)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    opt = make_optimizer("adamw", args.lr)
    opt_state = opt.init(params)
    server = init_server_state(params, mesh=mesh, cfg=cfg, oac=oac)
    step_fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                      out_shardings=bundle.out_shardings)
    print(f"[train_lm] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"{args.steps} steps, batch {args.batch} x seq {args.seq}, "
          f"OAC-FAIR-k {'on (rho=%.2f)' % args.rho if args.oac else 'off'}")

    t_start = time.time()
    with mesh:
        for t in range(args.steps):
            toks, labels = lm_batch(t, args.batch, args.seq, cfg.vocab)
            batch = {"tokens": jnp.asarray(toks)[None],
                     "labels": jnp.asarray(labels)[None]}
            if cfg.family == "vlm":
                batch["embeds"] = jnp.zeros(
                    (1, args.batch, cfg.n_patches, cfg.d_model),
                    jnp.dtype(cfg.compute_dtype))
                batch["tokens"] = batch["tokens"][:, :, :args.seq
                                                  - cfg.n_patches]
                batch["labels"] = batch["labels"][:, :, :args.seq
                                                  - cfg.n_patches]
            if cfg.family == "audio":
                batch["frames"] = jnp.zeros(
                    (1, args.batch, cfg.encoder_seq, cfg.d_model),
                    jnp.dtype(cfg.compute_dtype))
            params, opt_state, server, loss = step_fn(
                params, opt_state, server, batch,
                jnp.asarray(t, jnp.int32))
            if t % 10 == 0 or t == args.steps - 1:
                print(f"  step {t:4d}  loss {float(loss):.4f}  "
                      f"({(time.time()-t_start)/(t+1):.2f}s/step)",
                      flush=True)
            if args.ckpt_dir and (t + 1) % 50 == 0:
                checkpoint.save(args.ckpt_dir, jax.device_get(params),
                                step=t + 1)
    if args.ckpt_dir:
        path = checkpoint.save(args.ckpt_dir, jax.device_get(params),
                               step=args.steps)
        print(f"[train_lm] final checkpoint: {path}")
    print("[train_lm] done")


if __name__ == "__main__":
    main()

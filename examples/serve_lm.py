"""Batched serving example: prefill a batch of prompts through the sharded
production path, then greedy-decode new tokens step by step from the KV /
SSM caches.

  PYTHONPATH=src python examples/serve_lm.py --arch mamba2-370m --tokens 32
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.configs.base import InputShape
from repro.data.tokens import lm_batch
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.models import transformer as tr


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen2.5-32b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced_variant=True)
    n_dev = len(jax.devices())
    mesh = make_mesh((1, n_dev), ("data", "model"))
    capacity = args.prompt_len + args.tokens + (cfg.n_patches or 0)
    params = tr.init_lm(jax.random.PRNGKey(0), cfg)
    prompts, _ = lm_batch(0, args.batch, args.prompt_len, cfg.vocab)
    prompts = jnp.asarray(prompts)

    serve_shape = InputShape("serve", capacity, args.batch, "decode")
    serve = make_serve_step(cfg, serve_shape, mesh)
    step_fn = jax.jit(serve.fn, in_shardings=serve.in_shardings,
                      out_shardings=serve.out_shardings)

    with mesh:
        caches = tr.init_caches(cfg, args.batch, capacity)
        t0 = time.time()
        logits, caches = tr.prefill(params, cfg, prompts, caches)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        print(f"[serve_lm] {cfg.name}: prefill {args.batch}x"
              f"{args.prompt_len} in {time.time()-t0:.2f}s")
        out_tokens = [tok]
        t0 = time.time()
        for i in range(args.tokens - 1):
            pos = jnp.asarray(args.prompt_len + i, jnp.int32)
            logits, caches = step_fn(params, caches, tok, pos)
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            out_tokens.append(tok)
        dt = time.time() - t0
        gen = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    print(f"[serve_lm] decoded {args.tokens} tokens/seq in {dt:.2f}s "
          f"({args.batch*args.tokens/dt:.1f} tok/s aggregate)")
    for b in range(min(args.batch, 2)):
        print(f"  seq {b}: {gen[b][:16].tolist()} ...")


if __name__ == "__main__":
    main()

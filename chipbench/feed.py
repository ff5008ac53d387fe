"""Weights and batches made from the run's seed, on the device.

Both the program under test and the plain reference start from what this
module makes: the weights in one jitted call with the program's parameter
shardings, and a pool of distinct token batches in another.  Nothing here
is read from the program's own initialisers: the per-leaf rules below go
by leaf name, and are plain rather than published: weights normal over
the fan-in, the SSD decay rates spread over [1, 16], biases zero (the
time-step bias too, where the published model draws it by an inverse
softplus over [1e-3, 0.1]), norm scales and skips one."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

ZERO_LEAVES = ("b", "bias", "conv_x_b", "conv_bc_b", "dt_bias")
ONE_LEAVES = ("scale", "d_skip")


def seed32(seed: int, stream: int) -> int:
    """A 32-bit key seed for one stream of a run's (up to 64-bit) seed:
    ``PRNGKey`` keeps only the low 32 bits of a larger integer."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def _leaf_name(path) -> tuple:
    return tuple(str(getattr(e, "key", getattr(e, "idx", e))) for e in path)


def _init_leaf(key, names: tuple, shape: tuple, dtype):
    leaf = names[-1]
    if leaf in ZERO_LEAVES:
        return jnp.zeros(shape, dtype)
    if leaf in ONE_LEAVES:
        return jnp.ones(shape, dtype)
    if leaf == "a_log":
        # per-head decay rates A = -exp(a_log) spread over [1, 16]
        return jnp.broadcast_to(jnp.log(jnp.linspace(1.0, 16.0, shape[-1])),
                                shape).astype(dtype)
    z = jax.random.normal(key, shape, jnp.float32)
    if leaf == "embed":
        return (0.02 * z).astype(dtype)
    if leaf.startswith("conv_"):
        return (0.1 * z).astype(dtype)
    scale = 0.5 if "out_proj" in names else 1.0
    return (scale / math.sqrt(shape[-2]) * z).astype(dtype)


def init_weights(key, abstract):
    """The parameter tree of ``abstract``'s structure, drawn from ``key``
    (trace under ``jax.jit`` with output shardings)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    leaves = [_init_leaf(jax.random.fold_in(key, i), _leaf_name(p),
                         l.shape, l.dtype)
              for i, (p, l) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def make_weights(seed: int, abstract, shardings):
    """Weights on the device, in one jitted call."""
    fn = jax.jit(init_weights, out_shardings=shardings)
    return fn(jax.random.PRNGKey(seed32(seed, 0)), abstract)


def batch_pool(seed: int, n: int, n_micro: int, micro_batch: int,
               seq_len: int, vocab: int, shardings):
    """``n`` distinct batches of uniform tokens, each shaped as the train
    step takes them: ``(n_micro, micro_batch, seq_len)`` tokens and their
    next-token labels.  One jitted call; every row differs."""
    def gen(key):
        out = []
        for i in range(n):
            toks = jax.random.randint(
                jax.random.fold_in(key, i),
                (n_micro, micro_batch, seq_len + 1), 0, vocab, jnp.int32)
            out.append({"tokens": toks[..., :-1], "labels": toks[..., 1:]})
        return out
    fn = jax.jit(gen, out_shardings=[shardings] * n)
    return fn(jax.random.PRNGKey(seed32(seed, 1)))


def delta_norms(params, seed: int, abstract) -> dict:
    """Per-leaf norms of ``params`` minus the seed's initial weights, the
    initial weights drawn afresh leaf by leaf inside one jitted call (no
    second copy of the tree is kept)."""
    def fn(p, key):
        init = init_weights(key, abstract)
        return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))
                for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(init))]
    norms = jax.jit(fn)(params, jax.random.PRNGKey(seed32(seed, 0)))
    keys = [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(abstract)[0]]
    return {k: float(n) for k, n in zip(keys, norms)}

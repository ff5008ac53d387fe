"""Pallas kernel: FSK majority-vote aggregation (prototype path, Sec. V-B).

votes (N, k) one-bit client values -> (k,) majority signs PLUS the (k,)
superposed vote energy they were detected from.  Each grid step loads a
(N, block_k) tile into VMEM, reduces over the client axis on the VPU once
and writes both outputs — the energy used to be recomputed by callers as
a second full reduction over the vote matrix (the selection score of the
one-bit route is the consensus strength |energy|), which doubled the HBM
traffic of the uplink.  N is small (clients), so the tile is tall-thin;
block_k a multiple of 128 keeps lanes full.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _sign_mv_kernel(votes_ref, out_ref, energy_ref):
    v = votes_ref[...]                            # (N, block_k)
    s = jnp.where(v >= 0, 1.0, -1.0).sum(axis=0)
    energy_ref[...] = s
    out_ref[...] = jnp.where(s >= 0, 1.0, -1.0)


def _sign_mv_noise_kernel(votes_ref, noise_ref, out_ref, energy_ref):
    """Noisy variant: channel noise perturbs the superposed FSK energy
    (the vote sum) before the sign — Sec. V-B's non-coherent detection."""
    v = votes_ref[...]                            # (N, block_k)
    s = jnp.where(v >= 0, 1.0, -1.0).sum(axis=0) + noise_ref[...]
    energy_ref[...] = s
    out_ref[...] = jnp.where(s >= 0, 1.0, -1.0)


def _sign_from_energy_kernel(energy_ref, out_ref, energy_out_ref):
    s = energy_ref[...]                           # (block_k,)
    energy_out_ref[...] = s
    out_ref[...] = jnp.where(s >= 0, 1.0, -1.0)


def _sign_from_energy_noise_kernel(energy_ref, noise_ref, out_ref,
                                   energy_out_ref):
    s = energy_ref[...] + noise_ref[...]
    energy_out_ref[...] = s
    out_ref[...] = jnp.where(s >= 0, 1.0, -1.0)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def sign_from_energy_pallas(energy: Array, noise: Optional[Array] = None,
                            block_k: int = 2048,
                            interpret: bool = False) -> Tuple[Array, Array]:
    """Majority stage only, for a PRE-REDUCED (k,) vote-energy row.

    The streaming client fold accumulates per-chunk partial vote sums into
    one (k,) buffer (the (N, k) matrix is never live); this kernel applies
    the channel-noise perturbation and the non-coherent sign detection —
    one elementwise pass, same tiling as ``sign_mv_pallas``."""
    k = energy.shape[0]
    block_k = min(block_k, k)
    if k % block_k:
        raise ValueError(f"k={k} not divisible by block_k={block_k}")
    nb = k // block_k
    vec_spec = pl.BlockSpec((block_k,), lambda i: (i,))
    kernel = (_sign_from_energy_kernel if noise is None
              else _sign_from_energy_noise_kernel)
    in_specs = [vec_spec] if noise is None else [vec_spec, vec_spec]
    args = ((energy.astype(jnp.float32),) if noise is None
            else (energy.astype(jnp.float32), noise.astype(jnp.float32)))
    signs, energy_out = pl.pallas_call(
        kernel,
        name="sign_from_energy",
        grid=(nb,),
        in_specs=in_specs,
        out_specs=[vec_spec, vec_spec],
        out_shape=[jax.ShapeDtypeStruct((k,), jnp.float32)] * 2,
        interpret=interpret,
    )(*args)
    return signs, energy_out


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def sign_mv_pallas(votes: Array, noise: Optional[Array] = None,
                   block_k: int = 2048,
                   interpret: bool = False) -> Tuple[Array, Array]:
    n, k = votes.shape
    block_k = min(block_k, k)
    if k % block_k:
        raise ValueError(f"k={k} not divisible by block_k={block_k}")
    nb = k // block_k
    vote_spec = pl.BlockSpec((n, block_k), lambda i: (0, i))
    vec_spec = pl.BlockSpec((block_k,), lambda i: (i,))
    kernel = _sign_mv_kernel if noise is None else _sign_mv_noise_kernel
    in_specs = [vote_spec] if noise is None else [vote_spec, vec_spec]
    args = ((votes.astype(jnp.float32),) if noise is None
            else (votes.astype(jnp.float32), noise.astype(jnp.float32)))
    signs, energy = pl.pallas_call(
        kernel,
        name="sign_mv",
        grid=(nb,),
        in_specs=in_specs,
        out_specs=[vec_spec, vec_spec],
        out_shape=[jax.ShapeDtypeStruct((k,), jnp.float32)] * 2,
        interpret=interpret,
    )(*args)
    return signs, energy

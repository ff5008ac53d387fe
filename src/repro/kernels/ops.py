"""Dispatching wrappers for the Pallas kernels.

On TPU the real ``pl.pallas_call`` kernels run; elsewhere (the CPU)
the kernels execute in ``interpret=True`` mode when explicitly
requested (tests) or fall through to the pure-jnp oracles in ``ref.py``
(fast XLA path, used by benchmarks and the dry-run)."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.packing import PAD_AGE
from repro.kernels import ref
from repro.kernels.aou_merge import aou_merge_pallas
from repro.kernels.block_topk import block_topk_pallas
from repro.kernels.fairk_update import (BLOCK_QUANTUM, LANES,
                                        STATS_AGE_ROW, STATS_COUNT_ROW,
                                        STATS_MAG_ROW,
                                        fairk_ef_update_pallas,
                                        fairk_stats_update_pallas)
from repro.kernels.sign_mv import sign_from_energy_pallas, sign_mv_pallas

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def block_topk(x: Array, block_size: int = 4096, m: int = 16,
               mode: Optional[str] = None) -> Tuple[Array, Array]:
    """mode: None (auto) | "pallas" | "interpret" | "ref"."""
    mode = mode or ("pallas" if _on_tpu() else "ref")
    if mode == "ref":
        return ref.block_topk_ref(x, block_size, m)
    return block_topk_pallas(x, block_size, m, interpret=(mode == "interpret"))


def aou_merge(g_new: Array, g_old: Array, age: Array, mask: Array,
              mode: Optional[str] = None) -> Tuple[Array, Array]:
    mode = mode or ("pallas" if _on_tpu() else "ref")
    if mode == "ref":
        return ref.aou_merge_ref(g_new, g_old, age, mask)
    return aou_merge_pallas(g_new, g_old, age, mask,
                            interpret=(mode == "interpret"))


def sign_mv(votes: Array, noise: Optional[Array] = None,
            mode: Optional[str] = None) -> Tuple[Array, Array]:
    """FSK majority vote over (N, k) one-bit client values ->
    ``(signs, energy)``, both (k,).

    ``noise`` (optional, (k,)) perturbs the superposed vote energy before
    the sign — the Sec. V-B channel on the one-bit uplink.  ``energy`` is
    that (noisy) superposition itself: the one-bit routes score selection
    on |energy| (consensus strength), and emitting it from the same
    reduction removes the second full pass over the (N, k) vote matrix
    callers used to pay."""
    mode = mode or ("pallas" if _on_tpu() else "ref")
    if mode == "ref":
        return ref.sign_mv_ref(votes, noise)
    # largest lane-multiple block <= 2048 that tiles k exactly — a huge
    # non-2048-aligned k (e.g. a whole packed buffer from the one-bit
    # update_phase) must NOT degenerate to a single (n, k) VMEM tile
    n, k = votes.shape
    for block in (2048, 1024, 512, 256, 128):
        if k % block == 0:
            break
    else:
        block = k
    return sign_mv_pallas(votes, noise, block_k=block,
                          interpret=(mode == "interpret"))


def sign_from_energy(energy: Array, noise: Optional[Array] = None,
                     mode: Optional[str] = None) -> Tuple[Array, Array]:
    """Majority stage of ``sign_mv`` for a PRE-REDUCED (k,) vote-energy
    row -> ``(signs, energy')``.

    The streaming client aggregation (fl/trainer.py) folds each client
    chunk's partial vote sum into one (k,) accumulator — the (N, k) vote
    matrix is never materialised — and finishes here: optional channel
    noise on the superposed energy, then the non-coherent sign."""
    mode = mode or ("pallas" if _on_tpu() else "ref")
    if mode == "ref":
        return ref.sign_from_energy_ref(energy, noise)
    k = energy.shape[0]
    for block in (2048, 1024, 512, 256, 128):
        if k % block == 0:
            break
    else:
        block = k
    return sign_from_energy_pallas(energy, noise, block_k=block,
                                   interpret=(mode == "interpret"))


def global_topk_from_candidates(vals: Array, idxs: Array, k: int
                                ) -> Tuple[Array, Array]:
    """Second stage of two-stage top-k: global top-k over the (nb, m)
    candidate pool produced by ``block_topk``.  Exact whenever every block
    contributes <= m of the true top-k."""
    flat_vals = vals.reshape(-1)
    flat_idxs = idxs.reshape(-1)
    top_vals, pos = jax.lax.top_k(flat_vals, k)
    return top_vals, flat_idxs[pos]


def two_stage_topk(x: Array, k: int, block_size: int = 4096,
                   m: Optional[int] = None, mode: Optional[str] = None
                   ) -> Tuple[Array, Array]:
    """Scalable |x| top-k: per-block candidates -> global threshold.

    ``m`` defaults to a pool ~4x oversampled relative to a uniform spread
    of the top-k across blocks (keeps the approximation error negligible;
    exactness is guaranteed when no block holds more than m winners)."""
    nb = x.shape[0] // block_size
    if m is None:
        m = min(block_size, max(4, (4 * k + nb - 1) // nb))
    vals, idxs = block_topk(x, block_size, m, mode=mode)
    return global_topk_from_candidates(vals, idxs, k)


# trace-time counter: how many fused fairk_update passes a program traces.
# The packed-server bench smoke asserts packed == 1 vs per-leaf == n_leaves.
FAIRK_UPDATE_CALLS = 0


def fairk_update(g: Array, g_prev: Array, age: Array, theta_m, theta_a,
                 mode: Optional[str] = None,
                 block_size: int = 65536,
                 sanitize: bool = False) -> Tuple[Array, Array]:
    """Fused threshold-FAIR-k server update (see kernels.fairk_update) —
    the degenerate (no residual, no decoupled fresh) case of
    ``fairk_ef_update`` below; one fused launch either way."""
    g_t, age_out, _ = fairk_ef_update(g, g_prev, age, theta_m, theta_a,
                                      mode=mode, block_size=block_size,
                                      sanitize=sanitize)
    return g_t, age_out


def fairk_ef_update(g: Array, g_prev: Array, age: Array, theta_m, theta_a,
                    residual: Optional[Array] = None,
                    fresh: Optional[Array] = None,
                    mode: Optional[str] = None,
                    block_size: int = 65536,
                    sanitize: bool = False
                    ) -> Tuple[Array, Array, Optional[Array]]:
    """Fused FAIR-k server update, optionally with the residual
    (error-feedback) stage and/or decoupled ``fresh`` values — always ONE
    pass over HBM.

    ``residual``: selection scores ``g + residual`` (unsent mass folds back
    pre-selection) and the updated accumulator ``residual' = score -
    mask * sent`` comes back as the third output (None when no residual).
    ``fresh``: merged fresh values when they differ from the score source
    (the one-bit FSK-MV sign vector from ``sign_mv``).

    Accepts any length: inputs that are not a multiple of 128 (e.g.
    arbitrary parameter leaves routed through the SelectionEngine) are
    padded to the next one (age pad = PAD_AGE sentinel, so padding can
    never select) and sliced back.  Interior pads of packed buffers (core.packing) use the same
    sentinel and pass through untouched (incl. their residual)."""
    global FAIRK_UPDATE_CALLS
    FAIRK_UPDATE_CALLS += 1
    packing.G_READS += 1
    mode = mode or ("pallas" if _on_tpu() else "ref")
    tm = jnp.asarray(theta_m, jnp.float32)
    ta = jnp.asarray(theta_a, jnp.float32)
    if mode == "ref":
        return ref.fairk_ef_update_ref(g, g_prev, age, tm, ta,
                                       residual=residual, fresh=fresh,
                                       sanitize=sanitize)
    g, g_prev, age, residual, fresh, block, d = _block_pad(
        g, g_prev, age, residual, fresh, block_size)
    g_t, age_out, res_out = fairk_ef_update_pallas(
        g, g_prev, age, tm, ta, residual=residual, fresh=fresh,
        block_size=block, interpret=(mode == "interpret"),
        sanitize=sanitize)
    if g.shape[0] != d:
        return (g_t[:d], age_out[:d],
                res_out[:d] if res_out is not None else None)
    return g_t, age_out, res_out


def _block_pad(g, g_prev, age, residual, fresh, block_size):
    """Lane-align the streams for the kernel's (d/128, 128) view and pick
    its block: ``block_size`` rounded up to the (8, 128) tile quantum, or
    the whole buffer when that is smaller.  The packed server buffers are
    already lane-aligned, so on the launch path nothing is padded (a pad
    is a full copy of every stream); odd-length leaves get < 128 pads,
    carrying the PAD_AGE sentinel so they can neither select nor count.
    A partial last block is masked inside the kernel."""
    d = g.shape[0]
    pad = -d % LANES
    if pad:
        g, g_prev = (jnp.pad(x, (0, pad)) for x in (g, g_prev))
        age = jnp.pad(age, (0, pad), constant_values=PAD_AGE)
        if residual is not None:
            residual = jnp.pad(residual, (0, pad))
        if fresh is not None:
            fresh = jnp.pad(fresh, (0, pad))
    block = min(-(-block_size // BLOCK_QUANTUM) * BLOCK_QUANTUM, d + pad)
    return g, g_prev, age, residual, fresh, block, d


def fairk_stats_update(g: Array, g_prev: Array, age: Array, theta_m,
                       theta_a, residual: Optional[Array] = None,
                       fresh: Optional[Array] = None,
                       mode: Optional[str] = None,
                       block_size: int = 65536,
                       sanitize: bool = False
                       ) -> Tuple[Array, Array, Optional[Array], dict]:
    """``fairk_ef_update`` that ALSO emits the selection statistics from
    the same pass: (g_t, age', residual' | None, stats) where stats holds
    the pad-aware exact counts ``n_sel`` / ``n_sel_m`` and the strided
    ``mag_hist`` / ``age_hist`` (bin spec: ``core.packing``) — everything
    the warm-start threshold controller consumes, with NO additional read
    of the gradient buffer (the legacy accounting paid a masked count
    pass over ``(g, residual)`` plus, on re-estimation rounds, the
    sampled-quantile bootstrap pass).

    The histogram sample stride derives from the ORIGINAL d (pre
    block-alignment padding) so kernel and ref modes sample identical
    positions; the counts are full (not sampled)."""
    global FAIRK_UPDATE_CALLS
    FAIRK_UPDATE_CALLS += 1
    packing.G_READS += 1
    mode = mode or ("pallas" if _on_tpu() else "ref")
    tm = jnp.asarray(theta_m, jnp.float32)
    ta = jnp.asarray(theta_a, jnp.float32)
    stride = packing.hist_stride(g.shape[0])
    if mode == "ref":
        return ref.fairk_stats_update_ref(g, g_prev, age, tm, ta,
                                          residual=residual, fresh=fresh,
                                          stats_stride=stride,
                                          sanitize=sanitize)
    g, g_prev, age, residual, fresh, block, d = _block_pad(
        g, g_prev, age, residual, fresh, block_size)
    g_t, age_out, res_out, tiles = fairk_stats_update_pallas(
        g, g_prev, age, tm, ta, residual=residual, fresh=fresh,
        block_size=block, interpret=(mode == "interpret"),
        stats_stride=stride, sanitize=sanitize)
    tile = tiles.sum(axis=0)               # one tiny (nb, 8, 128) reduction
    stats = {"n_sel": tile[STATS_COUNT_ROW, 0],
             "n_sel_m": tile[STATS_COUNT_ROW, 1],
             "mag_hist": tile[STATS_MAG_ROW], "age_hist": tile[STATS_AGE_ROW]}
    if g.shape[0] != d:
        return (g_t[:d], age_out[:d],
                res_out[:d] if res_out is not None else None, stats)
    return g_t, age_out, res_out, stats

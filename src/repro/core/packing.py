"""Packed server state: the whole parameter pytree as one flat buffer.

The production server phase historically ran Eq. (8)-(11) leaf by leaf:
~100+ quantile estimations and ``fairk_update`` launches per step, each an
extra HBM round-trip, with per-leaf thresholds that skew the global FAIR-k
budget toward small leaves (a 256-element norm vector gets the same rho as
the embedding table).  ``PackedLayout`` lays every leaf into ONE contiguous
lane-aligned flat buffer per server-state dtype (g f32 / g_prev bf16 / age
int8 share the same offsets), so the server phase becomes a single fused
pass over the entire model with globally consistent (theta_M, theta_A).

Layout.  Each leaf occupies ``[offset, offset + size)`` with ``pad`` dead
coordinates after it so the next leaf starts lane-aligned (multiple of
``lane``, default 256 — the fused kernel's minimum tile).  The block table
is static Python data (built from abstract shapes at trace time), so
pack/unpack lower to reshapes + concatenate / static slices — no gathers.

Padding protocol.  Pad coordinates carry ``g = 0`` and ``age = PAD_AGE``
(= -1, int8-safe).  Real ages are always >= 0, so ``age < 0`` identifies
padding everywhere downstream:

* the fused kernel (``kernels.fairk_update``) refuses to select pad
  coordinates and leaves their age at the sentinel (round-trip stable),
* threshold estimation samples only valid coordinates
  (``PackedLayout.sample_ids`` — pad zeros would bias theta_M low),
* ``n_selected`` statistics count only valid coordinates (selected
  coordinates are exactly the ``age' == 0`` ones, and padding can never
  reach age 0).

Warm-start thresholds.  ``ThresholdState`` carries last round's
(theta_M, theta_A, n_sel_m, n_sel); on steady-state rounds the engine
multiplicatively corrects the carried thresholds toward the budget instead
of re-estimating quantiles (see ``warm_corrected_thresholds``), skipping
the strided-sample quantile pass entirely.
"""

from __future__ import annotations

import dataclasses
from math import prod
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

Array = jax.Array

# Age sentinel marking pad coordinates.  Real AoU values are >= 0; -1 fits
# int8 server state and survives the f32 round-trip through the kernel.
PAD_AGE = -1.0

# Staleness clip applied by EVERY age update (the fused kernel, its ref
# oracle, core.aou, the engine's masked merge and the sweep lanes).  The
# int8 server state stores ages directly, so any increment past 127 would
# wrap NEGATIVE and collide with the PAD_AGE sentinel — corrupting both
# pad detection and the unit-bin age histogram.  120 leaves headroom for
# async lag shifts (``shift_selected_age``) to add a few rounds on top of
# an already-capped age without ever reaching the int8 edge.
AGE_CAP = 120.0

LANE = 256          # minimum alignment: the fused kernel's 1-D tile quantum

# trace-time counters: how many pack / unpack tree copies a program traces.
# The persisted-server-state smoke (benchmarks/packed_bench.py --smoke)
# asserts a steady-state round packs exactly ONE tree (the fresh grads) and
# never re-packs g_prev / age from trees — the buffers persist flat.
PACK_CALLS = 0
UNPACK_CALLS = 0

# trace-time counter: how many full read passes over the packed gradient
# buffer a program traces.  Incremented by every primitive that streams the
# whole (or a strided sample of the) gradient buffer from HBM: the fused
# ``fairk_update`` launches (kernels/ops.py), the sampled-quantile /
# order-statistic threshold estimators (core/engine.py) and the legacy
# two-pass count accounting.  The fused-statistics smoke
# (``packed_bench --smoke``) asserts a steady-state round traces exactly
# ONE such read (the kernel itself) vs 3 on the pre-fused path.
G_READS = 0


def _relayout(slot: Array, shape: Tuple[int, ...]) -> Array:
    """One leaf's 1-D slot -> the leaf's shape, as its own op.

    A rank-2+ leaf is a real relayout on the TPU (the flat buffer's
    1024-element tiles become the leaf's (8, 128) tiles).  Left to
    itself, XLA rewrites ``reshape(slice(buffer))`` into
    ``slice(reshape(buffer))`` wherever the slot's bounds are multiples of
    the leaf's minor width, relaying the WHOLE buffer once per such width
    to pick a few leaves out of it; and it hoists the consumer's
    elementwise work (AdamW's two scalings of g) above the reshape,
    relaying each scaled copy.  The barriers pin the slot, then the
    relaid leaf, so each leaf is relaid exactly once."""
    if len(shape) < 2:
        return slot.reshape(shape)
    slot = jax.lax.optimization_barrier(slot)
    return jax.lax.optimization_barrier(slot.reshape(shape))


@dataclasses.dataclass(frozen=True)
class BlockEntry:
    """One leaf's slot in the packed buffer (static metadata)."""
    index: int                  # position in the flattened leaf list
    offset: int                 # start in the packed buffer (lane-aligned)
    size: int                   # number of real coordinates
    pad: int                    # dead coordinates after the leaf
    shape: Tuple[int, ...]
    dtype: Any


class PackedLayout:
    """Static packed layout for a pytree of arrays.

    Construct once from abstract (or concrete) leaves; all methods are pure
    functions of static metadata plus their array arguments, so they are
    jit/shard_map-safe and build-once-per-trace is free.
    """

    def __init__(self, treedef, entries: List[BlockEntry], lane: int = LANE):
        self.treedef = treedef
        self.table: Tuple[BlockEntry, ...] = tuple(entries)
        self.lane = lane
        last = entries[-1] if entries else None
        self.d_packed = (last.offset + last.size + last.pad) if last else 0
        self.d_valid = sum(e.size for e in entries)
        self.n_leaves = len(entries)

    # -- construction -------------------------------------------------------

    @classmethod
    def from_tree(cls, tree: Any, lane: int = LANE) -> "PackedLayout":
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        entries, offset = [], 0
        for i, leaf in enumerate(leaves):
            size = int(prod(leaf.shape)) if leaf.shape else 1
            padded = -(-size // lane) * lane
            entries.append(BlockEntry(i, offset, size, padded - size,
                                      tuple(leaf.shape),
                                      jnp.dtype(leaf.dtype)))
            offset += padded
        return cls(treedef, entries, lane)

    # -- pack / unpack ------------------------------------------------------

    def pack(self, tree: Any, dtype=jnp.float32, fill: float = 0.0) -> Array:
        """Tree -> (d_packed,) flat buffer: ONE concatenate over reshaped
        leaves with constant fill segments interleaved at the pad slots
        (measured ~6x faster than per-leaf ``jnp.pad`` on CPU XLA — one
        write pass over the buffer either way, but pad lowers poorly)."""
        global PACK_CALLS
        PACK_CALLS += 1
        leaves = self.treedef.flatten_up_to(tree)
        parts = []
        with obs.scope("pack"):
            for e, leaf in zip(self.table, leaves):
                parts.append(jnp.asarray(leaf).reshape(-1).astype(dtype))
                if e.pad:
                    parts.append(jnp.full((e.pad,), fill, dtype))
            if len(parts) == 1:
                return parts[0]
            return jnp.concatenate(parts)

    def pack_age(self, tree: Any, dtype=jnp.float32) -> Array:
        """Age tree -> flat buffer with PAD_AGE sentinel in the pads."""
        return self.pack(tree, dtype=dtype, fill=PAD_AGE)

    def unpack(self, flat: Array, cast: bool = True) -> Any:
        """(d_packed,) buffer -> tree of original shapes (static slices).

        Each leaf's bytes move once: its slot is sliced out of the buffer
        (and cast to the leaf's dtype with ``cast``), and only then relaid
        to the leaf's shape.  ``cast=False`` keeps the buffer's dtype, so a
        consumer that widens it reads the narrow slot."""
        global UNPACK_CALLS
        UNPACK_CALLS += 1
        out = []
        with obs.scope("unpack"):
            for e in self.table:
                leaf = jax.lax.slice(flat, (e.offset,),
                                     (e.offset + e.size,))
                if cast:
                    leaf = leaf.astype(e.dtype)
                out.append(_relayout(leaf, e.shape))
        return jax.tree_util.tree_unflatten(self.treedef, out)

    # -- pad bookkeeping ----------------------------------------------------

    def valid_mask(self) -> Array:
        """(d_packed,) bool — True on real coordinates (static constant)."""
        mask = np.zeros((self.d_packed,), bool)
        for e in self.table:
            mask[e.offset:e.offset + e.size] = True
        return jnp.asarray(mask)

    def init_age(self, dtype=jnp.int8) -> Array:
        """Fresh age buffer: 0 on valid coordinates, PAD_AGE in the pads."""
        age = np.full((self.d_packed,), PAD_AGE, np.float32)
        for e in self.table:
            age[e.offset:e.offset + e.size] = 0.0
        return jnp.asarray(age).astype(dtype)

    def sample_ids(self, cap: int) -> np.ndarray:
        """Packed positions of an even strided sample over VALID coordinates
        only (static int32).  This is the pad-excluding replacement for
        ``engine.strided_sample`` on packed buffers: pad zeros in the sample
        would bias theta_M low and overshoot the budget."""
        valid = np.concatenate(
            [np.arange(e.offset, e.offset + e.size, dtype=np.int64)
             for e in self.table]) if self.table else np.zeros(0, np.int64)
        stride = max(1, self.d_valid // max(1, cap))
        return valid[::stride].astype(np.int32)


# ---------------------------------------------------------------------------
# in-kernel selection statistics: histogram spec
# ---------------------------------------------------------------------------

# The fused kernel (kernels/fairk_update.py) emits, besides the selected
# counts, two small histograms per round — the raw material for
# re-estimating (θ_M, θ_A) WITHOUT re-reading the gradient buffer:
#
#   * magnitude histogram — |score| on quarter-octave log2 bins: bin b
#     covers log2|x| in [(b + MAG_LO_OCT·MAG_BINS_PER_OCT)/MAG_BINS_PER_OCT
#     + ...), i.e. 2^-24 .. 2^8 over 128 bins.  Out-of-range magnitudes
#     clamp to the end bins.
#   * age histogram — the POST-update AoU on unit integer bins (ages are
#     integers ≤ AGE_CAP = 120 < 128, so the binning is exact).  The
#     post-update vector IS the next round's input age distribution, so a
#     θ_A estimated from it has no staleness lag; within an integer atom
#     the index jitter is sub-uniform, which is what the fractional
#     interpolation in ``hist_thresholds`` assumes.
#
# Histograms are computed on a deterministic strided sample (every
# ``hist_stride(d)``-th coordinate — the same discipline the quantile
# bootstrap uses via ``strided_sample``) with pad coordinates carrying
# weight zero.  The stride is a power of two ≤ LANE so it divides every
# lane-aligned kernel block: the per-block partial histograms then sum
# bit-exactly to the single-pass histogram the ref oracle computes.
STATS_MAG_BINS = 128
STATS_AGE_BINS = 128
MAG_BINS_PER_OCT = 4.0
MAG_LO_OCT = -24.0           # bin 0 lower edge = 2^MAG_LO_OCT
STATS_SAMPLE_CAP = 1 << 15   # target histogram sample count


def hist_stride(d: int) -> int:
    """Power-of-two sample stride ≤ LANE for a d-coordinate buffer."""
    stride = 1
    while stride < LANE and d // (2 * stride) >= STATS_SAMPLE_CAP:
        stride *= 2
    return stride


def mag_bin(mag: Array) -> Array:
    """f32 magnitude -> f32 bin index in [0, STATS_MAG_BINS) (clip before
    any integer cast: log2(0) = -inf must land in bin 0, not wrap)."""
    raw = jnp.floor(MAG_BINS_PER_OCT * jnp.log2(mag)
                    - MAG_BINS_PER_OCT * MAG_LO_OCT)
    return jnp.clip(raw, 0.0, STATS_MAG_BINS - 1)


def age_bin(age: Array) -> Array:
    """f32 age -> f32 unit bin index (exact for integer ages ≤ AGE_CAP)."""
    return jnp.clip(jnp.floor(age), 0.0, STATS_AGE_BINS - 1)


# ---------------------------------------------------------------------------
# async-aggregation age bookkeeping (double-buffered server rounds)
# ---------------------------------------------------------------------------

def shift_selected_age(age_next: Array, lag) -> Array:
    """Record async delivery lag on the just-selected coordinates.

    In async-aggregation mode a selected coordinate's contribution lands
    ``lag`` rounds after it was produced, so instead of resetting to 0 its
    post-update age is ``lag`` — i.e. the carried age buffer remembers the
    staleness the deferred uplink added.  Must be applied to the POST-merge
    age vector (where selected coordinates are exactly the ``age == 0``
    ones): unselected ages are untouched, pads (age < 0) pass through, and
    the result stays clipped at ``AGE_CAP``.  ``lag = 0`` is the identity.
    """
    a = jnp.asarray(age_next, jnp.float32)
    sel = (a == 0.0).astype(jnp.float32)
    return jnp.minimum(a + sel * jnp.asarray(lag, jnp.float32), AGE_CAP)


def shift_age_hist(age_hist: Array, lag: int) -> Array:
    """The histogram counterpart of ``shift_selected_age``: move the
    selected (bin 0) mass to bin ``lag``.  Keeps the carried/emitted age
    histogram consistent with the shifted age buffer, so θ_A re-estimation
    and the budget controller see the true post-update distribution.
    ``lag = 0`` is an exact identity."""
    if lag <= 0:
        return age_hist
    h = jnp.asarray(age_hist, jnp.float32)
    b = min(int(lag), STATS_AGE_BINS - 1)
    return h.at[b].add(h[0]).at[0].set(0.0)


def advance_age_hist(age_hist: Array) -> Array:
    """Shift EVERY bin of an age histogram up by one — the exact
    post-update histogram of a round on which no coordinate was refreshed
    (total channel outage / realised participation 0: all valid ages
    advance together).  Top-bin mass folds onto itself, mirroring the
    ``age_bin`` clip at ``STATS_AGE_BINS - 1``."""
    h = jnp.asarray(age_hist, jnp.float32)
    return jnp.zeros_like(h).at[1:].set(h[:-1]).at[-1].add(h[-1])


def _tail_cut(hist: Array, target: Array) -> Tuple[Array, Array]:
    """Where the top-``target`` mass of ``hist`` ends: (bin index int32,
    fraction of that bin taken from its top, in [0, 1])."""
    suffix = jnp.cumsum(hist[::-1])[::-1]                  # S_b = Σ_{b'>=b}
    suffix_next = jnp.concatenate([suffix[1:],
                                   jnp.zeros((1,), jnp.float32)])
    # S is non-increasing: S_b >= target holds exactly for b <= b*
    bstar = jnp.clip(jnp.sum((suffix >= target).astype(jnp.float32)) - 1.0,
                     0.0, hist.shape[0] - 1).astype(jnp.int32)
    need = target - suffix_next[bstar]
    frac = jnp.clip(need / jnp.maximum(hist[bstar], 1.0), 0.0, 1.0)
    return bstar, frac


def _hist_theta_m(mag_hist: Array, rho_m) -> Array:
    """Finite-stage θ_M from the magnitude histogram (log-linear
    interpolation inside the cut bin; empty histogram -> 0)."""
    total_m = jnp.sum(mag_hist)
    b, frac = _tail_cut(mag_hist, rho_m * total_m)
    log2_lo = (b.astype(jnp.float32)
               + MAG_LO_OCT * MAG_BINS_PER_OCT) / MAG_BINS_PER_OCT
    return jnp.where(total_m > 0.0,
                     jnp.exp2(log2_lo + (1.0 - frac) / MAG_BINS_PER_OCT),
                     0.0).astype(jnp.float32)


def _hist_theta_a(age_hist: Array, rho_a) -> Array:
    """Finite-stage θ_A from the age histogram (linear inside the unit
    atom; empty histogram -> 0)."""
    total_a = jnp.sum(age_hist)
    b, frac = _tail_cut(age_hist, rho_a * total_a)
    return jnp.where(total_a > 0.0, b.astype(jnp.float32) + 1.0 - frac,
                     0.0).astype(jnp.float32)


def hist_thresholds(mag_hist: Array, age_hist: Array, *, rho: float,
                    k_m_frac) -> Tuple[Array, Array]:
    """(θ_M, θ_A) from the in-kernel histograms — the re-estimation path
    that replaces the sampled-quantile bootstrap (zero reads of g).

    Mirrors ``engine.thresholds_from_samples``: θ_M cuts the top
    ρ·k_m_frac of the magnitude mass (log-linear interpolation inside the
    cut bin), θ_A the top ρ_A = (ρ − ρ_M)/(1 − ρ_M) of the age mass
    (linear within the unit atom — the sub-unit index jitter is what the
    threshold compares against).  An EMPTY histogram (the very first
    round: nothing has been emitted yet) yields θ = 0 for an active stage
    — a full-refresh round that transmits everything once, after which the
    realised histogram takes over.  Degenerate stage budgets give θ = inf
    exactly like the sampled path.

    ``k_m_frac`` may be a *traced* scalar (the adaptive budget
    controller): the same estimator with the degenerate-stage
    short-circuits as ``where``s on data."""
    rho_m = rho * k_m_frac
    if isinstance(rho_m, (int, float)):
        rho_a = (rho - rho_m) / max(1.0 - rho_m, 1e-6)
        theta_m = (_hist_theta_m(mag_hist, rho_m) if rho_m > 0.0
                   else jnp.float32(jnp.inf))
        theta_a = (_hist_theta_a(age_hist, rho_a) if rho_a > 0.0
                   else jnp.float32(jnp.inf))
        return theta_m, theta_a
    rho_m = jnp.asarray(rho_m, jnp.float32)
    rho_a = (rho - rho_m) / jnp.maximum(1.0 - rho_m, 1e-6)
    theta_m = jnp.where(rho_m > 0.0, _hist_theta_m(mag_hist, rho_m),
                        jnp.inf).astype(jnp.float32)
    theta_a = jnp.where(rho_a > 0.0, _hist_theta_a(age_hist, rho_a),
                        jnp.inf).astype(jnp.float32)
    return theta_m, theta_a


# ---------------------------------------------------------------------------
# warm-start threshold state
# ---------------------------------------------------------------------------

# dict-pytree threshold state: carried across rounds by trainers.
#   theta_m / theta_a : thresholds used last round
#   n_sel_m / n_sel   : last round's magnitude-stage / total selected counts
#                       (emitted by the fused kernel on the fused-stats
#                       path; a separate masked pass on the legacy path)
#   init              : 0.0 until the first round has run
#   streak            : consecutive rounds whose count tracked the budget —
#                       the engine only trusts warm thresholds after a few
#                       (cold-start cohorts fail the streak and fall back
#                       to re-estimation: sampled quantiles on the legacy
#                       path, the carried histograms on the fused path)
#   mag_hist/age_hist : last round's in-kernel histograms (zeros until a
#                       fused-stats round has emitted them)
def init_threshold_state() -> Dict[str, Array]:
    z = jnp.float32(0.0)
    return {"theta_m": z, "theta_a": z, "n_sel_m": z, "n_sel": z,
            "init": z, "streak": z,
            "mag_hist": jnp.zeros((STATS_MAG_BINS,), jnp.float32),
            "age_hist": jnp.zeros((STATS_AGE_BINS,), jnp.float32)}


THRESHOLD_STATE_FIELDS = ("theta_m", "theta_a", "n_sel_m", "n_sel",
                          "init", "streak")
THRESHOLD_STATE_SIZE = (len(THRESHOLD_STATE_FIELDS)
                        + STATS_MAG_BINS + STATS_AGE_BINS)


def threshold_state_to_vec(ts: Dict[str, Array]) -> Array:
    """(THRESHOLD_STATE_SIZE,) f32 encoding — the six scalars followed by
    the two histograms — for server-state dicts that want one array."""
    scalars = jnp.stack([ts[f] for f in THRESHOLD_STATE_FIELDS])
    return jnp.concatenate([
        scalars, ts["mag_hist"], ts["age_hist"]]).astype(jnp.float32)


def threshold_state_from_vec(vec: Array) -> Dict[str, Array]:
    ns = len(THRESHOLD_STATE_FIELDS)
    ts = {f: vec[i] for i, f in enumerate(THRESHOLD_STATE_FIELDS)}
    if vec.shape[0] >= THRESHOLD_STATE_SIZE:       # scalar-only legacy vecs
        ts["mag_hist"] = vec[ns:ns + STATS_MAG_BINS]
        ts["age_hist"] = vec[ns + STATS_MAG_BINS:THRESHOLD_STATE_SIZE]
    else:
        ts["mag_hist"] = jnp.zeros((STATS_MAG_BINS,), jnp.float32)
        ts["age_hist"] = jnp.zeros((STATS_AGE_BINS,), jnp.float32)
    return ts


# ---------------------------------------------------------------------------
# layout (de)serialisation — checkpointing the packed server buffers
# ---------------------------------------------------------------------------

def layout_to_meta(layout: "PackedLayout") -> Dict[str, Any]:
    """JSON-serialisable description of the block table (no treedef — the
    restoring process rebuilds the layout from its own param tree and
    verifies compatibility with ``layout_matches``)."""
    return {
        "lane": layout.lane,
        "d_packed": layout.d_packed,
        "d_valid": layout.d_valid,
        "entries": [[e.offset, e.size, e.pad, list(e.shape),
                     str(np.dtype(e.dtype))] for e in layout.table],
    }


def layout_matches(layout: "PackedLayout", meta: Dict[str, Any]) -> bool:
    """True when ``layout`` describes the same buffer geometry as a saved
    ``layout_to_meta`` dict (offsets, sizes, pads, shapes and dtypes)."""
    if (layout.lane != meta["lane"] or layout.d_packed != meta["d_packed"]
            or layout.d_valid != meta["d_valid"]
            or len(layout.table) != len(meta["entries"])):
        return False
    for e, m in zip(layout.table, meta["entries"]):
        if [e.offset, e.size, e.pad, list(e.shape),
                str(np.dtype(e.dtype))] != m:
            return False
    return True


def warm_corrected_thresholds(ts: Dict[str, Array], *, k: int, k_m,
                              alpha: float = 0.5, clip: float = 2.0,
                              max_age_step: float = 0.5
                              ) -> Tuple[Array, Array]:
    """Budget-tracking correction of carried thresholds (one per stage).

    Stage M (multiplicative): |g| is a smooth, scale-free distribution, so
    if last round's magnitude stage selected n_m against a budget of k_m the
    threshold moves by ``(n_m / k_m) ** alpha`` (clipped to [1/clip, clip]):
    overshoot raises theta_M (selects less), undershoot lowers it.

    Stage A (additive, bounded): integer ages make the age distribution a
    staircase — atoms of O(k_a) coordinates one age unit apart, interpolated
    only by the sub-unit index jitter.  A multiplicative step of a few
    percent at theta_A ~ 10 crosses a WHOLE atom and overshoots the budget
    by thousands (which resets the atom, re-synchronizes the distribution,
    and sustains a limit cycle).  Instead theta_A moves additively by at
    most ``max_age_step`` (< 1 atom) per round, scaled by the relative
    budget error with the stationary slope estimate of ~k_a coordinates per
    age unit.  In steady state the age histogram is stationary (inflow at
    the top equals the k_a eaten), so the fixed point is a CONSTANT
    theta_A; cold-start cohort transients exceed what a bounded step can
    track and are handled by the engine's trust region (quantile
    re-bootstrap), which is exactly the fallback the sampled path provides.

    Remark-1 degenerate stages (k_m = 0 or k_a = 0 => theta = inf) pass
    through untouched.

    ``k_m`` may be a *traced* value (the adaptive budget controller):
    the identical corrections with the degenerate-stage branches as
    ``where``s on data.
    """
    if isinstance(k_m, (int, np.integer)):
        k_a = k - k_m
        if k_m > 0:
            f_m = jnp.clip((jnp.maximum(ts["n_sel_m"], 1.0) / k_m) ** alpha,
                           1.0 / clip, clip)
            theta_m = jnp.where(jnp.isinf(ts["theta_m"]), ts["theta_m"],
                                ts["theta_m"] * f_m)
        else:
            theta_m = jnp.float32(jnp.inf)
        if k_a > 0:
            n_a = ts["n_sel"] - ts["n_sel_m"]
            step = jnp.clip((n_a - k_a) / k_a, -1.0, 1.0) * max_age_step
            theta_a = jnp.where(jnp.isinf(ts["theta_a"]), ts["theta_a"],
                                ts["theta_a"] + step)
        else:
            theta_a = jnp.float32(jnp.inf)
        return jnp.asarray(theta_m, jnp.float32), jnp.asarray(theta_a,
                                                              jnp.float32)
    k_m_f = jnp.asarray(k_m, jnp.float32)
    k_a_f = k - k_m_f
    f_m = jnp.clip((jnp.maximum(ts["n_sel_m"], 1.0)
                    / jnp.maximum(k_m_f, 1.0)) ** alpha, 1.0 / clip, clip)
    theta_m = jnp.where(
        k_m_f > 0.0,
        jnp.where(jnp.isinf(ts["theta_m"]), ts["theta_m"],
                  ts["theta_m"] * f_m),
        jnp.inf)
    n_a = ts["n_sel"] - ts["n_sel_m"]
    step = jnp.clip((n_a - k_a_f) / jnp.maximum(k_a_f, 1.0),
                    -1.0, 1.0) * max_age_step
    theta_a = jnp.where(
        k_a_f > 0.0,
        jnp.where(jnp.isinf(ts["theta_a"]), ts["theta_a"],
                  ts["theta_a"] + step),
        jnp.inf)
    return (jnp.asarray(theta_m, jnp.float32),
            jnp.asarray(theta_a, jnp.float32))

"""Pallas kernel: fused threshold-FAIR-k server update (production path).

The sharded trainer's per-shard server phase (launch.steps._leaf_server_update)
is a chain of d-length elementwise ops: magnitude mask (>= theta_M), age+
jitter mask (>= theta_A), Eq. (8) stale merge, Eq. (10) AoU update.  Left to
XLA that is ~6 HBM passes over the shard; fused it is one pass reading
(g, g_prev, age) and writing (g_t, age') — the bandwidth-bound server hot
loop at d/256 ~ 10^9 coordinates per device.

Thresholds are scalars estimated outside (sampled quantiles); the index
jitter for integer-age tie-breaking is regenerated inside the kernel from
the global coordinate index (identical to launch.steps._index_jitter).

Pad protocol (core.packing): coordinates with ``age < 0`` are padding in a
packed multi-leaf buffer.  They can never be selected (neither stage), and
their age passes through unchanged so the sentinel survives round trips —
this is what lets the packed server phase keep interior lane-alignment pads
inside the buffer across steps without them polluting the selection budget.

Residual (error-feedback) stage.  ``fairk_ef_update_pallas`` extends the
fused pass with two optional streams while staying ONE HBM round trip:

* ``residual`` — the error-feedback accumulator.  The selection score
  becomes ``score = g + residual`` (the unsent mass folds back
  pre-selection), the merged fresh value is ``score`` itself, and the
  kernel emits ``residual' = score - mask * sent`` from the same pass —
  the unsent mass on unselected coordinates, the quantization error on
  selected ones.  Pads pass their residual through unchanged.
* ``fresh`` — decoupled transmitted values for the one-bit FSK-MV route
  (kernels.sign_mv): selection scores ``g`` (+ residual) but the merged
  fresh value is ``fresh`` (the majority-vote signs).

Fused selection statistics.  ``fairk_stats_update_pallas`` additionally
emits one small per-block accumulator tile — pad-aware partial counts of
the selected (``n_sel``) and magnitude-stage (``n_sel_m``) coordinates
plus strided-sample log-magnitude / age histograms (bin spec:
``core.packing``) — reduced once over the grid after the launch.  This
makes the fused kernel the ONLY read of the gradient buffer per
steady-state server round: the counts that the warm-start controller
consumes used to be a separate masked pass over ``(g, residual)``, and
the histograms let thresholds be re-estimated without the
sampled-quantile bootstrap pass whenever the trust region trips.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.packing import (AGE_CAP, STATS_AGE_BINS, STATS_MAG_BINS,
                                age_bin, mag_bin)

Array = jax.Array

# Layout (TPU tiling).  The kernel sees every d-length stream as a 2-D
# (d / LANES, LANES) array and each grid step a (block / LANES, LANES) tile
# — f32 vregs are (8, 128), so a block must be a multiple of
# ``BLOCK_QUANTUM`` = 8 * 128 coordinates (or span the whole buffer).  d
# need not be a multiple of the block: the last grid step is a partial
# tile whose rows past d are masked out of selection and statistics (and
# whose writes past d are dropped), so no stream is ever padded and copied.
LANES = 128
BLOCK_QUANTUM = 8 * LANES

# the kernel's name in the compiled program and in a profiler trace (both
# variants: with and without the statistics tile)
KERNEL_NAME = "fairk_update"

# per-block stats tile (f32, one (STATS_ROWS, LANES) tile per grid step):
# row STATS_COUNT_ROW holds [n_sel, n_sel_m, 0, ...], rows STATS_MAG_ROW /
# STATS_AGE_ROW the strided-sample magnitude / age histograms (one bin per
# lane), the remaining rows are zero.  Whole-tile rows keep every store
# lane- and sublane-aligned.
STATS_ROWS = 8
STATS_COUNT_ROW = 0
STATS_MAG_ROW = 1
STATS_AGE_ROW = 2
assert STATS_MAG_BINS == LANES and STATS_AGE_BINS == LANES


def _strided_hists(score: Array, age_next: Array, okf: Array,
                   stride: int) -> Tuple[Array, Array]:
    """(mag_hist, age_hist), each (1, LANES), over the block positions
    ``p % stride == 0`` (``stride`` a power of two <= 2 * LANES).

    The (R, LANES) tile puts a sample at lane ``c`` of every row for each
    ``c`` in ``range(0, LANES, stride)`` (stride <= LANES), or at lane 0 of
    every ``stride / LANES``-th row.  Each sample lane is pulled out as an
    (R, 1) column by a masked lane reduction (exact: one term is the value,
    the rest are +0), its bin ids are compared against an integer lane iota
    of bin ids, and the (R, LANES) one-hot is summed over rows.  Integer
    counts < 2^24 are exact in f32 in any order, so the per-block tiles sum
    bit-exactly to the oracle's single-pass histograms."""
    rows = score.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    bin_ids = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    row_step = max(1, stride // LANES)
    w_row = (jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) % row_step
             == 0).astype(jnp.float32)
    mag = jnp.abs(score)

    def column(x, c):
        return jnp.sum(jnp.where(lane == c, x, 0.0), axis=1, keepdims=True)

    def hist(bins, w):
        hit = bins.astype(jnp.int32) == bin_ids
        return jnp.sum(jnp.where(hit, w, 0.0), axis=0, keepdims=True)

    def body(j, acc):
        c = j * min(stride, LANES)
        w = column(okf, c) * w_row
        # NaN bins (an unsanitized non-finite score) must match no bin:
        # route them to -1 before the integer cast
        m = mag_bin(column(mag, c))
        m = jnp.where(m == m, m, -1.0)
        a = age_bin(column(age_next, c))
        return (acc[0] + hist(m, w), acc[1] + hist(a, w))

    zero = jnp.zeros((1, LANES), jnp.float32)
    return jax.lax.fori_loop(0, max(1, LANES // stride), body, (zero, zero))


def _fairk_kernel(*refs, block_size: int, d: int, has_res: bool,
                  has_fresh: bool, stats_stride: int = 0,
                  sanitize: bool = False):
    """Shared fused body.  Ref order: g, [fresh], g_prev, age, [res],
    thetas -> g_t, age', [res'], [stats tile].

    ``sanitize`` (static): mask non-finite score coordinates out of BOTH
    selection stages — a corrupted or erased uplink is semantically
    "unsent": its age keeps climbing (the ordinary unselected age path),
    its residual passes through unchanged (the mass stays in EF), and it
    weighs zero in the stats tile.  Off (the default) traces the exact
    historical graph — bit-identical, not merely equivalent."""
    emit_stats = stats_stride > 0
    it = iter(refs)
    g_ref = next(it)
    fresh_ref = next(it) if has_fresh else None
    gp_ref = next(it)
    age_ref = next(it)
    res_ref = next(it) if has_res else None
    thetas_ref = next(it)
    gt_ref = next(it)
    age_out_ref = next(it)
    res_out_ref = next(it) if has_res else None
    stats_ref = next(it) if emit_stats else None

    bid = pl.program_id(0)
    theta_m = thetas_ref[0]
    theta_a = thetas_ref[1]
    g = g_ref[...].astype(jnp.float32)
    age = age_ref[...].astype(jnp.float32)
    res = res_ref[...].astype(jnp.float32) if has_res else None
    score = g + res if has_res else g
    # deterministic per-coordinate jitter in [0, 1) (Knuth hash of the
    # global index).  int32 multiply wraps like the oracle's uint32 one, so
    # the low 24 bits agree.
    shape = g.shape
    idx = (bid * block_size
           + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    jitter = ((idx * jnp.int32(2654435761 - (1 << 32))) & ((1 << 24) - 1)
              ).astype(jnp.float32) / float(1 << 24)
    valid = age >= 0.0                      # age < 0 marks packing pads
    if d % block_size:
        valid = valid & (idx < d)           # the partial last tile's tail
    if sanitize:
        # non-finite score = corrupted/erased uplink: out of selection
        # (never "sent"), zeroed in the cleaned score so 0 * NaN can't
        # leak into the merge at unselected coordinates
        ok = valid & jnp.isfinite(score)
        score = jnp.where(jnp.isfinite(score), score, 0.0)
    else:
        ok = valid
    mask_m = ok & (jnp.abs(score) >= theta_m)
    mask = mask_m | (ok & (age + jitter >= theta_a) & (~mask_m))
    maskf = mask.astype(jnp.float32)
    keep = 1.0 - maskf
    sent = fresh_ref[...].astype(jnp.float32) if has_fresh else score
    if sanitize and has_fresh:
        sent = jnp.where(jnp.isfinite(sent), sent, 0.0)
    gt_ref[...] = maskf * sent + keep * gp_ref[...].astype(jnp.float32)
    age_next = jnp.where(valid, jnp.minimum((age + 1.0) * keep, AGE_CAP),
                         age)
    age_out_ref[...] = age_next
    if has_res:
        # bad coordinates keep their OLD residual: the blocked mass stays
        # in the accumulator, exactly like an unsent coordinate's
        res_out_ref[...] = jnp.where(ok, score - maskf * sent, res)
    if emit_stats:
        # pads (and, under sanitize, corrupted coordinates) weigh zero
        mag_h, age_h = _strided_hists(score, age_next,
                                      ok.astype(jnp.float32), stats_stride)

        def total(x):
            return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0,
                           keepdims=True)

        n_sel = total(maskf)
        n_sel_m = total(mask_m.astype(jnp.float32))
        row = jax.lax.broadcasted_iota(jnp.int32, (STATS_ROWS, LANES), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (STATS_ROWS, LANES), 1)
        counts = jnp.where(lane == 0, n_sel,
                           jnp.where(lane == 1, n_sel_m, 0.0))
        stats_ref[...] = jnp.where(
            row == STATS_COUNT_ROW, counts,
            jnp.where(row == STATS_MAG_ROW, mag_h,
                      jnp.where(row == STATS_AGE_ROW, age_h, 0.0)))


@functools.partial(jax.jit,
                   static_argnames=("block_size", "interpret", "sanitize"))
def fairk_ef_update_pallas(g: Array, g_prev: Array, age: Array,
                           theta_m: Array, theta_a: Array,
                           residual: Optional[Array] = None,
                           fresh: Optional[Array] = None,
                           block_size: int = 65536,
                           interpret: bool = False,
                           sanitize: bool = False
                           ) -> Tuple[Array, Array, Optional[Array]]:
    """Fused pass with the residual (error-feedback) stage and/or decoupled
    ``fresh`` values: (g_t, age', residual' | None) — see module docstring."""
    g_t, age_out, res_out, _ = _fairk_call(
        g, g_prev, age, theta_m, theta_a, residual=residual, fresh=fresh,
        block_size=block_size, interpret=interpret, stats_stride=0,
        sanitize=sanitize)
    return g_t, age_out, res_out


@functools.partial(jax.jit,
                   static_argnames=("block_size", "interpret",
                                    "stats_stride", "sanitize"))
def fairk_stats_update_pallas(g: Array, g_prev: Array, age: Array,
                              theta_m: Array, theta_a: Array,
                              residual: Optional[Array] = None,
                              fresh: Optional[Array] = None,
                              block_size: int = 65536,
                              interpret: bool = False,
                              stats_stride: int = 1,
                              sanitize: bool = False
                              ) -> Tuple[Array, Array, Optional[Array],
                                         Array]:
    """Fused pass that also emits the per-block selection-statistics tiles:
    (g_t, age', residual' | None, stats (nb, STATS_ROWS, LANES)).  Reduce
    them with ``stats.sum(0)`` — one tiny reduction replaces the full extra
    read passes of the two-pass accounting."""
    return _fairk_call(g, g_prev, age, theta_m, theta_a, residual=residual,
                       fresh=fresh, block_size=block_size,
                       interpret=interpret, stats_stride=stats_stride,
                       sanitize=sanitize)


def _fairk_call(g, g_prev, age, theta_m, theta_a, *, residual, fresh,
                block_size, interpret, stats_stride=0, sanitize=False):
    d = g.shape[0]
    block_size = min(block_size, d)
    if d % LANES or block_size % LANES:
        raise ValueError(f"d={d} and block_size={block_size} must be "
                         f"multiples of {LANES}")
    if stats_stride and block_size % stats_stride:
        raise ValueError(f"block_size={block_size} not divisible by "
                         f"stats_stride={stats_stride}")
    nb = -(-d // block_size)
    has_res = residual is not None
    has_fresh = fresh is not None
    thetas = jnp.stack([theta_m.astype(jnp.float32),
                        theta_a.astype(jnp.float32)])
    spec = pl.BlockSpec((block_size // LANES, LANES), lambda i: (i, 0))
    kernel = functools.partial(_fairk_kernel, block_size=block_size, d=d,
                               has_res=has_res, has_fresh=has_fresh,
                               stats_stride=stats_stride, sanitize=sanitize)
    # streams enter in their stored dtypes (the persisted bf16 g_prev and
    # int8 age of the packed server state) and are widened to f32 in VMEM:
    # an f32 copy made outside would be one more d-length HBM buffer each
    tile = lambda x: x.reshape(d // LANES, LANES)
    inputs = [tile(g)]
    in_specs = [spec]
    if has_fresh:
        inputs.append(tile(fresh))
        in_specs.append(spec)
    inputs += [tile(g_prev), tile(age)]
    in_specs += [spec, spec]
    if has_res:
        inputs.append(tile(residual))
        in_specs.append(spec)
    inputs.append(thetas)
    in_specs.append(pl.BlockSpec((2,), lambda i: (0,)))
    out_specs = [spec] * (3 if has_res else 2)
    out_shape = [jax.ShapeDtypeStruct((d // LANES, LANES), jnp.float32)
                 ] * len(out_specs)
    if stats_stride:
        out_specs.append(pl.BlockSpec((pl.squeezed, STATS_ROWS, LANES),
                                      lambda i: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((nb, STATS_ROWS, LANES),
                                              jnp.float32))
    out = pl.pallas_call(
        kernel,
        name=KERNEL_NAME,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*inputs)
    flat = lambda x: x.reshape(d)
    res_out = flat(out[2]) if has_res else None
    stats = out[-1] if stats_stride else None
    return flat(out[0]), flat(out[1]), res_out, stats

"""Unified SelectionEngine: one API over the three FAIR-k execution paths.

The paper's selection rule (Eq. 11) and server update (Eq. 8-10) exist at
three operating points in this repo, historically implemented three times:

* ``exact``      — index-form ``lax.top_k`` policies (``core.selection``),
  the paper-faithful simulation path.  Exact budget (k indices), supports
  all six policies, cost O(d log d) — fine to d ~ 1e7.
* ``threshold``  — sampled-quantile thresholds θ_M / θ_A plus the fused
  ``fairk_update`` Pallas kernel: one HBM pass over (g, g_prev, age), no
  sort.  Approximate budget (|selected| ≈ k), FAIR-k-family policies only,
  the d ~ 1e8-1e9 single-device production route.
* ``sharded``    — the threshold math inside ``shard_map``: every device
  updates its local shard with locally estimated thresholds, zero extra
  collectives.  The multi-device production route (launch.steps).

``SelectionEngine`` puts all three behind ``select_and_merge(g, g_prev,
age)`` -> ``(g_t, age', stats)`` so trainers, benchmarks and tests can swap
backends without touching call sites, and so cross-backend parity is
testable (see tests/test_engine.py): with ``exact_theta=True`` the
threshold/sharded backends compute order-statistic thresholds that select
*identical* coordinates to ``exact`` on tie-free inputs.

Semantics (all backends):
  selection scores the first argument ``g`` (the production server scores
  the fresh aggregate; the paper's trainer scores g_{t-1} — pass whichever
  the algorithm calls for), fresh values come from ``g``, stale values from
  ``g_prev``, and the AoU vector advances by Eq. (10) capped at
  ``AGE_CAP`` (the fused kernel's staleness clip).

Error feedback & one-bit (all backends, not just exact):
  ``select_and_merge(..., residual=...)`` folds the error-feedback
  accumulator back pre-selection — the score and the transmitted values
  become ``g + residual`` — and returns the updated accumulator in
  ``stats["residual"]`` (unsent mass on unselected coordinates,
  quantization error on selected ones).  On the threshold/packed backends
  the residual stage rides the SAME fused kernel pass
  (``kernels.fairk_ef_update``).  ``fresh=...`` decouples the transmitted
  values from the score source — the one-bit FSK-MV route passes the
  majority-vote sign vector (``kernels.sign_mv``) as ``fresh`` while
  scoring the vote energy.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import packing, selection

Array = jax.Array

BACKENDS = ("exact", "threshold", "sharded", "packed")

# FAIR-k-family policies expressible as (θ_M, θ_A) thresholds; the other
# three (toprand / agetopk / randk) need index arithmetic -> exact only.
THRESHOLD_POLICIES = ("fairk", "topk", "roundrobin")

# staleness clip baked into the fused kernel (kernels/fairk_update.py);
# canonical definition lives next to the int8/pad protocol in
# core.packing — re-exported here because every trainer imports it from
# the engine
AGE_CAP = packing.AGE_CAP


# ---------------------------------------------------------------------------
# threshold building blocks (promoted from launch/steps.py)
# ---------------------------------------------------------------------------

def jitter_from_ids(ids) -> Array:
    """Deterministic per-coordinate jitter in [0, 1): Knuth hash of the
    coordinate index.  THE canonical host-side formula — must stay
    bit-identical to the in-kernel recomputation in kernels/fairk_update.py
    and its oracle in kernels/ref.py (tie-break parity depends on it)."""
    u = jnp.asarray(ids).astype(jnp.uint32)
    return (u * jnp.uint32(2654435761) % jnp.uint32(1 << 24)
            ).astype(jnp.float32) / float(1 << 24)


def index_jitter(n: int, offset=0) -> Array:
    """Jitter for coordinates [offset, offset + n) — breaks integer-age
    ties without an extra input.  ``offset`` (static or traced) is the
    global index of the first local coordinate, so shards hash the same ids
    as the unsharded path."""
    return jitter_from_ids(jax.lax.iota(jnp.uint32, n)
                           + jnp.asarray(offset, jnp.uint32))


def strided_sample(x: Array, cap: int) -> Array:
    n = x.shape[0]
    stride = max(1, n // cap)
    return x[::stride]


def thresholds_from_samples(mag_s: Array, age_eff_s: Array, *, rho: float,
                            k_m_frac) -> Tuple[Array, Array]:
    """(θ_M, θ_A) quantiles from pre-drawn samples of |g| and jittered age.

    θ_M ≈ the (1 − ρ·k_m_frac) quantile of |g|; θ_A sizes the age stage to
    the residual budget over the whole vector (the complement correction is
    the (1 − ρ_M) denominator).  ``k_m_frac`` may be a traced scalar (the
    adaptive-budget controller, core/controller.py): the degenerate-stage
    short-circuits then become ``where``s on quantiles computed either
    way — same values, data-dependent instead of trace-dependent."""
    rho_m = rho * k_m_frac
    if isinstance(rho_m, (int, float)):
        rho_a = (rho - rho_m) / max(1.0 - rho_m, 1e-6)
        theta_m = (jnp.quantile(mag_s, 1.0 - rho_m)
                   if rho_m > 0.0 else jnp.float32(jnp.inf))
        theta_a = (jnp.quantile(age_eff_s, 1.0 - rho_a)
                   if rho_a > 0.0 else jnp.float32(jnp.inf))
        return theta_m.astype(jnp.float32), theta_a.astype(jnp.float32)
    rho_m = jnp.asarray(rho_m, jnp.float32)
    rho_a = (rho - rho_m) / jnp.maximum(1.0 - rho_m, 1e-6)
    theta_m = jnp.where(rho_m > 0.0,
                        jnp.quantile(mag_s, jnp.clip(1.0 - rho_m, 0.0, 1.0)),
                        jnp.inf)
    theta_a = jnp.where(rho_a > 0.0,
                        jnp.quantile(age_eff_s,
                                     jnp.clip(1.0 - rho_a, 0.0, 1.0)),
                        jnp.inf)
    return theta_m.astype(jnp.float32), theta_a.astype(jnp.float32)


def sampled_thresholds(g: Array, age: Array, *, rho: float, k_m_frac,
                       sample_cap: int,
                       sample_ids: Optional[Array] = None,
                       residual: Optional[Array] = None,
                       sanitize: bool = False
                       ) -> Tuple[Array, Array]:
    """(θ_M, θ_A) from strided-sample quantiles (no global sort).

    This is a read pass over (a strided sample of) the gradient buffer —
    on the fused-stats path it is replaced by ``packing.hist_thresholds``
    over the kernel-emitted histograms, and the trace counter below is
    what ``packed_bench --smoke`` uses to prove the replacement.

    ``sample_ids`` (static int32 positions, e.g. ``PackedLayout.sample_ids``)
    restricts the sample to those coordinates — REQUIRED on packed buffers,
    where pad zeros in the sample would bias θ_M low (jitter still hashes
    the true buffer positions so ties break identically to the kernel).

    ``residual`` (error feedback) folds into the magnitude statistic:
    θ_M is estimated on ``|g + residual|`` — the residual is sampled at the
    same positions and added sample-wise, so no d-length effective-gradient
    temp is materialised for the estimate.

    ``sanitize`` (static) demotes non-finite sample scores to magnitude 0
    and age −1 — they land at the bottom of both order statistics, so a
    corrupted coordinate can only *tighten* the estimated thresholds,
    never poison them with NaN (a single NaN sample makes
    ``jnp.quantile`` return NaN, which would zero the entire round)."""
    packing.G_READS += 1
    age32 = age.astype(jnp.float32)
    if sample_ids is None:
        g_s = strided_sample(g.astype(jnp.float32), sample_cap)
        if residual is not None:
            g_s = g_s + strided_sample(residual.astype(jnp.float32),
                                       sample_cap)
        age_s = strided_sample(age32 + index_jitter(g.shape[0]), sample_cap)
    else:
        ids = jnp.asarray(sample_ids)
        g_s = g[ids].astype(jnp.float32)
        if residual is not None:
            g_s = g_s + residual[ids].astype(jnp.float32)
        age_s = age32[ids] + jitter_from_ids(ids)
    if sanitize:
        fin_s = jnp.isfinite(g_s)
        g_s = jnp.where(fin_s, g_s, 0.0)
        age_s = jnp.where(fin_s, age_s, -1.0)
    return thresholds_from_samples(jnp.abs(g_s), age_s, rho=rho,
                                   k_m_frac=k_m_frac)


def exact_thresholds(g: Array, age: Array, *, k: int, k_m: int,
                     sanitize: bool = False) -> Tuple[Array, Array]:
    """Order-statistic (θ_M, θ_A) that reproduce exact FAIR-k on tie-free
    inputs: θ_M sits strictly between the k_m-th and (k_m+1)-th largest
    |g|, θ_A between the k_a-th and (k_a+1)-th largest jittered age *among
    the magnitude-stage complement*.  O(d log d) — parity/testing path.
    ``sanitize`` demotes non-finite scores to magnitude −1 / age −inf so
    they rank below every real coordinate in both stages."""
    packing.G_READS += 1
    d = g.shape[0]
    k_a = k - k_m
    g32 = g.astype(jnp.float32)
    mag = jnp.abs(g32)
    fin = None
    if sanitize:
        fin = jnp.isfinite(g32)
        mag = jnp.where(fin, mag, -1.0)
    if k_m == 0:
        theta_m = jnp.float32(jnp.inf)
        mask_m = jnp.zeros((d,), bool)
    else:
        vals = jax.lax.top_k(mag, min(k_m + 1, d))[0]
        edge = vals[-1] if k_m >= d else vals[k_m]
        theta_m = (vals[k_m - 1] + edge) / 2.0
        mask_m = mag >= theta_m
    if k_a == 0:
        return theta_m, jnp.float32(jnp.inf)
    age_eff = age.astype(jnp.float32) + index_jitter(d)
    rest = jnp.where(mask_m, -jnp.inf, age_eff)
    if fin is not None:
        rest = jnp.where(fin, rest, -jnp.inf)
    vals = jax.lax.top_k(rest, min(k_a + 1, d))[0]
    edge = vals[-1] if k_a >= d else vals[k_a]
    theta_a = (vals[k_a - 1] + edge) / 2.0
    return theta_m, theta_a


def exact_thresholds_dynamic(g: Array, age: Array, *, k: int, k_m,
                             sanitize: bool = False
                             ) -> Tuple[Array, Array]:
    """``exact_thresholds`` with a *traced* magnitude budget ``k_m``
    (int32 in [0, k]; ``k`` stays static — the adaptive controller only
    moves the split).  Identical thresholds to the static version at the
    same ``k_m``: both read the midpoints between the ranked order
    statistics, here gathered at a dynamic rank out of one static
    ``top_k(·, k + 1)`` whose leading values match the static call's."""
    packing.G_READS += 1
    d = g.shape[0]
    kk = min(k + 1, d)
    km = jnp.clip(jnp.asarray(k_m, jnp.int32), 0, k)
    g32 = g.astype(jnp.float32)
    mag = jnp.abs(g32)
    fin = None
    if sanitize:
        fin = jnp.isfinite(g32)
        mag = jnp.where(fin, mag, -1.0)
    vals = jax.lax.top_k(mag, kk)[0]
    hi = vals[jnp.maximum(km - 1, 0)]
    edge = vals[jnp.minimum(km, kk - 1)]
    theta_m = jnp.where(km == 0, jnp.inf, (hi + edge) / 2.0
                        ).astype(jnp.float32)
    mask_m = mag >= theta_m
    k_a = k - km
    age_eff = age.astype(jnp.float32) + index_jitter(d)
    rest = jnp.where(mask_m, -jnp.inf, age_eff)
    if fin is not None:
        rest = jnp.where(fin, rest, -jnp.inf)
    avals = jax.lax.top_k(rest, kk)[0]
    ahi = avals[jnp.maximum(k_a - 1, 0)]
    aedge = avals[jnp.minimum(k_a, kk - 1)]
    theta_a = jnp.where(k_a == 0, jnp.inf, (ahi + aedge) / 2.0
                        ).astype(jnp.float32)
    return theta_m, theta_a


# ---------------------------------------------------------------------------
# rank-based FAIR-k: the traced-k_m mask form (shared with fl/sweep.py)
# ---------------------------------------------------------------------------

def rank_desc(x: Array) -> Array:
    """rank[i] = number of entries strictly ranked above x[i] (descending,
    ties toward lower index — matching ``lax.top_k``)."""
    d = x.shape[0]
    order = jnp.argsort(-x, stable=True)
    return jnp.zeros((d,), jnp.int32).at[order].set(
        jnp.arange(d, dtype=jnp.int32))


def fair_k_masks_dynamic(score: Array, age: Array, k: int, k_m
                         ) -> Tuple[Array, Array]:
    """Rank-based FAIR-k (Eq. 11) with a *traced* magnitude budget ``k_m``:
    (mask, mask_m) float32, exactly ``k`` ones in ``mask``.  The exact
    index policies concatenate top-k vectors of static lengths, so a
    traced split selects by rank instead —

        mask_M = rank(score)        < k_m
        mask_A = rank(age ⊙ ¬mask_M) < k − k_m

    — the identical coordinate set (rank and top-k agree on tie-free
    inputs; ties break toward lower index in both).  ``score`` is the
    magnitude-stage statistic (|g| for FAIR-k, random for Rand-k)."""
    mask_m = rank_desc(score) < k_m
    # age stage on the complement; -1 can never win (ages are >= 0) and
    # the index tie-break mirrors lax.top_k via the stable argsort
    age_rest = jnp.where(mask_m, -1.0, age.astype(jnp.float32))
    mask_a = rank_desc(age_rest) < (k - k_m)
    return ((mask_m | mask_a).astype(jnp.float32),
            mask_m.astype(jnp.float32))


def fair_k_mask_dynamic(score: Array, age: Array, k: int, k_m) -> Array:
    """The combined FAIR-k mask of ``fair_k_masks_dynamic`` (the form the
    vmapped sweep grid consumes)."""
    return fair_k_masks_dynamic(score, age, k, k_m)[0]


def traced_km(k: int, k_m_frac) -> Array:
    """``k_m = round(k_m_frac · k)`` as traced int32 — THE rounding/clip
    convention of the traced-split stack (the engine backends, the FL
    trainer's exact-adaptive route and the sweep lanes all call this one
    function, so the bit-exact traced==static parity can never drift)."""
    return jnp.round(jnp.clip(jnp.asarray(k_m_frac, jnp.float32),
                              0.0, 1.0) * k).astype(jnp.int32)


def threshold_mask(g: Array, age: Array, theta_m: Array, theta_a: Array,
                   index_offset=0) -> Tuple[Array, Array]:
    """Dense float32 (mask, mask_m) for the two-stage threshold rule —
    the jnp mirror of the fused kernel's in-register mask.  When applied to
    a shard, pass the shard's global start index as ``index_offset`` so the
    age jitter matches the unsharded selection."""
    mag = jnp.abs(g.astype(jnp.float32))
    mask_m = mag >= theta_m
    age_eff = age.astype(jnp.float32) + index_jitter(g.shape[0],
                                                     index_offset)
    mask_a = (age_eff >= theta_a) & (~mask_m)
    return (mask_m | mask_a).astype(jnp.float32), mask_m.astype(jnp.float32)


def eff_score(g: Array, residual: Optional[Array]) -> Array:
    """The error-feedback fold ``score = g + residual`` in f32 — THE
    formula the fused kernel recomputes per block (kernels/fairk_update.py);
    every host-side use must stay bit-identical to it."""
    g32 = g.astype(jnp.float32)
    return g32 if residual is None else g32 + residual.astype(jnp.float32)


def masked_merge(fresh: Array, g_prev: Array, age: Array, mask: Array
                 ) -> Tuple[Array, Array]:
    """Eq. (8) stale merge + Eq. (10) AoU update (mask form, f32 out)."""
    keep = 1.0 - mask
    g_t = mask * fresh.astype(jnp.float32) + keep * g_prev.astype(jnp.float32)
    age_next = jnp.minimum((age.astype(jnp.float32) + 1.0) * keep, AGE_CAP)
    return g_t, age_next


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Backend-independent FAIR-k settings.

    Budgets derive from (rho, k_m_frac, r_frac) unless (k, k_m, r) are
    given explicitly.  ``exact_theta`` switches the threshold/sharded
    backends from sampled quantiles to order-statistic thresholds (parity
    mode); ``global_thresholds`` makes the sharded backend estimate one
    (θ_M, θ_A) pair on the full vector instead of per shard."""
    policy: str = "fairk"
    backend: str = "exact"
    rho: float = 0.1
    k_m_frac: float = 0.75
    r_frac: float = 1.5                  # AgeTop-k candidate ratio r / k
    k: Optional[int] = None
    k_m: Optional[int] = None
    r: Optional[int] = None
    sample_cap: int = 65536              # quantile sample size
    exact_theta: bool = False
    global_thresholds: bool = False
    noise_std: float = 0.0               # channel noise on fresh coords
    n_clients: int = 1                   # N in Eq. (7) (noise / N scaling)
    kernel_mode: Optional[str] = None    # None auto | pallas | interpret | ref
    # -- fused selection statistics -----------------------------------------
    # Emit n_sel / n_sel_m and the magnitude/age histograms from INSIDE the
    # fused kernel (ops.fairk_stats_update) instead of recomputing them as
    # extra read passes, and — with warm_start — re-estimate thresholds
    # from the carried histograms (packing.hist_thresholds) instead of the
    # sampled-quantile bootstrap whenever the trust region trips.  The
    # fused kernel becomes the ONLY read of the gradient buffer per round;
    # the very first round (no histogram yet) transmits everything once
    # (θ = 0) and self-heals from the realised statistics.  Off by default:
    # the legacy two-pass accounting bootstraps from the CURRENT round's
    # quantiles, which round-0-sensitive callers may prefer.
    fused_stats: bool = False
    # -- packed backend only ------------------------------------------------
    warm_start: bool = False             # carry (θ, counts) across rounds and
                                         # skip the quantile pass when warm
    warm_alpha: float = 0.5              # budget-correction exponent
    warm_clip: float = 2.0               # per-round correction factor bound
    warm_tol: float = 0.25               # trust region: re-run the quantile
                                         # pass when |n_sel - k| > tol * k
    warm_streak: int = 3                 # on-track rounds required before
                                         # carried thresholds are trusted
    # psum/pmean axes for threshold + count reduction when the packed path
    # runs inside shard_map (launch.steps): one tiny scalar collective makes
    # (θ_M, θ_A) globally consistent across shards
    reduce_axes: Tuple[str, ...] = ()


class SelectionEngine:
    """One ``select_and_merge`` over the exact / threshold / sharded paths.

    Construct once per (d, config); all methods are pure jit-compatible
    functions of their array arguments.  ``mesh`` is only required for the
    sharded backend (the flat vector is sharded across *all* mesh axes);
    ``layout`` (a ``core.packing.PackedLayout``) only for the packed backend,
    whose buffers are ``(layout.d_packed,)`` with budgets drawn against the
    ``layout.d_valid`` real coordinates."""

    def __init__(self, cfg: EngineConfig, d: int, mesh=None,
                 layout: Optional[packing.PackedLayout] = None):
        if cfg.backend not in BACKENDS:
            raise ValueError(f"unknown backend {cfg.backend!r}; "
                             f"choose from {BACKENDS}")
        if cfg.policy not in selection.POLICIES:
            raise ValueError(f"unknown policy {cfg.policy!r}; "
                             f"choose from {selection.POLICIES}")
        if cfg.backend != "exact" and cfg.policy not in THRESHOLD_POLICIES:
            raise ValueError(
                f"policy {cfg.policy!r} needs index arithmetic — only "
                f"{THRESHOLD_POLICIES} run on the {cfg.backend!r} backend")
        if cfg.backend == "sharded":
            if mesh is None:
                raise ValueError("sharded backend needs a mesh")
            n_dev = _mesh_size(mesh)
            if d % n_dev:
                raise ValueError(f"d={d} not divisible by {n_dev} devices")
        if cfg.backend == "packed":
            if layout is None:
                raise ValueError("packed backend needs a PackedLayout")
            if d != layout.d_packed:
                raise ValueError(f"d={d} != layout.d_packed="
                                 f"{layout.d_packed}")
        self.cfg = cfg
        self.d = d
        self.mesh = mesh
        self.layout = layout
        # budgets target the REAL coordinates (pads are dead weight)
        self.d_budget = layout.d_valid if layout is not None else d
        self._sample_ids = (jnp.asarray(layout.sample_ids(cfg.sample_cap))
                            if layout is not None else None)

    # -- budgets ------------------------------------------------------------

    def budgets(self) -> Tuple[int, int, int]:
        """(k, k_M, r) with the Remark-1 policy specialisations applied."""
        cfg = self.cfg
        k = (cfg.k if cfg.k is not None
             else max(2, round(cfg.rho * self.d_budget)))
        k_m = (cfg.k_m if cfg.k_m is not None
               else int(round(cfg.k_m_frac * k)))
        if cfg.policy == "topk":
            k_m = k
        if cfg.policy == "roundrobin":
            k_m = 0
        r = cfg.r if cfg.r is not None else max(k, round(cfg.r_frac * k))
        return k, k_m, r

    def _rho_parts(self) -> Tuple[float, float]:
        k, k_m, _ = self.budgets()
        return k / self.d_budget, (k_m / k if k else 0.0)

    def _km_traced(self, k_m_frac) -> Array:
        """Traced magnitude budget: ``k`` stays static (the controller
        only moves the split), ``k_m = round(k_m_frac · k)`` rides as
        int32 data — changing it can never trigger a recompile."""
        return traced_km(self.budgets()[0], k_m_frac)

    def _km_frac_eff(self, km: Array) -> Array:
        """The realised split ``k_m/k`` of a traced budget — mirrors the
        static ``_rho_parts`` rounding so traced and static runs at the
        same nominal fraction derive identical thresholds."""
        k, _, _ = self.budgets()
        return km.astype(jnp.float32) / k if k else jnp.float32(0.0)

    # -- selection ----------------------------------------------------------

    def select(self, key: Optional[Array], g: Array, age: Array) -> Array:
        """Exact index-form selection (all six policies): (k,) int32."""
        k, k_m, r = self.budgets()
        if key is None:
            if self.cfg.policy in ("toprand", "randk"):
                raise ValueError(f"policy {self.cfg.policy!r} needs a PRNG key")
            key = jax.random.PRNGKey(0)
        return selection.select_indices(self.cfg.policy, key, g, age,
                                        k=k, k_m=k_m, r=r)

    def thresholds(self, g: Array, age: Array,
                   residual: Optional[Array] = None,
                   k_m_frac=None, sanitize: bool = False
                   ) -> Tuple[Array, Array]:
        """(θ_M, θ_A) per config (order-statistic or sampled-quantile).
        ``residual`` folds into the magnitude statistic (score = g + res);
        ``k_m_frac`` (optional traced scalar) overrides the static split;
        ``sanitize`` keeps non-finite scores out of both estimates."""
        k, k_m, _ = self.budgets()
        if k_m_frac is None:
            if self.cfg.exact_theta:
                return exact_thresholds(eff_score(g, residual), age,
                                        k=k, k_m=k_m, sanitize=sanitize)
            rho, km_frac = self._rho_parts()
            return sampled_thresholds(g, age, rho=rho, k_m_frac=km_frac,
                                      sample_cap=self.cfg.sample_cap,
                                      residual=residual, sanitize=sanitize)
        km = self._km_traced(k_m_frac)
        if self.cfg.exact_theta:
            return exact_thresholds_dynamic(eff_score(g, residual), age,
                                            k=k, k_m=km, sanitize=sanitize)
        rho, _ = self._rho_parts()
        return sampled_thresholds(g, age, rho=rho,
                                  k_m_frac=self._km_frac_eff(km),
                                  sample_cap=self.cfg.sample_cap,
                                  residual=residual, sanitize=sanitize)

    # -- fused server phase -------------------------------------------------

    def select_and_merge(self, g: Array, g_prev: Array, age: Array, *,
                         key: Optional[Array] = None,
                         tstate: Optional[Dict[str, Array]] = None,
                         residual: Optional[Array] = None,
                         fresh: Optional[Array] = None,
                         k_m_frac=None,
                         age_lag: Optional[int] = None,
                         erase: Optional[Array] = None,
                         sanitize: bool = False
                         ) -> Tuple[Array, Array, Dict[str, Any]]:
        """One server phase: select on ``g``, merge fresh ``g`` over stale
        ``g_prev`` (Eq. 8), advance AoU (Eq. 10).  Returns f32
        ``(g_t, age', stats)``; stats holds the selection artefacts
        (count, thresholds, and — exact backend — the index vector).

        ``tstate`` (packed backend with ``warm_start=True`` only) is the
        carried threshold state from ``packing.init_threshold_state``; the
        successor state is returned in ``stats["tstate"]``.

        ``residual`` (error feedback, any backend): the accumulator folds
        back pre-selection — score and transmitted values become
        ``g + residual`` — and ``stats["residual"]`` carries the successor
        ``score - mask * sent`` (on the threshold/packed backends this is
        a pad-aware stage of the same fused kernel pass).

        ``fresh`` (one-bit FSK-MV, exact/threshold/packed): transmitted
        values when they differ from the score source — pass the
        ``kernels.sign_mv`` majority-vote signs while scoring the vote
        energy in ``g``.

        With ``fused_stats=True`` the stats additionally carry
        ``n_sel_m`` and the ``mag_hist`` / ``age_hist`` selection
        histograms on every backend (emitted by the fused kernel on
        threshold/packed, psum'd per-shard partials on sharded, jnp on
        exact), and ``tstate`` is honoured by the sharded backend too —
        its per-shard thresholds then warm-start from last round's
        reduced statistics instead of bootstrapping every round.

        ``k_m_frac`` (optional, any backend): a *traced* magnitude split
        overriding the static ``cfg.k_m_frac`` — the adaptive budget
        controller (core/controller.py) feeds its live split through
        here.  ``k`` stays static; only the stage split rides as data, so
        per-round ``k_m_frac`` changes never recompile.  FAIR-k only (the
        Remark-1 policies pin the split; the other three need index
        arithmetic with static stage sizes).

        ``age_lag`` (optional STATIC int, any backend): async-aggregation
        staleness accounting.  The just-selected coordinates' post-update
        age becomes ``age_lag`` instead of 0 (their deferred OAC
        contribution lands that many rounds late —
        ``packing.shift_selected_age``), and the emitted/carried age
        histogram is shifted to match, so θ_A re-estimation and the
        budget controller observe the true distribution.  Counts, noise
        masking and the returned ``stats["sel_mask"]`` (added only in
        this mode — the ``age' == 0`` convention no longer identifies the
        selected set downstream) all use the PRE-shift selection.
        ``age_lag in (None, 0)`` traces the unchanged synchronous
        program — bit-exact with today's trajectory.

        ``sanitize`` (STATIC bool, any backend): graceful degradation
        under fault injection (core/faults.py).  Non-finite score
        coordinates are excluded from BOTH selection stages — they are
        semantically "unsent": the merge keeps the stale value, age keeps
        climbing, the error-feedback residual passes through unchanged,
        and the emitted statistics (counts + histograms) never see them.
        ``sanitize=False`` (the default) traces the historical program
        bit-exactly — the guard predicate IS the pad-validity predicate,
        so off-mode costs nothing.

        ``erase`` (optional float mask, requires ``sanitize=True``):
        deep-fade block erasures on the aggregated OAC signal.  Erased
        coordinates (``erase > 0``) are demoted to NaN *before* selection
        so the sanitize stage treats them exactly like corrupted
        gradients — one degradation path for both fault channels.  Fold
        round outages (realised participation ``N_t == 0``) in with
        ``faults.erase_with_outage``: a fully-erased round degrades to
        the age-increment-only no-op round."""
        if age_lag is not None:
            if int(age_lag) < 0:
                raise ValueError(f"age_lag must be >= 0, got {age_lag}")
            age_lag = int(age_lag) or None        # 0 == synchronous
        if g.shape != (self.d,):
            raise ValueError(f"expected shape ({self.d},), got {g.shape}")
        if self.cfg.noise_std > 0.0 and key is None:
            raise ValueError("noise_std > 0 needs a PRNG key (identical "
                             "noise every round is not a channel)")
        if k_m_frac is not None and self.cfg.policy != "fairk":
            raise ValueError(
                f"traced k_m_frac adapts the FAIR-k split only — policy "
                f"{self.cfg.policy!r} pins or ignores it")
        if erase is not None and not sanitize:
            raise ValueError("erase needs sanitize=True — erased "
                             "coordinates degrade through the NaN path")
        if sanitize and self.cfg.policy not in THRESHOLD_POLICIES:
            raise ValueError(
                f"sanitize runs selection in threshold/rank form — policy "
                f"{self.cfg.policy!r} needs index arithmetic; choose from "
                f"{THRESHOLD_POLICIES}")
        with obs.scope("fairk"):
            if erase is not None:
                # one degradation path for both fault channels: erased
                # coordinates become NaN scores and ride the sanitize stage
                g = jnp.where(jnp.asarray(erase) > 0.0, jnp.float32(jnp.nan),
                              g.astype(jnp.float32))
            backend = self.cfg.backend
            if backend == "exact":
                return self._exact_update(g, g_prev, age, key, residual, fresh,
                                          k_m_frac, age_lag, sanitize)
            if backend == "threshold":
                return self._threshold_update(g, g_prev, age, key, residual,
                                              fresh, k_m_frac, age_lag,
                                              sanitize)
            if backend == "packed":
                return self._packed_update(g, g_prev, age, key, tstate,
                                           residual, fresh, k_m_frac, age_lag,
                                           sanitize)
            return self._sharded_update(g, g_prev, age, key, residual, fresh,
                                        tstate, k_m_frac, age_lag, sanitize)

    def _noisy(self, fresh: Array, key: Optional[Array]) -> Array:
        cfg = self.cfg
        if key is None or cfg.noise_std <= 0.0:
            return fresh.astype(jnp.float32)
        noise = (cfg.noise_std / cfg.n_clients) * jax.random.normal(
            key, fresh.shape, jnp.float32)
        return fresh.astype(jnp.float32) + noise

    def _exact_update(self, g, g_prev, age, key, residual=None, fresh=None,
                      k_m_frac=None, age_lag=None, sanitize=False):
        k, k_m, _ = self.budgets()
        key_sel = key_noise = None
        if key is not None:
            key_sel, key_noise = jax.random.split(key)
        score = eff_score(g, residual)
        fin = mask_m_s = None
        if sanitize:
            # rank-form selection on demoted statistics: non-finite
            # coordinates rank below every healthy one in both stages
            # (magnitude −1, age −1), and the final AND keeps them out
            # even when the budget exceeds the healthy coordinate count —
            # they stay "unsent" (stale value kept, age climbing)
            fin = jnp.isfinite(score)
            score = jnp.where(fin, score, 0.0)
            km = self._km_traced(k_m_frac) if k_m_frac is not None else k_m
            mag_eff = jnp.where(fin, jnp.abs(score), -1.0)
            age_eff = jnp.where(fin, age.astype(jnp.float32), -1.0)
            mask, mask_m_s = fair_k_masks_dynamic(mag_eff, age_eff, k, km)
            finf = fin.astype(jnp.float32)
            mask = mask * finf
            mask_m_s = mask_m_s * finf
            stats = {"n_selected": mask.sum(), "k": k}
            if k_m_frac is not None:
                stats["k_m"] = km
        elif k_m_frac is None:
            idx = self.select(key_sel, score, age)
            mask = selection.mask_from_indices(idx, self.d)
            stats = {"idx": idx, "n_selected": jnp.float32(k), "k": k}
        else:
            # traced split: the index-form top-k concatenation has static
            # stage lengths, so select by RANK instead — the identical
            # coordinate set (ties toward lower index in both)
            km = self._km_traced(k_m_frac)
            k_m = km.astype(jnp.float32)
            mask, _ = fair_k_masks_dynamic(jnp.abs(score), age, k, km)
            stats = {"n_selected": jnp.float32(k), "k": k, "k_m": km}
        sent = score if fresh is None else fresh.astype(jnp.float32)
        if sanitize and fresh is not None:
            sent = jnp.where(jnp.isfinite(sent), sent, 0.0)
        g_t, age_next = masked_merge(self._noisy(sent, key_noise), g_prev,
                                     age, mask)
        if age_lag is not None:
            # async mode: selected coordinates carry their delivery lag
            # forward; the histograms below bin the shifted ages directly
            age_next = packing.shift_selected_age(age_next, age_lag)
            stats["sel_mask"] = mask
        if self.cfg.fused_stats:
            # the index-form FAIR-k magnitude stage selects exactly k_M
            # coordinates; the histograms come from the same jnp helper
            # the kernel oracle uses, so they are bit-comparable to the
            # threshold/packed backends' kernel-emitted ones
            from repro.kernels import ref    # deferred: kernels import core
            hist_valid = age.astype(jnp.float32) >= 0.0
            if fin is not None:
                hist_valid = hist_valid & fin
            mag_hist, age_hist = ref.strided_hists_ref(
                score, age_next, hist_valid, packing.hist_stride(self.d))
            n_sel_m = (mask_m_s.sum() if mask_m_s is not None
                       else jnp.asarray(k_m, jnp.float32))
            stats |= {"n_sel_m": n_sel_m,
                      "mag_hist": mag_hist, "age_hist": age_hist}
        if residual is not None:
            # noise-free accounting (the channel error is not observable by
            # the clients) — identical formula to the fused kernel's stage;
            # sanitized-out coordinates keep their old residual
            res_next = score - mask * sent
            if fin is not None:
                res_next = jnp.where(fin, res_next,
                                     residual.astype(jnp.float32))
            stats["residual"] = res_next
        return g_t, age_next, stats

    def _threshold_update(self, g, g_prev, age, key, residual=None,
                          fresh=None, k_m_frac=None, age_lag=None,
                          sanitize=False):
        from repro.kernels import ops          # deferred: kernels import core
        k, _, _ = self.budgets()
        theta_m, theta_a = self.thresholds(g, age, residual=residual,
                                           k_m_frac=k_m_frac,
                                           sanitize=sanitize)
        if self.cfg.fused_stats:
            g_t, age_next, res_next, kstats = ops.fairk_stats_update(
                g, g_prev, age, theta_m, theta_a, residual=residual,
                fresh=fresh, mode=self.cfg.kernel_mode, sanitize=sanitize)
            n_sel = kstats["n_sel"]
            extra = {"n_sel_m": kstats["n_sel_m"],
                     "mag_hist": kstats["mag_hist"],
                     "age_hist": kstats["age_hist"]}
        else:
            g_t, age_next, res_next = ops.fairk_ef_update(
                g, g_prev, age, theta_m, theta_a, residual=residual,
                fresh=fresh, mode=self.cfg.kernel_mode, sanitize=sanitize)
            # selected coordinates are exactly the age-reset ones (Eq. 10)
            n_sel = (age_next == 0.0).astype(jnp.float32).sum()
            extra = {}
        if self.cfg.noise_std > 0.0:
            # selection saw the clean aggregate; the channel perturbs only
            # the fresh (transmitted) coordinates — one extra masked pass on
            # top of the fused kernel, equivalent to merging g + noise
            sel = (age_next == 0.0).astype(jnp.float32)
            g_t = g_t + sel * (self.cfg.noise_std / self.cfg.n_clients) * \
                jax.random.normal(key, g.shape, jnp.float32)
        stats = {"theta_m": theta_m, "theta_a": theta_a,
                 "n_selected": n_sel, "k": k, **extra}
        if age_lag is not None:
            # async: counts/noise above used the pre-shift selection (the
            # kernel's age' == 0 convention); the carried buffer and the
            # emitted histogram record the delivery lag
            stats["sel_mask"] = (age_next == 0.0).astype(jnp.float32)
            age_next = packing.shift_selected_age(age_next, age_lag)
            if "age_hist" in stats:
                stats["age_hist"] = packing.shift_age_hist(
                    stats["age_hist"], age_lag)
        if res_next is not None:
            stats["residual"] = res_next
        return g_t, age_next, stats

    def _stats_thresholds(self, tstate, k_m_frac=None
                          ) -> Tuple[Array, Array, Array]:
        """(θ_M, θ_A, streak') from the carried statistics ALONE — zero
        reads of the gradient buffer (the fused-stats steady state).

        Warm branch: last round's thresholds with the budget-tracking
        correction, once the prediction streak is established.  Otherwise
        (trust region tripped, cold-start drift, or the very first
        rounds): thresholds re-estimated from the kernel-emitted
        histograms (``packing.hist_thresholds``) — the replacement for
        the sampled-quantile bootstrap pass.  Both branches are a handful
        of scalar/128-bin flops, so a plain ``where`` suffices where the
        legacy path needed ``lax.cond`` to dodge the quantile pass.
        ``k_m_frac`` (traced) reroutes every budget reference through the
        live split — the adaptive controller's round costs the SAME
        scalar program."""
        cfg = self.cfg
        k, k_m, _ = self.budgets()
        rho, km_frac = self._rho_parts()
        if k_m_frac is not None:
            k_m = self._km_traced(k_m_frac)
            km_frac = self._km_frac_eff(k_m)
        hist_tm, hist_ta = packing.hist_thresholds(
            tstate["mag_hist"], tstate["age_hist"], rho=rho,
            k_m_frac=km_frac)
        pred_tm, pred_ta = packing.warm_corrected_thresholds(
            tstate, k=k, k_m=k_m, alpha=cfg.warm_alpha, clip=cfg.warm_clip)
        on_track = self._on_track(tstate, k)
        use_warm = on_track & (tstate["streak"] >= cfg.warm_streak)
        tm = jnp.where(use_warm, pred_tm, hist_tm)
        ta = jnp.where(use_warm, pred_ta, hist_ta)
        # streak: the warm predictor must keep agreeing with the
        # hist-measured thresholds (same gates as the legacy sampled path)
        streak = self._streak_update(tstate, on_track, tm, ta, pred_tm,
                                     pred_ta)
        return tm, ta, streak

    def _on_track(self, tstate, k) -> Array:
        """Trust gate 1: last round's realised count stayed inside the
        budget tolerance (shared by the fused and legacy warm paths)."""
        return ((tstate["init"] > 0.0)
                & (jnp.abs(tstate["n_sel"] - k) <= self.cfg.warm_tol * k))

    def _streak_update(self, tstate, on_track, tm, ta, pred_tm, pred_ta
                       ) -> Array:
        """Trust gate 2: the warm predictor must keep agreeing with the
        measured thresholds (sampled quantiles on the legacy path, the
        histogram estimates on the fused path) — ONE formula so the two
        paths can never drift apart."""
        both = lambda a, b: jnp.isinf(a) & jnp.isinf(b)
        ratio_tol = 1.0 + self.cfg.warm_tol
        pred_ok = (
            (both(ta, pred_ta) | (jnp.abs(ta - pred_ta) <= 0.75))
            & (both(tm, pred_tm)
               | ((pred_tm <= tm * ratio_tol) & (pred_tm * ratio_tol >= tm))))
        return jnp.where(on_track & pred_ok, tstate["streak"] + 1.0, 0.0)

    def _packed_thresholds(self, g, age, tstate, residual=None,
                           k_m_frac=None, sanitize=False):
        """(θ_M, θ_A, streak') for a packed buffer: pad-excluding sampled
        quantiles, or — when warm — last round's thresholds with the
        budget-tracking correction (no quantile pass at all on steady-state
        rounds, via lax.cond).  With ``fused_stats`` the bootstrap itself
        disappears from the trace: re-estimation runs on the carried
        in-kernel histograms (``_stats_thresholds``).  ``residual`` folds
        into the magnitude statistic (score = g + residual; pads carry
        residual 0).  ``k_m_frac`` (traced) replaces the static split in
        every branch."""
        cfg = self.cfg
        k, k_m, _ = self.budgets()
        streak = jnp.float32(0.0)
        if cfg.exact_theta:
            # pads (|g|=0, age=PAD_AGE+jitter < 0) can never enter either
            # top-k, so the order statistics are those of the valid coords
            if k_m_frac is not None:
                return (*exact_thresholds_dynamic(
                    eff_score(g, residual), age, k=k,
                    k_m=self._km_traced(k_m_frac),
                    sanitize=sanitize), streak)
            return (*exact_thresholds(eff_score(g, residual), age,
                                      k=k, k_m=k_m,
                                      sanitize=sanitize), streak)
        if cfg.fused_stats and cfg.warm_start and tstate is not None:
            return self._stats_thresholds(tstate, k_m_frac)
        rho, km_frac = self._rho_parts()
        if k_m_frac is not None:
            k_m = self._km_traced(k_m_frac)
            km_frac = self._km_frac_eff(k_m)

        def bootstrap(_):
            tm, ta = sampled_thresholds(
                g, age, rho=rho, k_m_frac=km_frac,
                sample_cap=cfg.sample_cap, sample_ids=self._sample_ids,
                residual=residual, sanitize=sanitize)
            if cfg.reduce_axes:
                tm = jax.lax.pmean(tm, cfg.reduce_axes)
                ta = jax.lax.pmean(ta, cfg.reduce_axes)
            return tm, ta

        if not (cfg.warm_start and tstate is not None):
            return (*bootstrap(None), streak)

        # trust region, two gates:
        #  * on_track — last round's realised count stayed inside the budget
        #    tolerance;
        #  * streak — the warm predictor must have AGREED with the sampled
        #    quantiles for ``warm_streak`` consecutive bootstrap rounds.
        #    During drift (the cold-start transient: every unselected age
        #    advances together for ~1/rho rounds) the sampled θ_A moves ~1
        #    age unit per round while the predictor is near-constant, so the
        #    streak never builds and every round bootstraps — which is the
        #    correct (and self-healing) behaviour.  Once the age histogram
        #    is stationary, predictions match, the streak builds, and the
        #    quantile pass stops executing (lax.cond).
        pred_tm, pred_ta = packing.warm_corrected_thresholds(
            tstate, k=k, k_m=k_m, alpha=cfg.warm_alpha, clip=cfg.warm_clip)
        on_track = self._on_track(tstate, k)
        use_warm = on_track & (tstate["streak"] >= cfg.warm_streak)
        tm, ta = jax.lax.cond(use_warm, lambda _: (pred_tm, pred_ta),
                              bootstrap, None)
        streak = self._streak_update(tstate, on_track, tm, ta, pred_tm,
                                     pred_ta)
        return tm, ta, streak

    def _packed_update(self, g, g_prev, age, key, tstate, residual=None,
                       fresh=None, k_m_frac=None, age_lag=None,
                       sanitize=False):
        """One fused FAIR-k pass over the whole packed pytree buffer.

        Exactly one quantile estimation (or none: warm rounds correct the
        carried thresholds, and with ``fused_stats`` even re-estimation
        runs on the kernel-emitted histograms) and exactly one
        ``fairk_update`` launch for the entire model — vs one of each per
        leaf on the historical per-leaf path.  The residual
        (error-feedback) stage, the one-bit ``fresh`` values and (with
        ``fused_stats``) the counts/histogram statistics all ride the
        same fused pass, so the steady-state round reads the gradient
        buffer exactly once."""
        from repro.kernels import ops          # deferred: kernels import core
        cfg = self.cfg
        k, _, _ = self.budgets()
        theta_m, theta_a, streak = self._packed_thresholds(g, age, tstate,
                                                           residual,
                                                           k_m_frac,
                                                           sanitize)
        if cfg.fused_stats:
            # counts AND histograms come out of the kernel itself — the
            # fused launch is the only read of (g, residual) this round
            g_t, age_next, res_next, kstats = ops.fairk_stats_update(
                g, g_prev, age, theta_m, theta_a, residual=residual,
                fresh=fresh, mode=cfg.kernel_mode, sanitize=sanitize)
            n_sel, n_sel_m = kstats["n_sel"], kstats["n_sel_m"]
            mag_hist, age_hist = kstats["mag_hist"], kstats["age_hist"]
        else:
            g_t, age_next, res_next = ops.fairk_ef_update(
                g, g_prev, age, theta_m, theta_a, residual=residual,
                fresh=fresh, mode=cfg.kernel_mode, sanitize=sanitize)
            # legacy two-pass accounting: selected coordinates are exactly
            # the age-reset ones (Eq. 10; pads keep the negative sentinel
            # so they never count), and the magnitude-stage count re-reads
            # (g, residual) — the extra pass fused_stats eliminates
            packing.G_READS += 1
            sel = (age_next == 0.0).astype(jnp.float32)
            n_sel = sel.sum()
            n_sel_m = (sel * (jnp.abs(eff_score(g, residual))
                              >= theta_m)).sum()
            mag_hist = age_hist = None
        if cfg.reduce_axes:
            # per-shard mean keeps counts comparable to the local budgets
            # (and the carried tstate identical on every shard)
            n_sel = jax.lax.pmean(n_sel, cfg.reduce_axes)
            n_sel_m = jax.lax.pmean(n_sel_m, cfg.reduce_axes)
            if mag_hist is not None:
                mag_hist = jax.lax.pmean(mag_hist, cfg.reduce_axes)
                age_hist = jax.lax.pmean(age_hist, cfg.reduce_axes)
        if sanitize and mag_hist is not None and tstate is not None:
            # graceful degradation under a fully-erased round (total
            # channel outage, realised participation 0, or an all-corrupt
            # aggregate): every coordinate is sanitized away, so the
            # kernel emits EMPTY histograms — re-estimating thresholds
            # from those would read as "nothing left to select" (θ = 0,
            # the cold-start convention) and fire a spurious full-refresh
            # round right after the outage.  Substitute the exact truth
            # instead: nothing was refreshed, so this round's post-update
            # age histogram is last round's shifted up one bin, and the
            # magnitude mass was merely unobserved (carry it).  Partial
            # erasures keep the kernel's measurement bit-exactly.
            keep = (age_hist.sum() <= 0.0) & (tstate["init"] > 0.0)
            mag_hist = jnp.where(keep, tstate["mag_hist"], mag_hist)
            age_hist = jnp.where(
                keep, packing.advance_age_hist(tstate["age_hist"]),
                age_hist)
        if cfg.noise_std > 0.0:
            sel = (age_next == 0.0).astype(jnp.float32)
            g_t = g_t + sel * (cfg.noise_std / cfg.n_clients) * \
                jax.random.normal(key, g.shape, jnp.float32)
        sel_mask = None
        if age_lag is not None:
            # async: counts/noise above used the pre-shift selection; the
            # carried age buffer and histogram record the delivery lag
            # (bin-0 mass moves to bin ``age_lag`` — identical to binning
            # the shifted ages, since the shift only touches age == 0)
            sel_mask = (age_next == 0.0).astype(jnp.float32)
            age_next = packing.shift_selected_age(age_next, age_lag)
            if age_hist is not None:
                age_hist = packing.shift_age_hist(age_hist, age_lag)
        tstate_next = {"theta_m": theta_m, "theta_a": theta_a,
                       "n_sel_m": n_sel_m, "n_sel": n_sel,
                       "init": jnp.float32(1.0), "streak": streak,
                       "mag_hist": (mag_hist if mag_hist is not None else
                                    jnp.zeros((packing.STATS_MAG_BINS,),
                                              jnp.float32)),
                       "age_hist": (age_hist if age_hist is not None else
                                    jnp.zeros((packing.STATS_AGE_BINS,),
                                              jnp.float32))}
        stats = {"theta_m": theta_m, "theta_a": theta_a,
                 "n_selected": n_sel, "k": k, "tstate": tstate_next}
        if mag_hist is not None:
            stats |= {"n_sel_m": n_sel_m, "mag_hist": mag_hist,
                      "age_hist": age_hist}
        if sel_mask is not None:
            stats["sel_mask"] = sel_mask
        if res_next is not None:
            stats["residual"] = res_next
        return g_t, age_next, stats

    def select_and_merge_tree(self, g_tree, g_prev_tree, age_tree, *,
                              key: Optional[Array] = None,
                              tstate: Optional[Dict[str, Array]] = None,
                              residual: Optional[Array] = None,
                              k_m_frac=None, sanitize: bool = False):
        """Pytree façade over the packed backend: pack (g, g_prev, age),
        run the single fused pass, unpack ``(g_t, age')`` back to the tree
        structure (leaf dtypes from the layout).  Returns
        ``(g_t_tree, age_tree', stats)``.  ``residual`` is a FLAT packed
        ``(d_packed,)`` buffer (persist it across rounds — re-packing it
        from a tree every step would defeat error feedback's one-pass
        cost); its successor stays flat in ``stats["residual"]``."""
        lay = self.layout
        if lay is None:
            raise ValueError("select_and_merge_tree needs the packed "
                             "backend (construct with layout=...)")
        g = lay.pack(g_tree)
        gp = lay.pack(g_prev_tree)
        ag = lay.pack_age(age_tree)
        g_t, age_next, stats = self._packed_update(g, gp, ag, key, tstate,
                                                   residual,
                                                   k_m_frac=k_m_frac,
                                                   sanitize=sanitize)
        return lay.unpack(g_t, cast=False), lay.unpack(age_next,
                                                       cast=False), stats

    def _sharded_update(self, g, g_prev, age, key, residual=None,
                        fresh=None, tstate=None, k_m_frac=None,
                        age_lag=None, sanitize=False):
        cfg = self.cfg
        mesh = self.mesh
        axes = tuple(mesh.axis_names)
        k, _, _ = self.budgets()
        rho, km_frac = self._rho_parts()
        vec = P(axes)
        if fresh is not None:
            raise ValueError("the sharded backend has no decoupled one-bit "
                             "fresh path — route one_bit through the "
                             "exact/threshold/packed backends")
        has_res = residual is not None
        fused = cfg.fused_stats
        # traced split: the replicated scalar rides into shard_map as an
        # operand so the per-shard bootstrap sizes its quantiles from the
        # live value (the warm/global branches consume it outside)
        dyn_km = k_m_frac is not None
        kmf_op = (self._km_frac_eff(self._km_traced(k_m_frac)) if dyn_km
                  else jnp.float32(km_frac))
        # warm sharded rounds: the threshold decision consumes only the
        # carried (replicated) statistics — psum'd per-shard partials from
        # last round — so it runs OUTSIDE shard_map and the historical
        # every-round per-shard bootstrap disappears entirely.  The
        # resulting (θ_M, θ_A) are globally consistent by construction.
        warm = fused and cfg.warm_start and tstate is not None
        use_global = cfg.global_thresholds or cfg.exact_theta
        streak = jnp.float32(0.0)
        if warm:
            theta_m, theta_a, streak = self._stats_thresholds(tstate,
                                                              k_m_frac)
        elif use_global:
            theta_m, theta_a = self.thresholds(g, age, residual=residual,
                                               k_m_frac=k_m_frac,
                                               sanitize=sanitize)
        else:
            theta_m = theta_a = jnp.float32(0.0)    # placeholder, unused
        per_shard_boot = not (warm or use_global)
        n_local = g.shape[0] // _mesh_size(mesh)
        stride = packing.hist_stride(self.d)
        # per-block partials only sum to the unsharded sample when the
        # shard length is a stride multiple; else fall back to local
        # stride 1 (counts stay exact either way — only hist sample
        # density changes, and hist thresholds are scale-free)
        if n_local % stride:
            stride = 1

        def shard_phase(g_l, gp_l, age_l, res_l, tm, ta, kmf_l, key_l):
            my = 0
            for ax in axes:
                my = my * mesh.shape[ax] + jax.lax.axis_index(ax)
            score = eff_score(g_l, res_l if has_res else None)
            fin = None
            if sanitize:
                # local graceful degradation, no extra collectives: the
                # cleaned score keeps 0 * NaN out of the merge and the
                # finite AND keeps corrupted coordinates unselected
                fin = jnp.isfinite(score)
                score = jnp.where(fin, score, 0.0)
            if per_shard_boot:
                tm, ta = sampled_thresholds(
                    score, age_l, rho=rho,
                    k_m_frac=kmf_l if dyn_km else km_frac,
                    sample_cap=cfg.sample_cap)
            # jitter hashes GLOBAL coordinate ids (my * n_local offset) so
            # the mask is the one the unsharded backends would compute
            mask, mask_m = threshold_mask(score, age_l, tm, ta,
                                          index_offset=my * g_l.shape[0])
            if fin is not None:
                finf = fin.astype(jnp.float32)
                mask = mask * finf
                mask_m = mask_m * finf
            fresh_l = score.astype(jnp.float32)
            if cfg.noise_std > 0.0:
                kk = jax.random.fold_in(key_l, my)
                fresh_l = fresh_l + (cfg.noise_std / cfg.n_clients) * \
                    jax.random.normal(kk, g_l.shape, jnp.float32)
            g_t, age_next = masked_merge(fresh_l, gp_l, age_l, mask)
            if age_lag is not None:
                # async: the local shard's carried ages record the
                # delivery lag BEFORE the histograms bin them, so the
                # psum'd partials come out naturally shifted
                age_next = packing.shift_selected_age(age_next, age_lag)
            if has_res:
                res_next = score - mask * score
                if fin is not None:
                    # sanitized-out coordinates keep their old residual
                    res_next = jnp.where(fin, res_next,
                                         res_l.astype(jnp.float32))
            else:
                res_next = jnp.zeros((), jnp.float32)
            n_sel = jax.lax.psum(mask.sum(), axes)
            if fused:
                from repro.kernels import ref      # deferred import
                hist_valid = age_l >= 0.0
                if fin is not None:
                    hist_valid = hist_valid & fin
                mh_l, ah_l = ref.strided_hists_ref(
                    score, age_next, hist_valid, stride)
                part = (jax.lax.psum(mask_m.sum(), axes),
                        jax.lax.psum(mh_l, axes), jax.lax.psum(ah_l, axes))
            else:
                part = (jnp.zeros((), jnp.float32),
                        jnp.zeros((packing.STATS_MAG_BINS,), jnp.float32),
                        jnp.zeros((packing.STATS_AGE_BINS,), jnp.float32))
            sel_out = mask if age_lag is not None else jnp.zeros(
                (), jnp.float32)
            return g_t, age_next, res_next, n_sel, part, sel_out

        fn = jax.shard_map(
            shard_phase, mesh=mesh,
            in_specs=(vec, vec, vec, vec if has_res else P(), P(), P(),
                      P(), P()),
            out_specs=(vec, vec, vec if has_res else P(), P(),
                       (P(), P(), P()),
                       vec if age_lag is not None else P()),
            check_vma=False)
        if key is None:
            key = jax.random.PRNGKey(0)
        res_in = residual if has_res else jnp.zeros((), jnp.float32)
        g_t, age_next, res_next, n_sel, part, sel_mask = fn(
            g, g_prev, age, res_in, theta_m, theta_a, kmf_op, key)
        n_sel_m, mag_hist, age_hist = part
        stats = {"n_selected": n_sel, "k": k}
        if age_lag is not None:
            stats["sel_mask"] = sel_mask
        if use_global or warm:
            stats |= {"theta_m": theta_m, "theta_a": theta_a}
        if fused:
            stats |= {"n_sel_m": n_sel_m, "mag_hist": mag_hist,
                      "age_hist": age_hist}
            stats["tstate"] = {
                "theta_m": theta_m, "theta_a": theta_a, "n_sel_m": n_sel_m,
                "n_sel": n_sel, "init": jnp.float32(1.0), "streak": streak,
                "mag_hist": mag_hist, "age_hist": age_hist}
        if has_res:
            stats["residual"] = res_next
        return g_t, age_next, stats


def _mesh_size(mesh) -> int:
    n = 1
    for ax in mesh.axis_names:
        n *= mesh.shape[ax]
    return n


def make_engine(policy: str = "fairk", backend: str = "exact", *,
                d: Optional[int] = None, mesh=None,
                layout: Optional[packing.PackedLayout] = None,
                **cfg_kw) -> SelectionEngine:
    """Convenience constructor mirroring the string-driven policy registry.
    ``d`` may be omitted when ``layout`` pins it (= ``layout.d_packed``)."""
    if d is None:
        if layout is None:
            raise ValueError("make_engine needs d (or a layout)")
        d = layout.d_packed
    return SelectionEngine(EngineConfig(policy=policy, backend=backend,
                                        **cfg_kw), d, mesh=mesh,
                           layout=layout)

"""Sharded step builders: train / prefill / serve, plus the FL-OAC step.

Two integrations of the paper's technique (DESIGN.md §3, §5):

* ``make_train_step`` — the production trainer for the 10 assigned
  architectures.  Gradients flow through the standard 2-D FSDPxTP backward
  (XLA inserts the data-axis reduction = the multiple-access superposition);
  the OAC server phase then runs inside a fully-manual ``shard_map``.  By
  default (``OacServerConfig.packed``) each shard packs its local pytree
  into ONE lane-aligned flat buffer (core.packing) and runs a single fused
  threshold-FAIR-k pass with globally consistent (θ_M, θ_A) — pmean'd
  across shards, two scalars — and warm-start thresholds that skip the
  quantile pass on steady-state rounds.  ``packed=False`` keeps the
  historical per-leaf loop (one quantile estimation + kernel launch per
  leaf) for comparison; benchmarks/packed_bench.py measures the gap.

* ``make_fl_oac_step`` — the paper's own regime at its own scale: every mesh
  device is one FL client holding a full model replica; FAIR-k is applied at
  *waveform-group* (block) granularity — mirroring the prototype's OFDM
  symbol groups — and ONLY the selected blocks are all-reduced.  The
  collective volume drops from d to rho*d, which the roofline table
  measures directly (compare ``baseline=True``).

Every scan body is annotated via known_trip_count in the compiled HLO, which
``repro.roofline`` reads back for loop-aware FLOP/byte accounting.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from math import prod as np_prod
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.configs.base import InputShape, ModelConfig
from repro.core import channel as chan
from repro.core import controller as budget
from repro.core import faults
from repro.core import packing
from repro.core import population as pop_mod
from repro.core.engine import (AGE_CAP, EngineConfig, SelectionEngine,
                               fair_k_masks_dynamic, index_jitter,
                               sampled_thresholds, threshold_mask,
                               traced_km)
from repro.launch import sharding as shlib
from repro.launch.mesh import axis_size, batch_axes
from repro.models import transformer as tr
from repro.optim import make_optimizer

Array = jax.Array
SDS = jax.ShapeDtypeStruct


@dataclasses.dataclass(frozen=True)
class OacServerConfig:
    """FAIR-k server-side compression settings for the big-model trainer."""
    rho: float = 0.1               # selection budget k/d
    k_m_frac: float = 0.75         # magnitude share of the budget
    noise_std: float = 0.0         # channel noise sigma_z (post-aggregation)
    n_clients: int = 16            # N in Eq. (7) (= data shards)
    sample_cap: int = 65536        # quantile sample size (per leaf when
                                   # packed=False, per shard when packed)
    packed: bool = True            # ONE fused FAIR-k pass over the whole
                                   # local pytree (core.packing) instead of
                                   # the historical per-leaf loop; server
                                   # state persists as flat lane-aligned
                                   # buffers across steps (no per-round
                                   # re-pack of g_prev / age)
    warm_start: bool = True        # carry (θ_M, θ_A) across rounds; skip
                                   # the quantile pass on steady-state
                                   # rounds (packed path only)
    fused_stats: bool = True       # emit the warm-start counts and the
                                   # threshold-re-estimation histograms
                                   # from INSIDE the fused kernel
                                   # (DESIGN.md §11): the kernel becomes
                                   # the round's only read of the packed
                                   # gradient buffer.  Step 0 transmits
                                   # everything once (no histogram yet).
                                   # False restores the legacy two-pass
                                   # accounting + quantile bootstrap.
    error_feedback: bool = False   # fold the unselected gradient mass back
                                   # next step (EF-SGD): a persisted flat
                                   # f32 residual buffer rides the fused
                                   # kernel's residual stage (packed only)
    adaptive_km: bool = False      # in-graph adaptive k_M/k split
                                   # (core/controller.py): the controller
                                   # state rides in the server state as a
                                   # replicated flat vector, the engine
                                   # consumes the split as a traced value,
                                   # and the update runs INSIDE the
                                   # compiled step off the kernel-emitted
                                   # age/magnitude histograms — zero host
                                   # syncs, zero recompiles across split
                                   # changes (packed + fused_stats only)
    async_agg: bool = False        # asynchronous double-buffered rounds
                                   # (DESIGN.md §13): the optimizer consumes
                                   # the PREVIOUS round's merged gradient
                                   # (persisted ``pending`` buffer) so round
                                   # t's pack -> fused kernel -> unpack
                                   # overlaps round t+1's client compute;
                                   # straggler OAC contributions land in the
                                   # NEXT round's merge via the persisted
                                   # ``shadow`` buffer, with their extra age
                                   # recorded in the carried age buffer
                                   # (engine ``age_lag``) so the adaptive
                                   # controller absorbs the staleness online
                                   # (packed only; off == bit-exact with the
                                   # synchronous trajectory)
    straggler_frac: float = 0.25   # fraction of coordinates whose uplink
                                   # contribution arrives one aggregation
                                   # late (deterministic Knuth-hash pattern
                                   # — reproducible, trace-static)
    straggler_lag: int = 1         # delivery lag (rounds) of the straggler
                                   # contributions; shifts the post-merge
                                   # age of every selected coordinate and
                                   # translates the Lemma-1 target by the
                                   # same amount (core.markov
                                   # shifted_aou_distribution)
    sanitize: bool = False         # graceful degradation (DESIGN.md §14):
                                   # the fused pass masks non-finite score
                                   # coordinates out of BOTH selection
                                   # stages — a crashed host's NaN/Inf
                                   # uplink garbage is semantically
                                   # "unsent" (age keeps climbing, EF
                                   # residual passes through) instead of
                                   # poisoning the merged gradient and the
                                   # optimizer state.  Off (default) keeps
                                   # the trace bit-exact with the
                                   # historical graph (packed only).
    fade: float = 0.0              # per-round deep-fade erasure
                                   # probability on the aggregated uplink,
                                   # at ``fade_block`` granularity (one
                                   # OFDM symbol group's worth of
                                   # coordinates per fade, paper Sec. II);
                                   # erased coordinates ride the same
                                   # sanitize path (needs ``sanitize``)
    fade_block: int = 128          # coordinates per fade block
    one_bit: bool = False          # one-bit uplink for the server phase:
                                   # the merged fresh values are the SIGNS
                                   # of the effective gradient, detected by
                                   # the sign_mv kernel from the (noisy)
                                   # energy (Sec. V-B).  Unlike the FL sim
                                   # (per-client votes) the trainer's
                                   # backward has already superposed the
                                   # data shards, so the vote matrix is the
                                   # single aggregate row; selection still
                                   # scores |g + residual| (the server has
                                   # the magnitudes).  Combine with
                                   # error_feedback so the quantization
                                   # error is re-injected (packed only).
    population: Optional[pop_mod.PopulationConfig] = None
                                   # population-scale churn for the
                                   # production trainer (DESIGN.md §15),
                                   # STATELESS: the memoryless modes (iid,
                                   # diurnal) recompute the round's
                                   # availability as a pure counter-based
                                   # function of (base key, round seed), so
                                   # no chain state rides the checkpointed
                                   # server buffers.  A total cohort outage
                                   # erases the round, mid-round churn
                                   # erases symbol blocks through the
                                   # sanitize path, and under ``async_agg``
                                   # the straggler pattern's threshold
                                   # becomes the round's TRACED population
                                   # slow-share instead of the fixed
                                   # ``straggler_frac``.  Needs packed +
                                   # sanitize; ``mode="ge"`` carries chain
                                   # state and is sim-trainer-only.
    wireless: Optional[chan.ChannelConfig] = None
                                   # geometric wireless channel (DESIGN.md
                                   # §16) in aggregate-equivalent form:
                                   # the pre-aggregated gradient has no
                                   # per-client axis, so one AR(1)
                                   # Rayleigh fading chain per
                                   # ``wireless.block`` symbol group
                                   # rides the persisted server state
                                   # (``fad`` — checkpoint-migratable,
                                   # the cold start is a deterministic
                                   # stationary draw) and each round
                                   # erases the blocks whose gain falls
                                   # below the threshold calibrated to
                                   # the truncation-outage rate
                                   # ``wireless.thin``; imperfect CSI
                                   # multiplies the fresh aggregate by a
                                   # per-block misalignment factor.
                                   # Elementwise only — the fused pass
                                   # stays the round's single read of
                                   # the packed gradient buffer.  Needs
                                   # packed + sanitize; composes with
                                   # fade / population / async_agg.


@dataclasses.dataclass
class StepBundle:
    """Everything the dry-run / launcher needs for one compiled step."""
    fn: Callable
    in_shardings: Any
    out_shardings: Any
    input_specs: Tuple          # SDS pytree, positional
    meta: Dict[str, Any]


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig):
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(functools.partial(tr.init_lm, cfg=cfg), key)


def _batch_parts(cfg: ModelConfig, shape: InputShape, mesh,
                 n_micro: Optional[int]) -> Tuple[int, int, int]:
    b_axes = batch_axes(mesh)
    n_shards = axis_size(mesh, b_axes)
    gb = shape.global_batch
    if n_micro is None:
        n_micro = max(1, gb // n_shards)       # 1 sample / shard / microstep
    if gb % n_micro:
        raise ValueError(f"global batch {gb} not divisible by n_micro {n_micro}")
    return n_micro, gb // n_micro, n_shards


def _text_len(cfg: ModelConfig, seq_len: int) -> int:
    return seq_len - cfg.n_patches if cfg.family == "vlm" else seq_len


def train_input_specs(cfg: ModelConfig, shape: InputShape, n_micro: int,
                      mb: int) -> Dict[str, SDS]:
    s_text = _text_len(cfg, shape.seq_len)
    specs = {
        "tokens": SDS((n_micro, mb, s_text), jnp.int32),
        "labels": SDS((n_micro, mb, s_text), jnp.int32),
    }
    if cfg.family == "vlm":
        specs["embeds"] = SDS((n_micro, mb, cfg.n_patches, cfg.d_model),
                              jnp.dtype(cfg.compute_dtype))
    if cfg.family == "audio":
        specs["frames"] = SDS((n_micro, mb, cfg.encoder_seq, cfg.d_model),
                              jnp.dtype(cfg.compute_dtype))
    return specs


def _batch_pspecs(cfg: ModelConfig, gb: int, mesh, micro: bool) -> Dict:
    mk = lambda extra: shlib.batch_pspec(gb, mesh, extra_dims=extra,
                                         leading_micro=micro)
    specs = {"tokens": mk(1), "labels": mk(1)}
    if cfg.family == "vlm":
        specs["embeds"] = mk(2)
    if cfg.family == "audio":
        specs["frames"] = mk(2)
    return specs


def fairk_threshold_masks(g_flat: Array, age_flat: Array,
                          oac: OacServerConfig, sample_cap: int
                          ) -> Tuple[Array, Array]:
    """Scalable FAIR-k: sampled-quantile thresholds instead of global sort.

    Stage M: |g| >= theta_M  (theta_M ~ (1 - rho*k_m_frac) quantile of |g|).
    Stage A: among the rest, age+jitter >= theta_A sized to rho*(1-k_m_frac).
    Returns (mask selected, mask_m).  Thin wrapper over the SelectionEngine
    threshold primitives (core.engine) — kept as the launch-facing name."""
    theta_m, theta_a = sampled_thresholds(
        g_flat, age_flat, rho=oac.rho, k_m_frac=oac.k_m_frac,
        sample_cap=sample_cap)
    return threshold_mask(g_flat, age_flat, theta_m, theta_a)


def _leaf_engine(oac: OacServerConfig, n: int) -> SelectionEngine:
    """Threshold-backend engine for one parameter leaf of ``n`` elements."""
    return SelectionEngine(
        EngineConfig(policy="fairk", backend="threshold", rho=oac.rho,
                     k_m_frac=oac.k_m_frac, sample_cap=oac.sample_cap,
                     noise_std=oac.noise_std, n_clients=oac.n_clients), n)


def _leaf_server_update(g: Array, g_prev: Array, age: Array, key: Array,
                        oac: OacServerConfig) -> Tuple[Array, Array, Array]:
    """Per-leaf (local shard) FAIR-k server phase.  Returns
    (reconstructed gradient g_t, new g_prev, new age)."""
    shape = g.shape
    gf = g.reshape(-1)
    eng = _leaf_engine(oac, gf.shape[0])
    g_t, age_next, _ = eng.select_and_merge(
        gf, g_prev.reshape(-1), age.reshape(-1),
        key=key if oac.noise_std > 0.0 else None)
    return (g_t.reshape(shape), g_t.astype(g_prev.dtype).reshape(shape),
            age_next.astype(jnp.int8).reshape(shape))


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def _local_shape(shape: Tuple[int, ...], spec, mesh) -> Tuple[int, ...]:
    """Per-shard shape of a global array under a PartitionSpec (dims that
    don't divide are never sharded — param_pspecs guarantees it)."""
    dims = list(shape)
    for i, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for ax in axes:
            n *= mesh.shape[ax]
        dims[i] //= n
    return tuple(dims)


def server_layout(params_abs: Any, p_specs: Any, mesh
                  ) -> packing.PackedLayout:
    """The per-shard ``PackedLayout`` of the persisted packed server state:
    identical to what ``PackedLayout.from_tree(local_grads)`` builds inside
    ``shard_map`` (same flatten order, local shard shapes)."""
    leaves, treedef = jax.tree_util.tree_flatten(params_abs)
    specs = treedef.flatten_up_to(p_specs)
    local = [SDS(_local_shape(l.shape, s, mesh), l.dtype)
             for l, s in zip(leaves, specs)]
    return packing.PackedLayout.from_tree(
        jax.tree_util.tree_unflatten(treedef, local))


def _mesh_devices(mesh) -> int:
    n = 1
    for ax in mesh.axis_names:
        n *= mesh.shape[ax]
    return n


@obs.span("server_init")
def init_server_state(params: Any, mesh=None, cfg: ModelConfig = None,
                      oac: Optional[OacServerConfig] = OacServerConfig()
                      ) -> Dict:
    """OAC server state matching ``make_train_step``'s expectations.

    Packed flavour (``oac.packed``, the default — needs ``mesh`` + ``cfg``):
    the state IS the lane-aligned flat buffers, persisted end-to-end —
    ``g`` (d,) bf16, ``age`` (d,) int8 with the PAD_AGE sentinel in the
    lane-alignment pads, optionally ``res`` (d,) f32 (error feedback), and
    the replicated warm-start ``theta`` vector (DESIGN.md §9-§10), where
    d = n_devices * d_packed_per_shard.  Only the fresh gradients are
    packed each step; g_prev/age are never re-packed from trees.

    Per-leaf flavour (``oac is None`` or ``oac.packed=False``): the
    historical tree state — g_prev bf16 / age int8 per parameter leaf."""
    if oac is not None and oac.packed:
        if mesh is None or cfg is None:
            raise ValueError("packed server state needs (mesh, cfg) to "
                             "derive the per-shard layout — pass "
                             "init_server_state(params, mesh, cfg) or use "
                             "OacServerConfig(packed=False)")
        p_specs = shlib.param_pspecs(params, cfg, mesh)
        lay = server_layout(params, p_specs, mesh)
        n = _mesh_devices(mesh)
        age_local = np.asarray(lay.init_age(jnp.int8))
        state = {
            "g": jnp.zeros((n * lay.d_packed,), jnp.bfloat16),
            "age": jnp.asarray(np.tile(age_local, n)),
            "theta": jnp.zeros((packing.THRESHOLD_STATE_SIZE,),
                               jnp.float32),
        }
        if oac.error_feedback:
            state["res"] = jnp.zeros((n * lay.d_packed,), jnp.float32)
        if oac.adaptive_km:
            state["ctrl"] = budget.controller_state_to_vec(
                budget.init_controller_state(oac.k_m_frac))
        if oac.async_agg:
            # double-buffer lifecycle (DESIGN.md §13): ``pending`` holds the
            # merged gradient the NEXT optimizer step consumes; ``shadow``
            # holds the straggler contribution deferred into the next merge.
            # Both start cold (zeros): round 0 applies a zero update.
            state["shadow"] = jnp.zeros((n * lay.d_packed,), jnp.bfloat16)
            state["pending"] = jnp.zeros((n * lay.d_packed,), jnp.bfloat16)
        if oac.wireless is not None:
            # per-block AR(1) fading chains (DESIGN.md §16), 2 floats per
            # symbol block per shard.  The cold start is the DETERMINISTIC
            # stationary draw (a pure function of the global block count —
            # see channel.init_block_fading), so migrating a pre-channel
            # checkpoint re-synthesizes this exact state.
            state["fad"] = chan.init_block_fading(
                n * chan.n_blocks(lay.d_packed, oac.wireless))
        return state
    return {
        "g": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.bfloat16), params),
        "age": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.int8), params),
        "theta": jnp.zeros((packing.THRESHOLD_STATE_SIZE,), jnp.float32),
    }


def abstract_server_state(params_abs: Any, mesh=None, p_specs: Any = None,
                          oac: Optional[OacServerConfig] = None) -> Dict:
    if oac is not None and oac.packed:
        lay = server_layout(params_abs, p_specs, mesh)
        d = _mesh_devices(mesh) * lay.d_packed
        state = {"g": SDS((d,), jnp.bfloat16), "age": SDS((d,), jnp.int8),
                 "theta": SDS((packing.THRESHOLD_STATE_SIZE,),
                              jnp.float32)}
        if oac.error_feedback:
            state["res"] = SDS((d,), jnp.float32)
        if oac.adaptive_km:
            state["ctrl"] = SDS((budget.CONTROLLER_STATE_SIZE,),
                                jnp.float32)
        if oac.async_agg:
            state["shadow"] = SDS((d,), jnp.bfloat16)
            state["pending"] = SDS((d,), jnp.bfloat16)
        if oac.wireless is not None:
            state["fad"] = SDS(
                (2 * _mesh_devices(mesh)
                 * chan.n_blocks(lay.d_packed, oac.wireless),), jnp.float32)
        return state
    return {
        "g": jax.tree.map(lambda p: SDS(p.shape, jnp.bfloat16), params_abs),
        "age": jax.tree.map(lambda p: SDS(p.shape, jnp.int8), params_abs),
        "theta": SDS((packing.THRESHOLD_STATE_SIZE,), jnp.float32),
    }


def _with_expert_axis(cfg: ModelConfig, mesh) -> ModelConfig:
    """Pin expert tensors to the model axis when E divides it (SS Perf)."""
    model_n = mesh.shape["model"]
    if (cfg.n_experts and not cfg.expert_shard_axis
            and cfg.n_experts % model_n == 0
            and cfg.n_experts >= 2 * model_n):
        # measured: helps when devices hold >= 2 experts (arctic: coll -43%,
        # mem -18%); REGRESSES at 1 expert/device (jamba: compute 4x) where
        # GSPMD's unpinned plan was already better -> gated.
        return dataclasses.replace(cfg, expert_shard_axis="model")
    return cfg


def make_train_step(cfg: ModelConfig, shape: InputShape, mesh, *,
                    n_micro: Optional[int] = None,
                    client_chunk: Optional[int] = None,
                    oac: Optional[OacServerConfig] = OacServerConfig(),
                    opt_name: Optional[str] = None,
                    lr=1e-3,
                    sequence_parallel: bool = True,
                    gather_dtype: Optional[str] = None) -> StepBundle:
    cfg = _with_expert_axis(cfg, mesh)
    n_micro, mb, n_shards = _batch_parts(cfg, shape, mesh, n_micro)
    if client_chunk is not None and (
            client_chunk < 1 or n_micro % client_chunk):
        raise ValueError(
            f"client_chunk must divide n_micro ({n_micro}), got "
            f"{client_chunk}")
    opt = make_optimizer(opt_name or cfg.optimizer, lr)

    params_abs = abstract_params(cfg)
    p_specs = shlib.param_pspecs(params_abs, cfg, mesh)
    opt_abs = jax.eval_shape(opt.init, params_abs)
    o_specs = shlib.opt_pspecs(opt_abs, p_specs)
    if oac is not None and oac.error_feedback and not oac.packed:
        raise ValueError("error_feedback needs the packed server phase "
                         "(the residual is a flat persisted buffer)")
    if oac is not None and oac.one_bit and not oac.packed:
        raise ValueError("one_bit needs the packed server phase (the sign "
                         "vector is detected on the flat packed buffer)")
    if oac is not None and oac.adaptive_km and not (oac.packed
                                                    and oac.fused_stats):
        raise ValueError("adaptive_km consumes the kernel-emitted age/"
                         "magnitude histograms — it needs the packed "
                         "server phase with fused_stats")
    if oac is not None and oac.sanitize and not oac.packed:
        raise ValueError("sanitize rides the fused kernel's masking stage "
                         "— it needs the packed server phase")
    if oac is not None and oac.fade > 0.0 and not oac.sanitize:
        raise ValueError("fade erasures degrade through the sanitize "
                         "path — set OacServerConfig(sanitize=True)")
    if oac is not None and oac.async_agg:
        if not oac.packed:
            raise ValueError("async_agg double-buffers the PACKED server "
                             "state (flat shadow/pending buffers) — it "
                             "needs the packed server phase")
        if not 0.0 <= oac.straggler_frac <= 1.0:
            raise ValueError(f"straggler_frac must be in [0, 1], got "
                             f"{oac.straggler_frac}")
        if oac.straggler_lag < 1:
            raise ValueError(f"straggler_lag must be >= 1, got "
                             f"{oac.straggler_lag}")
    if oac is not None and oac.population is not None:
        if not (oac.packed and oac.sanitize):
            raise ValueError("population churn erasures degrade through "
                             "the fused kernel's sanitize path — set "
                             "OacServerConfig(packed=True, sanitize=True)")
        if oac.one_bit:
            raise ValueError("population churn on the one-bit uplink is "
                             "not modelled — run population with "
                             "one_bit=False")
        if oac.population.mode == "ge":
            raise ValueError("the launch population is stateless (iid | "
                             "diurnal — recomputed per round from the "
                             "seed); Gilbert–Elliott bursts carry chain "
                             "state and run in the FL sim trainer only")
        if oac.population.slow_frac > 0.0 and not oac.async_agg:
            raise ValueError("population stragglers land through the "
                             "async shadow buffer — slow_frac > 0 needs "
                             "OacServerConfig(async_agg=True)")
    if oac is not None and oac.wireless is not None:
        if not (oac.packed and oac.sanitize):
            raise ValueError("wireless truncation outages degrade through "
                             "the fused kernel's sanitize path on the "
                             "packed buffers — set "
                             "OacServerConfig(packed=True, sanitize=True)")
    srv_abs = abstract_server_state(params_abs, mesh=mesh, p_specs=p_specs,
                                    oac=oac)
    srv_specs = shlib.server_pspecs(
        p_specs, mesh=mesh,
        packed=(oac is not None and oac.packed),
        error_feedback=(oac is not None and oac.error_feedback),
        adaptive_km=(oac is not None and oac.adaptive_km),
        async_agg=(oac is not None and oac.async_agg),
        wireless=(oac is not None and oac.wireless is not None))
    b_specs = _batch_pspecs(cfg, mb, mesh, micro=True)
    in_specs_batch = train_input_specs(cfg, shape, n_micro, mb)

    b_axes = batch_axes(mesh)
    seq_sp = _text_len(cfg, shape.seq_len) + (cfg.n_patches or 0)

    if sequence_parallel and seq_sp % mesh.shape["model"] == 0:
        sp_sharding = NamedSharding(
            mesh, P(b_axes if mb % n_shards == 0 else None, "model", None))

        def residual_fn(x):
            return jax.lax.with_sharding_constraint(x, sp_sharding)
    else:
        residual_fn = None

    def loss_micro(params, mbatch):
        return tr.loss_fn(params, cfg, mbatch, residual_fn=residual_fn)

    grad_fn = jax.value_and_grad(loss_micro, has_aux=True)

    if oac is not None:
        oac = dataclasses.replace(oac, n_clients=n_shards)
        if oac.wireless is not None:
            # the data shards ARE the radio clients: the deployment
            # geometry (path gains, outage rates, thin) follows the mesh
            oac = dataclasses.replace(
                oac, wireless=dataclasses.replace(oac.wireless,
                                                  n_clients=n_shards))
        mesh_axes = tuple(mesh.axis_names)
        # adaptive split: one controller per step builder — the Lemma-1
        # target table is static data baked at build time.  Under async
        # aggregation the stationary AoU pmf is the synchronous Lemma-1
        # pmf translated by the straggler lag (core.markov
        # shifted_aou_distribution), so the controller's target shifts by
        # the same constant — it absorbs the added staleness online with
        # no new host syncs.
        bctrl = (budget.BudgetController(
            rho=oac.rho,
            age_offset=(float(oac.straggler_lag) if oac.async_agg
                        else 0.0),
            # population churn and wireless truncation outage both thin
            # the refresh stream (DESIGN.md §15-§16): the controller's
            # Lemma-1 target absorbs the geometric mean shift
            # thin/(1-thin) as a constant offset; independent blockers'
            # rates add (to first order)
            thin=min(0.99, (oac.population.thin
                            if oac.population is not None else 0.0)
                     + (oac.wireless.thin
                        if oac.wireless is not None else 0.0)))
            if oac.adaptive_km else None)

        def _shard_noise_key(seed):
            """Per-shard channel-noise key: fold the round seed by the
            shard's linear index so the simulated noise is iid ACROSS
            shards (an un-folded key would repeat the same noise block on
            every shard — the global noise vector must not be periodic)."""
            my = 0
            for ax in mesh_axes:
                my = my * mesh.shape[ax] + jax.lax.axis_index(ax)
            return jax.random.fold_in(jax.random.PRNGKey(seed), my)

        def _packed_server_phase(server, grads, seed):
            """ONE fused FAIR-k pass over the whole local pytree, against
            PERSISTED flat server buffers: only the fresh gradients are
            packed (one tree copy); g_prev (bf16), age (int8, PAD_AGE
            sentinel in the lane pads) and the optional EF residual stay
            lane-aligned flat buffers across steps, so the step saves two
            tree packs + one tree unpack per round vs the PR-2 re-pack
            path and the buffer donation is fully in place.  (θ_M, θ_A)
            stay globally consistent (pmean across shards); with
            ``fused_stats`` (default) the warm-start counts and the
            threshold-re-estimation histograms come OUT of the fused
            kernel, so the steady-state round reads the packed gradient
            buffer exactly once — no separate count pass, no quantile
            bootstrap."""
            layout = packing.PackedLayout.from_tree(grads)
            eng = SelectionEngine(
                EngineConfig(policy="fairk", backend="packed", rho=oac.rho,
                             k_m_frac=oac.k_m_frac,
                             sample_cap=oac.sample_cap,
                             noise_std=(0.0 if oac.one_bit
                                        else oac.noise_std),
                             n_clients=oac.n_clients,
                             warm_start=oac.warm_start,
                             fused_stats=oac.fused_stats,
                             reduce_axes=mesh_axes),
                layout.d_packed, layout=layout)
            with obs.scope("fairk"):
                tstate = packing.threshold_state_from_vec(server["theta"])
            with obs.scope("server_stages"):
                cstate = kmf = None
                if oac.adaptive_km:
                    # the live split comes off the carried controller state —
                    # replicated across shards (its inputs are the pmean'd
                    # histograms, so every shard computes the same successor)
                    cstate = budget.controller_state_from_vec(server["ctrl"])
                    kmf = cstate["k_m_frac"]
                key = _shard_noise_key(seed) if oac.noise_std > 0.0 else None
                pop_stats = None
                if oac.population is not None:
                    # stateless population round (DESIGN.md §15): iid/diurnal
                    # chains are memoryless, so the round's availability grid
                    # is a pure counter-based function of (base key, seed) —
                    # no chain state rides the checkpointed server buffers,
                    # and consecutive round seeds walk a lawful trajectory.
                    # Replicated computation: no shard fold-in, so every
                    # shard derives identical round stats (no collective).
                    pop_stats = pop_mod.stateless_round(
                        jax.random.PRNGKey(0x509), seed, oac.population)
            g_flat = layout.pack(grads)            # the ONLY pack per step
            with obs.scope("server_stages"):
                new_fad = wl_erase = None
                if oac.wireless is not None:
                    # aggregate-equivalent wireless round (DESIGN.md §16):
                    # advance this shard's per-block AR(1) fading chains and
                    # mark the blocks whose gain misses the threshold
                    # calibrated to the truncation-outage rate (the erasure
                    # composes into the sanitize path below); imperfect CSI
                    # multiplies the fresh aggregate by the per-block
                    # misalignment factor.  Per-shard draws (disjoint
                    # coordinate slices => the global pattern), decorrelated
                    # from the noise/fade/churn streams by distinct fold-ins;
                    # everything elementwise — G_READS stays 1.
                    new_fad, wl_erase = chan.block_outage(
                        server["fad"],
                        jax.random.fold_in(_shard_noise_key(seed), 0xC4A),
                        layout.d_packed, oac.wireless)
                    g_flat = g_flat * chan.csi_block_factor(
                        jax.random.fold_in(_shard_noise_key(seed), 0xC51),
                        layout.d_packed, oac.wireless)
                age_lag = None
                new_shadow = None
                if oac.async_agg:
                    # straggler OAC contributions land one aggregation late: a
                    # Knuth-hash pattern of coordinates defers its share of
                    # THIS round's uplink into the shadow buffer while LAST
                    # round's shadow joins the merge.  Elementwise mixing on
                    # the packed buffer — not an extra instrumented read of
                    # the persisted gradient state, so G_READS stays 1.  With
                    # a population the threshold is the round's TRACED
                    # straggler share (sampled from the live cohort) instead
                    # of the fixed ``straggler_frac`` — same hash pattern,
                    # data-dependent coverage, still zero recompiles.
                    frac = (pop_stats["slow_share"]
                            if oac.population is not None
                            else oac.straggler_frac)
                    strag = (index_jitter(layout.d_packed)
                             < frac).astype(jnp.float32)
                    new_shadow = g_flat * strag
                    g_flat = (g_flat * (1.0 - strag)
                              + server["shadow"].astype(jnp.float32))
                    age_lag = oac.straggler_lag
                fresh = None
                if oac.one_bit:
                    # one-bit uplink: the transmitted values are the SIGNS of
                    # the effective gradient, detected by the sign_mv kernel
                    # from the (noisy) energy — with EF the sign is taken on
                    # score = g + residual, the same fold the fused kernel
                    # applies, so residual' = score - mask*sign accumulates
                    # the quantization error.  Channel noise rides the vote
                    # energy (engine noise off), like the FL sim's route.
                    from repro.kernels import ops
                    eff = g_flat
                    if "res" in server:
                        eff = eff + server["res"]
                    # unscaled sigma_z on the superposed energy — the same
                    # convention as the FL sim's one-bit route (the noise
                    # perturbs the detection statistic once; it does NOT
                    # average down over clients like the coherent channel)
                    noise = (oac.noise_std
                             * jax.random.normal(_shard_noise_key(seed),
                                                 g_flat.shape, jnp.float32)
                             if oac.noise_std > 0.0 else None)
                    fresh, _ = ops.sign_mv(eff[None, :], noise=noise)
                    key = None
                erase = None
                if oac.fade > 0.0:
                    # deep-fade block erasures on the aggregated signal: a
                    # per-shard draw (each shard owns a disjoint coordinate
                    # slice, so independent per-shard masks ARE the global
                    # mask), decorrelated from the channel-noise stream by a
                    # fold-in.  The engine converts erased coordinates to NaN
                    # and the sanitize stage keeps them out of selection.
                    erase = faults.fade_mask(
                        jax.random.fold_in(_shard_noise_key(seed), 0xFADE),
                        layout.d_packed,
                        faults.FaultConfig(fade=oac.fade,
                                           fade_block=oac.fade_block))
                if oac.population is not None:
                    # mid-round churn erasure (DESIGN.md §15): symbol blocks
                    # lost to participants whose chain dropped mid-round, at
                    # the round's traced churn rate; a TOTAL cohort outage
                    # erases everything.  Per-shard draw (disjoint slices =>
                    # the global mask), decorrelated from the fade stream.
                    churn_er = faults.erase_with_outage(
                        pop_mod.churn_erase_mask(
                            jax.random.fold_in(_shard_noise_key(seed), 0x509),
                            layout.d_packed, pop_stats["churn"],
                            oac.population),
                        pop_stats["n_t"])
                    erase = (churn_er if erase is None
                             else jnp.maximum(erase, churn_er))
                if wl_erase is not None:
                    erase = (wl_erase if erase is None
                             else jnp.maximum(erase, wl_erase))
            g_t, age_next, stats = eng.select_and_merge(
                g_flat, server["g"], server["age"], key=key, tstate=tstate,
                residual=server.get("res"), fresh=fresh, k_m_frac=kmf,
                age_lag=age_lag, erase=erase, sanitize=oac.sanitize)
            with obs.scope("server_cast"):
                new_server = {
                    "g": g_t.astype(jnp.bfloat16),
                    "age": age_next.astype(jnp.int8),
                    "theta": packing.threshold_state_to_vec(stats["tstate"]),
                }
            if "res" in server:
                new_server["res"] = stats["residual"]
            if oac.wireless is not None:
                new_server["fad"] = new_fad
            if oac.adaptive_km:
                # in-graph controller step off the (pmean'd) kernel
                # histograms — the same compiled program at every split
                with obs.scope("server_stages"):
                    cstate = bctrl.update(cstate, stats["age_hist"],
                                          stats["mag_hist"])
                    new_server["ctrl"] = budget.controller_state_to_vec(
                        cstate)
            if oac.async_agg:
                # double-buffer swap: the optimizer consumes the PREVIOUS
                # round's merged gradient, so this round's fused pass has
                # no consumer inside the step — XLA overlaps it with the
                # next round's client compute.  Round 0's pending buffer
                # is zeros (a no-op update), matching the one-round
                # pipeline fill.
                with obs.scope("server_cast"):
                    new_server["shadow"] = new_shadow.astype(jnp.bfloat16)
                    new_server["pending"] = g_t.astype(jnp.bfloat16)
                out = server["pending"]
            else:
                out = g_t
            # the optimizer consumes per-leaf trees: ONE unpack per step, in
            # the buffer's dtype (AdamW widens bf16 ``pending`` leaf by leaf
            # as it reads it)
            return layout.unpack(out, cast=False), new_server

        def _per_leaf_server_phase(server, grads, seed):
            """Historical per-leaf loop (oac.packed=False): one threshold
            estimation + one fused kernel per parameter leaf."""
            leaves_g, treedef = jax.tree_util.tree_flatten(grads)
            leaves_gp = treedef.flatten_up_to(server["g"])
            leaves_age = treedef.flatten_up_to(server["age"])
            key = _shard_noise_key(seed)
            g_t, new_gp, new_age = [], [], []
            for i, (g, gp, ag) in enumerate(zip(leaves_g, leaves_gp,
                                                leaves_age)):
                kk = jax.random.fold_in(key, i)
                a, b, c = _leaf_server_update(g, gp, ag, kk, oac)
                g_t.append(a)
                new_gp.append(b)
                new_age.append(c)
            g_t = jax.tree_util.tree_unflatten(treedef, g_t)
            new_server = {
                "g": jax.tree_util.tree_unflatten(treedef, new_gp),
                "age": jax.tree_util.tree_unflatten(treedef, new_age),
                "theta": server["theta"],
            }
            return g_t, new_server

        def update_phase(params, opt_state, server, grads, seed):
            """Runs under fully-manual shard_map: leaves are local shards."""
            phase = (_packed_server_phase if oac.packed
                     else _per_leaf_server_phase)
            g_t, new_server = phase(server, grads, seed)
            with obs.scope("adamw"):
                g_t = jax.tree.map(lambda gt, p: gt.astype(p.dtype), g_t,
                                   params)
                updates, new_opt = opt.update(g_t, opt_state, params)
                new_params = jax.tree.map(
                    lambda p, u: p + u.astype(p.dtype), params, updates)
            return new_params, new_opt, new_server

        update_sharded = jax.shard_map(
            update_phase, mesh=mesh,
            in_specs=(p_specs, o_specs, srv_specs, p_specs, P()),
            out_specs=(p_specs, o_specs, srv_specs), check_vma=False)
    else:
        def update_sharded(params, opt_state, server, grads, seed):
            with obs.scope("adamw"):
                updates, new_opt = opt.update(grads, opt_state, params)
                new_params = jax.tree.map(
                    lambda p, u: p + u.astype(p.dtype), params, updates)
            return new_params, new_opt, server

    def client_phase(params, batch):
        """The clients' forward and backward over the round's microbatches:
        (mean loss, mean gradient in the parameters' dtypes)."""
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        if gather_dtype is not None:
            # §Perf: compute-params cast once per step (sharded, local) so
            # the per-layer FSDP all-gathers carry 2-byte weights and the
            # backward reduce-scatters carry 2-byte cotangents
            gdt = jnp.dtype(gather_dtype)
            params_c = jax.tree.map(
                lambda p: p.astype(gdt) if p.ndim > 1 else p, params)
        else:
            params_c = params

        if client_chunk is None:
            def microbatch_body(carry, mbatch):
                loss_acc, g_acc = carry
                (loss, _), grads = grad_fn(params_c, mbatch)
                g_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                     g_acc, grads)
                return (loss_acc + loss, g_acc), None

            (loss, grads), _ = jax.lax.scan(
                microbatch_body, (jnp.zeros((), jnp.float32), zeros), batch)
        else:
            # streaming chunked accumulation (DESIGN.md §17): the scan
            # walks n_micro / C chunks and each step vmaps the grad over
            # its C microbatches, folding the chunk's gradient sum into
            # the same (d,)-per-leaf accumulators the per-microbatch body
            # carries — memory scales with the chunk, not with n_micro.
            batch_c = jax.tree.map(
                lambda x: x.reshape((n_micro // client_chunk, client_chunk)
                                    + x.shape[1:]), batch)

            def chunk_body(carry, mchunk):
                loss_acc, g_acc = carry
                (loss, _), grads = jax.vmap(
                    lambda mb_: grad_fn(params_c, mb_))(mchunk)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32).sum(axis=0),
                    g_acc, grads)
                return (loss_acc + loss.sum(), g_acc), None

            (loss, grads), _ = jax.lax.scan(
                chunk_body, (jnp.zeros((), jnp.float32), zeros), batch_c)
        loss = loss / n_micro
        grads = jax.tree.map(lambda g, p: (g / n_micro).astype(p.dtype),
                             grads, params)
        return loss, grads

    def train_step(params, opt_state, server, batch, seed):
        with obs.scope("client"):
            loss, grads = client_phase(params, batch)
        new_params, new_opt, new_server = update_sharded(
            params, opt_state, server, grads, seed)
        return new_params, new_opt, new_server, loss

    named = lambda specs: shlib.to_named(specs, mesh)
    in_sh = (named(p_specs), named(o_specs), named(srv_specs),
             named(b_specs), NamedSharding(mesh, P()))
    out_sh = (named(p_specs), named(o_specs), named(srv_specs),
              NamedSharding(mesh, P()))
    input_specs = (params_abs, opt_abs, srv_abs, in_specs_batch,
                   SDS((), jnp.int32))
    meta = {
        "kind": "train", "n_micro": n_micro, "micro_batch": mb,
        "client_chunk": client_chunk,
        "seq_len": shape.seq_len, "oac": oac is not None,
        "oac_packed": bool(oac.packed) if oac is not None else False,
        "oac_warm_start": bool(oac.warm_start) if oac is not None else False,
        "oac_ef": bool(oac.error_feedback) if oac is not None else False,
        "oac_fused_stats": bool(oac.fused_stats) if oac is not None
        else False,
        "oac_one_bit": bool(oac.one_bit) if oac is not None else False,
        "oac_adaptive_km": bool(oac.adaptive_km) if oac is not None
        else False,
        "oac_async": bool(oac.async_agg) if oac is not None else False,
        "oac_sanitize": bool(oac.sanitize) if oac is not None else False,
        "oac_fade": float(oac.fade) if oac is not None else 0.0,
        "oac_population": (oac.population.n_clients
                           if oac is not None and oac.population is not None
                           else 0),
        "oac_wireless": bool(oac.wireless is not None) if oac is not None
        else False,
        "optimizer": opt_name or cfg.optimizer, "lr": lr,
        "gather_dtype": gather_dtype,
        "scans": {"microbatch": n_micro, "layers": cfg.n_scan_blocks},
    }
    return StepBundle(train_step, in_sh, out_sh, input_specs, meta)


# ---------------------------------------------------------------------------
# prefill / serve steps
# ---------------------------------------------------------------------------

def _serve_capacity(cfg: ModelConfig, shape: InputShape) -> Tuple[int, bool]:
    """(cache capacity, ring?) for decode shapes."""
    if shape.seq_len > 32768 and cfg.sliding_window and cfg.family not in (
            "ssm", "hybrid"):
        return cfg.sliding_window, True       # long-context sliding window
    return shape.seq_len, False


def make_prefill_step(cfg: ModelConfig, shape: InputShape, mesh) -> StepBundle:
    cfg = _with_expert_axis(cfg, mesh)
    gb = shape.global_batch
    s_text = _text_len(cfg, shape.seq_len)
    params_abs = abstract_params(cfg)
    p_specs = shlib.param_pspecs(params_abs, cfg, mesh)
    cache_abs = tr.cache_specs(cfg, gb, shape.seq_len)
    c_specs = shlib.cache_pspecs(cache_abs, cfg, mesh)

    def prefill_step(params, caches, batch):
        return tr.prefill(params, cfg, batch["tokens"], caches,
                          embeds=batch.get("embeds"),
                          frames=batch.get("frames"))

    batch_specs = {"tokens": SDS((gb, s_text), jnp.int32)}
    b_pspecs = {"tokens": shlib.batch_pspec(gb, mesh, 1, False)}
    if cfg.family == "vlm":
        batch_specs["embeds"] = SDS((gb, cfg.n_patches, cfg.d_model),
                                    jnp.dtype(cfg.compute_dtype))
        b_pspecs["embeds"] = shlib.batch_pspec(gb, mesh, 2, False)
    if cfg.family == "audio":
        batch_specs["frames"] = SDS((gb, cfg.encoder_seq, cfg.d_model),
                                    jnp.dtype(cfg.compute_dtype))
        b_pspecs["frames"] = shlib.batch_pspec(gb, mesh, 2, False)

    named = lambda s: shlib.to_named(s, mesh)
    logits_spec = P(batch_axes(mesh) if gb % axis_size(
        mesh, batch_axes(mesh)) == 0 else None, None, None)
    in_sh = (named(p_specs), named(c_specs), named(b_pspecs))
    out_sh = (NamedSharding(mesh, logits_spec), named(c_specs))
    meta = {"kind": "prefill", "seq_len": shape.seq_len,
            "global_batch": gb,
            "scans": {"layers": cfg.n_scan_blocks}}
    return StepBundle(prefill_step, in_sh, out_sh,
                      (params_abs, cache_abs, batch_specs), meta)


def make_serve_step(cfg: ModelConfig, shape: InputShape, mesh) -> StepBundle:
    cfg = _with_expert_axis(cfg, mesh)
    gb = shape.global_batch
    capacity, ring = _serve_capacity(cfg, shape)
    params_abs = abstract_params(cfg)
    p_specs = shlib.param_pspecs(params_abs, cfg, mesh)
    cache_abs = tr.cache_specs(cfg, gb, capacity, ring=ring)
    c_specs = shlib.cache_pspecs(cache_abs, cfg, mesh,
                                 shard_capacity=(gb == 1))
    window = cfg.sliding_window if ring else 0

    def serve_step(params, caches, token, pos):
        return tr.decode_step(params, cfg, token, pos, caches, window=window)

    named = lambda s: shlib.to_named(s, mesh)
    b_axes = batch_axes(mesh)
    tok_spec = P(b_axes if gb % axis_size(mesh, b_axes) == 0 else None, None)
    logits_spec = P(tok_spec[0], None, None)
    in_sh = (named(p_specs), named(c_specs), NamedSharding(mesh, tok_spec),
             NamedSharding(mesh, P()))
    out_sh = (NamedSharding(mesh, logits_spec), named(c_specs))
    input_specs = (params_abs, cache_abs, SDS((gb, 1), jnp.int32),
                   SDS((), jnp.int32))
    meta = {"kind": "decode", "seq_len": shape.seq_len, "global_batch": gb,
            "capacity": capacity, "ring": ring,
            "scans": {"layers": cfg.n_scan_blocks}}
    return StepBundle(serve_step, in_sh, out_sh, input_specs, meta)


# ---------------------------------------------------------------------------
# FL-OAC step: the paper's regime at its own scale (clients = devices)
# ---------------------------------------------------------------------------

def make_fl_oac_step(cfg: ModelConfig, mesh, *, seq_len: int = 1024,
                     local_batch: int = 1, rho: float = 0.1,
                     k_m_frac: float = 0.75, block: int = 4096,
                     noise_std: float = 1.0,
                     baseline: bool = False,
                     one_bit: bool = False,
                     adaptive_km: bool = False) -> StepBundle:
    """Every device = one OAC-FL client with a full model replica.

    FAIR-k runs at waveform-group granularity (``block`` coordinates per
    group, mirroring the prototype's OFDM symbol groups): blocks are scored
    by gradient L2 (stage M) and group AoU (stage A); only the selected
    rho-fraction of blocks is all-reduced -> the uplink collective carries
    rho*d values instead of d (``baseline=True`` all-reduces everything).

    The magnitude/age split is a TRACED value (the engine's rank-based
    ``fair_k_masks_dynamic`` — same coordinate set as the historical
    static ``top_k`` concatenation, incl. the toward-lower-index
    tie-break), so ``adaptive_km`` can close the loop at this scale too:
    the budget controller state rides the step as an extra replicated
    vector, re-derives the split from the block-AoU histogram every round,
    and never recompiles."""
    axes = tuple(mesh.axis_names)
    n_clients = axis_size(mesh, axes)
    bctrl = budget.BudgetController(rho=rho) if adaptive_km else None

    params_abs = abstract_params(cfg)
    leaves_abs, treedef = jax.tree_util.tree_flatten(params_abs)
    sizes = [int(np_prod(l.shape)) for l in leaves_abs]
    offsets = [0]
    for sz in sizes:
        offsets.append(offsets[-1] + sz)
    d = offsets[-1]

    def unravel(flat):
        out = [flat[offsets[i]:offsets[i + 1]].reshape(leaves_abs[i].shape)
               .astype(leaves_abs[i].dtype) for i in range(len(sizes))]
        return jax.tree_util.tree_unflatten(treedef, out)
    d_pad = -(-d // block) * block
    nb = d_pad // block
    kb = max(1, int(round(rho * nb)))

    def fl_oac_core(w_flat, g_prev, age_b, ctrl_vec, batch, seed):
        """w_flat/g_prev: (d,) replicated; age_b: (nb,) block AoU;
        ctrl_vec: replicated controller state (adaptive only, else None);
        batch: per-client {tokens, labels} (local_batch, seq)."""
        # --- local client update ------------------------------------------
        def local_loss(w):
            return tr.loss_fn(unravel(w), cfg, batch)[0]
        loss, grads = jax.value_and_grad(local_loss)(w_flat)
        gb_local = jnp.pad(grads, (0, d_pad - d)).reshape(nb, block)
        # --- shared selection (replicated inputs -> identical everywhere) --
        # The split ``kb_m`` is TRACED (the engine's rank-based machinery,
        # one rounding convention via traced_km): rank and top_k agree on
        # the selected set incl. the toward-lower-index tie-break, so the
        # static regime is value-identical to the historical concatenated
        # top_k form while the adaptive regime re-derives the split from
        # the carried controller state without recompiling.
        cstate = (budget.controller_state_from_vec(ctrl_vec)
                  if adaptive_km else None)
        kmf = cstate["k_m_frac"] if adaptive_km else jnp.float32(k_m_frac)
        gp = jnp.pad(g_prev, (0, d_pad - d)).reshape(nb, block)
        score = jnp.sum(gp.astype(jnp.float32) ** 2, axis=1)
        mask_sel, _ = fair_k_masks_dynamic(
            score, age_b.astype(jnp.float32), kb, traced_km(kb, kmf))
        # exactly kb ones in mask_sel; gather/scatter below are
        # order-insensitive (unique indices), so ascending order is fine
        idx = jnp.nonzero(mask_sel, size=kb, fill_value=0)[0]
        idx = idx.astype(jnp.int32)
        # --- OAC uplink: only the selected blocks ride the channel ---------
        key = jax.random.PRNGKey(seed)
        my = 0
        for ax in axes:
            my = my * mesh.shape[ax] + jax.lax.axis_index(ax)
        h = jax.random.rayleigh(
            jax.random.fold_in(key, 0), 1.0 / 1.2533141373155003,
            shape=(n_clients,), dtype=jnp.float32)[my]
        if baseline:
            # 1/N audit (DESIGN.md §14): n_clients is the static mesh size
            # — every device always contributes to the psum, so the
            # denominator can never be a traced zero.  Any rescale by a
            # REALIZED participation count must instead route through
            # faults.participation_scale (the guarded helper).
            agg = jax.lax.psum(h * gb_local, axes) / n_clients
            fresh_blocks = agg[idx]
        elif one_bit:
            # §Perf: prototype-style one-bit uplink (sign + FSK majority
            # vote, Sec. V-B) — votes ride the channel as int8 within the
            # model axis, widened to int16 across the remaining axes
            # (worst-case sum 512 < 2^15), then the server takes the sign.
            votes = jnp.where(gb_local[idx] >= 0, 1, -1).astype(jnp.int8)
            s1 = jax.lax.psum(votes, "model").astype(jnp.int16)
            rest = tuple(a for a in axes if a != "model")
            s2 = jax.lax.psum(s1, rest) if rest else s1
            fresh_blocks = jnp.where(s2 >= 0, 1.0, -1.0).astype(jnp.float32)
        else:
            compact = h * gb_local[idx]                    # (kb, block)
            # static mesh-size denominator — safe (see the 1/N audit note
            # on the baseline branch above)
            fresh_blocks = jax.lax.psum(compact, axes) / n_clients
        noise = noise_std / n_clients * jax.random.normal(
            jax.random.fold_in(key, 1), fresh_blocks.shape, jnp.float32)
        fresh_blocks = fresh_blocks + noise
        # --- Eq. (8)-(10) at block granularity ------------------------------
        g_new = gp.astype(jnp.float32).at[idx].set(fresh_blocks)
        # Eq. (10) with the engine's staleness clip: without it the block
        # AoU grows unbounded over a long run and breaks the int8-safety
        # invariant (DESIGN.md §5) the coordinate-level paths guarantee
        age_next = jnp.minimum((age_b + 1.0).at[idx].set(0.0), AGE_CAP)
        ctrl_next = None
        if adaptive_km:
            # close the loop at the device-as-client scale: the block-AoU
            # histogram drives the same in-graph controller the big-model
            # trainer carries (replicated inputs -> identical successor
            # state on every shard, no collective needed)
            from repro.kernels import ref
            _, age_hist = ref.strided_hists_ref(
                score, age_next, jnp.ones((nb,), bool),
                packing.hist_stride(nb))
            ctrl_next = budget.controller_state_to_vec(
                bctrl.update(cstate, age_hist))
        g_new_flat = g_new.reshape(-1)[:d]
        w_next = w_flat - 0.01 * g_new_flat.astype(w_flat.dtype)
        loss_mean = jax.lax.pmean(loss, axes)
        return (w_next, g_new_flat.astype(g_prev.dtype), age_next,
                ctrl_next, loss_mean)

    if adaptive_km:
        fl_oac_step = fl_oac_core
    else:
        def fl_oac_step(w_flat, g_prev, age_b, batch, seed):
            w, g, a, _, loss = fl_oac_core(w_flat, g_prev, age_b, None,
                                           batch, seed)
            return w, g, a, loss

    batch_specs = {
        "tokens": SDS((n_clients * local_batch, seq_len), jnp.int32),
        "labels": SDS((n_clients * local_batch, seq_len), jnp.int32),
    }
    b_pspec = {"tokens": P(axes, None), "labels": P(axes, None)}
    ctrl_in = (P(),) if adaptive_km else ()
    fn = jax.shard_map(fl_oac_step, mesh=mesh,
                       in_specs=(P(), P(), P(), *ctrl_in, b_pspec, P()),
                       out_specs=(P(), P(), P(), *ctrl_in, P()),
                       check_vma=False)
    named = lambda s: shlib.to_named(s, mesh)
    repl = NamedSharding(mesh, P())
    ctrl_sh = (repl,) if adaptive_km else ()
    ctrl_abs = ((SDS((budget.CONTROLLER_STATE_SIZE,), jnp.float32),)
                if adaptive_km else ())
    in_sh = (repl, repl, repl, *ctrl_sh, named(b_pspec), repl)
    out_sh = (repl, repl, repl, *ctrl_sh, repl)
    input_specs = (SDS((d,), jnp.float32), SDS((d,), jnp.float32),
                   SDS((nb,), jnp.float32), *ctrl_abs, batch_specs,
                   SDS((), jnp.int32))
    meta = {"kind": "fl_oac", "d": d, "blocks": nb, "kb": kb,
            "n_clients": n_clients, "rho": rho, "baseline": baseline,
            "one_bit": one_bit, "adaptive_km": adaptive_km,
            "scans": {"layers": cfg.n_scan_blocks}}
    return StepBundle(fn, in_sh, out_sh, input_specs, meta)

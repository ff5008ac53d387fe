"""Packed server phase benchmark: per-leaf loop vs ONE fused FAIR-k pass.

Times the three server-phase execution strategies on a transformer-scale
parameter pytree (per-layer leaves, torch-style — the worst case for the
per-leaf loop):

* ``per_leaf``    — the historical path: one sampled-quantile estimation +
  one ``fairk_update`` launch per parameter leaf (~100 of each per step).
* ``packed``      — core.packing: pack (g, g_prev, age) into lane-aligned
  flat buffers, ONE quantile estimation + ONE fused pass for the whole
  model, unpack.
* ``packed_warm`` — packed with warm-start thresholds on a steady-state
  round: the strided-sample quantile pass is skipped entirely (lax.cond on
  the carried threshold state).
* ``persisted``   — the launch.steps production shape: g_prev / age (and
  the EF residual) live as flat buffers ACROSS rounds, so a steady-state
  round packs exactly ONE tree (the fresh grads) and unpacks exactly ONE
  (g_t for the optimizer) — zero re-pack copies of the carried state.
  This is the pre-fused-stats production path: its round still pays 3
  trace-time reads of the packed gradient buffer (quantile bootstrap +
  fused kernel + masked count pass).
* ``persisted_ef`` — persisted plus the fused kernel's residual
  (error-feedback) stage.
* ``persisted_warm`` — persisted on a steady-state round whose lax.cond
  skips the quantile pass at runtime (the count passes remain).
* ``fused_stats``  — the one-HBM-pass round (DESIGN.md §11): counts and
  threshold-re-estimation histograms emitted from inside the kernel, so
  the steady-state round traces exactly ONE read of the gradient buffer
  and even trust-region re-estimation rounds never re-read it.
* ``adaptive``     — fused_stats plus the in-graph budget controller
  (core/controller.py, DESIGN.md §12): the k_M/k split rides as traced
  controller state and the update runs inside the same compiled round.
  Still ONE read of g, and — asserted by the controller's trace counter —
  ONE compilation across arbitrarily many k_m_frac operating points.
* ``async``        — the ``--async-agg`` double-buffered round
  (DESIGN.md §13): the straggler share of the fresh grads is deferred
  into the carried ``shadow`` buffer, last round's deferred share merges
  in its place with ``straggler_lag`` rounds of extra age, and the
  optimizer consumes LAST round's merged gradient (``pending``).  The
  optimizer-facing unpack therefore depends only on carried state — the
  round's pack + fused kernel sits off the optimizer's critical path,
  and ``overlap_ratio`` measures the wall-clock fraction of the round
  that overlap can hide.  Still 1 pack, 1 unpack, ONE read of g.
* ``sanitize``     — the graceful-degradation round's PRODUCTION shape
  (DESIGN.md §14): non-finite masking armed inside the fused launch, no
  simulated faults.  ``sanitize_vs_fused`` is the <=5%
  robustness-overhead claim: the masking is a few elementwise ops riding
  the one kernel pass, not a second pass.
* ``chaos``        — the same round under the in-graph fault harness:
  per-round NaN/Inf corruption of the aggregated uplink plus
  block-granular deep-fade erasures, degraded through ``sanitize=True``.
  The injection's full-buffer PRNG draws are a simulation-only cost
  (dominant on CPU-XLA, cheap on TPU) — structurally the round still
  pays 1 pack, 1 unpack, ONE read of g.
* ``channel``      — the wireless fading round (DESIGN.md §16): the
  carried per-block AR(1) fading chain advances in-graph, truncation
  outages erase through the same sanitize path, the CSI misalignment
  factor is one elementwise multiply — same 1-pack/1-unpack/1-read
  discipline.

Emits CSV rows through ``benchmarks.run`` and writes
benchmarks/artifacts/packed_bench.json.  ``--smoke`` runs a tiny pytree and
asserts the structural claims (packed traces exactly ONE fused update vs
one per leaf; the persisted path performs ZERO re-pack copies of
g_prev/age per steady-state round; the fused_stats round traces exactly
ONE read of the packed gradient buffer vs 3; the adaptive round keeps the
one-read invariant and never recompiles across split changes) — wired
into CI, which also guards the measured speedup ratios against
benchmarks/BENCH_packed.json (tools/check_bench_regression.py).

  PYTHONPATH=src python -m benchmarks.packed_bench [--full | --smoke]
"""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from repro.core import channel, controller, faults, packing
from repro.core.engine import EngineConfig, SelectionEngine, index_jitter
from repro.kernels import ops


def timed_med(fn, repeats=3):
    """Median-of-N single-round timing (us).  The per-round variants
    differ by tens of ms on a ~100 ms base; a mean over back-to-back runs
    lets one co-tenant hiccup swamp the ratio, the median does not."""
    out = fn()                                  # warmup / compile
    ts = []
    for _ in range(max(repeats, 5)):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6, out


def make_transformer_tree(n_layers: int, d_model: int, vocab: int,
                          seed: int = 0):
    """Per-layer transformer pytree (unstacked leaves — the per-leaf loop's
    worst case and the layout's target shape)."""
    rng = np.random.default_rng(seed)
    ff = 4 * d_model

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype("f4"))

    tree = {"embed": arr(vocab, d_model), "head": arr(d_model, vocab),
            "final_norm": arr(d_model)}
    for i in range(n_layers):
        tree[f"layer_{i:02d}"] = {
            "wq": arr(d_model, d_model), "wk": arr(d_model, d_model),
            "wv": arr(d_model, d_model), "wo": arr(d_model, d_model),
            "wu": arr(d_model, ff), "wd": arr(ff, d_model),
            "norm1": arr(d_model), "norm2": arr(d_model),
        }
    return tree


def _server_state(tree, seed=1):
    rng = np.random.default_rng(seed)
    g_prev = jax.tree.map(
        lambda p: jnp.asarray(rng.standard_normal(p.shape).astype("f4")),
        tree)
    age = jax.tree.map(
        lambda p: jnp.asarray(rng.integers(0, 40, p.shape).astype("i1")),
        tree)
    return g_prev, age


def _mk_engine(backend, d_or_layout, *, warm=False, rho=0.1,
               fused_stats=False):
    cfg = EngineConfig(policy="fairk", backend=backend, rho=rho,
                       k_m_frac=0.75, warm_start=warm,
                       fused_stats=fused_stats)
    if backend == "packed":
        return SelectionEngine(cfg, d_or_layout.d_packed,
                               layout=d_or_layout)
    return SelectionEngine(cfg, d_or_layout)


def build_per_leaf_fn(tree):
    """The historical update_phase: per-leaf threshold engines."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    engines = [_mk_engine("threshold", int(np.prod(l.shape)))
               for l in leaves]

    def per_leaf(g_tree, gp_tree, age_tree):
        gs = treedef.flatten_up_to(g_tree)
        gps = treedef.flatten_up_to(gp_tree)
        ages = treedef.flatten_up_to(age_tree)
        out_g, out_age = [], []
        for eng, g, gp, ag in zip(engines, gs, gps, ages):
            g_t, age_next, _ = eng.select_and_merge(
                g.reshape(-1), gp.reshape(-1).astype(jnp.float32),
                ag.reshape(-1).astype(jnp.float32))
            out_g.append(g_t.reshape(g.shape))
            out_age.append(age_next.reshape(g.shape).astype(jnp.int8))
        return (jax.tree_util.tree_unflatten(treedef, out_g),
                jax.tree_util.tree_unflatten(treedef, out_age))

    return jax.jit(per_leaf), len(leaves)


def build_packed_fn(tree, *, warm):
    layout = packing.PackedLayout.from_tree(tree)
    eng = _mk_engine("packed", layout, warm=warm)

    def packed(g_tree, gp_tree, age_tree, tstate):
        g_t, age_tree_out, stats = eng.select_and_merge_tree(
            g_tree, gp_tree, age_tree, tstate=tstate)
        return (g_t,
                jax.tree.map(lambda x: x.astype(jnp.int8), age_tree_out),
                stats["tstate"])

    return jax.jit(packed), layout, eng


def build_persisted_fn(tree, *, warm, error_feedback=False,
                       fused_stats=False):
    """The launch.steps._packed_server_phase shape: carried state is FLAT
    (g_prev bf16, age int8, optional EF residual f32) — only the fresh
    grads are packed, only the optimizer-facing g_t is unpacked.
    ``fused_stats=True`` is the one-HBM-pass round (counts + histograms
    out of the kernel, thresholds re-estimated from the carried state)."""
    layout = packing.PackedLayout.from_tree(tree)
    eng = _mk_engine("packed", layout, warm=warm, fused_stats=fused_stats)

    def persisted(g_tree, gp_flat, age_flat, res_flat, tstate):
        g_flat = layout.pack(g_tree)           # the only pack per round
        g_t, age_next, stats = eng.select_and_merge(
            g_flat, gp_flat, age_flat, tstate=tstate, residual=res_flat)
        g_t_tree = layout.unpack(g_t, cast=False)   # optimizer-facing tree
        return (g_t_tree, g_t.astype(jnp.bfloat16),
                age_next.astype(jnp.int8),
                stats.get("residual"), stats["tstate"])

    def flat_state(gp_tree, age_tree):
        gp = layout.pack(gp_tree).astype(jnp.bfloat16)
        ag = layout.pack_age(age_tree).astype(jnp.int8)
        res = (jnp.zeros((layout.d_packed,), jnp.float32)
               if error_feedback else None)
        return gp, ag, res

    return jax.jit(persisted), flat_state, layout


def build_adaptive_fn(tree, *, rho=0.1):
    """The adaptive-``k_m`` production round: the persisted fused-stats
    shape plus the in-graph BudgetController — the split comes off the
    carried controller state and the controller update rides the same
    compiled round (launch.steps._packed_server_phase with
    ``adaptive_km``)."""
    layout = packing.PackedLayout.from_tree(tree)
    eng = _mk_engine("packed", layout, warm=True, rho=rho, fused_stats=True)
    bc = controller.BudgetController(rho=rho)

    def adaptive(g_tree, gp_flat, age_flat, tstate, cvec):
        cs = controller.controller_state_from_vec(cvec)
        g_flat = layout.pack(g_tree)           # the only pack per round
        g_t, age_next, stats = eng.select_and_merge(
            g_flat, gp_flat, age_flat, tstate=tstate,
            k_m_frac=cs["k_m_frac"])
        cs = bc.update(cs, stats["age_hist"], stats["mag_hist"])
        g_t_tree = layout.unpack(g_t, cast=False)
        return (g_t_tree, g_t.astype(jnp.bfloat16),
                age_next.astype(jnp.int8), stats["tstate"],
                controller.controller_state_to_vec(cs))

    return jax.jit(adaptive), layout


def build_async_fn(tree, *, rho=0.1, straggler_frac=0.25, straggler_lag=1):
    """The ``--async-agg`` production round (DESIGN.md §13): the
    double-buffered launch.steps._packed_server_phase shape on top of the
    fused-stats engine.  The straggler share of the fresh grads defers
    into the carried ``shadow`` buffer, last round's deferred share merges
    in its place carrying ``straggler_lag`` rounds of extra age, and the
    optimizer-facing unpack reads the carried ``pending`` buffer — it
    depends on NOTHING this round computed, which is what makes the round
    overlappable with the next round's client compute."""
    layout = packing.PackedLayout.from_tree(tree)
    eng = _mk_engine("packed", layout, warm=True, rho=rho, fused_stats=True)

    def async_round(g_tree, gp_flat, age_flat, tstate, shadow, pending):
        g_flat = layout.pack(g_tree)           # the only pack per round
        strag = (index_jitter(layout.d_packed)
                 < straggler_frac).astype(jnp.float32)
        new_shadow = (g_flat * strag).astype(jnp.bfloat16)
        g_flat = (g_flat * (1.0 - strag) + shadow.astype(jnp.float32))
        g_t, age_next, stats = eng.select_and_merge(
            g_flat, gp_flat, age_flat, tstate=tstate,
            age_lag=straggler_lag)
        out_tree = layout.unpack(pending, cast=False)
        return (out_tree, g_t.astype(jnp.bfloat16),
                age_next.astype(jnp.int8), stats["tstate"],
                new_shadow, g_t.astype(jnp.bfloat16))

    def critical_path(pending):
        # exactly the slice of the round the optimizer must wait for
        return layout.unpack(pending, cast=False)

    return jax.jit(async_round), jax.jit(critical_path), layout


def build_chaos_fn(tree, *, rho=0.1, fade=0.05, nan_rate=1e-4):
    """The graceful-degradation round (DESIGN.md §14): the fused-stats
    production shape with the fault channels ON — per-round NaN/Inf
    corruption of the aggregated uplink plus block-granular deep-fade
    erasures, degraded through ``sanitize=True`` so poisoned coordinates
    are masked out of BOTH selection stages in the same kernel pass
    ('unsent': age climbs, EF mass rides through).  The structural claim
    is that robustness is free at the memory level: corruption/erasure
    injection is elementwise math on the packed buffer — not an extra
    instrumented read — and the sanitize masking rides the one fused
    kernel launch, so the chaos round keeps the sync round's exact
    1-pack/1-unpack/1-read discipline."""
    layout = packing.PackedLayout.from_tree(tree)
    eng = _mk_engine("packed", layout, warm=True, rho=rho, fused_stats=True)
    fcfg = faults.FaultConfig(fade=fade, nan_rate=nan_rate)

    def chaos_round(g_tree, gp_flat, age_flat, tstate, key):
        g_flat = layout.pack(g_tree)           # the only pack per round
        k_c, k_f = jax.random.split(key)
        g_flat = faults.corrupt(g_flat, k_c, fcfg)
        erase = faults.fade_mask(k_f, layout.d_packed, fcfg)
        g_t, age_next, stats = eng.select_and_merge(
            g_flat, gp_flat, age_flat, tstate=tstate, erase=erase,
            sanitize=True)
        g_t_tree = layout.unpack(g_t, cast=False)
        return (g_t_tree, g_t.astype(jnp.bfloat16),
                age_next.astype(jnp.int8), stats["tstate"])

    def sanitize_round(g_tree, gp_flat, age_flat, tstate):
        # the PRODUCTION cost of robustness: sanitize masking armed, no
        # simulated faults injected (a real deployment's faults arrive in
        # the uplink itself — the corrupt/fade draws above are the chaos
        # harness's cost, paid only when simulating)
        g_flat = layout.pack(g_tree)
        g_t, age_next, stats = eng.select_and_merge(
            g_flat, gp_flat, age_flat, tstate=tstate, sanitize=True)
        g_t_tree = layout.unpack(g_t, cast=False)
        return (g_t_tree, g_t.astype(jnp.bfloat16),
                age_next.astype(jnp.int8), stats["tstate"])

    return jax.jit(chaos_round), jax.jit(sanitize_round), layout


def build_channel_fn(tree, *, rho=0.1, pmax=10.0, gmin=0.3, csi_err=0.05):
    """The wireless fading round (DESIGN.md §16): the fused-stats
    production shape with the truncated-channel-inversion layer ON — the
    carried per-block AR(1) fading chain advances in-graph, deep-outage
    blocks erase through the same ``sanitize=True`` path the fault
    harness uses, and the CSI misalignment factor rides the packed buffer
    as one elementwise multiply.  The structural claim mirrors the chaos
    round's: the channel is elementwise math plus a tiny ``(2 n_blocks,)``
    carried chain — not an extra instrumented read of g, not an extra
    tree copy, not a second kernel launch."""
    layout = packing.PackedLayout.from_tree(tree)
    eng = _mk_engine("packed", layout, warm=True, rho=rho, fused_stats=True)
    ccfg = channel.ChannelConfig(n_clients=16, pmax=pmax, gmin=gmin,
                                 csi_err=csi_err, rho_f=0.5)

    def channel_round(g_tree, gp_flat, age_flat, tstate, fad, key):
        g_flat = layout.pack(g_tree)           # the only pack per round
        k_f, k_c = jax.random.split(key)
        new_fad, erase = channel.block_outage(fad, k_f, layout.d_packed,
                                              ccfg)
        g_flat = g_flat * channel.csi_block_factor(k_c, layout.d_packed,
                                                   ccfg)
        g_t, age_next, stats = eng.select_and_merge(
            g_flat, gp_flat, age_flat, tstate=tstate, erase=erase,
            sanitize=True)
        g_t_tree = layout.unpack(g_t, cast=False)
        return (g_t_tree, g_t.astype(jnp.bfloat16),
                age_next.astype(jnp.int8), stats["tstate"], new_fad)

    fad0 = channel.init_block_fading(channel.n_blocks(layout.d_packed,
                                                      ccfg))
    return jax.jit(channel_round), fad0, layout


def _traced_counts(fn, *args):
    """(fused launches, packs, unpacks, g reads) ONE trace of ``fn``
    records — the structural packed-vs-per-leaf, persisted-state and
    one-HBM-pass claims, independent of timers.  Counted in a single
    ``eval_shape`` because a second trace with the same signature hits the
    jit cache and never re-runs the Python body (so its counters would
    read zero)."""
    before = (ops.FAIRK_UPDATE_CALLS, packing.PACK_CALLS,
              packing.UNPACK_CALLS, packing.G_READS)
    jax.eval_shape(fn, *args)
    return (ops.FAIRK_UPDATE_CALLS - before[0],
            packing.PACK_CALLS - before[1],
            packing.UNPACK_CALLS - before[2],
            packing.G_READS - before[3])


def bench_tree(n_layers, d_model, vocab, repeats=3):
    tree = make_transformer_tree(n_layers, d_model, vocab)
    g_prev, age = _server_state(tree)
    per_leaf_fn, n_leaves = build_per_leaf_fn(tree)
    packed_fn, layout, eng = build_packed_fn(tree, warm=False)
    warm_fn, _, _ = build_packed_fn(tree, warm=True)
    persisted_fn, flat_state, _ = build_persisted_fn(tree, warm=False)
    persisted_warm_fn, _, _ = build_persisted_fn(tree, warm=True)
    persisted_ef_fn, flat_state_ef, _ = build_persisted_fn(
        tree, warm=False, error_feedback=True)
    fused_fn, _, _ = build_persisted_fn(tree, warm=True, fused_stats=True)
    adaptive_fn, _ = build_adaptive_fn(tree)
    async_fn, async_crit_fn, _ = build_async_fn(tree)
    chaos_fn, sanitize_fn, _ = build_chaos_fn(tree)
    channel_fn, fad0, _ = build_channel_fn(tree)

    ts0 = packing.init_threshold_state()
    gp_flat, age_flat, _ = flat_state(g_prev, age)
    _, _, res_flat = flat_state_ef(g_prev, age)
    calls_per_leaf, _, _, _ = _traced_counts(per_leaf_fn, tree, g_prev, age)
    # per-round tree copies: the PR-2 re-pack path packs 3 trees + unpacks
    # 2; the persisted path packs 1 (fresh grads) + unpacks 1 (g_t) — the
    # carried g_prev/age (and EF residual) are NEVER re-packed
    calls_packed, *copies_packed, _ = _traced_counts(packed_fn, tree,
                                                     g_prev, age, ts0)
    # trace-time reads of the packed gradient buffer per round: the
    # pre-fused path pays 3 (quantile bootstrap + fused kernel + masked
    # count pass); the fused-stats round pays exactly 1 (the kernel)
    _, *copies_persisted, reads_persisted = _traced_counts(
        persisted_fn, tree, gp_flat, age_flat, None, ts0)
    _, *copies_persisted_ef, _ = _traced_counts(
        persisted_ef_fn, tree, gp_flat, age_flat, res_flat, ts0)
    _, *copies_fused, reads_fused = _traced_counts(
        fused_fn, tree, gp_flat, age_flat, None, ts0)
    # the adaptive round: count its reads at trace time, then EXECUTE the
    # same jitted function at several k_m_frac operating points — the
    # controller's trace counter must advance exactly once (the split is
    # data; changing it can never recompile)
    cvec0 = controller.controller_state_to_vec(
        controller.init_controller_state(0.75))
    traces_before = controller.UPDATE_TRACES
    _, *copies_adaptive, reads_adaptive = _traced_counts(
        adaptive_fn, tree, gp_flat, age_flat, ts0, cvec0)
    for frac in (0.25, 0.5, 0.9):
        cv = controller.controller_state_to_vec(
            controller.init_controller_state(frac))
        cv = jax.block_until_ready(
            adaptive_fn(tree, gp_flat, age_flat, ts0, cv))[4]
    adaptive_traces = controller.UPDATE_TRACES - traces_before
    # the async double-buffered round: same copy/read discipline as the
    # sync fused round — the shadow mixing is plain elementwise math, not
    # a re-read of the instrumented gradient buffer, and the pending swap
    # replaces (not adds to) the optimizer-facing unpack
    calls_async, *copies_async, reads_async = _traced_counts(
        async_fn, tree, gp_flat, age_flat, ts0, gp_flat, gp_flat)
    # the chaos round: corruption + fade injection and the sanitize
    # masking all ride the single fused launch — faults cost no extra
    # instrumented read of g and no extra tree copies
    chaos_key = jax.random.PRNGKey(7)
    calls_chaos, *copies_chaos, reads_chaos = _traced_counts(
        chaos_fn, tree, gp_flat, age_flat, ts0, chaos_key)
    calls_san, *copies_san, reads_san = _traced_counts(
        sanitize_fn, tree, gp_flat, age_flat, ts0)
    # the wireless channel round: fading advance, block outage erasure
    # and the CSI multiply all ride the single fused launch — the channel
    # costs no extra instrumented read of g and no extra tree copies
    chan_key = jax.random.PRNGKey(9)
    calls_chan, *copies_chan, reads_chan = _traced_counts(
        channel_fn, tree, gp_flat, age_flat, ts0, fad0, chan_key)

    res = {"n_leaves": n_leaves, "d_valid": layout.d_valid,
           "d_packed": layout.d_packed, "k": eng.budgets()[0],
           "fused_calls_per_leaf": calls_per_leaf,
           "fused_calls_packed": calls_packed,
           "copies_packed": tuple(copies_packed),
           "copies_persisted": tuple(copies_persisted),
           "copies_persisted_ef": tuple(copies_persisted_ef),
           "copies_fused_stats": tuple(copies_fused),
           "copies_adaptive": tuple(copies_adaptive),
           "g_reads_persisted": reads_persisted,
           "g_reads_fused_stats": reads_fused,
           "g_reads_adaptive": reads_adaptive,
           "adaptive_traces": adaptive_traces,
           "fused_calls_async": calls_async,
           "copies_async": tuple(copies_async),
           "g_reads_async": reads_async,
           "fused_calls_chaos": calls_chaos,
           "copies_chaos": tuple(copies_chaos),
           "g_reads_chaos": reads_chaos,
           "fused_calls_sanitize": calls_san,
           "copies_sanitize": tuple(copies_san),
           "g_reads_sanitize": reads_san,
           "fused_calls_channel": calls_chan,
           "copies_channel": tuple(copies_chan),
           "g_reads_channel": reads_chan}

    us, _ = timed(lambda: jax.block_until_ready(
        per_leaf_fn(tree, g_prev, age)), repeats=repeats)
    res["per_leaf_us"] = us
    us, (g_t, age_next, ts1) = timed_med(lambda: jax.block_until_ready(
        packed_fn(tree, g_prev, age, ts0)), repeats=repeats)
    res["packed_us"] = us
    us, _ = timed_med(lambda: jax.block_until_ready(
        persisted_fn(tree, gp_flat, age_flat, None, ts0)), repeats=repeats)
    res["persisted_us"] = us
    us, _ = timed_med(lambda: jax.block_until_ready(
        persisted_ef_fn(tree, gp_flat, age_flat, res_flat, ts0)),
        repeats=repeats)
    res["persisted_ef_us"] = us
    # steady-state warm round: a carried state whose counts track the
    # budget and whose prediction streak is established — the lax.cond
    # takes the warm branch and the quantile pass never executes
    k = res["k"]
    ts_warm = dict(ts1, n_sel=jnp.float32(k),
                   n_sel_m=jnp.float32(round(0.75 * k)),
                   init=jnp.float32(1.0), streak=jnp.float32(10.0))
    us, _ = timed_med(lambda: jax.block_until_ready(
        warm_fn(tree, g_prev, age, ts_warm)), repeats=repeats)
    res["packed_warm_us"] = us
    us, _ = timed_med(lambda: jax.block_until_ready(
        persisted_warm_fn(tree, gp_flat, age_flat, None, ts_warm)),
        repeats=repeats)
    res["persisted_warm_us"] = us
    # fused-stats steady state: same warm carried state, but with the
    # kernel-emitted histograms attached (what a real fused round carries)
    # — trust-tripped rounds cost the SAME program (hist re-estimation is
    # scalar work), so one number covers warm AND re-estimation rounds
    _, _, _, _, ts_f = fused_fn(tree, gp_flat, age_flat, None, ts0)
    ts_fused = dict(ts_f, n_sel=jnp.float32(k),
                    n_sel_m=jnp.float32(round(0.75 * k)),
                    init=jnp.float32(1.0), streak=jnp.float32(10.0))
    us, _ = timed_med(lambda: jax.block_until_ready(
        fused_fn(tree, gp_flat, age_flat, None, ts_fused)),
        repeats=repeats)
    res["fused_stats_us"] = us
    # adaptive steady state: the warm fused round plus the in-graph
    # controller — cv carries a settled (init=1, EMA'd) controller state
    # from the executions above, so the timed program is the production
    # shape
    us, _ = timed_med(lambda: jax.block_until_ready(
        adaptive_fn(tree, gp_flat, age_flat, ts_fused, cv)),
        repeats=repeats)
    res["adaptive_us"] = us
    # async steady state: the same warm fused round plus the double
    # buffer (shadow/pending ride as bf16 flats — gp_flat stands in for
    # both, their values do not change the program).  The critical path
    # is timed separately: the optimizer only ever waits on the pending
    # unpack, everything else can overlap the next round's client compute
    us, _ = timed_med(lambda: jax.block_until_ready(
        async_fn(tree, gp_flat, age_flat, ts_fused, gp_flat, gp_flat)),
        repeats=repeats)
    res["async_us"] = us
    us, _ = timed(lambda: jax.block_until_ready(async_crit_fn(gp_flat)),
                  repeats=max(repeats, 5))
    res["async_critical_path_us"] = us
    # chaos steady state: the fused round with the fault channels on —
    # the sanitize overhead claim (DESIGN.md §14) is that degradation
    # costs a few elementwise ops riding the same program, not a second
    # pass, so chaos_vs_fused should sit near 1.0
    us, _ = timed_med(lambda: jax.block_until_ready(
        chaos_fn(tree, gp_flat, age_flat, ts_fused, chaos_key)),
        repeats=repeats)
    res["chaos_us"] = us
    us, _ = timed_med(lambda: jax.block_until_ready(
        sanitize_fn(tree, gp_flat, age_flat, ts_fused)),
        repeats=repeats)
    res["sanitize_us"] = us
    # wireless channel steady state: the fused round with the fading
    # layer on — like chaos_vs_fused, the ratio is recorded for the
    # artifact, the structural counters are what CI guards
    us, _ = timed_med(lambda: jax.block_until_ready(
        channel_fn(tree, gp_flat, age_flat, ts_fused, fad0, chan_key)),
        repeats=repeats)
    res["channel_us"] = us
    res["speedup_packed"] = res["per_leaf_us"] / res["packed_us"]
    res["speedup_warm"] = res["per_leaf_us"] / res["packed_warm_us"]
    res["warm_vs_cold"] = res["packed_us"] / res["packed_warm_us"]
    res["speedup_persisted"] = res["per_leaf_us"] / res["persisted_us"]
    res["persisted_vs_repack"] = res["packed_us"] / res["persisted_us"]
    # the headline fused-stats ratios: vs the pre-fused production round
    # (persisted, 3 reads: the cost the current path pays on every
    # bootstrap / trust-region re-estimation round — the fused path never
    # pays it again) and vs the pre-fused packed steady state
    res["speedup_fused_stats"] = res["persisted_us"] / res["fused_stats_us"]
    res["fused_vs_packed_warm"] = (res["packed_warm_us"]
                                   / res["fused_stats_us"])
    res["fused_vs_persisted_warm"] = (res["persisted_warm_us"]
                                      / res["fused_stats_us"])
    # controller overhead: the adaptive round vs the fused steady-state
    # round it extends — a ~1.0 ratio of near-identical programs, so it
    # travels across runner hardware and is safe to guard
    res["adaptive_vs_fused"] = res["fused_stats_us"] / res["adaptive_us"]
    # wall-clock round-overlap ratio (the tentpole's headline number):
    # the fraction of the async round the double buffer removes from the
    # optimizer's critical path — everything except the pending unpack
    # can run behind the next round's client compute
    res["overlap_ratio"] = (1.0 - res["async_critical_path_us"]
                            / res["async_us"])
    res["async_vs_fused"] = res["fused_stats_us"] / res["async_us"]
    # sanitize/fault overhead: the chaos round vs the fused steady-state
    # round it extends — like adaptive_vs_fused this compares
    # near-identical programs, kept in the artifact for the record (the
    # acceptance target is >= ~0.95, i.e. <= ~5% overhead) but NOT
    # guarded: the shared-runner denominator swings too much for a gate.
    # sanitize_vs_fused is the <=5% production-overhead claim (masking
    # armed, no injected faults — ~1.0); chaos_vs_fused/chaos_vs_async
    # include the chaos harness's per-round PRNG draws over the full
    # packed buffer, a simulation-only cost that dominates on CPU-XLA
    res["sanitize_vs_fused"] = res["fused_stats_us"] / res["sanitize_us"]
    res["chaos_vs_fused"] = res["fused_stats_us"] / res["chaos_us"]
    res["chaos_vs_async"] = res["async_us"] / res["chaos_us"]
    res["channel_vs_fused"] = res["fused_stats_us"] / res["channel_us"]

    # isolate the threshold stage: sampled quantile pass (bootstrap branch)
    # vs warm correction (a handful of scalar flops) — the work the warm
    # path eliminates on steady-state rounds
    warm_eng = _mk_engine("packed", layout, warm=True)
    g_buf = layout.pack(tree)
    age_buf = layout.pack_age(age)
    thr = jax.jit(lambda g, ag, ts:
                  warm_eng._packed_thresholds(g, ag, ts)[:2])
    us, _ = timed(lambda: jax.block_until_ready(
        thr(g_buf, age_buf, ts0)), repeats=max(repeats, 5))
    res["theta_bootstrap_us"] = us
    us, _ = timed(lambda: jax.block_until_ready(
        thr(g_buf, age_buf, ts_warm)), repeats=max(repeats, 5))
    res["theta_warm_us"] = us
    res["quantile_pass_eliminated_x"] = (res["theta_bootstrap_us"]
                                         / max(res["theta_warm_us"], 1e-9))
    return res


def run(fast: bool = True):
    shape = (12, 192, 8192) if fast else (24, 320, 32000)
    res = bench_tree(*shape)
    rows = [
        ("packed/per_leaf", res["per_leaf_us"],
         f"leaves={res['n_leaves']}"),
        ("packed/fused", res["packed_us"],
         f"speedup={res['speedup_packed']:.2f}x"),
        ("packed/fused_warm", res["packed_warm_us"],
         f"speedup={res['speedup_warm']:.2f}x"),
        ("packed/persisted", res["persisted_us"],
         f"vs_repack={res['persisted_vs_repack']:.2f}x"),
        ("packed/persisted_ef", res["persisted_ef_us"],
         f"copies={res['copies_persisted_ef']}"),
        ("packed/fused_stats", res["fused_stats_us"],
         f"vs_packed_warm={res['fused_vs_packed_warm']:.2f}x "
         f"vs_reestimation={res['speedup_fused_stats']:.2f}x "
         f"reads={res['g_reads_fused_stats']}"),
        ("packed/adaptive", res["adaptive_us"],
         f"vs_fused={res['adaptive_vs_fused']:.2f}x "
         f"reads={res['g_reads_adaptive']} "
         f"traces={res['adaptive_traces']}"),
        ("packed/async", res["async_us"],
         f"overlap={res['overlap_ratio']:.3f} "
         f"crit_us={res['async_critical_path_us']:.1f} "
         f"reads={res['g_reads_async']}"),
        ("packed/sanitize", res["sanitize_us"],
         f"vs_fused={res['sanitize_vs_fused']:.2f}x "
         f"reads={res['g_reads_sanitize']}"),
        ("packed/chaos", res["chaos_us"],
         f"vs_fused={res['chaos_vs_fused']:.2f}x "
         f"vs_async={res['chaos_vs_async']:.2f}x "
         f"reads={res['g_reads_chaos']}"),
        ("packed/channel", res["channel_us"],
         f"vs_fused={res['channel_vs_fused']:.2f}x "
         f"reads={res['g_reads_channel']}"),
    ]
    detail = {"tree": {"n_layers": shape[0], "d_model": shape[1],
                       "vocab": shape[2]}, **res,
              "note": "per_leaf = historical per-leaf loop; packed = one "
                      "fused pass (core.packing, re-packs state trees); "
                      "packed_warm = packed + warm-start thresholds "
                      "(steady-state round, no quantile pass); persisted = "
                      "flat g_prev/age carried across rounds (1 pack + 1 "
                      "unpack per round); persisted_ef = + the fused "
                      "kernel's residual (error-feedback) stage; "
                      "fused_stats = the one-HBM-pass round (counts + "
                      "histograms out of the kernel; re-estimation never "
                      "re-reads g).  Ratios: fused_vs_packed_warm = the "
                      "headline steady-state comparison vs the packed "
                      "BACKEND round as it ships today (warm re-pack "
                      "path); speedup_fused_stats = vs the persisted "
                      "round WITH its bootstrap, the 3-read cost the "
                      "pre-fused path pays on every cold / trust-region "
                      "re-estimation round; fused_vs_persisted_warm = "
                      "warm-round-to-warm-round (on CPU-XLA the count "
                      "passes partially fuse, so this ratio is modest "
                      "here — on TPU they are real extra HBM passes; the "
                      "structural 3-reads-to-1 claim is asserted at "
                      "trace level by --smoke either way); adaptive = "
                      "fused_stats + the in-graph k_M/k budget controller "
                      "(adaptive_vs_fused ~ 1: the controller is a few "
                      "hundred scalar flops riding the same round; "
                      "adaptive_traces = compilations observed across a "
                      "multi-split execution sweep, asserted == 1 by "
                      "--smoke); async = the --async-agg double-buffered "
                      "round (DESIGN.md §13): same 1-pack/1-unpack/1-read "
                      "discipline, the optimizer consumes the carried "
                      "pending buffer, so overlap_ratio = the wall-clock "
                      "fraction of the round off the optimizer's critical "
                      "path (guarded against the committed baseline); "
                      "sanitize = the graceful-degradation round's "
                      "PRODUCTION shape (DESIGN.md §14): non-finite "
                      "masking armed inside the fused launch, no "
                      "simulated faults — sanitize_vs_fused is the <=5% "
                      "robustness-overhead claim (~1.0); chaos = the "
                      "same round under the in-graph fault harness "
                      "(per-round NaN/Inf corruption + deep-fade "
                      "erasures), whose full-buffer PRNG draws are a "
                      "simulation-only cost that dominates on CPU-XLA — "
                      "structural counters guarded for both, ratios "
                      "recorded only (the shared-runner denominator "
                      "swings too much for a gate)"}
    out_dir = os.path.join(os.path.dirname(__file__), "artifacts")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "packed_bench.json"), "w") as f:
        json.dump(detail, f, indent=1)
    return rows, detail


def smoke() -> dict:
    """CI gate: structural claims on a tiny pytree (seconds, not minutes).

    Asserts (a) the packed server phase traces EXACTLY ONE fused update vs
    one per leaf for the loop, (b) the persisted path performs ZERO
    re-pack copies of the carried state per steady-state round — exactly
    1 pack (the fresh grads) and 1 unpack (the optimizer-facing g_t),
    vs 3 packs + 2 unpacks on the re-pack path — and (c) the fused-stats
    round traces EXACTLY ONE read of the packed gradient buffer (the
    kernel itself) where the pre-fused round traces 3 (quantile bootstrap
    + kernel + masked count pass), and (d) the async double-buffered round
    keeps all three invariants while its optimizer-facing critical path
    stays a strict sub-interval of the round.
    Deliberately NO wall-clock assertion:
    a single timing sample at tiny sizes is scheduler noise on shared
    runners — the speedup claim is checked against the committed baseline
    ratios by tools/check_bench_regression.py."""
    res = bench_tree(2, 32, 256, repeats=1)
    assert res["fused_calls_packed"] == 1, res
    assert res["fused_calls_per_leaf"] == res["n_leaves"], res
    assert res["copies_packed"] == (3, 2), res        # the PR-2 re-pack path
    assert res["copies_persisted"] == (1, 1), res     # zero state re-packs
    assert res["copies_persisted_ef"] == (1, 1), res  # EF adds no copies
    assert res["copies_fused_stats"] == (1, 1), res
    # the tentpole claim: ONE trace-time read of g per steady-state round
    assert res["g_reads_fused_stats"] == 1, res
    assert res["g_reads_persisted"] == 3, res         # what it replaces
    # the adaptive-controller claims: the split rides as data — the round
    # still reads g exactly once, adds no tree copies, and the SAME
    # compiled program served every k_m_frac operating point (one trace
    # of the controller body across the multi-split execution sweep)
    assert res["g_reads_adaptive"] == 1, res
    assert res["copies_adaptive"] == (1, 1), res
    assert res["adaptive_traces"] == 1, res
    # the async double-buffer claims: the shadow mixing is not a g
    # re-read, the pending swap replaces (not adds to) the unpack, and
    # the optimizer's critical path is a strict sub-interval of the round
    assert res["fused_calls_async"] == 1, res
    assert res["copies_async"] == (1, 1), res
    assert res["g_reads_async"] == 1, res
    assert 0.0 < res["overlap_ratio"] < 1.0, res
    # the chaos-round claims (DESIGN.md §14): corruption/fade injection
    # is elementwise math on the packed buffer and the sanitize masking
    # rides the one fused launch — faults add no instrumented read of g,
    # no extra tree copies, no extra kernel call
    assert res["fused_calls_chaos"] == 1, res
    assert res["copies_chaos"] == (1, 1), res
    assert res["g_reads_chaos"] == 1, res
    assert res["fused_calls_sanitize"] == 1, res
    assert res["copies_sanitize"] == (1, 1), res
    assert res["g_reads_sanitize"] == 1, res
    # the wireless-channel claims (DESIGN.md §16): the AR(1) fading
    # advance, the truncation-outage erasure and the CSI multiply all
    # ride the one fused launch — a channel-on round keeps the sync
    # round's exact 1-pack/1-unpack/1-read discipline
    assert res["fused_calls_channel"] == 1, res
    assert res["copies_channel"] == (1, 1), res
    assert res["g_reads_channel"] == 1, res
    out_dir = os.path.join(os.path.dirname(__file__), "artifacts")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "packed_bench_smoke.json"), "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res, indent=1))
    print(f"[packed_bench --smoke] OK: 1 fused call vs "
          f"{res['n_leaves']} per-leaf; persisted round = "
          f"{res['copies_persisted']} (pack, unpack) tree copies; "
          f"fused-stats round = {res['g_reads_fused_stats']} read of g "
          f"vs {res['g_reads_persisted']}; adaptive round = "
          f"{res['g_reads_adaptive']} read, {res['adaptive_traces']} "
          f"compilation across k_m_frac changes; async round = "
          f"{res['g_reads_async']} read, {res['copies_async']} copies, "
          f"overlap_ratio={res['overlap_ratio']:.3f}; chaos round = "
          f"{res['g_reads_chaos']} read, {res['copies_chaos']} copies "
          f"under injected faults; channel round = "
          f"{res['g_reads_channel']} read, {res['copies_channel']} "
          f"copies under wireless fading")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    rows, detail = run(fast=not args.full)
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    print(json.dumps({k: v for k, v in detail.items() if k != "tree"},
                     indent=1))


if __name__ == "__main__":
    main()

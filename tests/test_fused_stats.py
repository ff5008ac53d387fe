"""Fused selection statistics (DESIGN.md §11).

Pins the tentpole guarantees of the one-HBM-pass server round:

* the kernel-emitted counts are bit-exact vs the legacy two-pass
  accounting, and cross-backend consistent: exact ≡ threshold ≡ sharded ≡
  packed under ``exact_theta`` on tie-free inputs — for ``n_sel``,
  ``n_sel_m``, the magnitude/age histograms AND the thresholds derived
  from those histograms;
* pad coordinates (age = PAD_AGE sentinel) are excluded from every
  in-kernel counter and histogram;
* ``packing.hist_thresholds`` reproduces sampled-quantile-grade budget
  tracking from the histograms alone (incl. the degenerate-stage and
  empty-histogram fallbacks);
* the warm-start controller runs entirely on carried statistics: steady
  state keeps tracking the budget with ZERO trace-time reads of g beyond
  the fused kernel itself, on the packed AND the sharded backend;
* the packed server-state checkpoint (repro.checkpoint) round-trips the
  flat bf16/int8/f32 buffers + PackedLayout metadata bit-exactly and an
  exactly-restarted round reproduces the original.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint
from repro.core import packing
from repro.core.engine import EngineConfig, SelectionEngine
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh


def _tie_free(d, seed=0):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=d).astype("f4"))
    gp = jnp.asarray(rng.normal(size=d).astype("f4"))
    age = jnp.asarray(rng.permutation(d).astype("f4"))
    return g, gp, age


def _stats_of(stats):
    return (float(stats["n_selected"]) if "n_selected" in stats
            else float(stats["n_sel"]),
            float(stats["n_sel_m"]),
            np.asarray(stats["mag_hist"]),
            np.asarray(stats["age_hist"]))


# ---------------------------------------------------------------------------
# cross-backend parity of the fused statistics (the acceptance criterion)
# ---------------------------------------------------------------------------

class TestCrossBackendStatsParity:
    def test_exact_threshold_sharded_packed_agree(self):
        d = 4096
        g, gp, age = _tie_free(d)
        common = dict(policy="fairk", rho=0.1, k_m_frac=0.75,
                      exact_theta=True, fused_stats=True)
        ex = SelectionEngine(EngineConfig(backend="exact", **common), d)
        th = SelectionEngine(EngineConfig(backend="threshold", **common), d)
        mesh = make_mesh((1,), ("shard",))
        sh = SelectionEngine(EngineConfig(backend="sharded", **common), d,
                             mesh=mesh)
        lay = packing.PackedLayout.from_tree([jnp.zeros((d,))])
        assert lay.d_packed == d                   # lane-aligned, no pads
        pk = SelectionEngine(EngineConfig(backend="packed", **common), d,
                             layout=lay)
        outs = [jax.jit(e.select_and_merge)(g, gp, age)
                for e in (ex, th, sh, pk)]
        n0, nm0, mh0, ah0 = _stats_of(outs[0][2])
        for g_t, age_next, stats in outs[1:]:
            np.testing.assert_array_equal(np.asarray(outs[0][0]),
                                          np.asarray(g_t))
            np.testing.assert_array_equal(np.asarray(outs[0][1]),
                                          np.asarray(age_next))
            n, nm, mh, ah = _stats_of(stats)
            assert n == n0 and nm == nm0
            np.testing.assert_array_equal(mh0, mh)
            np.testing.assert_array_equal(ah0, ah)
        # histogram-derived thresholds are a pure function of the (equal)
        # histograms -> equal across backends
        thetas = [packing.hist_thresholds(
            jnp.asarray(mh0), jnp.asarray(ah0), rho=0.1, k_m_frac=0.75)]
        for _, _, stats in outs[1:]:
            _, _, mh, ah = _stats_of(stats)
            tm, ta = packing.hist_thresholds(jnp.asarray(mh),
                                             jnp.asarray(ah),
                                             rho=0.1, k_m_frac=0.75)
            assert float(tm) == float(thetas[0][0])
            assert float(ta) == float(thetas[0][1])

    def test_counts_match_legacy_two_pass_accounting(self):
        """Bit-exact vs the accounting the fused path replaces:
        n_sel == (age'==0).sum(), n_sel_m == (sel & |score|>=θ_M).sum()."""
        d = 8192
        g, gp, age = _tie_free(d, seed=3)
        res = jnp.asarray(
            np.random.default_rng(4).normal(size=d).astype("f4"))
        for fused in (False, True):
            eng = SelectionEngine(
                EngineConfig(policy="fairk", backend="packed", rho=0.1,
                             k_m_frac=0.75, warm_start=True,
                             fused_stats=fused),
                d, layout=packing.PackedLayout.from_tree([jnp.zeros((d,))]))
            _, age_next, stats = eng.select_and_merge(
                g, gp, age, residual=res,
                tstate=packing.init_threshold_state())
            ts = stats["tstate"]
            sel = (np.asarray(age_next) == 0.0).astype(np.float32)
            score = np.asarray(g) + np.asarray(res)
            tm = float(stats["theta_m"])
            if fused:
                fused_counts = (float(ts["n_sel"]), float(ts["n_sel_m"]))
            assert float(ts["n_sel"]) == sel.sum()
            assert float(ts["n_sel_m"]) == (sel
                                            * (np.abs(score) >= tm)).sum()
        # and the two modes agree with each other (same θ bootstrap on
        # round 0 would differ: legacy samples quantiles, fused starts
        # from the empty histogram — so compare against the realised
        # masks, which is what the assertions above already did)
        assert fused_counts[0] > 0


# ---------------------------------------------------------------------------
# pad exclusion from every in-kernel counter
# ---------------------------------------------------------------------------

class TestPadExclusion:
    @pytest.mark.parametrize("mode", ["ref", "interpret"])
    def test_interior_pads_never_counted(self, mode):
        rng = np.random.default_rng(7)
        d = 2048
        g = jnp.asarray(rng.normal(size=d).astype("f4"))
        gp = jnp.asarray(rng.normal(size=d).astype("f4"))
        age = jnp.asarray(rng.integers(0, 40, d).astype("f4"))
        pad = np.zeros(d, bool)
        pad[300:812] = True                     # interior pad block
        g = g.at[300:812].set(7.7)              # huge |g|: would select
        age = age.at[300:812].set(packing.PAD_AGE)
        g_t, age_next, _, stats = ops.fairk_stats_update(
            g, gp, age, jnp.float32(0.5), jnp.float32(0.0), mode=mode,
            block_size=256)
        n_valid = int((~pad).sum())
        # θ_A = 0 selects every valid coordinate; pads select nothing
        assert float(stats["n_sel"]) == n_valid
        assert float(stats["n_sel_m"]) <= n_valid
        stride = packing.hist_stride(d)
        n_sampled = int((~pad)[::stride].sum())
        assert float(stats["mag_hist"].sum()) == n_sampled
        assert float(stats["age_hist"].sum()) == n_sampled
        # the pads' huge magnitude must not appear in the histogram: all
        # sampled |score| < 2 except the pad 7.7s
        top_bin = int(np.asarray(packing.mag_bin(jnp.float32(7.7))))
        assert float(stats["mag_hist"][top_bin]) == 0.0

    @pytest.mark.parametrize("mode", ["ref", "interpret"])
    def test_kernel_equals_oracle_with_pads(self, mode):
        rng = np.random.default_rng(9)
        d = 5000                                # odd: exercises tail pads
        g = jnp.asarray(rng.normal(size=d).astype("f4"))
        gp = jnp.asarray(rng.normal(size=d).astype("f4"))
        age = jnp.asarray((rng.permutation(d) % 120).astype("f4"))
        res = jnp.asarray(rng.normal(size=d).astype("f4"))
        fresh = jnp.where(g + res >= 0, 1.0, -1.0)
        out_r = ops.fairk_stats_update(g, gp, age, jnp.float32(1.1),
                                       jnp.float32(60.0), residual=res,
                                       fresh=fresh, mode="ref")
        out_k = ops.fairk_stats_update(g, gp, age, jnp.float32(1.1),
                                       jnp.float32(60.0), residual=res,
                                       fresh=fresh, mode=mode,
                                       block_size=512)
        for a, b in zip(out_r[:3], out_k[:3]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
        for key in ("n_sel", "n_sel_m", "mag_hist", "age_hist"):
            np.testing.assert_array_equal(np.asarray(out_r[3][key]),
                                          np.asarray(out_k[3][key]))

    @pytest.mark.parametrize("stride", [1, 32, 128, 256])
    @pytest.mark.parametrize("sanitize", [False, True])
    def test_kernel_sample_stride_equals_oracle(self, stride, sanitize):
        """Every sample stride the packed buffers use (lane- and row-strided
        samples of the (rows, 128) kernel tile), with pads, non-finite
        scores and a partial last block: the outputs and the summed
        per-block tiles equal the single-pass oracle."""
        from repro.kernels.fairk_update import (STATS_AGE_ROW,
                                                STATS_COUNT_ROW,
                                                STATS_MAG_ROW,
                                                fairk_stats_update_pallas)
        rng = np.random.default_rng(stride)
        d = 4 * 2048 + 384                     # partial last block
        g = rng.normal(size=d).astype("f4")
        if sanitize:                           # unsanitized NaN poisons g_t
            g[rng.integers(0, d, 64)] = np.nan
            g[rng.integers(0, d, 64)] = np.inf
        age = (rng.permutation(d) % 120).astype("f4")
        age[1000:1500] = packing.PAD_AGE
        g, age = jnp.asarray(g), jnp.asarray(age)
        gp = jnp.asarray(rng.normal(size=d).astype("f4"))
        tm, ta = jnp.float32(1.1), jnp.float32(60.0)
        g_r, age_r, _, want = ref.fairk_stats_update_ref(
            g, gp, age, tm, ta, stats_stride=stride, sanitize=sanitize)
        g_k, age_k, _, tiles = fairk_stats_update_pallas(
            g, gp, age, tm, ta, block_size=2048, interpret=True,
            stats_stride=stride, sanitize=sanitize)
        np.testing.assert_array_equal(np.asarray(g_r), np.asarray(g_k))
        np.testing.assert_array_equal(np.asarray(age_r), np.asarray(age_k))
        got = np.asarray(tiles).sum(axis=0)
        assert got[STATS_COUNT_ROW, 0] == float(want["n_sel"])
        assert got[STATS_COUNT_ROW, 1] == float(want["n_sel_m"])
        np.testing.assert_array_equal(got[STATS_MAG_ROW],
                                      np.asarray(want["mag_hist"]))
        np.testing.assert_array_equal(got[STATS_AGE_ROW],
                                      np.asarray(want["age_hist"]))


# ---------------------------------------------------------------------------
# histogram-derived thresholds
# ---------------------------------------------------------------------------

class TestHistThresholds:
    def test_tracks_budget_like_sampled_quantiles(self):
        rng = np.random.default_rng(1)
        d = 1 << 16
        g = jnp.asarray(rng.normal(size=d).astype("f4"))
        age = jnp.asarray((rng.permutation(d) % 80).astype("f4"))
        _, _, _, stats = ops.fairk_stats_update(
            g, jnp.zeros((d,)), age, jnp.float32(jnp.inf),
            jnp.float32(jnp.inf), mode="ref")
        tm, ta = packing.hist_thresholds(stats["mag_hist"],
                                         stats["age_hist"],
                                         rho=0.1, k_m_frac=0.75)
        n_m = int((np.abs(np.asarray(g)) >= float(tm)).sum())
        assert abs(n_m - 0.075 * d) < 0.1 * 0.075 * d   # within 10%
        rho_a = 0.025 / (1 - 0.075)
        # age_hist is the POST-update distribution; with θ = inf nothing
        # selects, so ages advanced by one — θ_A targets that shifted
        # distribution, as next round's selection will see it
        n_a = int(((np.asarray(age) + 1.0) >= float(ta)).sum())
        assert abs(n_a - rho_a * d) < 0.35 * rho_a * d

    def test_degenerate_stage_budgets_are_inf(self):
        h = jnp.ones((packing.STATS_MAG_BINS,), jnp.float32)
        a = jnp.ones((packing.STATS_AGE_BINS,), jnp.float32)
        tm, ta = packing.hist_thresholds(h, a, rho=0.1, k_m_frac=1.0)
        assert np.isinf(float(ta)) and np.isfinite(float(tm))
        tm, ta = packing.hist_thresholds(h, a, rho=0.1, k_m_frac=0.0)
        assert np.isinf(float(tm)) and np.isfinite(float(ta))

    def test_empty_histogram_selects_everything(self):
        """Round 0 fallback: no histogram yet -> θ = 0 -> one full-refresh
        round (every valid coordinate transmits), then self-heals."""
        z = jnp.zeros((packing.STATS_MAG_BINS,), jnp.float32)
        tm, ta = packing.hist_thresholds(z, z, rho=0.1, k_m_frac=0.75)
        assert float(tm) == 0.0 and float(ta) == 0.0


# ---------------------------------------------------------------------------
# warm-start on carried statistics (packed + sharded)
# ---------------------------------------------------------------------------

class TestFusedWarmStart:
    def _run_rounds(self, eng, lay, rounds=120, seed=0):
        rng = np.random.default_rng(seed)
        d = lay.d_packed
        gp = jnp.zeros((d,), jnp.float32)
        ag = lay.init_age(jnp.float32)
        ts = packing.init_threshold_state()
        step = jax.jit(lambda g, gp, ag, ts:
                       eng.select_and_merge(g, gp, ag, tstate=ts))
        sels = []
        for r in range(rounds):
            g = lay.pack([jnp.asarray(
                rng.normal(size=(lay.d_valid,)).astype("f4"))])
            g_t, ag2, stats = step(g, gp, ag, ts)
            ts, gp, ag = stats["tstate"], g_t, ag2
            sels.append(float(stats["n_selected"]))
        return np.asarray(sels), ts

    def test_packed_steady_state_tracks_budget_without_bootstrap(self):
        lay = packing.PackedLayout.from_tree([jnp.zeros((20000,))])
        eng = SelectionEngine(
            EngineConfig(policy="fairk", backend="packed", rho=0.1,
                         k_m_frac=0.75, warm_start=True, fused_stats=True),
            lay.d_packed, layout=lay)
        k = eng.budgets()[0]
        sels, ts = self._run_rounds(eng, lay)
        assert sels[0] == lay.d_valid          # round-0 full refresh
        assert abs(np.mean(sels[60:]) - k) < 0.15 * k
        assert max(sels[10:]) < 2.5 * k        # no cohort blow-ups
        assert float(ts["mag_hist"].sum()) > 0

    def test_packed_round_traces_one_read(self):
        """The acceptance claim at engine level: a steady-state
        select_and_merge traces exactly ONE read of g."""
        lay = packing.PackedLayout.from_tree([jnp.zeros((4096,))])
        eng = SelectionEngine(
            EngineConfig(policy="fairk", backend="packed", rho=0.1,
                         k_m_frac=0.75, warm_start=True, fused_stats=True),
            lay.d_packed, layout=lay)
        g, gp, age = _tie_free(lay.d_packed, seed=5)
        ts = packing.init_threshold_state()
        before = packing.G_READS
        jax.eval_shape(lambda *a: eng.select_and_merge(
            a[0], a[1], a[2], tstate=ts), g, gp, age)
        assert packing.G_READS - before == 1

    def test_sharded_warm_start_from_reduced_stats(self):
        """The sharded backend accepts tstate and its steady state stops
        bootstrapping per-shard thresholds every round: counts keep
        tracking the GLOBAL budget from the psum'd statistics."""
        d = 16384
        mesh = make_mesh((1,), ("shard",))
        eng = SelectionEngine(
            EngineConfig(policy="fairk", backend="sharded", rho=0.1,
                         k_m_frac=0.75, warm_start=True, fused_stats=True),
            d, mesh=mesh)
        k = eng.budgets()[0]
        rng = np.random.default_rng(11)
        gp = jnp.zeros((d,), jnp.float32)
        ag = jnp.zeros((d,), jnp.float32)
        ts = packing.init_threshold_state()
        step = jax.jit(lambda g, gp, ag, ts:
                       eng.select_and_merge(g, gp, ag, tstate=ts))
        sels = []
        for r in range(100):
            g = jnp.asarray(rng.normal(size=d).astype("f4"))
            g_t, ag2, stats = step(g, gp, ag, ts)
            ts, gp, ag = stats["tstate"], g_t, ag2
            sels.append(float(stats["n_selected"]))
        assert sels[0] == d                    # round-0 full refresh
        assert abs(np.mean(sels[60:]) - k) < 0.2 * k
        assert float(ts["n_sel_m"]) > 0

    def test_sharded_without_tstate_unchanged(self):
        """No tstate -> the historical per-shard bootstrap path (with the
        stats riding along when fused_stats is on)."""
        d = 8192
        mesh = make_mesh((1,), ("shard",))
        g, gp, age = _tie_free(d, seed=13)
        eng = SelectionEngine(
            EngineConfig(policy="fairk", backend="sharded", rho=0.1,
                         k_m_frac=0.75, fused_stats=True), d, mesh=mesh)
        _, _, stats = jax.jit(eng.select_and_merge)(g, gp, age)
        k = eng.budgets()[0]
        assert abs(float(stats["n_selected"]) - k) < 0.2 * k
        assert float(stats["mag_hist"].sum()) > 0


# ---------------------------------------------------------------------------
# threshold-state vector round trip (now carries the histograms)
# ---------------------------------------------------------------------------

def test_threshold_state_vec_round_trips_histograms():
    ts = packing.init_threshold_state()
    ts["theta_m"] = jnp.float32(1.5)
    ts["mag_hist"] = ts["mag_hist"].at[7].set(42.0)
    ts["age_hist"] = ts["age_hist"].at[100].set(3.0)
    vec = packing.threshold_state_to_vec(ts)
    assert vec.shape == (packing.THRESHOLD_STATE_SIZE,)
    back = packing.threshold_state_from_vec(vec)
    for f in packing.THRESHOLD_STATE_FIELDS:
        assert float(back[f]) == float(ts[f])
    np.testing.assert_array_equal(np.asarray(back["mag_hist"]),
                                  np.asarray(ts["mag_hist"]))
    np.testing.assert_array_equal(np.asarray(back["age_hist"]),
                                  np.asarray(ts["age_hist"]))


# ---------------------------------------------------------------------------
# packed server-state checkpoint round trip (satellite)
# ---------------------------------------------------------------------------

class TestServerStateCheckpoint:
    def _server_and_layout(self, seed=0):
        rng = np.random.default_rng(seed)
        leaves = [jnp.zeros((300,)), jnp.zeros((512,)), jnp.zeros((77,))]
        lay = packing.PackedLayout.from_tree(leaves)
        d = lay.d_packed
        server = {
            "g": jnp.asarray(rng.normal(size=d).astype("f4")
                             ).astype(jnp.bfloat16),
            "age": jnp.asarray(rng.integers(-1, 100, d).astype("i1")),
            "res": jnp.asarray(rng.normal(size=d).astype("f4")),
            "theta": packing.threshold_state_to_vec(
                packing.init_threshold_state()),
        }
        return server, lay

    def test_round_trip_bit_exact(self, tmp_path):
        server, lay = self._server_and_layout()
        path = checkpoint.save_server_state(
            str(tmp_path / "srv.npz"), server, layout=lay)
        back, meta = checkpoint.restore_server_state(path, layout=lay)
        assert set(back) == set(server)
        for k2 in server:
            a = np.asarray(server[k2])
            b = back[k2]
            assert a.dtype == b.dtype, k2
            np.testing.assert_array_equal(
                a.view(np.uint8), np.asarray(b).view(np.uint8))
        assert packing.layout_matches(lay, meta)

    def test_restore_rejects_mismatched_layout(self, tmp_path):
        server, lay = self._server_and_layout()
        path = checkpoint.save_server_state(
            str(tmp_path / "srv.npz"), server, layout=lay)
        other = packing.PackedLayout.from_tree([jnp.zeros((1024,))])
        with pytest.raises(ValueError):
            checkpoint.restore_server_state(path, layout=other)

    def test_exact_restart_round(self, tmp_path):
        """The acceptance test: a server round run from restored buffers
        is bit-identical to the round run from the originals."""
        server, lay = self._server_and_layout(seed=2)
        eng = SelectionEngine(
            EngineConfig(policy="fairk", backend="packed", rho=0.1,
                         k_m_frac=0.75, warm_start=True, fused_stats=True),
            lay.d_packed, layout=lay)
        rng = np.random.default_rng(3)
        g = jnp.asarray(rng.normal(size=lay.d_packed).astype("f4"))
        path = checkpoint.save_server_state(
            str(tmp_path / "srv.npz"), server, layout=lay)
        back, _ = checkpoint.restore_server_state(path, layout=lay)

        def round_(srv):
            ts = packing.threshold_state_from_vec(jnp.asarray(srv["theta"]))
            g_t, age_next, stats = eng.select_and_merge(
                g, jnp.asarray(srv["g"]).astype(jnp.float32),
                jnp.asarray(srv["age"]).astype(jnp.float32),
                residual=jnp.asarray(srv["res"]), tstate=ts)
            return g_t, age_next, stats["residual"]

        for a, b in zip(round_(server), round_(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_latest_server_step(self, tmp_path):
        server, lay = self._server_and_layout()
        assert checkpoint.latest_server_step(str(tmp_path)) is None
        checkpoint.save_server_state(str(tmp_path), server, layout=lay,
                                     step=3)
        checkpoint.save_server_state(str(tmp_path), server, layout=lay,
                                     step=11)
        assert checkpoint.latest_server_step(str(tmp_path)) == 11


# ---------------------------------------------------------------------------
# launch integration: fused stats + one-bit update_phase (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestLaunchIntegration:
    def _run_steps(self, oac, n=3):
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.data.tokens import lm_batch
        from repro.launch.steps import init_server_state, make_train_step
        from repro.models import transformer as tr
        from repro.optim import make_optimizer
        cfg = get_config("mamba2-370m", reduced_variant=True)
        mesh = make_mesh((1, 1), ("data", "model"))
        shape = InputShape("t", 64, 2, "train")
        bundle = make_train_step(cfg, shape, mesh, oac=oac)
        params = tr.init_lm(jax.random.PRNGKey(0), cfg)
        opt = make_optimizer(bundle.meta["optimizer"], 3e-3)
        opt_state = opt.init(params)
        server = init_server_state(params, mesh=mesh, cfg=cfg, oac=oac)
        step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                       out_shardings=bundle.out_shardings)
        nm = bundle.meta["n_micro"]
        with mesh:
            for t in range(n):
                toks, labels = lm_batch(t, 2, 64, cfg.vocab)
                batch = {
                    "tokens": jnp.asarray(toks).reshape(nm, 2 // nm, 64),
                    "labels": jnp.asarray(labels).reshape(nm, 2 // nm, 64)}
                params, opt_state, server, loss = step(
                    params, opt_state, server, batch,
                    jnp.asarray(t, jnp.int32))
        return server, float(loss)

    def test_fused_stats_update_phase(self):
        from repro.launch.steps import OacServerConfig
        server, loss = self._run_steps(OacServerConfig())
        assert np.isfinite(loss)
        ages = np.asarray(server["age"])
        valid = ages >= 0
        # step 0 is the full refresh; steps 1-2 run on hist thresholds —
        # the fresh fraction must be back near the rho = 0.1 budget
        frac = (ages[valid] == 0).mean()
        assert 0.02 < frac < 0.35, frac
        theta = np.asarray(server["theta"])
        assert theta.shape == (packing.THRESHOLD_STATE_SIZE,)
        assert theta[4] == 1.0                             # init flag
        assert theta[len(packing.THRESHOLD_STATE_FIELDS):].sum() > 0

    def test_one_bit_update_phase(self):
        from repro.launch.steps import OacServerConfig
        server, loss = self._run_steps(
            OacServerConfig(one_bit=True, error_feedback=True))
        assert np.isfinite(loss)
        g = np.asarray(server["g"]).astype(np.float32)
        ages = np.asarray(server["age"])
        sel = (ages == 0)
        # selected coordinates carry the ±1 sign vector
        assert set(np.unique(g[sel])) <= {-1.0, 1.0}
        assert float(np.abs(np.asarray(server["res"])).sum()) > 0.0

    def test_adaptive_km_update_phase(self):
        from repro.core import controller
        from repro.launch.steps import OacServerConfig
        server, loss = self._run_steps(OacServerConfig(adaptive_km=True),
                                       n=4)
        assert np.isfinite(loss)
        assert server["ctrl"].shape == (controller.CONTROLLER_STATE_SIZE,)
        cs = controller.controller_state_from_vec(
            jnp.asarray(server["ctrl"]))
        assert 0.05 <= float(cs["k_m_frac"]) <= 0.95
        assert float(cs["init"]) == 1.0           # controller has observed
        assert float(jnp.sum(cs["age_ema"])) > 0  # histogram EMA seeded

    def test_adaptive_km_requires_fused_packed(self):
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.launch.steps import OacServerConfig, make_train_step
        cfg = get_config("mamba2-370m", reduced_variant=True)
        mesh = make_mesh((1, 1), ("data", "model"))
        for bad in (OacServerConfig(adaptive_km=True, packed=False),
                    OacServerConfig(adaptive_km=True, fused_stats=False)):
            with pytest.raises(ValueError):
                make_train_step(cfg, InputShape("t", 64, 2, "train"), mesh,
                                oac=bad)

    def test_one_bit_requires_packed(self):
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.launch.steps import OacServerConfig, make_train_step
        cfg = get_config("mamba2-370m", reduced_variant=True)
        mesh = make_mesh((1, 1), ("data", "model"))
        with pytest.raises(ValueError):
            make_train_step(cfg, InputShape("t", 64, 2, "train"), mesh,
                            oac=OacServerConfig(packed=False,
                                                one_bit=True))

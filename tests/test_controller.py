"""In-graph adaptive budget controller (core/controller.py, DESIGN.md §12).

Pins the tentpole guarantees of the traced-``k_m`` refactor:

* the controller's staleness pmf (derived from the kernel-emitted
  ``age_hist``) IS the empirical post-update age distribution, and it
  tracks ``core/markov.py``'s Lemma-1 stationary prediction on a small
  (d, k, k_m) chain;
* a traced ``k_m_frac`` reproduces the static-split engine BIT-EXACTLY on
  all four backends under ``exact_theta``;
* the control law: clipped, damped, deadbanded steps toward the Lemma-1
  setpoint, bounds respected, no step off a round-0 full-refresh
  histogram;
* adaptation is zero-recompile (one trace of the controller update across
  many ``k_m_frac`` operating points) and zero-extra-read (``G_READS`` of
  the adaptive packed round == 1);
* the controller state round-trips the flat-vector codec and the
  ``save/restore_server_state`` checkpoint;
* the FL trainer's ``fairk_auto`` alias / ``adaptive_km`` flag runs the
  controller in-graph and records the split trajectory on-device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import statutil
from repro import checkpoint
from repro.core import controller, markov, packing
from repro.core.engine import EngineConfig, SelectionEngine
from repro.launch.mesh import make_mesh


def _tie_free(d, seed=0):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=d).astype("f4"))
    gp = jnp.asarray(rng.normal(size=d).astype("f4"))
    age = jnp.asarray(rng.permutation(d).astype("f4"))
    return g, gp, age


# ---------------------------------------------------------------------------
# staleness pmf: empirical match + Lemma-1 tracking (satellite)
# ---------------------------------------------------------------------------

class TestStalenessPmf:
    def test_age_hist_pmf_is_empirical_pmf(self):
        """At stride 1 (d < 2·STATS_SAMPLE_CAP) the kernel-emitted
        age_hist is EXACTLY the histogram of the post-update age vector,
        so the controller's pmf equals the empirical staleness pmf."""
        d = 8192
        assert packing.hist_stride(d) == 1
        g, gp, age = _tie_free(d)
        eng = SelectionEngine(EngineConfig(policy="fairk", backend="packed",
                                           rho=0.1, k_m_frac=0.75,
                                           fused_stats=True, warm_start=True),
                              d, layout=packing.PackedLayout.from_tree(
                                  [jnp.zeros((d,))]))
        _, age_next, stats = eng.select_and_merge(
            g, gp, age % 100.0, tstate=packing.init_threshold_state())
        pmf = np.asarray(controller.staleness_pmf(stats["age_hist"]))
        emp, _ = np.histogram(np.asarray(age_next),
                              bins=np.arange(129) - 0.5)
        np.testing.assert_allclose(pmf, emp / emp.sum(), atol=1e-7)

    def test_pmf_tracks_lemma1_stationary_prediction(self):
        """Run the engine's FAIR-k with iid re-drawn scores (the
        well-mixed exchange regime: k0 = k_M(1 − k_M/d)) and compare the
        time-averaged age_hist pmf against Lemma 1's stationary π on the
        same small (d, k, k_m) chain — mean staleness within 10%, total
        variation < 0.1, same regulated quantile bin."""
        d, k, k_m = 512, 64, 32
        eng = SelectionEngine(EngineConfig(policy="fairk", backend="exact",
                                           k=k, k_m=k_m, fused_stats=True),
                              d)
        acc = statutil.accumulate_age_hist(eng, d)
        k0 = int(round(k_m * (1 - k_m / d)))
        support, pred = markov.aou_distribution(
            markov.FairKChain(d=d, k=k, k_m=k_m, k0=k0))
        emp = statutil.assert_pmf_close(acc, support, pred, mean_rtol=0.1)
        pred_full = statutil.embed_pmf(support, pred)
        q = controller.pmf_quantile
        assert abs(float(q(jnp.asarray(emp, jnp.float32), 0.9))
                   - float(q(jnp.asarray(pred_full, jnp.float32), 0.9))) < 1.5

    def test_lemma1_target_table_monotone_in_split(self):
        """More magnitude share = fewer age slots = staler tail: the
        Lemma-1 target table must increase with k_m_frac."""
        fracs, targets = controller.lemma1_target_table(
            controller.ControllerConfig(), rho=0.1)
        assert len(fracs) == len(targets)
        assert (np.diff(targets) >= -1e-6).all()
        assert targets[-1] > targets[0]

    def test_pmf_quantile_interpolates(self):
        pmf = jnp.zeros((128,), jnp.float32).at[4].set(0.5).at[10].set(0.5)
        assert abs(float(controller.pmf_quantile(pmf, 0.25)) - 4.5) < 1e-5
        assert float(controller.pmf_quantile(pmf, 0.75)) == pytest.approx(
            10.5, abs=1e-5)


# ---------------------------------------------------------------------------
# traced-k_m engine parity (satellite / acceptance)
# ---------------------------------------------------------------------------

class TestTracedKmParity:
    SEEDS = {"exact": 7, "threshold": 11, "sharded": 13, "packed": 17}

    @pytest.mark.parametrize("backend", ["exact", "threshold", "sharded",
                                         "packed"])
    def test_traced_equals_static_exact_theta(self, backend):
        """select_and_merge(k_m_frac=traced 0.75) ≡ the static-split
        engine, bit-exact, on tie-free inputs under exact_theta."""
        d = 4096
        g, gp, age = _tie_free(d, seed=self.SEEDS[backend])
        common = dict(policy="fairk", rho=0.1, k_m_frac=0.75,
                      exact_theta=True, fused_stats=True)
        kw = {}
        if backend == "sharded":
            kw["mesh"] = make_mesh((1,), ("shard",))
        if backend == "packed":
            kw["layout"] = packing.PackedLayout.from_tree([jnp.zeros((d,))])
        eng = SelectionEngine(EngineConfig(backend=backend, **common), d,
                              **kw)
        out_s = jax.jit(eng.select_and_merge)(g, gp, age)
        out_t = jax.jit(lambda g, gp, age, f: eng.select_and_merge(
            g, gp, age, k_m_frac=f))(g, gp, age, jnp.float32(0.75))
        np.testing.assert_array_equal(np.asarray(out_s[0]),
                                      np.asarray(out_t[0]))
        np.testing.assert_array_equal(np.asarray(out_s[1]),
                                      np.asarray(out_t[1]))
        assert float(out_s[2]["n_selected"]) == float(out_t[2]["n_selected"])

    def test_traced_split_actually_moves_the_split(self):
        """Different traced fracs through ONE jitted function change the
        magnitude-stage share (trace reuse, different data)."""
        d = 4096
        g, gp, age = _tie_free(d, seed=3)
        eng = SelectionEngine(EngineConfig(policy="fairk", backend="exact",
                                           rho=0.1, fused_stats=True), d)
        fn = jax.jit(lambda f: eng.select_and_merge(g, gp, age,
                                                    k_m_frac=f))
        n_lo = float(fn(jnp.float32(0.25))[2]["n_sel_m"])
        n_hi = float(fn(jnp.float32(0.75))[2]["n_sel_m"])
        k = eng.budgets()[0]
        assert n_lo == round(0.25 * k) and n_hi == round(0.75 * k)

    def test_non_fairk_policy_rejected(self):
        d = 256
        g, gp, age = _tie_free(d)
        eng = SelectionEngine(EngineConfig(policy="topk", backend="exact"),
                              d)
        with pytest.raises(ValueError):
            eng.select_and_merge(g, gp, age, k_m_frac=jnp.float32(0.5))


# ---------------------------------------------------------------------------
# control law
# ---------------------------------------------------------------------------

class TestControlLaw:
    def _hist_at(self, age):
        return jnp.zeros((packing.STATS_AGE_BINS,),
                         jnp.float32).at[age].set(1000.0)

    def _settled(self, bc, cs, hist, rounds=12):
        for _ in range(rounds):
            cs = bc.update(cs, hist)
        return cs

    def test_stale_population_lowers_split(self):
        """Measured quantile far above the setpoint -> budget shifts to
        the age stage (k_m_frac decreases), bounded per actuation."""
        bc = controller.BudgetController(rho=0.1)
        cs = self._settled(bc, bc.init_state(0.75), self._hist_at(110))
        assert float(cs["k_m_frac"]) < 0.75
        assert abs(float(cs["prev_step"])) <= bc.cfg.max_step + 1e-6

    def test_fresh_population_raises_split(self):
        bc = controller.BudgetController(rho=0.1)
        cs = self._settled(bc, bc.init_state(0.5), self._hist_at(2))
        assert float(cs["k_m_frac"]) > 0.5

    def test_bounds_respected(self):
        bc = controller.BudgetController(rho=0.1)
        cs = self._settled(bc, bc.init_state(0.9), self._hist_at(2),
                           rounds=400)
        assert float(cs["k_m_frac"]) <= bc.cfg.max_frac + 1e-6
        cs = self._settled(bc, bc.init_state(0.1), self._hist_at(120),
                           rounds=400)
        assert float(cs["k_m_frac"]) >= bc.cfg.min_frac - 1e-6

    def test_first_observation_never_steps(self):
        """Round 0 emits a full-refresh histogram (everything at age 0);
        the controller must only seed its EMA off it."""
        bc = controller.BudgetController(rho=0.1)
        cs = bc.update(bc.init_state(0.5), self._hist_at(0))
        assert float(cs["k_m_frac"]) == 0.5
        assert float(cs["init"]) == 1.0

    def test_deadband_holds_at_setpoint(self):
        """A population sitting exactly at the Lemma-1 setpoint stays
        parked (the Sec. V-A plateau makes small moves pure noise)."""
        bc = controller.BudgetController(rho=0.1)
        cs0 = bc.init_state(0.5)
        tgt = int(round(float(bc.target_for(jnp.float32(0.5)))))
        cs = self._settled(bc, cs0, self._hist_at(tgt), rounds=50)
        assert abs(float(cs["k_m_frac"]) - 0.5) < 1e-6

    def test_fixed_target_mode(self):
        bc = controller.BudgetController(
            controller.ControllerConfig(target_age=7.0), rho=0.1)
        assert float(bc.target_for(jnp.float32(0.3))) == 7.0
        assert float(bc.target_for(jnp.float32(0.9))) == 7.0


# ---------------------------------------------------------------------------
# zero recompiles + one read (acceptance)
# ---------------------------------------------------------------------------

class TestNoRecompileOneRead:
    def test_one_trace_across_km_changes_and_one_g_read(self):
        """One jitted adaptive packed round executed at several controller
        operating points: the controller body traces ONCE (no recompile —
        the split is data) and the round reads g exactly once."""
        d = 4096
        lay = packing.PackedLayout.from_tree([jnp.zeros((d,))])
        eng = SelectionEngine(EngineConfig(policy="fairk", backend="packed",
                                           rho=0.1, warm_start=True,
                                           fused_stats=True),
                              d, layout=lay)
        bc = controller.BudgetController(rho=0.1)

        @jax.jit
        def round_(g, gp, age, ts, cs):
            g_t, age_next, stats = eng.select_and_merge(
                g, gp, age, tstate=ts, k_m_frac=cs["k_m_frac"])
            return g_t, age_next, stats["tstate"], bc.update(
                cs, stats["age_hist"], stats["mag_hist"])

        g, gp, age = _tie_free(d, seed=11)
        ts = packing.init_threshold_state()
        before_tr = controller.UPDATE_TRACES
        before_rd = packing.G_READS
        for frac in (0.25, 0.5, 0.75, 0.9):
            cs = controller.init_controller_state(frac)
            round_(g, gp, age, ts, cs)
        assert controller.UPDATE_TRACES - before_tr == 1
        assert packing.G_READS - before_rd == 1


# ---------------------------------------------------------------------------
# state codec + checkpoint round trip (acceptance)
# ---------------------------------------------------------------------------

class TestStateRoundTrip:
    def test_vec_codec(self):
        cs = controller.init_controller_state(0.37)
        cs["prev_step"] = jnp.float32(-0.01)
        cs["age_ema"] = cs["age_ema"].at[17].set(3.5)
        cs["mag_ema"] = cs["mag_ema"].at[99].set(2.5)
        vec = controller.controller_state_to_vec(cs)
        assert vec.shape == (controller.CONTROLLER_STATE_SIZE,)
        back = controller.controller_state_from_vec(vec)
        for f in controller.CTRL_SCALAR_FIELDS:
            assert float(back[f]) == float(cs[f])
        np.testing.assert_array_equal(np.asarray(back["age_ema"]),
                                      np.asarray(cs["age_ema"]))
        np.testing.assert_array_equal(np.asarray(back["mag_ema"]),
                                      np.asarray(cs["mag_ema"]))

    def test_controller_state_survives_server_checkpoint(self, tmp_path):
        """The acceptance criterion: controller state round-trips through
        save/restore_server_state next to the packed buffers, and the
        restored round reproduces the original bit-exactly."""
        rng = np.random.default_rng(5)
        lay = packing.PackedLayout.from_tree([jnp.zeros((300,)),
                                              jnp.zeros((512,))])
        d = lay.d_packed
        cs = controller.init_controller_state(0.6)
        cs["age_ema"] = cs["age_ema"].at[12].set(100.0)
        cs["init"] = jnp.float32(1.0)
        server = {
            "g": jnp.asarray(rng.normal(size=d).astype("f4")
                             ).astype(jnp.bfloat16),
            "age": jnp.asarray(rng.integers(-1, 100, d).astype("i1")),
            "theta": packing.threshold_state_to_vec(
                packing.init_threshold_state()),
            "ctrl": controller.controller_state_to_vec(cs),
        }
        path = checkpoint.save_server_state(str(tmp_path / "srv.npz"),
                                            server, layout=lay)
        back, _ = checkpoint.restore_server_state(path, layout=lay)
        np.testing.assert_array_equal(np.asarray(server["ctrl"]),
                                      back["ctrl"])

        eng = SelectionEngine(EngineConfig(policy="fairk", backend="packed",
                                           rho=0.1, warm_start=True,
                                           fused_stats=True),
                              d, layout=lay)
        bc = controller.BudgetController(rho=0.1)
        g = jnp.asarray(rng.normal(size=d).astype("f4"))

        def round_(srv):
            ts = packing.threshold_state_from_vec(jnp.asarray(srv["theta"]))
            c = controller.controller_state_from_vec(
                jnp.asarray(srv["ctrl"]))
            g_t, age_next, stats = eng.select_and_merge(
                g, jnp.asarray(srv["g"]).astype(jnp.float32),
                jnp.asarray(srv["age"]).astype(jnp.float32),
                tstate=ts, k_m_frac=c["k_m_frac"])
            c = bc.update(c, stats["age_hist"], stats["mag_hist"])
            return g_t, age_next, controller.controller_state_to_vec(c)

        for a, b in zip(round_(server), round_(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# FL trainer integration (fairk_auto alias, adaptive_km flag)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestTrainerIntegration:
    def _task(self):
        from repro.data import partition, synthetic
        from repro.models import cnn
        spec = synthetic.DatasetSpec("t", (8, 8, 1), 4, 600, 150,
                                     noise_std=0.8, sparsity=0.1)
        (xtr, ytr), _ = synthetic.make_dataset(spec, seed=0)
        parts = partition.dirichlet_partition(ytr, 6, 0.3, seed=0)
        params0 = cnn.init_mlp_classifier(jax.random.PRNGKey(0), 64, 4,
                                          hidden=(32,))

        def loss_fn(p, x, y):
            return cnn.softmax_xent(cnn.mlp_classifier(p, x), y)

        def sample(t):
            return partition.client_batches(xtr, ytr, parts, 10, 3,
                                            seed=100 + t)
        return params0, loss_fn, sample

    @pytest.mark.parametrize("backend", ["exact", "packed"])
    def test_adaptive_trains_and_logs_split(self, backend):
        from repro.core.oac import ChannelConfig
        from repro.fl import FLConfig, train
        params0, loss_fn, sample = self._task()
        fl = FLConfig(n_clients=6, local_steps=3, batch_size=10, rounds=30,
                      policy="fairk_auto", compression_ratio=0.1,
                      backend=backend, local_lr=0.05, global_lr=0.05,
                      channel=ChannelConfig(fading="rayleigh", mean=1.0,
                                            noise_std=0.1))
        h = train(fl, params0, loss_fn, sample)
        km = np.asarray(h["km_frac"])
        assert km.shape == (30,)
        assert km[0] == pytest.approx(fl.k_m_frac)
        assert (km >= fl.controller.min_frac - 1e-6).all()
        assert (km <= fl.controller.max_frac + 1e-6).all()
        assert len(h["mean_aou"]) == 30 and np.isfinite(h["mean_aou"]).all()

    def test_static_run_records_constant_split(self):
        from repro.core.oac import ChannelConfig
        from repro.fl import FLConfig, train
        params0, loss_fn, sample = self._task()
        fl = FLConfig(n_clients=6, local_steps=3, batch_size=10, rounds=5,
                      policy="fairk", compression_ratio=0.1,
                      local_lr=0.05, global_lr=0.05,
                      channel=ChannelConfig(fading="rayleigh", mean=1.0,
                                            noise_std=0.1))
        h = train(fl, params0, loss_fn, sample)
        km = np.asarray(h["km_frac"])
        assert (km == km[0]).all()            # constant: no controller
        # the realised split round(k_m_frac*k)/k, within rounding of 0.75
        assert abs(km[0] - fl.k_m_frac) < 0.01

    def test_adaptive_rejects_pinned_policies(self):
        from repro.fl import FLConfig, make_fl_step
        with pytest.raises(ValueError):
            make_fl_step(FLConfig(policy="topk", adaptive_km=True),
                         lambda w: w, lambda p, x, y: 0.0, 16)

"""Geometric wireless channel layer (core/channel.py, DESIGN.md §16).

Acceptance for the fading-channel robustness PR:

* config validation + the static geometry (gains / outage / thin) and its
  numerical identity with the analysis side (``markov.truncation_thin``);
* the post-update staleness pmf under truncated channel inversion matches
  ``markov.channel_aou_distribution`` within the suite-standard TV
  tolerance on the exact AND packed backends (memoryless ``rho_f = 0``
  runs — Lemma-1's geometric thinning is exact only for iid blocking; the
  AR(1)-correlated regime gets stationarity tests instead, see
  tests/test_stat_properties.py);
* the truncation × population-churn composition tracks the
  ``extra_thin``-composed law;
* ``faults.fade_mask`` stays bit-exact with the pre-channel inline draw
  after becoming an alias over ``channel.block_erase_mask``;
* trainer / sweep / launch integration: wireless rounds run finite and
  compose with one-bit, EF, watchdog, faults and population; the launch
  path persists + checkpoints the per-block fading chain and migrates
  pre-channel checkpoints by re-synthesizing the stationary draw.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import statutil
from repro.core import channel as chan
from repro.core import faults, markov, packing
from repro.core.engine import make_engine
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.channel


# ---------------------------------------------------------------------------
# config validation + static geometry
# ---------------------------------------------------------------------------

class TestChannelConfig:
    def test_defaults_valid(self):
        cfg = chan.ChannelConfig()
        assert cfg.g_eff == pytest.approx(max(cfg.gmin, 1.0 / cfg.pmax))
        assert cfg.gains.shape == (cfg.n_clients,)
        assert np.all(cfg.gains > 0.0)
        assert np.all((cfg.outage > 0.0) & (cfg.outage < 1.0))
        assert 0.0 <= cfg.thin <= 0.99

    @pytest.mark.parametrize("kw", [
        dict(n_clients=0), dict(pmax=0.0), dict(pmax=-1.0),
        dict(pmax=float("inf")), dict(gmin=-0.1), dict(rho_f=-0.01),
        dict(rho_f=1.0), dict(csi_err=-0.5), dict(pl_exp=-1.0),
        dict(shadow_db=-2.0), dict(near=0.0), dict(near=1.5),
        dict(block=0),
    ])
    def test_rejects_bad_fields(self, kw):
        with pytest.raises(ValueError):
            chan.ChannelConfig(**kw)

    def test_gains_deterministic_and_ordered(self):
        """Same config -> same gains (pure function); nearer clients have
        the larger path gain when shadowing is off."""
        a = chan.ChannelConfig(n_clients=8, pl_exp=3.0, shadow_db=1.5,
                               geo_seed=7)
        np.testing.assert_array_equal(a.gains,
                                      chan.ChannelConfig(
                                          n_clients=8, pl_exp=3.0,
                                          shadow_db=1.5, geo_seed=7).gains)
        b = chan.ChannelConfig(n_clients=8, pl_exp=3.0)
        assert np.all(np.diff(b.gains) < 0.0)
        # different shadowing seed -> different deployment
        c = chan.ChannelConfig(n_clients=8, pl_exp=3.0, shadow_db=1.5,
                               geo_seed=8)
        assert not np.array_equal(a.gains, c.gains)

    def test_power_budget_floor_binds(self):
        """g_eff = max(gmin, 1/pmax): a tight power budget overrides a
        loose designed threshold."""
        assert chan.ChannelConfig(pmax=2.0, gmin=0.01).g_eff == 0.5
        assert chan.ChannelConfig(pmax=100.0, gmin=0.3).g_eff == 0.3

    def test_thin_matches_markov_truncation_thin(self):
        """The simulation's controller setpoint and the analysis law must
        be numerically IDENTICAL — same expm1/prod arithmetic."""
        for cfg in (chan.ChannelConfig(n_clients=4, near=1.0, pl_exp=0.0,
                                       gmin=1.0, pmax=10.0),
                    chan.ChannelConfig(n_clients=3, near=0.8, pl_exp=2.0,
                                       gmin=1.5, pmax=10.0),
                    chan.ChannelConfig(n_clients=16, shadow_db=4.0,
                                       geo_seed=3)):
            assert cfg.thin == markov.truncation_thin(cfg.pmax, cfg.gmin,
                                                      cfg.gains)


# ---------------------------------------------------------------------------
# fade_mask alias (satellite: one erasure code path)
# ---------------------------------------------------------------------------

def test_fade_mask_bit_exact_with_pre_channel_draw():
    """``faults.fade_mask`` is now a thin alias over
    ``channel.block_erase_mask`` — the draw must stay bit-exact with the
    pre-channel inline implementation (uniform-per-block + repeat)."""
    fcfg = faults.FaultConfig(fade=0.37, fade_block=96)
    d = 1000
    for s in range(3):
        key = jax.random.PRNGKey(s)
        nb = -(-d // fcfg.fade_block)
        hit = jax.random.uniform(key, (nb,)) < fcfg.fade
        want = jnp.repeat(hit.astype(jnp.float32), fcfg.fade_block)[:d]
        np.testing.assert_array_equal(
            np.asarray(faults.fade_mask(key, d, fcfg)), np.asarray(want))
    # fade = 0 short-circuits to exact zeros (no trace of the draw)
    z = faults.fade_mask(jax.random.PRNGKey(0), d,
                         faults.FaultConfig(fade=0.0))
    assert float(jnp.abs(z).sum()) == 0.0


# ---------------------------------------------------------------------------
# per-client chain semantics
# ---------------------------------------------------------------------------

class TestChannelRound:
    def test_deterministic_and_state_advances(self):
        cfg = chan.ChannelConfig(n_clients=6, rho_f=0.7)
        st = chan.init_channel_state(jax.random.PRNGKey(1), cfg)
        key = jax.random.PRNGKey(2)
        s1, r1 = chan.channel_round(st, key, cfg)
        s2, r2 = chan.channel_round(st, key, cfg)
        np.testing.assert_array_equal(np.asarray(s1["fad"]),
                                      np.asarray(s2["fad"]))
        np.testing.assert_array_equal(np.asarray(r1["sent"]),
                                      np.asarray(r2["sent"]))
        assert not np.array_equal(np.asarray(st["fad"]),
                                  np.asarray(s1["fad"]))
        assert float(r1["n_sent"]) == float(np.asarray(r1["sent"]).sum())

    def test_sent_iff_gain_clears_threshold(self):
        cfg = chan.ChannelConfig(n_clients=32, gmin=0.8, pmax=10.0)
        st = chan.init_channel_state(jax.random.PRNGKey(0), cfg)
        _, r = chan.channel_round(st, jax.random.PRNGKey(3), cfg)
        gain = np.asarray(r["gain"])
        np.testing.assert_array_equal(
            np.asarray(r["sent"]), (gain >= cfg.g_eff).astype(np.float32))

    def test_csi_weights(self):
        cfg0 = chan.ChannelConfig(n_clients=5, csi_err=0.0)
        np.testing.assert_array_equal(
            np.asarray(chan.csi_weights(jax.random.PRNGKey(0), 5, cfg0)),
            np.ones(5, np.float32))
        cfg = chan.ChannelConfig(n_clients=5, csi_err=0.1)
        w = np.asarray(chan.csi_weights(jax.random.PRNGKey(0), 5, cfg))
        assert w.shape == (5,) and not np.allclose(w, 1.0)
        assert np.all(np.abs(w - 1.0) < 1.0)       # 0.1 std: tiny misalign


# ---------------------------------------------------------------------------
# staleness law under truncated channel inversion (acceptance)
# ---------------------------------------------------------------------------

def _total_outage_masks(cfg: chan.ChannelConfig, d: int, rounds: int,
                        seed: int):
    """Per-round erase masks of the per-client chain: all-ones on a TOTAL
    truncation outage (nothing superposed -> round erased), None
    otherwise — exactly what the trainer's erase_with_outage produces."""
    step = jax.jit(chan.channel_round, static_argnums=2)
    st = chan.init_channel_state(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1)
    ones = np.ones((d,), np.float32)
    masks = []
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        st, stats = step(st, sub, cfg)
        masks.append(ones if float(stats["n_sent"]) == 0.0 else None)
    return masks


def _pmf_engine(backend, d, k, k_m):
    if backend == "packed":
        eng = make_engine("fairk", "packed",
                          layout=packing.PackedLayout.from_tree(
                              [jnp.zeros((d,))], lane=1),
                          k=k, k_m=k_m, fused_stats=True, warm_start=True)
        return eng, packing.init_threshold_state()
    return make_engine("fairk", backend, d=d, k=k, k_m=k_m,
                       fused_stats=True), None


@pytest.mark.parametrize("backend", ["exact", "packed"])
@pytest.mark.parametrize("geo", ["homogeneous", "heterogeneous"])
def test_empirical_pmf_matches_channel_law(backend, geo):
    """Truncated channel inversion blocks a refresh exactly when every
    client is in outage at once; at ``rho_f = 0`` the blocking is iid
    across rounds, so the stationary post-update AoU pmf must track
    ``markov.channel_aou_distribution`` — the geometric thinning of
    Lemma 1 at rate ``truncation_thin`` — within the suite-standard TV
    tolerance, on the exact AND packed backends (seeded run,
    tests/statutil.py)."""
    d, k, k_m = 512, 64, 32
    # operating points chosen per the statutil doctrine (thin enough for
    # the geometric approximation, thick enough to test something: seeded
    # TVs land ~ 0.05-0.07 with the 0.1 tolerance)
    if geo == "homogeneous":
        cfg = chan.ChannelConfig(n_clients=4, near=1.0, pl_exp=0.0,
                                 gmin=0.9, pmax=10.0)       # thin ~ 0.124
    else:
        cfg = chan.ChannelConfig(n_clients=3, near=0.8, pl_exp=2.0,
                                 gmin=0.9, pmax=10.0)       # thin ~ 0.137
    rounds = 600
    masks = _total_outage_masks(cfg, d, rounds, seed=0)
    # the seeded empirical outage frequency must sit near the analytic
    # rate, or the pmf test below tests nothing
    frac = sum(m is not None for m in masks) / rounds
    assert abs(frac - cfg.thin) < 0.05
    eng, ts = _pmf_engine(backend, d, k, k_m)
    acc = statutil.accumulate_age_hist(
        eng, d, rounds=rounds, tstate=ts, sanitize=True,
        erase_fn=lambda r: masks[r], count_erased=True)
    k0 = int(round(k_m * (1 - k_m / d)))
    support, pred = markov.channel_aou_distribution(
        markov.FairKChain(d=d, k=k, k_m=k_m, k0=k0),
        cfg.pmax, cfg.gmin, cfg.gains)
    statutil.assert_pmf_close(acc, support, pred)


@pytest.mark.parametrize("backend", ["exact", "packed"])
def test_empirical_pmf_matches_composed_channel_churn_law(backend):
    """Truncation outage × an independent per-coordinate churn channel at
    rate ``extra_thin``: per-coordinate blocking composes as
    1 - (1-t)(1-e), which is exactly what
    ``channel_aou_distribution(..., extra_thin=e)`` folds into the
    thinned law."""
    d, k, k_m, extra = 512, 64, 32, 0.1
    cfg = chan.ChannelConfig(n_clients=4, near=1.0, pl_exp=0.0,
                             gmin=0.9, pmax=10.0)
    rounds = 600
    masks = _total_outage_masks(cfg, d, rounds, seed=1)
    rng = np.random.default_rng(2)

    def erase_fn(r):
        iid = (rng.random(d) < extra).astype(np.float32)
        return np.maximum(masks[r], iid) if masks[r] is not None else iid

    eng, ts = _pmf_engine(backend, d, k, k_m)
    acc = statutil.accumulate_age_hist(eng, d, rounds=rounds, tstate=ts,
                                       sanitize=True, erase_fn=erase_fn,
                                       count_erased=True)
    k0 = int(round(k_m * (1 - k_m / d)))
    support, pred = markov.channel_aou_distribution(
        markov.FairKChain(d=d, k=k, k_m=k_m, k0=k0),
        cfg.pmax, cfg.gmin, cfg.gains, extra_thin=extra)
    statutil.assert_pmf_close(acc, support, pred)


# ---------------------------------------------------------------------------
# analysis-side law (markov)
# ---------------------------------------------------------------------------

class TestMarkovChannelLaw:
    def test_truncation_thin_validates(self):
        gains = np.array([1.0, 0.5])
        for bad in (0.0, -1.0, float("inf"), float("nan")):
            with pytest.raises(ValueError):
                markov.truncation_thin(bad, 0.1, gains)
        with pytest.raises(ValueError):
            markov.truncation_thin(10.0, -0.1, gains)
        with pytest.raises(ValueError):
            markov.truncation_thin(10.0, 0.1, np.array([]))
        with pytest.raises(ValueError):
            markov.truncation_thin(10.0, 0.1, np.array([1.0, 0.0]))
        with pytest.raises(ValueError):
            markov.truncation_thin(10.0, 0.1, np.ones((2, 2)))

    def test_channel_aou_reduces_to_thinned_law(self):
        chain = markov.FairKChain(d=512, k=64, k_m=32, k0=30)
        cfg = chan.ChannelConfig(n_clients=4, near=1.0, pl_exp=0.0,
                                 gmin=1.0, pmax=10.0)
        s, p = markov.channel_aou_distribution(chain, cfg.pmax, cfg.gmin,
                                               cfg.gains)
        s2, p2 = markov.thinned_aou_distribution(chain, cfg.thin)
        np.testing.assert_array_equal(s, s2)
        np.testing.assert_allclose(p, p2, atol=1e-12)
        with pytest.raises(ValueError):
            markov.channel_aou_distribution(chain, cfg.pmax, cfg.gmin,
                                            cfg.gains, extra_thin=1.0)

    def test_extra_thin_composes_exactly(self):
        chain = markov.FairKChain(d=512, k=64, k_m=32, k0=30)
        cfg = chan.ChannelConfig(n_clients=4, near=1.0, pl_exp=0.0,
                                 gmin=1.0, pmax=10.0)
        e = 0.2
        s, p = markov.channel_aou_distribution(chain, cfg.pmax, cfg.gmin,
                                               cfg.gains, extra_thin=e)
        composed = 1.0 - (1.0 - cfg.thin) * (1.0 - e)
        s2, p2 = markov.thinned_aou_distribution(chain, composed)
        np.testing.assert_array_equal(s, s2)
        np.testing.assert_allclose(p, p2, atol=1e-12)


# ---------------------------------------------------------------------------
# trainer integration
# ---------------------------------------------------------------------------

def _toy_task(n_clients=4, local=2, batch=8):
    def loss_fn(params, x, y):
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    init = {"w": jnp.zeros((3,)), "b": jnp.zeros(())}

    def sample_round(t):
        r = np.random.default_rng(t)
        xs = r.normal(size=(n_clients, local, batch, 3)).astype(np.float32)
        ys = (xs @ np.array([1.0, -2.0, 0.5])).astype(np.float32)
        return xs, ys

    return init, loss_fn, sample_round


def _wcfg(n, **kw):
    base = dict(pmax=10.0, gmin=0.05, rho_f=0.6, csi_err=0.05,
                pl_exp=2.0, near=0.5)
    base.update(kw)
    return chan.ChannelConfig(n_clients=n, **base)


class TestTrainerWireless:
    N = 4

    def _run(self, **kw):
        from repro.fl import trainer
        init, loss_fn, sample_round = _toy_task(self.N)
        fl = trainer.FLConfig(n_clients=self.N, local_steps=2, batch_size=8,
                              rounds=6, compression_ratio=0.5, seed=3, **kw)
        hist = trainer.train(fl, init, loss_fn, sample_round)
        w = np.asarray(jax.flatten_util.ravel_pytree(hist["params"])[0])
        assert np.all(np.isfinite(w))
        return w

    @pytest.mark.parametrize("backend", ["exact", "threshold", "packed"])
    def test_wireless_round_runs_finite(self, backend):
        self._run(backend=backend, wireless=_wcfg(self.N))

    @pytest.mark.parametrize("backend", ["exact", "packed"])
    def test_one_bit_composes(self, backend):
        self._run(backend=backend, wireless=_wcfg(self.N), one_bit=True)

    def test_error_feedback_composes(self):
        self._run(backend="packed", wireless=_wcfg(self.N),
                  error_feedback=True)

    def test_watchdog_composes(self):
        self._run(backend="packed", wireless=_wcfg(self.N),
                  watchdog=faults.WatchdogConfig())

    def test_faults_and_population_compose(self):
        from repro.core import population
        pcfg = population.PopulationConfig(n_clients=1024, cohort_size=64,
                                           participants=self.N)
        self._run(backend="packed", wireless=_wcfg(self.N), population=pcfg,
                  faults=faults.FaultConfig(fade=0.05, nan_rate=0.01))
        self._run(backend="exact", wireless=_wcfg(self.N),
                  faults=faults.FaultConfig(dropout=0.2, fade=0.05))

    def test_scan_rounds_bit_exact(self):
        """The wireless fading carry must ride the lax.scan fusion on the
        same bit-exact trajectory as the per-round loop."""
        a = self._run(backend="packed", wireless=_wcfg(self.N))
        b = self._run(backend="packed", wireless=_wcfg(self.N),
                      scan_rounds=3)
        np.testing.assert_array_equal(a, b)

    def test_total_outage_round_merges_stale(self):
        """A config in permanent total outage (g_eff unreachable) must
        never refresh: ages climb every round, params never move, and no
        NaN reaches the merged state."""
        from repro.fl import trainer
        init, loss_fn, sample_round = _toy_task(self.N)
        # near=1, pl_exp=0 -> unit gains; gmin far above any Exp(1) draw
        wl = chan.ChannelConfig(n_clients=self.N, near=1.0, pl_exp=0.0,
                                gmin=60.0, pmax=1e6)
        fl = trainer.FLConfig(n_clients=self.N, local_steps=2, batch_size=8,
                              rounds=5, compression_ratio=0.5, backend="packed",
                              wireless=wl, seed=0)
        hist = trainer.train(fl, init, loss_fn, sample_round)
        w = np.asarray(jax.flatten_util.ravel_pytree(hist["params"])[0])
        np.testing.assert_array_equal(w, np.zeros_like(w))
        assert min(hist["mean_aou"]) > 0.0
        assert hist["mean_aou"][-1] == pytest.approx(5.0)

    def test_validation(self):
        from repro.fl import trainer
        init, loss_fn, _ = _toy_task(self.N)
        with pytest.raises(ValueError, match="n_clients"):
            trainer.make_fl_step(
                trainer.FLConfig(n_clients=self.N,
                                 wireless=_wcfg(self.N + 3)),
                lambda w: w, loss_fn, 4)
        with pytest.raises(ValueError, match="policy"):
            trainer.make_fl_step(
                trainer.FLConfig(n_clients=self.N, wireless=_wcfg(self.N),
                                 policy="randk"),
                lambda w: w, loss_fn, 4)


# ---------------------------------------------------------------------------
# sweep integration
# ---------------------------------------------------------------------------

class TestSweepWireless:
    def test_wireless_lanes_run_and_compose(self):
        from repro.core import population
        from repro.fl import sweep
        n = 8
        wl = _wcfg(n, gmin=0.2)
        base = dict(d=256, n_clients=n, rho=0.25, rounds=16)
        r = sweep.run_sweep(sweep.SweepConfig(wireless=wl, **base),
                            policies=("fairk", "fairk_auto"), n_seeds=2)
        assert np.all(np.isfinite(r["loss"]))
        assert "n_sent" in r and 0.0 <= r["n_sent"].mean() <= n
        pcfg = population.PopulationConfig(n_clients=1024, cohort_size=64,
                                           participants=n)
        r2 = sweep.run_sweep(
            sweep.SweepConfig(wireless=wl, population=pcfg,
                              faults=faults.FaultConfig(fade=0.05), **base),
            n_seeds=2)
        assert np.all(np.isfinite(r2["loss"]))

    def test_validation(self):
        from repro.fl import sweep
        with pytest.raises(ValueError, match="n_clients"):
            sweep.SweepConfig(n_clients=8, wireless=_wcfg(3))


# ---------------------------------------------------------------------------
# launch integration: persisted fading chain + checkpoint migration
# ---------------------------------------------------------------------------

def test_block_outage_calibration_and_determinism():
    """The aggregate-equivalent per-block chain: marginal erasure rate
    matches ``cfg.thin`` (the calibrated threshold on an Exp(1) gain) and
    the chain is deterministic in (state, key)."""
    cfg = chan.ChannelConfig(n_clients=2, near=1.0, pl_exp=0.0, gmin=1.0,
                             pmax=10.0, block=4)      # thin ~ 0.4
    d = 4096
    nb = chan.n_blocks(d, cfg)
    fad = chan.init_block_fading(nb)
    m1a, e1a = chan.block_outage(fad, jax.random.PRNGKey(5), d, cfg)
    m1b, e1b = chan.block_outage(fad, jax.random.PRNGKey(5), d, cfg)
    np.testing.assert_array_equal(np.asarray(m1a), np.asarray(m1b))
    np.testing.assert_array_equal(np.asarray(e1a), np.asarray(e1b))
    # long-run marginal erasure rate -> thin (memoryless rho_f = 0)
    hits, key = [], jax.random.PRNGKey(6)
    for _ in range(400):
        key, sub = jax.random.split(key)
        fad, er = chan.block_outage(fad, sub, d, cfg)
        hits.append(float(jnp.mean(er)))
    assert abs(np.mean(hits) - cfg.thin) < 0.03


def test_csi_block_factor_block_structure():
    cfg = chan.ChannelConfig(n_clients=16, csi_err=0.2, block=8)
    f = np.asarray(chan.csi_block_factor(jax.random.PRNGKey(0), 40, cfg))
    assert f.shape == (40,)
    blocks = f.reshape(5, 8)
    assert np.all(blocks == blocks[:, :1])     # constant within a block
    assert len(np.unique(blocks[:, 0])) == 5   # distinct across blocks
    z = chan.csi_block_factor(
        jax.random.PRNGKey(0), 40,
        chan.ChannelConfig(n_clients=16, csi_err=0.0, block=8))
    np.testing.assert_array_equal(np.asarray(z), np.ones(40, np.float32))


@pytest.mark.slow
class TestLaunchWireless:
    def _setup(self, oac):
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.launch import sharding as shlib
        from repro.launch.steps import (abstract_params,
                                        abstract_server_state,
                                        init_server_state, make_train_step)
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
        params_abs = abstract_params(cfg)
        p_specs = shlib.param_pspecs(params_abs, cfg, mesh)
        srv_abs = abstract_server_state(params_abs, mesh=mesh,
                                        p_specs=p_specs, oac=oac)
        return cfg, mesh, bundle, params, opt_state, server, srv_abs

    def _steps(self, cfg, mesh, bundle, params, opt_state, server, n=2):
        from repro.data.tokens import lm_batch
        step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                       out_shardings=bundle.out_shardings,
                       donate_argnums=(0, 1, 2))
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
        return params, opt_state, server, loss

    def test_two_wireless_steps_and_persisted_fad(self):
        from repro.launch.steps import OacServerConfig
        oac = OacServerConfig(sanitize=True,
                              wireless=_wcfg(16, gmin=0.3, rho_f=0.5))
        (cfg, mesh, bundle, params, opt_state, server,
         srv_abs) = self._setup(oac)
        assert bundle.meta["oac_wireless"]
        assert set(server) == set(srv_abs) == {"g", "age", "theta", "fad"}
        fad0 = np.asarray(server["fad"]).copy()
        params, opt_state, server, loss = self._steps(
            cfg, mesh, bundle, params, opt_state, server)
        assert np.isfinite(float(loss))
        fad1 = np.asarray(server["fad"])
        assert fad1.shape == fad0.shape
        assert not np.array_equal(fad0, fad1)     # the chain advanced
        assert np.all(np.isfinite(fad1))
        ages = np.asarray(server["age"])
        assert (ages[ages < 0] == packing.PAD_AGE).all()

    def test_composes_with_fade_ef_async(self):
        from repro.launch.steps import OacServerConfig
        oac = OacServerConfig(sanitize=True, error_feedback=True,
                              async_agg=True, fade=0.05,
                              wireless=_wcfg(16, gmin=0.3))
        (cfg, mesh, bundle, params, opt_state, server,
         srv_abs) = self._setup(oac)
        assert set(server) == {"g", "age", "theta", "fad", "res",
                               "shadow", "pending"}
        *_, loss = self._steps(cfg, mesh, bundle, params, opt_state,
                               server)
        assert np.isfinite(float(loss))

    def test_requires_packed_sanitize(self):
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.launch.steps import OacServerConfig, make_train_step
        cfg = get_config("mamba2-370m", reduced_variant=True)
        mesh = make_mesh((1, 1), ("data", "model"))
        shape = InputShape("t", 64, 2, "train")
        with pytest.raises(ValueError, match="sanitize"):
            make_train_step(cfg, shape, mesh,
                            oac=OacServerConfig(wireless=_wcfg(16)))
        with pytest.raises(ValueError, match="sanitize"):
            make_train_step(cfg, shape, mesh,
                            oac=OacServerConfig(packed=False, sanitize=True,
                                                wireless=_wcfg(16)))

    def test_checkpoint_roundtrip_and_migration(self, tmp_path):
        """A wireless checkpoint round-trips the fading chain bit-exactly;
        a PRE-channel checkpoint migrates by re-synthesizing the
        deterministic stationary draw (value-bearing — NOT zeros)."""
        from repro import checkpoint
        from repro.launch.steps import OacServerConfig
        oac = OacServerConfig(sanitize=True,
                              wireless=_wcfg(16, gmin=0.3, rho_f=0.5))
        (cfg, mesh, bundle, params, opt_state, server,
         srv_abs) = self._setup(oac)
        params, opt_state, server, _ = self._steps(
            cfg, mesh, bundle, params, opt_state, server)
        path = checkpoint.save_server_state(str(tmp_path / "w.npz"), server)
        back, _ = checkpoint.restore_server_state(path)
        np.testing.assert_array_equal(np.asarray(back["fad"]),
                                      np.asarray(server["fad"]))
        # pre-channel checkpoint: drop fad, migrate it back
        pre = {k: v for k, v in server.items() if k != "fad"}
        p2 = checkpoint.save_server_state(str(tmp_path / "pre.npz"), pre)
        srv_np, _ = checkpoint.restore_server_state(p2)
        out = checkpoint.migrate_server_state(srv_np, like=server)
        assert set(out) == set(server)
        np.testing.assert_array_equal(
            np.asarray(out["fad"]),
            np.asarray(chan.init_block_fading(
                int(server["fad"].shape[0]) // 2)))
        assert float(np.abs(np.asarray(out["fad"])).sum()) > 0.0
        # dropping the fading chain in the wireless -> plain direction
        # still rejects (it would silently lose the outage correlation)
        with pytest.raises(ValueError, match="fad"):
            checkpoint.migrate_server_state(dict(server), like=pre)

"""Property tests for the analytic staleness machinery (satellite).

Pins, for ARBITRARY valid parameters (not just the hand-picked operating
points of the acceptance tests):

* Gilbert–Elliott chains hit their stationary targets exactly — both the
  fault chain (``pi_bad == dropout``) and the population availability
  chain (``pi_good == avail``) — whenever the feasibility validators
  admit the configuration;
* every pmf ``core/markov.py`` can emit (Lemma 1, lag-shifted, thinned,
  population-thinned) is nonnegative and sums to one;
* the shift (translation) and thin (geometric convolution) transforms
  commute with each other and shift composes additively — the algebra
  the composed async + churn predictions rely on.
"""

import jax
import numpy as np

import statutil
from hypothesis import given, settings
from hypothesis import strategies as st
from repro.core import channel as chan
from repro.core import faults, markov, population


def _chain(d: int, k_frac: float, km_frac: float) -> markov.FairKChain:
    """Map unconstrained draws onto a valid FairKChain parameterization
    (0 < k_m < k <= d/2, 0 < k0 < k_m)."""
    k = max(2, min(d // 2, int(round(k_frac * d / 2))))
    k_m = max(1, min(k - 1, int(round(km_frac * k))))
    k0 = max(1, min(k_m - 1, int(round(k_m * (1.0 - k_m / d))))) \
        if k_m > 1 else None
    if k0 is None:                       # k_m == 1 leaves no room for k0
        k_m, k = 2, max(3, k)
        k = min(k, d // 2)
        k0 = 1
    return markov.FairKChain(d=d, k=k, k_m=k_m, k0=k0)


# ---------------------------------------------------------------------------
# Gilbert–Elliott stationarity — fault chain and population chain
# ---------------------------------------------------------------------------

class TestGEStationarity:
    @settings(max_examples=25, deadline=None)
    @given(dropout=st.floats(min_value=0.01, max_value=0.6),
           burst_scale=st.floats(min_value=1.0, max_value=10.0))
    def test_fault_chain_hits_stationary_dropout(self, dropout, burst_scale):
        """For every (dropout, burst) the feasibility validator admits,
        ``ge_probs`` must deliver pi_bad = p_gb / (p_gb + p_bg) equal to
        the requested dropout — no silent clamping."""
        need = dropout / (1.0 - dropout)
        burst = max(1.0, need * burst_scale)
        cfg = faults.FaultConfig(dropout=dropout, burst=burst)
        p_gb, p_bg = faults.ge_probs(cfg)
        assert 0.0 < p_gb <= 1.0 and 0.0 < p_bg <= 1.0
        pi_bad = p_gb / (p_gb + p_bg)
        assert abs(pi_bad - dropout) < 1e-9
        assert abs(1.0 / p_bg - burst) < 1e-9     # mean bad dwell

    @settings(max_examples=25, deadline=None)
    @given(dropout=st.floats(min_value=0.01, max_value=0.6))
    def test_fault_chain_iid_special_case(self, dropout):
        """burst=None is the memoryless chain: next state independent of
        the current one, stationary mass still exactly ``dropout``."""
        p_gb, p_bg = faults.ge_probs(faults.FaultConfig(dropout=dropout))
        assert abs(p_gb - dropout) < 1e-12
        assert abs(p_gb + p_bg - 1.0) < 1e-12     # memoryless
        assert abs(p_gb / (p_gb + p_bg) - dropout) < 1e-9

    @settings(max_examples=25, deadline=None)
    @given(avail=st.floats(min_value=0.3, max_value=0.99),
           burst_scale=st.floats(min_value=1.0, max_value=10.0))
    def test_population_chain_hits_stationary_avail(self, avail, burst_scale):
        need = (1.0 - avail) / avail
        burst = max(1.0, need * burst_scale)
        cfg = population.PopulationConfig(
            n_clients=1024, cohort_size=256, participants=8,
            avail=avail, mode="ge", burst=burst)
        p_gb, p_bg = population.transition_probs(cfg)
        assert 0.0 < p_gb <= 1.0 and 0.0 < p_bg <= 1.0
        pi_good = p_bg / (p_gb + p_bg)
        assert abs(pi_good - avail) < 1e-9


# ---------------------------------------------------------------------------
# every markov pmf is a pmf
# ---------------------------------------------------------------------------

class TestPmfsNormalized:
    @settings(max_examples=10, deadline=None)
    @given(d=st.sampled_from([96, 128, 256]),
           k_frac=st.floats(min_value=0.2, max_value=1.0),
           km_frac=st.floats(min_value=0.1, max_value=0.9),
           lag=st.integers(min_value=0, max_value=7),
           thin=st.floats(min_value=0.0, max_value=0.7))
    def test_all_distributions(self, d, k_frac, km_frac, lag, thin):
        chain = _chain(d, k_frac, km_frac)
        for support, pmf in (
                markov.aou_distribution(chain),
                markov.shifted_aou_distribution(chain, lag),
                markov.thinned_aou_distribution(chain, thin)):
            assert (np.asarray(pmf) >= 0.0).all()
            assert abs(float(np.asarray(pmf).sum()) - 1.0) < 1e-6
            assert len(support) == len(pmf)

    @settings(max_examples=10, deadline=None)
    @given(d=st.sampled_from([96, 128, 256]),
           k_frac=st.floats(min_value=0.2, max_value=1.0),
           km_frac=st.floats(min_value=0.1, max_value=0.9),
           avail=st.floats(min_value=0.3, max_value=0.99),
           participants=st.integers(min_value=1, max_value=64))
    def test_population_distribution(self, d, k_frac, km_frac, avail,
                                     participants):
        chain = _chain(d, k_frac, km_frac)
        support, pmf = markov.population_aou_distribution(
            chain, avail, 1.0 - avail, participants)
        assert (np.asarray(pmf) >= 0.0).all()
        assert abs(float(np.asarray(pmf).sum()) - 1.0) < 1e-6
        # thinning only delays: population mean >= synchronous mean
        sync_s, sync_p = markov.aou_distribution(chain)
        assert float((support * pmf).sum()) >= \
            float((sync_s * sync_p).sum()) - 1e-9


# ---------------------------------------------------------------------------
# transform algebra: shift and thin compose
# ---------------------------------------------------------------------------

class TestTransformAlgebra:
    @settings(max_examples=10, deadline=None)
    @given(d=st.sampled_from([96, 128]),
           k_frac=st.floats(min_value=0.3, max_value=1.0),
           km_frac=st.floats(min_value=0.2, max_value=0.8),
           lag=st.integers(min_value=0, max_value=9),
           thin=st.floats(min_value=0.0, max_value=0.7))
    def test_shift_and_thin_commute(self, d, k_frac, km_frac, lag, thin):
        """A deterministic lag and an independent geometric delay add —
        the order of the transforms cannot matter."""
        base = markov.aou_distribution(_chain(d, k_frac, km_frac))
        s_a, p_a = markov.thin_pmf(*markov.shift_pmf(*base, lag), thin)
        s_b, p_b = markov.shift_pmf(*markov.thin_pmf(*base, thin), lag)
        assert int(s_a[0]) == int(s_b[0])
        n = min(len(p_a), len(p_b))
        np.testing.assert_allclose(p_a[:n], p_b[:n], atol=1e-12)
        assert float(np.abs(p_a[n:]).sum()) < 1e-9
        assert float(np.abs(p_b[n:]).sum()) < 1e-9

    @settings(max_examples=10, deadline=None)
    @given(lag1=st.integers(min_value=0, max_value=6),
           lag2=st.integers(min_value=0, max_value=6))
    def test_shift_composes_additively(self, lag1, lag2):
        base = markov.aou_distribution(
            markov.FairKChain(d=128, k=32, k_m=16, k0=14))
        s_ab, p_ab = markov.shift_pmf(*markov.shift_pmf(*base, lag1), lag2)
        s_sum, p_sum = markov.shift_pmf(*base, lag1 + lag2)
        np.testing.assert_array_equal(s_ab, s_sum)
        np.testing.assert_allclose(p_ab, p_sum, atol=0.0)

    @settings(max_examples=25, deadline=None)
    @given(avail=st.floats(min_value=0.3, max_value=0.99),
           participants=st.integers(min_value=1, max_value=64),
           exposure=st.floats(min_value=0.05, max_value=1.0))
    def test_population_thin_matches_config(self, avail, participants,
                                            exposure):
        """``markov.population_thin`` (numpy-side prediction) and
        ``PopulationConfig.thin`` (jax-side simulator) are the SAME
        number — the validation suite depends on that identity."""
        cfg = population.PopulationConfig(
            n_clients=1024, cohort_size=256, participants=participants,
            avail=avail, exposure=exposure)
        pred = markov.population_thin(avail, cfg.vanish_rate, participants,
                                      exposure)
        assert 0.0 <= pred <= 0.99
        assert abs(pred - cfg.thin) < 1e-12


# ---------------------------------------------------------------------------
# wireless channel: truncation law, composition, AR(1) fading
# ---------------------------------------------------------------------------

class TestChannelLaw:
    @settings(max_examples=20, deadline=None)
    @given(d=st.sampled_from([96, 128]),
           k_frac=st.floats(min_value=0.3, max_value=1.0),
           km_frac=st.floats(min_value=0.2, max_value=0.8),
           pmax=st.floats(min_value=0.5, max_value=100.0),
           gmin=st.floats(min_value=0.0, max_value=2.0),
           n=st.integers(min_value=1, max_value=16),
           pl=st.floats(min_value=0.0, max_value=4.0))
    def test_channel_pmf_is_pmf(self, d, k_frac, km_frac, pmax, gmin, n,
                                pl):
        """For ARBITRARY valid (pmax, gmin, gains) the truncated-inversion
        law stays a pmf, and its thinning rate stays inside [0, 0.99]."""
        gains = chan.ChannelConfig(n_clients=n, pmax=pmax, gmin=gmin,
                                   pl_exp=pl).gains
        t = markov.truncation_thin(pmax, gmin, gains)
        assert 0.0 <= t <= 0.99
        support, pmf = markov.channel_aou_distribution(
            _chain(d, k_frac, km_frac), pmax, gmin, gains)
        assert (np.asarray(pmf) >= 0.0).all()
        assert abs(float(np.asarray(pmf).sum()) - 1.0) < 1e-6
        assert len(support) == len(pmf)

    @settings(max_examples=20, deadline=None)
    @given(pmax=st.floats(min_value=1.0, max_value=50.0),
           gmin=st.floats(min_value=0.3, max_value=1.5),
           n=st.integers(min_value=1, max_value=8),
           extra=st.floats(min_value=0.0, max_value=0.7))
    def test_truncation_and_population_thin_commute(self, pmax, gmin, n,
                                                    extra):
        """Independent blocking channels compose symmetrically:
        1 - (1-t)(1-e) no matter which is folded in as ``extra_thin``."""
        chain = markov.FairKChain(d=128, k=32, k_m=16, k0=14)
        gains = chan.ChannelConfig(n_clients=n, pmax=pmax, gmin=gmin).gains
        t = markov.truncation_thin(pmax, gmin, gains)
        composed = min(0.99, 1.0 - (1.0 - t) * (1.0 - extra))
        s_a, p_a = markov.channel_aou_distribution(chain, pmax, gmin,
                                                   gains, extra_thin=extra)
        s_b, p_b = markov.thinned_aou_distribution(chain, composed)
        np.testing.assert_array_equal(s_a, s_b)
        np.testing.assert_allclose(p_a, p_b, atol=1e-12)

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(min_value=2, max_value=32),
           pmax=st.floats(min_value=1.0, max_value=50.0),
           gmin=st.floats(min_value=0.0, max_value=1.0),
           pl=st.floats(min_value=0.0, max_value=4.0),
           shadow=st.floats(min_value=0.0, max_value=6.0),
           seed=st.integers(min_value=0, max_value=999))
    def test_thin_identity_config_vs_markov(self, n, pmax, gmin, pl,
                                            shadow, seed):
        """``ChannelConfig.thin`` (simulator setpoint) and
        ``markov.truncation_thin`` (analysis law) are the SAME number for
        every deployment geometry — the controller absorbs exactly the
        rate the prediction assumes."""
        cfg = chan.ChannelConfig(n_clients=n, pmax=pmax, gmin=gmin,
                                 pl_exp=pl, shadow_db=shadow,
                                 geo_seed=seed)
        assert abs(cfg.thin
                   - markov.truncation_thin(pmax, gmin, cfg.gains)) < 1e-12


class TestFadingChain:
    @settings(max_examples=5, deadline=None)
    @given(rho=st.sampled_from([0.0, 0.5, 0.9]),
           seed=st.integers(min_value=0, max_value=99))
    def test_ar1_power_is_stationary_exp1(self, rho, seed):
        """|f|^2 of the complex AR(1) chain stays Exp(1)-distributed for
        every correlation: the innovation scaling sqrt(1 - rho^2)
        preserves the stationary Rayleigh marginal exactly.  Binned mass
        vs the analytic exponential via the statutil TV harness."""
        import jax.numpy as jnp
        cfg = chan.ChannelConfig(n_clients=512, rho_f=rho)
        st_ = chan.init_channel_state(jax.random.PRNGKey(seed), cfg)
        key = jax.random.PRNGKey(seed + 1)
        step = jax.jit(chan.fading_step, static_argnums=2)
        pows = []
        for r in range(60):
            key, sub = jax.random.split(key)
            st_ = {"fad": step(st_["fad"], sub, rho)}
            if r >= 20:
                f = np.asarray(st_["fad"])
                pows.append(f[:, 0] ** 2 + f[:, 1] ** 2)
        p = np.concatenate(pows)
        edges = np.linspace(0.0, 4.0, 17)
        emp_mass, _ = np.histogram(p, bins=edges)
        emp = np.concatenate([emp_mass / len(p),
                              [(p >= edges[-1]).mean()]])
        cdf = 1.0 - np.exp(-edges)
        pred = np.concatenate([np.diff(cdf), [np.exp(-edges[-1])]])
        # high rho_f correlates consecutive rounds (effective sample count
        # shrinks by the ~1/(1 - rho^2) mixing time), hence the tolerance
        assert statutil.tv_distance(emp, pred) < 0.05

    def test_fading_deterministic_in_state_and_key(self):
        cfg = chan.ChannelConfig(n_clients=64, rho_f=0.8)
        st0 = chan.init_channel_state(jax.random.PRNGKey(3), cfg)
        a = chan.fading_step(st0["fad"], jax.random.PRNGKey(4), cfg.rho_f)
        b = chan.fading_step(st0["fad"], jax.random.PRNGKey(4), cfg.rho_f)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c = chan.fading_step(st0["fad"], jax.random.PRNGKey(5), cfg.rho_f)
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_rho_zero_is_memoryless(self):
        """At rho_f = 0 the next fading state is a pure function of the
        key — independent of the carried state."""
        key = jax.random.PRNGKey(7)
        s1 = chan.init_channel_state(jax.random.PRNGKey(0),
                                     chan.ChannelConfig(n_clients=32))
        s2 = chan.init_channel_state(jax.random.PRNGKey(1),
                                     chan.ChannelConfig(n_clients=32))
        a = chan.fading_step(s1["fad"], key, 0.0)
        b = chan.fading_step(s2["fad"], key, 0.0)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

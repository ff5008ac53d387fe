"""Over-the-air channel model + aggregation (paper Sec. III-A, Eq. 7-8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import oac
from repro.core.oac import ChannelConfig


class TestFading:
    def test_rayleigh_moments(self):
        cfg = ChannelConfig(fading="rayleigh", mean=1.0)
        h = oac.sample_fading(jax.random.PRNGKey(0), 200_000, cfg)
        assert float(h.mean()) == pytest.approx(1.0, abs=0.01)
        assert float(h.var()) == pytest.approx(cfg.sigma_c2, rel=0.05)
        assert float(h.min()) >= 0.0

    def test_none_fading_is_constant(self):
        cfg = ChannelConfig(fading="none", mean=1.0)
        h = oac.sample_fading(jax.random.PRNGKey(0), 16, cfg)
        np.testing.assert_allclose(np.asarray(h), 1.0)

    @pytest.mark.parametrize("mode", ["rician", "", "RAYLEIGH", "None"])
    def test_rejects_unknown_fading_mode(self, mode):
        """Unknown modes used to fall through ``sigma_c2`` to 0.0 (a
        silently deterministic channel) and only blow up at sample time —
        they must be rejected at construction."""
        with pytest.raises(ValueError, match="fading"):
            ChannelConfig(fading=mode)

    def test_rejects_rayleigh_with_explicit_std(self):
        """Rayleigh derives sigma_c from the mean; an explicit std used to
        be silently ignored."""
        with pytest.raises(ValueError, match="sigma_c"):
            ChannelConfig(fading="rayleigh", std=0.3)
        # gaussian owns its std, rayleigh owns std=0 — both construct
        assert ChannelConfig(fading="gaussian", std=0.3).sigma_c2 \
            == pytest.approx(0.09)
        ChannelConfig(fading="rayleigh", std=0.0)


class TestAggregation:
    def test_noiseless_equals_fedavg(self):
        """With h=1 and no noise, OAC == plain client averaging on S_t."""
        rng = np.random.default_rng(0)
        grads = jnp.asarray(rng.normal(size=(8, 64)).astype("f4"))
        g_prev = jnp.asarray(rng.normal(size=64).astype("f4"))
        idx = jnp.asarray([3, 7, 11, 20, 33, 41], jnp.int32)
        g_t, agg = oac.oac_round(jax.random.PRNGKey(0), g_prev, idx, grads,
                                 oac.NOISELESS)
        np.testing.assert_allclose(np.asarray(agg),
                                   np.asarray(grads[:, idx].mean(0)),
                                   rtol=1e-6)
        # stale entries untouched (Eq. 8)
        mask = np.ones(64, bool)
        mask[np.asarray(idx)] = False
        np.testing.assert_array_equal(np.asarray(g_t)[mask],
                                      np.asarray(g_prev)[mask])

    def test_noise_scales_inverse_n(self):
        """Eq. (7): the noise term enters as xi / N."""
        cfg = ChannelConfig(fading="none", mean=1.0, noise_std=1.0)
        zeros = jnp.zeros((50, 4096))
        agg = oac.oac_aggregate(jax.random.PRNGKey(1), zeros, cfg)
        assert float(jnp.std(agg)) == pytest.approx(1.0 / 50, rel=0.1)

    def test_unbiased_under_fading(self):
        """E[h] = mu_c = 1 -> aggregated gradient unbiased (many clients)."""
        cfg = ChannelConfig(fading="rayleigh", mean=1.0, noise_std=0.0)
        vals = jnp.ones((4000, 8))
        agg = oac.oac_aggregate(jax.random.PRNGKey(2), vals, cfg)
        np.testing.assert_allclose(np.asarray(agg), 1.0, atol=0.05)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 30), d=st.integers(8, 100), data=st.data())
def test_property_reconstruction_partition(n, d, data):
    """Every coordinate of g_t is either freshly aggregated or stale — and
    the selected set is exactly S_t (Eq. 8 partition invariant)."""
    k = data.draw(st.integers(1, d))
    rng = np.random.default_rng(n * 1000 + d)
    idx = jnp.asarray(rng.permutation(d)[:k].astype("i4"))
    grads = jnp.asarray(rng.normal(size=(n, d)).astype("f4"))
    g_prev = jnp.asarray(rng.normal(size=d).astype("f4"))
    g_t, agg = oac.oac_round(jax.random.PRNGKey(0), g_prev, idx, grads,
                             oac.NOISELESS)
    g_t, g_prev_n = np.asarray(g_t), np.asarray(g_prev)
    fresh = np.zeros(d, bool)
    fresh[np.asarray(idx)] = True
    np.testing.assert_array_equal(g_t[~fresh], g_prev_n[~fresh])
    np.testing.assert_allclose(g_t[np.asarray(idx)], np.asarray(agg),
                               rtol=1e-6)

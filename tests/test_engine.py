"""SelectionEngine: cross-backend parity + budget-accuracy properties.

The engine's whole value is the guarantee that the three execution paths —
exact lax.top_k, threshold kernel, sharded shard_map — implement the SAME
selection rule.  The parity tests pin that down bit-exactly on
dense-tie-free inputs (distinct |g| magnitudes, distinct integer ages) with
order-statistic thresholds; the property tests bound the sampled-quantile
budget error the production path actually runs with."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import selection
from repro.core.engine import (AGE_CAP, EngineConfig, SelectionEngine,
                               exact_thresholds, index_jitter, make_engine,
                               masked_merge, threshold_mask)
from repro.kernels import ops
from repro.launch.mesh import make_mesh


def _tie_free(d, seed=0):
    """(g, g_prev, age): distinct |g| (generic normals), distinct int ages."""
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=d).astype("f4"))
    g_prev = jnp.asarray(rng.normal(size=d).astype("f4"))
    age = jnp.asarray(rng.permutation(d).astype("f4"))
    return g, g_prev, age


# ---------------------------------------------------------------------------
# cross-backend parity (the acceptance-criterion test)
# ---------------------------------------------------------------------------

class TestBackendParity:
    @pytest.mark.parametrize("policy,k_m_frac", [
        ("fairk", 0.75), ("fairk", 0.25), ("topk", 1.0), ("roundrobin", 0.0),
    ])
    def test_exact_threshold_sharded_identical(self, policy, k_m_frac):
        """All three backends reconstruct identical (g_t, age') on tie-free
        inputs when the threshold backends use order-statistic thetas."""
        d = 4096
        g, g_prev, age = _tie_free(d, seed=hash(policy) % 100)
        common = dict(policy=policy, rho=0.1, k_m_frac=k_m_frac,
                      exact_theta=True)
        ex = SelectionEngine(EngineConfig(backend="exact", **common), d)
        th = SelectionEngine(EngineConfig(backend="threshold",
                                          kernel_mode="interpret", **common),
                             d)
        mesh = make_mesh((1,), ("shard",))
        sh = SelectionEngine(EngineConfig(backend="sharded", **common), d,
                             mesh=mesh)

        g1, a1, s1 = jax.jit(ex.select_and_merge)(g, g_prev, age)
        g2, a2, s2 = th.select_and_merge(g, g_prev, age)
        g3, a3, s3 = jax.jit(sh.select_and_merge)(g, g_prev, age)

        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g3))
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a3))
        k = ex.budgets()[0]
        assert float(s2["n_selected"]) == k
        assert float(s3["n_selected"]) == k

    def test_threshold_ref_equals_interpret_kernel(self):
        """The fused Pallas kernel (interpret) and the jnp oracle agree."""
        d = 4096
        g, g_prev, age = _tie_free(d, seed=7)
        tm, ta = exact_thresholds(g, age, k=409, k_m=306)
        out_ref = ops.fairk_update(g, g_prev, age, tm, ta, mode="ref")
        out_ker = ops.fairk_update(g, g_prev, age, tm, ta, mode="interpret")
        np.testing.assert_allclose(np.asarray(out_ref[0]),
                                   np.asarray(out_ker[0]), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(out_ref[1]),
                                      np.asarray(out_ker[1]))

    def test_kernel_pad_path_non_aligned(self):
        """fairk_update pads non-block-aligned d without leaking padding."""
        d = 1000  # not a multiple of any pow-2 block
        g, g_prev, age = _tie_free(d, seed=3)
        tm, ta = exact_thresholds(g, age, k=100, k_m=75)
        out_ref = ops.fairk_update(g, g_prev, age, tm, ta, mode="ref")
        out_ker = ops.fairk_update(g, g_prev, age, tm, ta, mode="interpret",
                                   block_size=256)
        assert out_ker[0].shape == (d,)
        np.testing.assert_allclose(np.asarray(out_ref[0]),
                                   np.asarray(out_ker[0]), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(out_ref[1]),
                                      np.asarray(out_ker[1]))

    def test_exact_matches_index_policy(self):
        """Exact backend == the raw core.selection policy + Eq. (8)/(10)."""
        d = 2048
        g, g_prev, age = _tie_free(d, seed=11)
        eng = make_engine("fairk", "exact", d=d, rho=0.1, k_m_frac=0.75)
        k, k_m, _ = eng.budgets()
        g_t, age_next, stats = eng.select_and_merge(g, g_prev, age)
        idx = selection.fair_k_indices(g, age, k=k, k_m=k_m)
        np.testing.assert_array_equal(np.asarray(stats["idx"]),
                                      np.asarray(idx))
        mask = np.zeros(d, np.float32)
        mask[np.asarray(idx)] = 1.0
        expect = mask * np.asarray(g) + (1 - mask) * np.asarray(g_prev)
        np.testing.assert_allclose(np.asarray(g_t), expect, rtol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(age_next),
            np.minimum((np.asarray(age) + 1) * (1 - mask), AGE_CAP))


# ---------------------------------------------------------------------------
# threshold budget properties (the sampled-quantile production path)
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 1000), data=st.data())
def test_property_threshold_count_near_k(seed, data):
    """|selected| within 15% of k for the sampled-quantile thresholds over
    generic Gaussian gradients and bounded integer ages."""
    d = 1 << 14
    rho = data.draw(st.sampled_from([0.05, 0.1, 0.2]))
    k_m_frac = data.draw(st.sampled_from([0.25, 0.5, 0.75]))
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=d).astype("f4"))
    age = jnp.asarray(rng.integers(0, 40, d).astype("f4"))
    eng = make_engine("fairk", "threshold", d=d, rho=rho,
                      k_m_frac=k_m_frac, sample_cap=d)
    _, _, stats = eng.select_and_merge(g, jnp.zeros((d,), jnp.float32), age)
    k = eng.budgets()[0]
    assert abs(float(stats["n_selected"]) - k) <= 0.15 * k, (
        float(stats["n_selected"]), k)


def test_exact_theta_sits_between_order_stats():
    d = 512
    g, _, age = _tie_free(d, seed=5)
    k, k_m = 64, 48
    tm, ta = exact_thresholds(g, age, k=k, k_m=k_m)
    mag = np.sort(np.abs(np.asarray(g)))[::-1]
    assert mag[k_m - 1] >= float(tm) >= mag[k_m]
    mask, mask_m = threshold_mask(g, age, tm, ta)
    assert float(np.asarray(mask_m).sum()) == k_m
    assert float(np.asarray(mask).sum()) == k


def test_jitter_deterministic_and_bounded():
    j = np.asarray(index_jitter(1 << 16))
    assert (0.0 <= j).all() and (j < 1.0).all()
    np.testing.assert_array_equal(j, np.asarray(index_jitter(1 << 16)))


# ---------------------------------------------------------------------------
# engine API surface
# ---------------------------------------------------------------------------

class TestEngineApi:
    def test_all_policies_exact_backend(self):
        d = 512
        g, g_prev, age = _tie_free(d, seed=13)
        for policy in selection.POLICIES:
            eng = make_engine(policy, "exact", d=d, rho=0.05)
            g_t, age_next, stats = eng.select_and_merge(
                g, g_prev, age, key=jax.random.PRNGKey(0))
            k = eng.budgets()[0]
            idx = np.asarray(stats["idx"])
            assert idx.shape == (k,)
            assert len(set(idx.tolist())) == k
            assert float((np.asarray(age_next) == 0).sum()) == k

    def test_threshold_rejects_index_policies(self):
        for policy in ("toprand", "agetopk", "randk"):
            with pytest.raises(ValueError):
                make_engine(policy, "threshold", d=128)

    def test_sharded_needs_mesh_and_divisibility(self):
        with pytest.raises(ValueError):
            make_engine("fairk", "sharded", d=128)
        mesh = make_mesh((1,), ("shard",))
        with pytest.raises(ValueError):
            SelectionEngine(EngineConfig(backend="fancy"), 128, mesh=mesh)

    def test_budgets_remark1(self):
        assert make_engine("topk", "exact", d=1000, rho=0.1).budgets()[1] == 100
        assert make_engine("roundrobin", "exact", d=1000,
                           rho=0.1).budgets()[1] == 0
        eng = make_engine("fairk", "exact", d=1000, k=64, k_m=16, r=96)
        assert eng.budgets() == (64, 16, 96)

    def test_noise_injection_only_on_selected(self):
        """With noise, unselected coordinates must stay exactly g_prev."""
        d = 1024
        g, g_prev, age = _tie_free(d, seed=17)
        eng = make_engine("fairk", "threshold", d=d, rho=0.1,
                          k_m_frac=0.75, exact_theta=True, noise_std=1.0,
                          n_clients=8)
        g_t, age_next, stats = eng.select_and_merge(
            g, g_prev, age, key=jax.random.PRNGKey(2))
        stale = np.asarray(age_next) > 0
        np.testing.assert_array_equal(np.asarray(g_t)[stale],
                                      np.asarray(g_prev)[stale])
        # fresh coords differ from the clean g (noise went in)
        fresh = ~stale
        assert (np.asarray(g_t)[fresh] != np.asarray(g)[fresh]).any()

    def test_masked_merge_age_cap(self):
        age = jnp.full((16,), AGE_CAP, jnp.float32)
        _, age_next = masked_merge(jnp.zeros(16), jnp.zeros(16), age,
                                   jnp.zeros(16))
        assert float(age_next.max()) == AGE_CAP

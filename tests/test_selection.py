"""Unit + property tests for the selection policies (paper Sec. III-B)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import selection
from repro.core.aou import update_age_by_indices


def _rand(d, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=d).astype("f4"))


class TestFairK:
    def test_exact_k_unique(self):
        g, age = _rand(200), jnp.arange(200, dtype=jnp.float32)
        idx = selection.fair_k_indices(g, age, k=20, k_m=15)
        assert idx.shape == (20,)
        assert len(set(np.asarray(idx).tolist())) == 20

    def test_reduces_to_topk(self):
        """Remark 1: k_m = k  =>  Top-k."""
        g, age = _rand(300, 1), _rand(300, 2) ** 2
        i1 = np.sort(np.asarray(selection.fair_k_indices(g, age, k=30, k_m=30)))
        i2 = np.sort(np.asarray(selection.top_k_indices(g, k=30)))
        np.testing.assert_array_equal(i1, i2)

    def test_reduces_to_round_robin(self):
        """Remark 1: k_m = 0  =>  age-priority (round robin)."""
        g = _rand(300, 1)
        age = jnp.asarray(np.random.default_rng(3).permutation(300).astype("f4"))
        i1 = np.sort(np.asarray(selection.fair_k_indices(g, age, k=30, k_m=0)))
        i2 = np.sort(np.asarray(selection.round_robin_indices(age, k=30)))
        np.testing.assert_array_equal(i1, i2)

    def test_magnitude_stage_takes_top(self):
        g = jnp.zeros(100).at[7].set(100.0).at[42].set(-99.0)
        idx = selection.fair_k_indices(g, jnp.zeros(100), k=10, k_m=2)
        assert {7, 42} <= set(np.asarray(idx[:2]).tolist())

    def test_age_stage_excludes_magnitude_picks(self):
        # entry 0: huge magnitude AND huge age -> must appear exactly once
        g = jnp.zeros(64).at[0].set(50.0)
        age = jnp.zeros(64).at[0].set(1000.0)
        idx = np.asarray(selection.fair_k_indices(g, age, k=8, k_m=4))
        assert (idx == 0).sum() == 1

    def test_round_robin_cycles(self):
        """With equal ages the schedule must sweep all of [d] in d/k rounds."""
        d, k = 64, 8
        age = jnp.zeros(d)
        seen = set()
        for _ in range(d // k):
            idx = selection.round_robin_indices(age, k=k)
            seen.update(np.asarray(idx).tolist())
            age = update_age_by_indices(age, idx)
        assert seen == set(range(d))

    def test_max_staleness_bound(self):
        """Lemma 1: staleness never exceeds T = ceil((d-k_m)/k_a)."""
        d, k, k_m = 120, 12, 9
        T = -(-(d - k_m) // (k - k_m))
        rng = np.random.default_rng(0)
        g = jnp.zeros(d)
        age = jnp.zeros(d)
        for t in range(8 * T):
            g = jnp.asarray(rng.normal(size=d).astype("f4"))
            idx = selection.fair_k_indices(g, age, k=k, k_m=k_m)
            age = update_age_by_indices(age, idx)
            assert float(age.max()) <= T, f"round {t}: age {float(age.max())}"


class TestBaselines:
    def test_age_topk_subset_of_top_r(self):
        g, age = _rand(256, 5), _rand(256, 6) ** 2
        idx = np.asarray(selection.age_top_k_indices(g, age, k=16, r=24))
        top_r = set(np.asarray(selection.top_k_indices(g, k=24)).tolist())
        assert set(idx.tolist()) <= top_r
        assert len(set(idx.tolist())) == 16

    def test_top_rand_contains_top_m(self):
        key = jax.random.PRNGKey(0)
        g = _rand(256, 7)
        idx = np.asarray(selection.top_rand_indices(key, g, k=16, k_m=12))
        top_m = set(np.asarray(selection.top_k_indices(g, k=12)).tolist())
        assert top_m <= set(idx.tolist())
        assert len(set(idx.tolist())) == 16

    def test_rand_k_uniform_coverage(self):
        key = jax.random.PRNGKey(0)
        counts = np.zeros(64)
        for i in range(200):
            key, sub = jax.random.split(key)
            idx = np.asarray(selection.rand_k_indices(sub, 64, k=8))
            counts[idx] += 1
        # every entry selected at least once over 200 rounds (p_miss ~ 3e-12)
        assert (counts > 0).all()

    @pytest.mark.parametrize("policy", selection.POLICIES)
    def test_registry_all_policies(self, policy):
        key = jax.random.PRNGKey(1)
        g, age = _rand(128, 8), _rand(128, 9) ** 2
        idx = selection.select_indices(policy, key, g, age, k=16, k_m=12, r=24)
        assert idx.shape == (16,)
        assert len(set(np.asarray(idx).tolist())) == 16


@settings(max_examples=30, deadline=None)
@given(d=st.integers(10, 300), data=st.data())
def test_property_fairk_budget(d, data):
    """For any (d, k, k_m): exactly k unique indices, all in range."""
    k = data.draw(st.integers(1, d))
    k_m = data.draw(st.integers(0, k))
    rng = np.random.default_rng(d)
    g = jnp.asarray(rng.normal(size=d).astype("f4"))
    age = jnp.asarray(rng.integers(0, 50, d).astype("f4"))
    idx = np.asarray(selection.fair_k_indices(g, age, k=k, k_m=k_m))
    assert idx.shape == (k,)
    assert len(set(idx.tolist())) == k
    assert (0 <= idx).all() and (idx < d).all()


@settings(max_examples=20, deadline=None)
@given(d=st.integers(20, 200), data=st.data())
def test_property_age_stage_picks_oldest(d, data):
    """The age stage must pick the k_a oldest among non-magnitude-picked."""
    k = data.draw(st.integers(2, min(d, 20)))
    k_m = data.draw(st.integers(1, k - 1))
    rng = np.random.default_rng(d + 1)
    g = jnp.asarray(rng.normal(size=d).astype("f4"))
    age = jnp.asarray(rng.permutation(d).astype("f4"))  # unique ages
    idx = np.asarray(selection.fair_k_indices(g, age, k=k, k_m=k_m))
    mag_picks = set(idx[:k_m].tolist())
    age_np = np.asarray(age)
    rest = [i for i in range(d) if i not in mag_picks]
    expected = set(sorted(rest, key=lambda i: -age_np[i])[: k - k_m])
    assert set(idx[k_m:].tolist()) == expected

"""Pallas kernels: interpret-mode execution vs pure-jnp oracles, swept over
shapes and dtypes (per the kernel-validation requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref


def _arr(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32)).astype(dtype)


class TestBlockTopK:
    @pytest.mark.parametrize("d,block,m", [
        (4096, 512, 4), (8192, 1024, 8), (16384, 4096, 16), (2048, 2048, 32),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, d, block, m, dtype):
        x = _arr((d,), dtype, seed=d + m)
        v_ker, i_ker = ops.block_topk(x, block, m, mode="interpret")
        v_ref, i_ref = ref.block_topk_ref(x.astype(jnp.float32), block, m)
        np.testing.assert_allclose(np.asarray(v_ker), np.asarray(v_ref),
                                   rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(i_ker), np.asarray(i_ref))

    def test_two_stage_exact_when_pool_sufficient(self):
        x = _arr((8192,), jnp.float32, seed=7)
        tv, ti = ops.two_stage_topk(x, k=64, block_size=1024, mode="interpret")
        ev, _ = jax.lax.top_k(jnp.abs(x), 64)
        np.testing.assert_allclose(np.sort(np.asarray(tv)),
                                   np.sort(np.asarray(ev)), rtol=1e-6)

    def test_indices_point_at_values(self):
        x = _arr((4096,), jnp.float32, seed=9)
        vals, idxs = ops.block_topk(x, 512, 8, mode="interpret")
        np.testing.assert_allclose(
            np.asarray(vals).ravel(),
            np.abs(np.asarray(x))[np.asarray(idxs).ravel()], rtol=1e-6)


class TestAouMerge:
    @pytest.mark.parametrize("d,block", [(8192, 1024), (65536, 65536),
                                         (4096, 512)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, d, block, dtype):
        rng = np.random.default_rng(d)
        g_new = _arr((d,), dtype, 1)
        g_old = _arr((d,), dtype, 2)
        age = jnp.asarray(rng.integers(0, 40, d).astype("f4"))
        mask = jnp.asarray((rng.random(d) < 0.1).astype("f4"))
        g_k, a_k = ops.aou_merge(g_new, g_old, age, mask, mode="interpret")
        g_r, a_r = ref.aou_merge_ref(g_new.astype(jnp.float32),
                                     g_old.astype(jnp.float32), age, mask)
        np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r), rtol=1e-6)

    @settings(max_examples=20, deadline=None)
    @given(d=st.sampled_from([256, 1024, 4096]), seed=st.integers(0, 99))
    def test_property_merge_partition(self, d, seed):
        """Selected coords get g_new and age 0; others keep g_old, age+1."""
        rng = np.random.default_rng(seed)
        g_new = jnp.asarray(rng.normal(size=d).astype("f4"))
        g_old = jnp.asarray(rng.normal(size=d).astype("f4"))
        age = jnp.asarray(rng.integers(0, 30, d).astype("f4"))
        mask = jnp.asarray((rng.random(d) < 0.2).astype("f4"))
        g, a = ops.aou_merge(g_new, g_old, age, mask, mode="interpret")
        g, a, m = np.asarray(g), np.asarray(a), np.asarray(mask).astype(bool)
        np.testing.assert_allclose(g[m], np.asarray(g_new)[m], rtol=1e-6)
        np.testing.assert_allclose(g[~m], np.asarray(g_old)[~m], rtol=1e-6)
        np.testing.assert_allclose(a[m], 0.0)
        np.testing.assert_allclose(a[~m], np.asarray(age)[~m] + 1)


class TestSignMV:
    @pytest.mark.parametrize("n,k", [(5, 2048), (21, 4096), (50, 1024),
                                     (2, 8192)])
    def test_matches_oracle(self, n, k):
        rng = np.random.default_rng(n * k)
        votes = jnp.asarray(np.sign(rng.normal(size=(n, k))).astype("f4"))
        signs_k, energy_k = ops.sign_mv(votes, mode="interpret")
        signs_r, energy_r = ref.sign_mv_ref(votes)
        np.testing.assert_array_equal(np.asarray(signs_k),
                                      np.asarray(signs_r))
        np.testing.assert_array_equal(np.asarray(energy_k),
                                      np.asarray(energy_r))
        # the energy IS the superposed vote sum — no second reduction
        np.testing.assert_array_equal(np.asarray(energy_k),
                                      np.asarray(votes.sum(axis=0)))

    @pytest.mark.parametrize("mode", ["ref", "interpret"])
    def test_noisy_energy_consistency(self, mode):
        """With channel noise the energy is perturbed BEFORE the sign
        (Sec. V-B non-coherent detection): signs == sign(energy) and
        energy == clean vote sum + noise, kernel == oracle."""
        rng = np.random.default_rng(7)
        votes = jnp.asarray(np.sign(rng.normal(size=(9, 1024))).astype("f4"))
        noise = jnp.asarray((3.0 * rng.normal(size=1024)).astype("f4"))
        signs, energy = ops.sign_mv(votes, noise=noise, mode=mode)
        signs_r, energy_r = ref.sign_mv_ref(votes, noise)
        np.testing.assert_allclose(np.asarray(energy),
                                   np.asarray(votes.sum(0) + noise),
                                   rtol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(signs), np.where(np.asarray(energy) >= 0, 1.0, -1.0))
        np.testing.assert_array_equal(np.asarray(signs),
                                      np.asarray(signs_r))
        np.testing.assert_allclose(np.asarray(energy),
                                   np.asarray(energy_r), rtol=1e-6)

    def test_majority_semantics(self):
        votes = jnp.asarray(np.vstack([np.ones((3, 128)),
                                       -np.ones((2, 128))]).astype("f4"))
        signs, energy = ops.sign_mv(votes, mode="interpret")
        np.testing.assert_array_equal(np.asarray(signs), 1.0)
        np.testing.assert_array_equal(np.asarray(energy), 1.0)  # 3 - 2

    @pytest.mark.parametrize("mode", ["ref", "interpret"])
    @pytest.mark.parametrize("noisy", [False, True])
    def test_sign_from_energy_matches_sign_mv(self, mode, noisy):
        """The streaming one-bit fold pre-reduces the votes chunk by chunk
        and detects on the (k,) energy row: sign_from_energy on the summed
        votes must match sign_mv on the full matrix bit for bit."""
        rng = np.random.default_rng(13)
        votes = jnp.asarray(np.sign(rng.normal(size=(9, 2048)) + 0.05)
                            .astype("f4"))
        noise = (jnp.asarray((2.0 * rng.normal(size=2048)).astype("f4"))
                 if noisy else None)
        signs_d, energy_d = ops.sign_mv(votes, noise=noise, mode=mode)
        signs_s, energy_s = ops.sign_from_energy(votes.sum(axis=0),
                                                 noise=noise, mode=mode)
        np.testing.assert_array_equal(np.asarray(signs_d),
                                      np.asarray(signs_s))
        np.testing.assert_array_equal(np.asarray(energy_d),
                                      np.asarray(energy_s))

    def test_sign_from_energy_odd_length_falls_back(self):
        # k with no aligned block divisor exercises the block_k == k path
        energy = jnp.asarray(np.linspace(-3, 3, 771).astype("f4"))
        signs, e = ops.sign_from_energy(energy, mode="interpret")
        signs_r, e_r = ref.sign_from_energy_ref(energy)
        np.testing.assert_array_equal(np.asarray(signs),
                                      np.asarray(signs_r))
        np.testing.assert_array_equal(np.asarray(e), np.asarray(e_r))


class TestFairKUpdate:
    @pytest.mark.parametrize("d,block", [(8192, 1024), (65536, 65536),
                                         (16384, 4096)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, d, block, dtype):
        rng = np.random.default_rng(d)
        g = _arr((d,), dtype, 11)
        gp = _arr((d,), dtype, 12)
        age = jnp.asarray(rng.integers(0, 40, d).astype("f4"))
        tm, ta = jnp.float32(1.2), jnp.float32(33.7)
        out_k = ops.fairk_update(g, gp, age, tm, ta, mode="interpret")
        out_r = ref.fairk_update_ref(g.astype(jnp.float32),
                                     gp.astype(jnp.float32), age, tm, ta)
        for a, b in zip(out_k, out_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)

    def test_selected_fraction_tracks_thresholds(self):
        """With theta_M at the (1-rho_m) quantile and theta_A sized for the
        rest, the fused update refreshes ~rho of coordinates."""
        rng = np.random.default_rng(0)
        d = 1 << 16
        g = jnp.asarray(rng.normal(size=d).astype("f4"))
        gp = jnp.zeros((d,), jnp.float32)
        age = jnp.asarray(rng.integers(0, 40, d).astype("f4"))
        rho, km = 0.1, 0.75
        tm = jnp.quantile(jnp.abs(g), 1 - rho * km)
        ta = jnp.quantile(age + 0.5, 1 - rho * (1 - km) / (1 - rho * km))
        g_t, age_next = ops.fairk_update(g, gp, age, tm, ta,
                                         mode="interpret")
        frac_fresh = float((np.asarray(age_next) == 0).mean())
        assert abs(frac_fresh - rho) < 0.03

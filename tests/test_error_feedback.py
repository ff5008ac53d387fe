"""Error feedback & one-bit on the threshold/packed backends (PR 3).

Pins the tentpole guarantees:
* bit-exact parity: exact-EF == threshold-EF == packed-EF (and the sharded
  backend) under ``exact_theta`` on tie-free inputs — the residual stage of
  the fused kernel computes the SAME (g_t, age', residual') as the index
  path;
* residual conservation: selected mass + residual' == effective gradient
  (``mask * sent + residual' == g + residual``), bit-exact;
* pad protocol: packing pads are never selected and pass their residual
  through unchanged;
* the one-bit ``fresh`` decoupling (sign_mv majority votes merged while the
  vote energy is scored) agrees across backends;
* regression: ``FLConfig(backend="packed"/"threshold", error_feedback=True
  / one_bit=True)`` no longer raises and trains end-to-end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing
from repro.core.engine import EngineConfig, SelectionEngine
from repro.kernels import ops
from repro.launch.mesh import make_mesh


def _tie_free(d, seed=0):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=d).astype("f4"))
    g_prev = jnp.asarray(rng.normal(size=d).astype("f4"))
    age = jnp.asarray(rng.permutation(d).astype("f4"))
    res = jnp.asarray(rng.normal(size=d).astype("f4"))
    return g, g_prev, age, res


def _engines(d, backend_kw=None, **common):
    common = dict(policy="fairk", rho=0.1, k_m_frac=0.75, exact_theta=True,
                  **common)
    ex = SelectionEngine(EngineConfig(backend="exact", **common), d)
    th = SelectionEngine(EngineConfig(backend="threshold", **common), d)
    return ex, th


# ---------------------------------------------------------------------------
# engine parity with residual / fresh (the acceptance criterion)
# ---------------------------------------------------------------------------

class TestEngineParityEF:
    def test_exact_vs_threshold_ef_bit_exact(self):
        d = 4096
        g, gp, age, res = _tie_free(d)
        ex, th = _engines(d)
        g1, a1, s1 = jax.jit(ex.select_and_merge)(g, gp, age, residual=res)
        g2, a2, s2 = th.select_and_merge(g, gp, age, residual=res)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        np.testing.assert_array_equal(np.asarray(s1["residual"]),
                                      np.asarray(s2["residual"]))

    def test_exact_vs_sharded_ef_bit_exact(self):
        d = 4096
        g, gp, age, res = _tie_free(d, seed=3)
        common = dict(policy="fairk", rho=0.1, k_m_frac=0.75,
                      exact_theta=True)
        ex = SelectionEngine(EngineConfig(backend="exact", **common), d)
        mesh = make_mesh((1,), ("shard",))
        sh = SelectionEngine(EngineConfig(backend="sharded", **common), d,
                             mesh=mesh)
        g1, a1, s1 = jax.jit(ex.select_and_merge)(g, gp, age, residual=res)
        g2, a2, s2 = jax.jit(sh.select_and_merge)(g, gp, age, residual=res)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        np.testing.assert_array_equal(np.asarray(s1["residual"]),
                                      np.asarray(s2["residual"]))

    def test_exact_vs_packed_ef_bit_exact_on_packed_tree(self):
        """The headline claim: exact-EF == packed-EF bit-exact under
        exact_theta on a REAL multi-leaf packed layout (pads inside)."""
        rng = np.random.default_rng(5)
        leaves = [rng.normal(size=s).astype("f4")
                  for s in (300, 4096, 77, 1000)]
        lay = packing.PackedLayout.from_tree(
            [jnp.asarray(l) for l in leaves])
        d = lay.d_packed
        g_buf = lay.pack([jnp.asarray(l) for l in leaves])
        gp_buf = lay.pack([jnp.asarray(rng.normal(size=l.shape)
                                       .astype("f4")) for l in leaves])
        age_buf = lay.pack_age(
            [jnp.asarray(a.astype("f4")) for a in np.split(
                rng.permutation(lay.d_valid),
                np.cumsum([l.size for l in leaves])[:-1])])
        res_buf = lay.pack([jnp.asarray(rng.normal(size=l.shape)
                                        .astype("f4")) for l in leaves])
        pk = SelectionEngine(
            EngineConfig(policy="fairk", backend="packed", rho=0.1,
                         k_m_frac=0.75, exact_theta=True,
                         kernel_mode="interpret"), d, layout=lay)
        k, k_m, r = pk.budgets()
        ex = SelectionEngine(
            EngineConfig(policy="fairk", backend="exact", k=k, k_m=k_m,
                         r=r), d)
        g1, a1, s1 = pk.select_and_merge(g_buf, gp_buf, age_buf,
                                         residual=res_buf)
        g2, a2, s2 = jax.jit(ex.select_and_merge)(g_buf, gp_buf, age_buf,
                                                  residual=res_buf)
        valid = np.asarray(lay.valid_mask())
        np.testing.assert_array_equal(np.asarray(g1)[valid],
                                      np.asarray(g2)[valid])
        np.testing.assert_array_equal(np.asarray(a1)[valid],
                                      np.asarray(a2)[valid])
        np.testing.assert_array_equal(np.asarray(s1["residual"])[valid],
                                      np.asarray(s2["residual"])[valid])
        assert float(s1["n_selected"]) == k
        # pads: never selected, sentinel + residual pass through unchanged
        np.testing.assert_array_equal(np.asarray(a1)[~valid],
                                      packing.PAD_AGE)
        np.testing.assert_array_equal(np.asarray(s1["residual"])[~valid],
                                      np.asarray(res_buf)[~valid])

    def test_one_bit_fresh_parity_exact_vs_threshold(self):
        """Decoupled ``fresh`` (the one-bit majority-vote signs) merges the
        same values on the exact and threshold backends."""
        d = 4096
        g, gp, age, _ = _tie_free(d, seed=9)
        fresh = jnp.where(g >= 0, 1.0, -1.0).astype(jnp.float32)
        ex, th = _engines(d)
        g1, a1, _ = jax.jit(ex.select_and_merge)(g, gp, age, fresh=fresh)
        g2, a2, _ = th.select_and_merge(g, gp, age, fresh=fresh)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        # selected coords carry the ±1 signs, the rest stay stale
        sel = np.asarray(a1) == 0.0
        assert set(np.unique(np.asarray(g1)[sel])) <= {-1.0, 1.0}
        np.testing.assert_array_equal(np.asarray(g1)[~sel],
                                      np.asarray(gp)[~sel])

    def test_sharded_rejects_fresh(self):
        d = 256
        g, gp, age, _ = _tie_free(d)
        mesh = make_mesh((1,), ("shard",))
        sh = SelectionEngine(
            EngineConfig(policy="fairk", backend="sharded", rho=0.1,
                         exact_theta=True), d, mesh=mesh)
        with pytest.raises(ValueError):
            sh.select_and_merge(g, gp, age, fresh=g)


# ---------------------------------------------------------------------------
# residual conservation (selected + residual mass accounting)
# ---------------------------------------------------------------------------

class TestResidualConservation:
    @pytest.mark.parametrize("backend", ["exact", "threshold"])
    def test_mass_accounting_bit_exact(self, backend):
        """mask * sent + residual' == g + residual, coordinate-wise exact:
        nothing is lost between the merge and the accumulator."""
        d = 2048
        g, gp, age, res = _tie_free(d, seed=11)
        eng = SelectionEngine(
            EngineConfig(policy="fairk", backend=backend, rho=0.15,
                         k_m_frac=0.75, exact_theta=True), d)
        g_t, age_next, stats = jax.jit(eng.select_and_merge)(
            g, gp, age, residual=res)
        sel = (np.asarray(age_next) == 0.0).astype(np.float32)
        score = np.asarray(g) + np.asarray(res)
        np.testing.assert_array_equal(
            sel * score + np.asarray(stats["residual"]), score)
        # unselected coordinates accumulate their full effective mass
        np.testing.assert_array_equal(
            np.asarray(stats["residual"])[sel == 0.0], score[sel == 0.0])
        # selected coordinates sent everything: residual resets to zero
        np.testing.assert_array_equal(
            np.asarray(stats["residual"])[sel == 1.0], 0.0)

    def test_sampled_thresholds_fold_residual(self):
        """The sampled-quantile estimate must see |g + residual|, not |g| —
        a residual that concentrates mass on low-|g| coordinates must move
        θ_M accordingly (no d-length temp needed for the estimate)."""
        from repro.core.engine import sampled_thresholds
        rng = np.random.default_rng(2)
        d = 1 << 14
        g = jnp.asarray(rng.normal(size=d).astype("f4"))
        res = jnp.asarray((10.0 * rng.normal(size=d)).astype("f4"))
        age = jnp.asarray(rng.permutation(d).astype("f4"))
        kw = dict(rho=0.1, k_m_frac=1.0, sample_cap=d)
        tm_plain, _ = sampled_thresholds(g, age, **kw)
        tm_ef, _ = sampled_thresholds(g, age, residual=res, **kw)
        tm_ref, _ = sampled_thresholds(g + res, age, **kw)
        assert float(tm_ef) == pytest.approx(float(tm_ref), rel=1e-6)
        assert float(tm_ef) > 2.0 * float(tm_plain)


# ---------------------------------------------------------------------------
# fused kernel: EF stage ref vs interpret, pad protocol
# ---------------------------------------------------------------------------

class TestEFKernel:
    def test_ref_equals_interpret(self):
        d = 4096
        g, gp, age, res = _tie_free(d, seed=21)
        age = age % 120.0
        fresh = jnp.where(g + res >= 0, 1.0, -1.0)
        tm, ta = jnp.float32(1.2), jnp.float32(100.0)
        for kw in (dict(residual=res), dict(fresh=fresh),
                   dict(residual=res, fresh=fresh)):
            out_r = ops.fairk_ef_update(g, gp, age, tm, ta, mode="ref",
                                        **kw)
            out_k = ops.fairk_ef_update(g, gp, age, tm, ta,
                                        mode="interpret", **kw)
            for a, b in zip(out_r, out_k):
                if a is None:
                    assert b is None
                    continue
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6)

    @pytest.mark.parametrize("mode", ["ref", "interpret"])
    def test_pads_pass_residual_through(self, mode):
        rng = np.random.default_rng(7)
        d = 1024
        g = jnp.asarray(rng.normal(size=d).astype("f4"))
        gp = jnp.asarray(rng.normal(size=d).astype("f4"))
        res = jnp.asarray(rng.normal(size=d).astype("f4"))
        age = jnp.asarray(rng.integers(0, 40, d).astype("f4"))
        pad = np.zeros(d, bool)
        pad[100:356] = True                       # interior pad block
        g = g.at[100:356].set(0.0)
        res = res.at[100:356].set(0.123)          # nonzero sentinel check
        age = age.at[100:356].set(packing.PAD_AGE)
        g_t, age_next, res_next = ops.fairk_ef_update(
            g, gp, age, jnp.float32(0.05), jnp.float32(0.0),
            residual=res, mode=mode, block_size=256)
        assert (np.asarray(age_next)[pad] == packing.PAD_AGE).all()
        np.testing.assert_array_equal(np.asarray(g_t)[pad],
                                      np.asarray(gp)[pad])
        np.testing.assert_array_equal(np.asarray(res_next)[pad],
                                      np.float32(0.123))
        assert (np.asarray(age_next)[~pad] == 0).all()
        np.testing.assert_array_equal(np.asarray(res_next)[~pad], 0.0)


# ---------------------------------------------------------------------------
# FL trainer regression: threshold/packed accept one_bit / error_feedback
# ---------------------------------------------------------------------------

class TestFLRegression:
    def _tiny_task(self):
        from repro.models import cnn
        params0 = cnn.init_mlp_classifier(jax.random.PRNGKey(0), 16, 3,
                                          hidden=(8,))

        def loss_fn(p, x, y):
            return cnn.softmax_xent(cnn.mlp_classifier(p, x), y)

        rng = np.random.default_rng(0)
        xs = rng.normal(size=(6, 2, 4, 16)).astype("f4")
        ys = rng.integers(0, 3, size=(6, 2, 4)).astype("i4")
        return params0, loss_fn, (xs, ys)

    @pytest.mark.parametrize("backend", ["threshold", "packed"])
    @pytest.mark.parametrize("one_bit,ef", [(False, True), (True, False),
                                            (True, True)])
    def test_no_longer_raises_and_runs(self, backend, one_bit, ef):
        """The trainer.py gate that raised on non-exact one_bit /
        error_feedback is gone: the step builds AND executes a round."""
        from repro.fl import FLConfig, make_fl_step
        from repro.core import packing as pk
        from jax.flatten_util import ravel_pytree
        params0, loss_fn, (xs, ys) = self._tiny_task()
        flat, unravel = ravel_pytree(params0)
        d = flat.shape[0]
        fl = FLConfig(n_clients=6, local_steps=2, batch_size=4, rounds=1,
                      backend=backend, one_bit=one_bit, error_feedback=ef,
                      compression_ratio=0.2)
        from repro.core import controller as budget
        step = make_fl_step(fl, unravel, loss_fn, d)
        z = jnp.zeros((d,), jnp.float32)
        w, g, age, cnt, res, mask, ts, cs, rm = step(
            jax.random.PRNGKey(0), flat, z, z, z, jnp.asarray(xs),
            jnp.asarray(ys), z, pk.init_threshold_state(),
            budget.init_controller_state())
        assert np.isfinite(np.asarray(w)).all()
        assert float(mask.sum()) > 0
        if ef:
            assert np.isfinite(np.asarray(res)).all()

    def test_unknown_backend_still_rejected(self):
        from repro.fl import FLConfig, make_fl_step
        with pytest.raises(ValueError):
            make_fl_step(FLConfig(backend="bogus"), lambda w: w,
                         lambda p, x, y: 0.0, 16)


# ---------------------------------------------------------------------------
# vmapped sweep: EF knob
# ---------------------------------------------------------------------------

def test_sweep_error_feedback_runs_and_accumulates():
    from repro.fl.sweep import SweepConfig, run_sweep
    base = dict(d=256, n_clients=4, rounds=30, noise_std=0.1)
    out_ef = run_sweep(SweepConfig(error_feedback=True, **base),
                       policies=("fairk",), n_seeds=2)
    out_no = run_sweep(SweepConfig(**base), policies=("fairk",), n_seeds=2)
    assert np.isfinite(out_ef["loss"]).all()
    assert out_ef["res_norm"][:, -1].max() > 0.0
    assert (out_no["res_norm"] == 0.0).all()

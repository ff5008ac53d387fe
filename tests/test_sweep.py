"""Vmapped sweep driver: dynamic-rank FAIR-k correctness + grid execution."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import selection
from repro.fl.sweep import (SweepConfig, fair_k_mask_dynamic, run_sweep,
                            sweep_grid)


@settings(max_examples=15, deadline=None)
@given(d=st.integers(32, 512), data=st.data())
def test_dynamic_mask_equals_exact_fairk(d, data):
    """Rank-based FAIR-k with traced k_m == exact index FAIR-k, for any
    (k, k_m), on tie-free inputs."""
    k = data.draw(st.integers(1, d))
    k_m = data.draw(st.integers(0, k))
    rng = np.random.default_rng(d + k)
    g = jnp.asarray(rng.normal(size=d).astype("f4"))
    age = jnp.asarray(rng.permutation(d).astype("f4"))
    m_dyn = np.asarray(fair_k_mask_dynamic(jnp.abs(g), age, k,
                                           jnp.int32(k_m)))
    idx = np.asarray(selection.fair_k_indices(g, age, k=k, k_m=k_m))
    m_exact = np.zeros(d, np.float32)
    m_exact[idx] = 1.0
    np.testing.assert_array_equal(m_dyn, m_exact)
    assert m_dyn.sum() == k


def test_grid_shapes_and_labels():
    cfg = SweepConfig(d=128, rounds=10, n_clients=4)
    seeds, pids, kms, adaptives, labels = sweep_grid(
        ("fairk", "topk"), (0.25, 0.75), 3, cfg)
    # topk pins k_m = k (Remark 1), so its k_m axis collapses to ONE point:
    # fairk contributes 2 fracs x 3 seeds, topk 1 x 3 — no duplicates
    assert seeds.shape == pids.shape == kms.shape == adaptives.shape == (9,)
    assert len(labels) == len(set(labels)) == 9
    assert int(adaptives.sum()) == 0              # no fairk_auto lanes
    topk_kms = [int(kms[i]) for i, l in enumerate(labels) if l[0] == "topk"]
    assert topk_kms == [cfg.k] * 3


def test_sweep_one_program_runs_and_converges():
    """The whole (policy x k_m x seed) grid runs in one compiled program;
    FAIR-k reaches the heterogeneity floor while pure Top-k starves."""
    cfg = SweepConfig(d=256, rounds=80, n_clients=8)
    out = run_sweep(cfg, policies=("fairk", "topk"), k_m_fracs=(0.75,),
                    n_seeds=2)
    assert out["loss"].shape == (4, 80)
    assert np.isfinite(out["loss"]).all()
    by_pol = {}
    for i, (pol, _, _) in enumerate(out["labels"]):
        by_pol.setdefault(pol, []).append(out["loss"][i, -1])
    # fairk converges (well below start), topk's stale coordinates never
    # refresh -> the paper's Fig. 4 ordering in miniature
    start = out["loss"][:, 0].mean()
    assert np.mean(by_pol["fairk"]) < 0.3 * start
    assert np.mean(by_pol["fairk"]) < 0.5 * np.mean(by_pol["topk"])


def test_sweep_budget_respected_every_round():
    cfg = SweepConfig(d=128, rounds=20, n_clients=4, rho=0.25)
    out = run_sweep(cfg, policies=("fairk",), k_m_fracs=(0.5,), n_seeds=1)
    np.testing.assert_allclose(out["frac_fresh"], cfg.k / cfg.d, rtol=1e-6)


def test_sweep_rejects_unknown_policy():
    with pytest.raises(ValueError):
        run_sweep(SweepConfig(d=64, rounds=2), policies=("agetopk",))

"""Async double-buffered server rounds + the age-saturation bugfix sweep.

Covers (DESIGN.md §13):

* int8 wrap regression — every age-update site clips at ``AGE_CAP`` so the
  packed int8 buffer can never wrap past 127 into the ``age < 0`` pad
  sentinel, even under async lag shifts on top of saturated ages;
* ``shift_selected_age`` / ``shift_age_hist`` semantics (lag 0 identity,
  pad preservation, histogram/buffer consistency);
* engine ``age_lag`` parity: async off is bit-exact with the synchronous
  trajectory on every backend, async on shifts ONLY the selected ages;
* async staleness accounting: the stationary post-update AoU pmf under an
  injected lag matches the lag-shifted Lemma-1 prediction
  (``markov.shifted_aou_distribution``) within the existing TV tolerance.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import statutil
from repro.core import aou, markov, packing
from repro.core.engine import (AGE_CAP, EngineConfig, SelectionEngine,
                               fair_k_masks_dynamic, make_engine, traced_km)
from repro.kernels import ref
from repro.launch.mesh import make_mesh

SDS = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# satellite 2: int8 age saturation / pad-sentinel wrap regression
# ---------------------------------------------------------------------------

def test_age_cap_is_int8_safe():
    # the whole point of the cap: age + a few rounds of async lag must
    # stay strictly below the int8 wrap point
    assert AGE_CAP == packing.AGE_CAP
    assert AGE_CAP + 6.0 < 127.0


def test_ref_oracle_age_clipped_at_cap():
    d = 512
    g = jnp.zeros((d,), jnp.float32)           # nothing selected by magnitude
    gp = jnp.zeros((d,), jnp.float32)
    age = jnp.full((d,), AGE_CAP, jnp.float32)
    theta_m = jnp.float32(jnp.inf)
    theta_a = jnp.float32(jnp.inf)             # nothing selected by age
    _, age_next = ref.fairk_update_ref(g, gp, age, theta_m, theta_a)
    assert float(age_next.max()) == AGE_CAP    # fixed point, no wrap
    # int8 round-trip survives (this is the buffer dtype in launch.steps)
    assert int(age_next.astype(jnp.int8).min()) == int(AGE_CAP)


def test_aou_merge_ref_clipped_at_cap():
    age = jnp.full((64,), AGE_CAP, jnp.float32)
    mask = jnp.zeros((64,), jnp.float32)
    _, age_next = ref.aou_merge_ref(jnp.zeros(64), jnp.zeros(64), age, mask)
    assert float(age_next.max()) == AGE_CAP


def test_aou_helpers_clipped_at_cap():
    age = jnp.full((64,), AGE_CAP, jnp.float32)
    assert float(aou.update_age(age, jnp.zeros(64)).max()) == AGE_CAP
    out = aou.update_age_by_indices(age, jnp.asarray([0], jnp.int32))
    assert float(out.max()) == AGE_CAP and float(out[0]) == 0.0


def test_int8_buffer_never_wraps_under_lag():
    """Regression: pre-fix, ages past AGE_CAP cast to int8 wrapped negative
    and collided with the PAD_AGE sentinel.  With the clamp the round-trip
    through the int8 server buffer is stable for any number of rounds plus
    any async lag shift."""
    d = 256
    age = jnp.concatenate([jnp.full((d - 8,), AGE_CAP - 1.0),
                           jnp.full((8,), packing.PAD_AGE)]).astype(jnp.int8)
    mask = jnp.zeros((d,), jnp.float32).at[0].set(1.0)
    a = age.astype(jnp.float32)
    for _ in range(10):                        # 10 rounds past saturation
        a = aou.update_age(a, mask)
        # pads would be destroyed by update_age; the production paths gate
        # on age >= 0 — emulate that here
        a = jnp.where(age.astype(jnp.float32) < 0.0,
                      age.astype(jnp.float32), a)
        a = packing.shift_selected_age(a, 3)   # async lag on the selected
        a8 = a.astype(jnp.int8)                # the persisted buffer dtype
        assert int(a8.max()) <= int(AGE_CAP)
        assert (np.asarray(a8)[-8:] == packing.PAD_AGE).all()
        assert (np.asarray(a8)[:-8] >= 0).all()        # no sentinel wrap
        a = a8.astype(jnp.float32)


# ---------------------------------------------------------------------------
# shift helpers
# ---------------------------------------------------------------------------

def test_shift_selected_age_semantics():
    age_next = jnp.asarray([0.0, 5.0, 0.0, packing.PAD_AGE, AGE_CAP])
    out = packing.shift_selected_age(age_next, 2)
    np.testing.assert_allclose(
        np.asarray(out), [2.0, 5.0, 2.0, packing.PAD_AGE, AGE_CAP])
    # lag 0 is the identity
    np.testing.assert_array_equal(
        np.asarray(packing.shift_selected_age(age_next, 0)),
        np.asarray(age_next))


def test_shift_age_hist_matches_shifted_buffer():
    rng = np.random.default_rng(0)
    age_next = jnp.asarray(
        rng.choice([0.0, 0.0, 1.0, 3.0, 7.0], size=4096).astype(np.float32))
    lag = 2
    valid = jnp.ones((4096,), bool)
    _, h_sync = ref.strided_hists_ref(jnp.zeros(4096), age_next, valid, 1)
    _, h_shifted = ref.strided_hists_ref(
        jnp.zeros(4096), packing.shift_selected_age(age_next, lag), valid, 1)
    np.testing.assert_array_equal(
        np.asarray(packing.shift_age_hist(h_sync, lag)),
        np.asarray(h_shifted))
    assert packing.shift_age_hist(h_sync, 0) is h_sync     # exact identity


# ---------------------------------------------------------------------------
# engine age_lag: async off ≡ sync bit-exact; async on shifts ONLY the
# selected ages (and the emitted histogram with them)
# ---------------------------------------------------------------------------

def _engine_and_kwargs(backend, d):
    if backend == "packed":
        layout = packing.PackedLayout.from_tree([jnp.zeros((d,))], lane=1)
        eng = make_engine("fairk", "packed", layout=layout, rho=0.125,
                          k_m_frac=0.75, fused_stats=True, warm_start=True)
        return eng, {"tstate": packing.init_threshold_state()}
    eng = make_engine("fairk", backend, d=d, rho=0.125, k_m_frac=0.75,
                      fused_stats=(backend != "exact"))
    return eng, {}


@pytest.mark.parametrize("backend", ["exact", "threshold", "packed"])
def test_engine_age_lag_parity(backend):
    d = 4096
    key = jax.random.PRNGKey(3)
    g = jax.random.normal(key, (d,), jnp.float32)
    gp = jax.random.normal(jax.random.fold_in(key, 1), (d,), jnp.float32)
    age = jnp.floor(10.0 * jax.random.uniform(jax.random.fold_in(key, 2),
                                              (d,), jnp.float32))
    lag = 2
    eng, kw = _engine_and_kwargs(backend, d)
    g_sync, age_sync, st_sync = eng.select_and_merge(g, gp, age, **kw)
    g_async, age_async, st_async = eng.select_and_merge(g, gp, age,
                                                        age_lag=lag, **kw)
    # the merge itself is untouched — only the age bookkeeping shifts
    np.testing.assert_array_equal(np.asarray(g_sync), np.asarray(g_async))
    np.testing.assert_array_equal(
        np.asarray(packing.shift_selected_age(age_sync, lag)),
        np.asarray(age_async))
    # async mode hands the selection mask back explicitly (the age_next==0
    # convention no longer identifies it)
    np.testing.assert_array_equal(
        np.asarray(st_async["sel_mask"]),
        np.asarray((age_sync == 0.0).astype(jnp.float32)))
    assert "sel_mask" not in st_sync
    # the emitted histogram bins the SHIFTED ages
    if "age_hist" in st_sync:
        np.testing.assert_array_equal(
            np.asarray(packing.shift_age_hist(st_sync["age_hist"], lag)),
            np.asarray(st_async["age_hist"]))
    # lag 0 normalizes to the synchronous trace — bit-exact, no sel_mask
    g_z, age_z, st_z = eng.select_and_merge(g, gp, age, age_lag=0, **kw)
    np.testing.assert_array_equal(np.asarray(g_z), np.asarray(g_sync))
    np.testing.assert_array_equal(np.asarray(age_z), np.asarray(age_sync))
    assert "sel_mask" not in st_z
    with pytest.raises(ValueError):
        eng.select_and_merge(g, gp, age, age_lag=-1, **kw)


# ---------------------------------------------------------------------------
# satellite 4: stationary post-update AoU pmf under injected stragglers ==
# the lag-shifted Lemma-1 prediction (exact + packed backends)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["exact", "packed"])
def test_empirical_pmf_matches_shifted_lemma1(backend):
    """Run FAIR-k with iid re-drawn scores (the well-mixed exchange regime,
    k0 = k_M(1 − k_M/d)) under an injected delivery lag and compare the
    time-averaged age_hist pmf against ``markov.shifted_aou_distribution``
    on the same chain — the existing TV tolerance (< 0.1)."""
    d, k, k_m, lag = 512, 64, 32, 3
    if backend == "packed":
        eng = make_engine("fairk", "packed",
                          layout=packing.PackedLayout.from_tree(
                              [jnp.zeros((d,))], lane=1),
                          k=k, k_m=k_m, fused_stats=True, warm_start=True)
        ts = packing.init_threshold_state()
    else:
        eng = make_engine("fairk", "exact", d=d, k=k, k_m=k_m,
                          fused_stats=True)
        ts = None
    acc = statutil.accumulate_age_hist(eng, d, tstate=ts, age_lag=lag)
    k0 = int(round(k_m * (1 - k_m / d)))
    support, pred = markov.shifted_aou_distribution(
        markov.FairKChain(d=d, k=k, k_m=k_m, k0=k0), lag)
    assert int(support[0]) == lag                     # translated support
    emp = statutil.assert_pmf_close(acc, support, pred)
    assert emp[:lag].sum() == 0.0                     # nothing younger than lag


def test_shifted_aou_distribution_validates():
    chain = markov.FairKChain(d=512, k=64, k_m=32, k0=30)
    with pytest.raises(ValueError):
        markov.shifted_aou_distribution(chain, -1)
    s0, p0 = markov.shifted_aou_distribution(chain, 0)
    s1, p1 = markov.aou_distribution(chain)
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_array_equal(p0, p1)


# ---------------------------------------------------------------------------
# satellite 1: traced k_M split in the FL-OAC step ≡ the static top_k
# concatenation (same selected set, incl. the toward-lower-index tie-break)
# ---------------------------------------------------------------------------

def test_fl_oac_traced_split_matches_static():
    nb, kb = 192, 24
    rng = np.random.default_rng(5)
    score = jnp.asarray(rng.normal(size=nb).astype("f4") ** 2)
    # INTEGER block ages — heavy ties, the regime where a tie-break
    # mismatch between rank and top_k would show
    age_b = jnp.asarray(rng.integers(0, 6, size=nb).astype("f4"))
    for kmf in (0.0, 0.25, 0.5, 0.75, 1.0):
        kb_m = int(round(kmf * kb))
        # the historical static-split selection (pre-traced form)
        _, idx_m = jax.lax.top_k(score, kb_m)
        age_masked = age_b.at[idx_m].set(-1.0)
        _, idx_a = jax.lax.top_k(age_masked, kb - kb_m)
        static_set = set(np.concatenate([np.asarray(idx_m),
                                         np.asarray(idx_a)]).tolist())
        # the traced split (what make_fl_oac_step now runs)
        km_t = traced_km(kb, jnp.float32(kmf))
        assert int(km_t) == kb_m                      # rounding parity
        mask, _ = fair_k_masks_dynamic(score, age_b, kb, km_t)
        idx = jnp.nonzero(mask, size=kb, fill_value=0)[0]
        traced_set = set(np.asarray(idx).tolist())
        assert traced_set == static_set, kmf
        assert len(traced_set) == kb


# ---------------------------------------------------------------------------
# FL trainer: lax.scan round fusion ≡ the per-round loop; async_lag floors
# the refreshed ages at the lag
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fl_task():
    from repro.data import partition, synthetic
    from repro.models import cnn
    spec = synthetic.DatasetSpec("t", (8, 8, 1), 4, 400, 100,
                                 noise_std=0.8, sparsity=0.1)
    (xtr, ytr), (xte, yte) = synthetic.make_dataset(spec, seed=0)
    parts = partition.dirichlet_partition(ytr, 4, 0.3, seed=0)
    params0 = cnn.init_mlp_classifier(jax.random.PRNGKey(0), 64, 4,
                                      hidden=(16,))

    def loss_fn(p, x, y):
        return cnn.softmax_xent(cnn.mlp_classifier(p, x), y)

    @jax.jit
    def eval_fn(p):
        return {"acc": cnn.accuracy(cnn.mlp_classifier(p, jnp.asarray(xte)),
                                    jnp.asarray(yte))}

    def sample_round(t):
        return partition.client_batches(xtr, ytr, parts, 8, 2, seed=100 + t)

    return params0, loss_fn, eval_fn, sample_round


def _fl_base(**kw):
    from repro.core.oac import ChannelConfig
    from repro.fl import FLConfig
    base = dict(n_clients=4, local_steps=2, batch_size=8, rounds=10,
                compression_ratio=0.1, local_lr=0.05, global_lr=0.05,
                channel=ChannelConfig(fading="rayleigh", mean=1.0,
                                      noise_std=0.1))
    base.update(kw)
    return FLConfig(**base)


def test_fl_scan_rounds_matches_loop(fl_task):
    """scan_rounds > 1 fuses rounds into one compiled lax.scan; the key
    splits inside the scan exactly as the loop splits it on the host, so
    both walk the same trajectory (same PRNG stream, same data order,
    same eval schedule)."""
    from jax.flatten_util import ravel_pytree
    from repro.fl import train
    params0, loss_fn, eval_fn, sample_round = fl_task
    h_loop = train(_fl_base(), params0, loss_fn, sample_round,
                   eval_fn=eval_fn, eval_every=5)
    h_scan = train(_fl_base(scan_rounds=4), params0, loss_fn, sample_round,
                   eval_fn=eval_fn, eval_every=5)
    assert h_loop["round"] == h_scan["round"]         # same eval schedule
    assert len(h_scan["mean_aou"]) == 10
    np.testing.assert_allclose(h_loop["mean_aou"], h_scan["mean_aou"],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(h_loop["sel_count"], h_scan["sel_count"])
    w_loop = ravel_pytree(h_loop["params"])[0]
    w_scan = ravel_pytree(h_scan["params"])[0]
    np.testing.assert_allclose(np.asarray(w_loop), np.asarray(w_scan),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ["exact", "threshold"])
def test_fl_trainer_async_lag_age_floor(fl_task, backend):
    """With async_lag the refreshed coordinates restart at the lag, so
    once the run is past the initial ramp NO coordinate can sit at an age
    in [0, lag) — while the synchronous run always has fresh (age-0)
    coordinates after the last round."""
    from repro.fl import train
    params0, loss_fn, eval_fn, sample_round = fl_task
    lag = 3
    h_async = train(_fl_base(backend=backend, async_lag=lag, rounds=12),
                    params0, loss_fn, sample_round)
    h_sync = train(_fl_base(backend=backend, rounds=12),
                   params0, loss_fn, sample_round)
    assert float(h_async["final_age"].min()) >= lag
    assert float(h_sync["final_age"].min()) == 0.0


def test_fl_config_rejects_negative_lag(fl_task):
    from repro.fl.trainer import make_fl_step
    with pytest.raises(ValueError):
        make_fl_step(_fl_base(async_lag=-1), lambda w: w,
                     lambda p, x, y: 0.0, 64)


# ---------------------------------------------------------------------------
# FL-OAC step: the adaptive (traced-split) regime runs and carries the
# controller state
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fl_oac_adaptive_step_runs():
    from jax.flatten_util import ravel_pytree
    from repro.configs import get_config
    from repro.core import controller as budget
    from repro.data.tokens import lm_batch
    from repro.launch.steps import make_fl_oac_step
    from repro.models import transformer as tr

    mesh = make_mesh((1,), ("data",))
    cfg = get_config("mamba2-370m", reduced_variant=True)
    b = make_fl_oac_step(cfg, mesh, seq_len=32, rho=0.05, adaptive_km=True)
    assert b.meta["adaptive_km"]
    params = tr.init_lm(jax.random.PRNGKey(0), cfg)
    w, _ = ravel_pytree(params)
    d, nb = b.meta["d"], b.meta["blocks"]
    g_prev = jnp.zeros((d,), jnp.float32)
    age = jnp.zeros((nb,), jnp.float32)
    ctrl = budget.controller_state_to_vec(
        budget.init_controller_state(0.75))
    toks, labels = lm_batch(0, 1, 32, cfg.vocab)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
    with mesh:
        fn = jax.jit(b.fn, in_shardings=b.in_shardings,
                     out_shardings=b.out_shardings)
        for t in range(2):
            w, g_prev, age, ctrl, loss = fn(w, g_prev, age, ctrl, batch,
                                            jnp.asarray(t, jnp.int32))
    assert np.isfinite(float(loss))
    assert ctrl.shape == (budget.CONTROLLER_STATE_SIZE,)
    cs = budget.controller_state_from_vec(ctrl)
    assert 0.0 <= float(cs["k_m_frac"]) <= 1.0
    assert float(jnp.max(age)) <= AGE_CAP
    assert float(jnp.min(age)) == 0.0                 # selected blocks reset

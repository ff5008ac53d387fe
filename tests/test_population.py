"""Population-scale client simulator (core/population.py, DESIGN.md §15).

Pins the tentpole end to end:

* config validation and the derived chain/thinning algebra;
* stationarity of all three availability modes (iid, Gilbert–Elliott
  bursts with the right down-dwell, the diurnal wave pinned at the right
  time-average);
* cohort-layout determinism — the same seed produces bit-identical
  availability/participation/churn traces whatever ``cohort_size`` packs
  the grid;
* churn-erase-mask block semantics and the participation stats contract;
* the stateless launch-path round (memoryless modes only, reproducible,
  stationary);
* the Sec. IV validation suite: the empirical post-update staleness pmf
  of an engine fed population-churn erasures matches the
  participation-thinned Lemma-1 prediction
  (``markov.population_aou_distribution``) within TV < 0.1 on the exact
  AND packed backends (via ``tests/statutil.py``);
* FL-trainer and launch-config wiring (validation + a fused
  ``scan_rounds`` chaos-style run), and the ``population``-marked
  1e5-client compiled-scan smokes.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import statutil
from repro.core import faults, markov, packing, population
from repro.core.engine import make_engine
from repro.core.population import PAD, PopulationConfig
from repro.launch.mesh import make_mesh


def _cfg(**kw):
    base = dict(n_clients=1000, cohort_size=256, participants=8, avail=0.9)
    base.update(kw)
    return PopulationConfig(**base)


# ---------------------------------------------------------------------------
# config validation + derived algebra
# ---------------------------------------------------------------------------

def test_population_config_validates():
    for bad in (dict(n_clients=0), dict(cohort_size=0),
                dict(participants=0), dict(participants=1001),
                dict(avail=0.0), dict(avail=1.2), dict(mode="lunar"),
                dict(mode="ge", burst=0.5),
                dict(mode="ge", avail=0.1, burst=2.0),   # needs burst >= 9
                dict(mode="diurnal", period=1),
                dict(mode="diurnal", depth=-0.1),
                dict(mode="diurnal", avail=0.95, depth=0.2),  # peak > 1
                dict(slow_frac=1.0), dict(exposure=0.0),
                dict(erase_block=0)):
        with pytest.raises(ValueError):
            _cfg(**bad)


def test_population_config_derived():
    cfg = _cfg(n_clients=1000, cohort_size=256)
    assert cfg.n_cohorts == 4 and cfg.n_padded == 1024
    assert _cfg(n_clients=1024, cohort_size=256).n_padded == 1024
    # iid vanish rate is the miss rate; bursts slow mid-round churn down
    assert _cfg(avail=0.8).vanish_rate == pytest.approx(0.2)
    assert _cfg(avail=0.8, mode="ge", burst=8.0).vanish_rate == \
        pytest.approx(0.2 / (0.8 * 8.0))
    cfg = _cfg(avail=0.75, participants=4, exposure=0.5)
    assert cfg.thin == pytest.approx(0.5 * 0.25 + 0.25 ** 4)
    assert cfg.thin == markov.population_thin(0.75, cfg.vanish_rate, 4, 0.5)


def test_transition_probs_stationary():
    cfg = _cfg(avail=0.8, mode="ge", burst=8.0)
    p_gb, p_bg = population.transition_probs(cfg)
    assert p_bg == pytest.approx(1.0 / 8.0)
    assert p_gb / (p_gb + p_bg) == pytest.approx(0.2)   # pi_down
    p_gb, p_bg = population.transition_probs(_cfg(avail=0.8))
    assert (p_gb, p_bg) == (pytest.approx(0.2), pytest.approx(0.8))


# ---------------------------------------------------------------------------
# packed state + chain stationarity
# ---------------------------------------------------------------------------

def test_init_state_pads_and_stationary_draw():
    cfg = _cfg(n_clients=100, cohort_size=64, avail=0.9)
    st = population.init_population_state(jax.random.PRNGKey(0), cfg)
    assert st["avail"].shape == (2, 64) and st["avail"].dtype == jnp.int8
    flat = np.asarray(st["avail"]).reshape(-1)
    assert (flat[100:] == PAD).all()
    assert set(np.unique(flat[:100])) <= {0, 1}


@pytest.mark.parametrize("mode", ["iid", "ge", "diurnal"])
def test_chain_stationarity(mode):
    """Each availability mode holds its stationary rate: the live-client
    fraction over a 300-round compiled scan stays within 2% of ``avail``
    (seeded run; the binomial noise floor at n=4096 is ~0.5%)."""
    kw = dict(burst=6.0) if mode == "ge" else {}
    cfg = _cfg(n_clients=4096, cohort_size=1024, avail=0.8, mode=mode, **kw)
    _, tr = population.population_scan_jit(cfg, 300, jax.random.PRNGKey(3))
    frac = np.asarray(tr["n_avail"]) / cfg.n_clients
    assert abs(float(frac.mean()) - 0.8) < 0.02
    if mode == "diurnal":
        # the wave actually swings (plus/minus depth around the mean)...
        assert float(frac.min()) < 0.8 - 0.05
        assert float(frac.max()) > 0.8 + 0.05
        rate = np.asarray(tr["rate"])
        assert float(rate.min()) == pytest.approx(0.8 * 0.9, abs=1e-3)
        assert float(rate.max()) == pytest.approx(0.8 * 1.1, abs=1e-3)


def test_ge_bursts_have_the_right_dwell():
    """Gilbert–Elliott memory: a down client stays down with probability
    1 - 1/burst, so the empirical down->down rate over many rounds pins
    the dwell (iid would give 1 - avail = 0.2 instead)."""
    cfg = _cfg(n_clients=2048, cohort_size=512, avail=0.8, mode="ge",
               burst=8.0)
    step = jax.jit(population.population_step, static_argnums=2)
    st = population.init_population_state(jax.random.PRNGKey(1), cfg)
    stay, downs = 0.0, 0.0
    for r in range(100):
        nxt = step(st, jax.random.fold_in(jax.random.PRNGKey(2), r), cfg)
        down = np.asarray(st["avail"]).reshape(-1)[:cfg.n_clients] == 0
        nxt_down = np.asarray(nxt["avail"]).reshape(-1)[:cfg.n_clients] == 0
        downs += down.sum()
        stay += (down & nxt_down).sum()
        st = nxt
    assert abs(stay / downs - (1.0 - 1.0 / 8.0)) < 0.02


def test_cohort_layout_determinism():
    """THE packing contract: bit-identical traces whatever cohort_size
    the host picked — availability, participation, churn, and the final
    per-client availability grid."""
    traces, finals = [], []
    for cs in (64, 333, 1024):
        cfg = _cfg(n_clients=1000, cohort_size=cs, avail=0.85,
                   participants=16)
        fin, tr = population.population_scan_jit(cfg, 50,
                                                 jax.random.PRNGKey(9))
        traces.append({k: np.asarray(v) for k, v in tr.items()})
        finals.append(np.asarray(fin["avail"]).reshape(-1)[:1000])
    for other, fin in zip(traces[1:], finals[1:]):
        for k in traces[0]:
            np.testing.assert_array_equal(traces[0][k], other[k], err_msg=k)
        np.testing.assert_array_equal(finals[0], fin)


def test_client_jitter_static_propensity():
    ids = jnp.arange(100_000)
    j = np.asarray(population.client_jitter(ids))
    assert ((0.0 <= j) & (j < 1.0)).all()
    np.testing.assert_array_equal(
        j, np.asarray(population.client_jitter(ids)))   # trace-static
    assert abs(float((j < 0.3).mean()) - 0.3) < 0.01    # uniform-ish hash


# ---------------------------------------------------------------------------
# round-level effects
# ---------------------------------------------------------------------------

def test_churn_erase_mask_block_semantics():
    cfg = _cfg(erase_block=16, exposure=1.0)
    key = jax.random.PRNGKey(4)
    zero = np.asarray(population.churn_erase_mask(key, 96, jnp.float32(0.0),
                                                  cfg))
    assert (zero == 0.0).all()
    one = np.asarray(population.churn_erase_mask(key, 96, jnp.float32(1.0),
                                                 cfg))
    assert (one == 1.0).all()
    # blocks erase as units; a ragged tail block still fills to d
    m = np.asarray(population.churn_erase_mask(key, 100, jnp.float32(0.5),
                                               cfg))
    assert m.shape == (100,)
    assert all(len(set(m[i:i + 16])) == 1 for i in range(0, 96, 16))


def test_population_round_stats_contract():
    cfg = _cfg(n_clients=2048, cohort_size=512, avail=0.75,
               participants=32, slow_frac=0.5)
    st = population.init_population_state(jax.random.PRNGKey(5), cfg)
    rnd = jax.jit(population.population_round, static_argnums=2)
    slow_seen = 0.0
    for r in range(20):
        st, ps = rnd(st, jax.random.fold_in(jax.random.PRNGKey(6), r), cfg)
        part = np.asarray(ps["part"])
        assert part.shape == (32,) and set(np.unique(part)) <= {0.0, 1.0}
        assert float(ps["n_t"]) == part.sum() <= 32
        assert 0.0 <= float(ps["churn"]) <= 1.0
        assert 0.0 <= float(ps["slow_share"]) <= 1.0
        slow_seen += float(ps["slow"].sum())
    assert slow_seen > 0.0                      # half the ids are slow


def test_stateless_round_contract():
    with pytest.raises(ValueError, match="stateless"):
        population.stateless_round(jax.random.PRNGKey(0), 3,
                                   _cfg(mode="ge", burst=8.0))
    cfg = _cfg(n_clients=4096, cohort_size=1024, avail=0.8,
               participants=16)
    key = jax.random.PRNGKey(7)
    a = population.stateless_round(key, 5, cfg)
    b = population.stateless_round(key, 5, cfg)
    np.testing.assert_array_equal(np.asarray(a["part"]),
                                  np.asarray(b["part"]))
    # stationary across the counter-based trajectory
    n_av = np.array([float(population.stateless_round(key, t, cfg)
                           ["n_avail"]) for t in range(60)])
    assert abs(n_av.mean() / cfg.n_clients - 0.8) < 0.02


# ---------------------------------------------------------------------------
# acceptance: empirical staleness pmf == participation-thinned Lemma 1
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["exact", "packed"])
def test_population_pmf_matches_thinned_lemma1(backend):
    """Sec. IV validation: drive FAIR-k with the erasure stream an actual
    population produces (per-round churn from a compiled availability
    scan, block erasures at ``exposure * churn``, whole-round outage when
    the sampled cohort is empty) and compare the stationary post-update
    age pmf against ``markov.population_aou_distribution`` — the same
    TV < 0.1 bar as the sync/async/thinned laws (seeded run, see
    tests/statutil.py)."""
    d, k, k_m = 512, 64, 32
    cfg = _cfg(n_clients=2048, cohort_size=512, participants=32,
               avail=0.75, exposure=0.5, erase_block=8)
    _, tr = population.population_scan_jit(cfg, 600, jax.random.PRNGKey(11))
    churn = np.asarray(tr["churn"])
    n_t = np.asarray(tr["n_t"])
    erng = np.random.default_rng(7)
    nb = -(-d // cfg.erase_block)

    def erase_fn(r):
        hit = (erng.random(nb) < cfg.exposure * churn[r]).astype("f4")
        mask = np.repeat(hit, cfg.erase_block)[:d]
        return np.ones(d, "f4") if n_t[r] == 0 else mask

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
    acc = statutil.accumulate_age_hist(eng, d, tstate=ts,
                                       erase_fn=erase_fn, sanitize=True)
    k0 = int(round(k_m * (1 - k_m / d)))
    support, pred = markov.population_aou_distribution(
        markov.FairKChain(d=d, k=k, k_m=k_m, k0=k0),
        cfg.avail, cfg.vanish_rate, cfg.participants, cfg.exposure)
    statutil.assert_pmf_close(acc, support, pred)


# ---------------------------------------------------------------------------
# FL trainer + launch wiring
# ---------------------------------------------------------------------------

def _pop_task():
    from repro.models import cnn
    params0 = cnn.init_mlp_classifier(jax.random.PRNGKey(0), 16, 2,
                                      hidden=(8,))

    def loss_fn(p, x, y):
        return cnn.softmax_xent(cnn.mlp_classifier(p, x), y)

    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(16,))

    def sample_round(t):
        r = np.random.default_rng(100 + t)
        xs = r.normal(size=(8, 3, 10, 16)).astype("f4")
        ys = (xs @ w_true > 0).astype("i4")
        return xs, ys

    return params0, loss_fn, sample_round


@pytest.mark.parametrize("backend", ["exact", "packed"])
def test_trainer_population_scan_completes_finite(backend):
    """A fused ``scan_rounds`` run where every round samples its cohort
    from a live 4096-client population (diurnal wave + stragglers)
    completes with finite weights and AoU accounting."""
    from repro.fl.trainer import FLConfig, train
    params0, loss_fn, sample_round = _pop_task()
    fl = FLConfig(n_clients=8, local_steps=3, batch_size=10, rounds=8,
                  policy="fairk", backend=backend, compression_ratio=0.1,
                  local_lr=0.05, global_lr=0.05, scan_rounds=4, seed=0,
                  population=PopulationConfig(
                      n_clients=4096, cohort_size=1024, participants=8,
                      avail=0.85, mode="diurnal", period=6, depth=0.1,
                      slow_frac=0.25))
    h = train(fl, params0, loss_fn, sample_round)
    w = np.asarray(jax.flatten_util.ravel_pytree(h["params"])[0])
    assert np.isfinite(w).all()
    assert np.isfinite(h["mean_aou"]).all()


def test_trainer_population_validation():
    from repro.fl.trainer import FLConfig, make_fl_step
    loss = lambda p, x, y: 0.0
    unravel = lambda w: w
    pop = PopulationConfig(n_clients=4096, participants=16, avail=0.9)
    with pytest.raises(ValueError, match="participants"):
        make_fl_step(FLConfig(n_clients=8, population=pop), unravel, loss,
                     64)
    pop8 = PopulationConfig(n_clients=4096, participants=8, avail=0.9)
    with pytest.raises(ValueError, match="availability"):
        make_fl_step(FLConfig(n_clients=8, population=pop8,
                              faults=faults.FaultConfig(dropout=0.2)),
                     unravel, loss, 64)
    with pytest.raises(ValueError, match="one_bit"):
        make_fl_step(FLConfig(n_clients=8, population=pop8, one_bit=True),
                     unravel, loss, 64)


def test_sweep_population_validation():
    from repro.fl.sweep import SweepConfig
    pop = PopulationConfig(n_clients=4096, participants=16, avail=0.9)
    with pytest.raises(ValueError, match="participants"):
        SweepConfig(n_clients=8, population=pop)
    pop8 = PopulationConfig(n_clients=4096, participants=8, avail=0.9)
    with pytest.raises(ValueError, match="dropout"):
        SweepConfig(n_clients=8, population=pop8,
                    faults=faults.FaultConfig(dropout=0.2))


def test_launch_population_validation():
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.launch.steps import OacServerConfig, make_train_step
    cfg = get_config("mamba2-370m", reduced_variant=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = InputShape("t", 64, 2, "train")
    pop = PopulationConfig(n_clients=4096, participants=16, avail=0.9)
    with pytest.raises(ValueError, match="sanitize"):
        make_train_step(cfg, shape, mesh,
                        oac=OacServerConfig(population=pop))
    with pytest.raises(ValueError, match="stateless"):
        make_train_step(cfg, shape, mesh,
                        oac=OacServerConfig(
                            sanitize=True,
                            population=PopulationConfig(
                                n_clients=4096, participants=16,
                                avail=0.9, mode="ge", burst=8.0)))
    with pytest.raises(ValueError, match="async"):
        make_train_step(cfg, shape, mesh,
                        oac=OacServerConfig(
                            sanitize=True,
                            population=PopulationConfig(
                                n_clients=4096, participants=16,
                                avail=0.9, slow_frac=0.25)))


# ---------------------------------------------------------------------------
# population-scale smokes (the 1e5-client acceptance runs)
# ---------------------------------------------------------------------------

@pytest.mark.population
def test_population_scan_1e5_smoke():
    """1e5 virtual clients advance through one compiled scan — no Python
    loop, stationarity intact."""
    cfg = PopulationConfig(n_clients=100_000, cohort_size=4096,
                           participants=16, avail=0.9)
    _, tr = population.population_scan_jit(cfg, 32, jax.random.PRNGKey(0))
    frac = np.asarray(tr["n_avail"]) / cfg.n_clients
    assert frac.shape == (32,) and np.isfinite(frac).all()
    assert abs(float(frac.mean()) - 0.9) < 0.01
    assert float(np.asarray(tr["n_t"]).mean()) > 12.0   # ~0.9 * 16


@pytest.mark.population
def test_trainer_scan_1e5_virtual_clients():
    """The acceptance run: a compiled ``scan_rounds`` trainer whose
    cohorts are sampled from a 1e5-client population completes finite."""
    from repro.fl.trainer import FLConfig, train
    params0, loss_fn, sample_round = _pop_task()
    fl = FLConfig(n_clients=8, local_steps=3, batch_size=10, rounds=8,
                  policy="fairk", backend="packed", compression_ratio=0.1,
                  local_lr=0.05, global_lr=0.05, scan_rounds=4, seed=0,
                  population=PopulationConfig(
                      n_clients=100_000, cohort_size=4096, participants=8,
                      avail=0.9))
    h = train(fl, params0, loss_fn, sample_round)
    w = np.asarray(jax.flatten_util.ravel_pytree(h["params"])[0])
    assert np.isfinite(w).all()

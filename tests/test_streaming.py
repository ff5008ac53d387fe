"""Streaming client aggregation (DESIGN.md §17).

Four pin families around the chunked client fold in ``fl/trainer.py`` and
``fl/sweep.py``:

* golden trajectory pins — ``client_chunk=None`` must stay BIT-EXACT with
  the pre-refactor materialise-then-einsum trace for every
  chaos x population x wireless x backend combination
  (``tests/golden/fl_trajectories.json``).  The pins were captured before
  the refactor on an older JAX and recaptured on jax 0.9.0, whose default
  ``jax_threefry_partitionable=True`` draws different random bits: with
  the old bit stream (``JAX_THREEFRY_PARTITIONABLE=0``) the recapturing
  tree reproduced 17 of the 19 old pins bit for bit, and the two EF pins
  to within 2e-6 in the residual alone;
* the chunk-parity matrix (marked ``streaming``) — chunked runs
  (chunk in {1, 3, N}) match the dense trajectory within float tolerance,
  and chunk == N is bit-exact with ``None`` (same reshape, same trace);
* the named-key ladder (``core/keys.py``) — both historical split walks
  (trainer and sweep, which disagree on the availability key's position
  under population) are reproduced name for name;
* structural guarantees — one streaming fold per traced round
  (``trainer.CLIENT_STREAM_PASSES``), no live (N, d) gradient aval in the
  chunked jaxpr, and the divisibility validation on every entry point.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flutil
from repro.core import keys as keys_mod
from repro.fl import sweep as sweep_mod
from repro.fl import trainer as fl_trainer
from repro.launch.mesh import make_mesh

PARITY_TOL = 5e-5     # float reassociation over 3 rounds at D=32
GOLDENS = flutil.load_goldens()


# ---------------------------------------------------------------------------
# golden pins: client_chunk=None is the historical trace, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(flutil.combo_configs()))
def test_golden_pin_bitexact(name):
    w, g, age, res = flutil.run_rounds(flutil.combo_configs()[name])
    gold = GOLDENS[name]
    np.testing.assert_array_equal(w, np.asarray(gold["w"], np.float32))
    np.testing.assert_array_equal(g, np.asarray(gold["g"], np.float32))
    np.testing.assert_array_equal(age, np.asarray(gold["age"], age.dtype))
    np.testing.assert_array_equal(res, np.asarray(gold["res"], np.float32))


# ---------------------------------------------------------------------------
# chunk parity: the fold must not depend on the chunking
# ---------------------------------------------------------------------------

# exact and packed backends per the acceptance matrix, plus the uplink
# variants whose folds differ (one-bit votes, EF residual) and the fully
# composed gated round
PARITY_COMBOS = ("exact", "exact_onebit_ef", "packed", "packed_onebit",
                 "pop_chaos_wl")


@pytest.mark.streaming
@pytest.mark.parametrize("chunk", [1, 3, flutil.N_CLIENTS])
@pytest.mark.parametrize("name", PARITY_COMBOS)
def test_chunk_parity(name, chunk):
    fl = flutil.combo_configs()[name]
    dense = flutil.run_rounds(fl)
    chunked = flutil.run_rounds(
        dataclasses.replace(fl, client_chunk=chunk))
    if chunk == fl.n_clients:
        # one chunk IS the dense fold: same reshape, same trace
        for a, b in zip(dense, chunked):
            np.testing.assert_array_equal(a, b)
        return
    for a, b in zip(dense, chunked):
        np.testing.assert_allclose(a, b, atol=PARITY_TOL, rtol=PARITY_TOL)


@pytest.mark.streaming
@pytest.mark.parametrize("chunk", [2, 6])
def test_sweep_chunk_parity(chunk):
    cfg = sweep_mod.SweepConfig(d=64, n_clients=6, rounds=5,
                                error_feedback=True)
    dense = sweep_mod.run_sweep(cfg, policies=("fairk",), n_seeds=2)
    chunked = sweep_mod.run_sweep(
        dataclasses.replace(cfg, client_chunk=chunk),
        policies=("fairk",), n_seeds=2)
    for k, v in dense.items():
        if k == "labels":
            continue
        if chunk == cfg.n_clients:
            np.testing.assert_array_equal(v, chunked[k], err_msg=k)
        else:
            np.testing.assert_allclose(v, chunked[k], atol=1e-4, rtol=1e-4,
                                       err_msg=k)


@pytest.mark.streaming
def test_sweep_chunk_parity_wireless():
    cfg = sweep_mod.SweepConfig(d=64, n_clients=6, rounds=5,
                                wireless=flutil._WL)
    dense = sweep_mod.run_sweep(cfg, policies=("fairk",), n_seeds=2)
    chunked = sweep_mod.run_sweep(dataclasses.replace(cfg, client_chunk=3),
                                  policies=("fairk",), n_seeds=2)
    for k, v in dense.items():
        if k != "labels":
            np.testing.assert_allclose(v, chunked[k], atol=1e-4, rtol=1e-4,
                                       err_msg=k)


# ---------------------------------------------------------------------------
# named-key ladder: both historical split walks, name for name
# ---------------------------------------------------------------------------

def test_round_key_names_trainer_ladder():
    base = ("sel", "ch")
    f = lambda **kw: keys_mod.round_key_names(base=base, **kw)
    assert f() == ("sel", "ch")
    assert f(chaos=True) == ("sel", "ch", "av", "fd", "nz")
    assert f(pop=True) == ("sel", "ch", "pop", "er")
    assert f(wl=True) == ("sel", "ch", "fad", "csi")
    # trainer: the availability key is drawn under population too
    assert f(chaos=True, pop=True) == ("sel", "ch", "av", "fd", "nz",
                                       "pop", "er")
    assert f(chaos=True, pop=True, wl=True) == (
        "sel", "ch", "av", "fd", "nz", "pop", "er", "fad", "csi")


def test_round_key_names_sweep_ladder():
    base = ("pol", "h", "z")
    f = lambda **kw: keys_mod.round_key_names(base=base, av_with_pop=False,
                                              **kw)
    assert f() == ("pol", "h", "z")
    assert f(chaos=True) == ("pol", "h", "z", "av", "fd", "nz")
    # sweep: population REPLACES the availability draw
    assert f(chaos=True, pop=True) == ("pol", "h", "z", "fd", "nz",
                                       "pop", "er")
    assert f(pop=True, wl=True) == ("pol", "h", "z", "pop", "er",
                                    "fad", "csi")


def test_split_named_matches_raw_split():
    key = jax.random.PRNGKey(7)
    names = ("sel", "ch", "av", "fd", "nz")
    ks = keys_mod.split_named(key, names)
    raw = jax.random.split(key, len(names))
    for i, n in enumerate(names):
        np.testing.assert_array_equal(np.asarray(ks[n]),
                                      np.asarray(raw[i]))
    # the historical 2-way walk was jax.random.split(key) — identical to
    # split(key, 2), which the named ladder relies on for bit-exactness
    two = keys_mod.split_named(key, ("a", "b"))
    k0, k1 = jax.random.split(key)
    np.testing.assert_array_equal(np.asarray(two["a"]), np.asarray(k0))
    np.testing.assert_array_equal(np.asarray(two["b"]), np.asarray(k1))


# ---------------------------------------------------------------------------
# structural guarantees
# ---------------------------------------------------------------------------

def _walk_avals(jaxpr, out):
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(aval)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    _walk_avals(inner, out)
                elif hasattr(sub, "eqns"):
                    _walk_avals(sub, out)
    return out


def _step_avals(fl):
    params0, loss_fn, xs, ys = flutil.make_problem(fl.n_clients)
    state, unravel = fl_trainer.init_server(params0, fl)
    d = state.w.shape[0]
    step = fl_trainer.make_fl_step(fl, unravel, loss_fn, d)
    key = jax.random.PRNGKey(0)
    closed = jax.make_jaxpr(step)(key, state.w, state.g, state.age,
                                  state.sel_count, xs, ys, state.residual,
                                  state.theta, state.ctrl)
    return _walk_avals(closed.jaxpr, [])


@pytest.mark.streaming
def test_chunked_jaxpr_has_no_nd_gradient_buffer():
    """With chunk < N no (N, d) float32 intermediate may be live; the
    dense fold (client_chunk=None == one chunk of N) still carries one —
    the contrast proves the walk actually sees the client matrix."""
    fl = flutil.combo_configs()["exact"]
    nd = (flutil.N_CLIENTS, flutil.D)
    is_nd = lambda a: (tuple(a.shape) == nd
                       and a.dtype == jnp.float32)
    assert any(is_nd(a) for a in _step_avals(fl))
    chunked = _step_avals(dataclasses.replace(fl, client_chunk=2))
    assert not any(is_nd(a) for a in chunked)


@pytest.mark.streaming
@pytest.mark.parametrize("chunk", [None, 1, 3])
def test_one_stream_pass_per_trace(chunk):
    """The scan body traces once: one accumulation pass over the clients
    per traced round, whatever the chunk count."""
    fl = dataclasses.replace(flutil.combo_configs()["exact"],
                             client_chunk=chunk)
    before = fl_trainer.CLIENT_STREAM_PASSES
    _step_avals(fl)
    assert fl_trainer.CLIENT_STREAM_PASSES - before == 1


def test_trainer_chunk_validation():
    params0, loss_fn, _, _ = flutil.make_problem()
    for bad in (4, 0, 7):
        fl = dataclasses.replace(flutil.combo_configs()["exact"],
                                 client_chunk=bad)
        state, unravel = fl_trainer.init_server(params0, fl)
        with pytest.raises(ValueError, match="client_chunk"):
            fl_trainer.make_fl_step(fl, unravel, loss_fn,
                                    state.w.shape[0])


def test_sweep_chunk_validation():
    for bad in (5, 0):
        with pytest.raises(ValueError, match="client_chunk"):
            sweep_mod.SweepConfig(n_clients=16, client_chunk=bad)


def test_launch_chunk_validation():
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.launch.steps import make_train_step
    cfg = get_config("mamba2-370m", reduced_variant=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="client_chunk"):
        make_train_step(cfg, InputShape("t", 64, 4, "train"), mesh,
                        n_micro=4, client_chunk=3)

"""Shared harness for FL-trainer trajectory tests.

One tiny linear-regression FL problem plus a round-loop driver that walks
``make_fl_step`` exactly the way ``trainer.train`` does (same key-split
discipline, same carry threading).  Used by

* the golden-trajectory pins (``test_streaming.py``): every
  chaos x population x wireless x backend combination is pinned bit-exact
  against ``tests/golden/fl_trajectories.json``, so ``client_chunk=None``
  can never drift from the historical einsum trace, and
* the chunk-parity matrix: chunked runs (``client_chunk`` in {1, 3, N})
  must match the single-chunk trajectory within float tolerance.

Kept import-light (no fixtures) so benchmark code can reuse it.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel as chan_mod
from repro.core import faults as fault_mod
from repro.core import oac
from repro.core import population as pop_mod
from repro.fl import trainer as fl_trainer
from repro.fl.trainer import FLConfig

D = 32          # model dimension of the shared problem
N_CLIENTS = 6   # divisible by the parity chunks {1, 2, 3, 6}

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden",
                           "fl_trajectories.json")

_OAC_CH = oac.ChannelConfig(fading="rayleigh", mean=1.0, noise_std=0.1)
_FAULTS = fault_mod.FaultConfig(dropout=0.3, fade=0.2, fade_block=8,
                                nan_rate=0.05)
# population composes with fade/nan_rate but not dropout
_FAULTS_NODROP = fault_mod.FaultConfig(fade=0.2, fade_block=8,
                                       nan_rate=0.05)
_POP = pop_mod.PopulationConfig(n_clients=64, cohort_size=16,
                                participants=N_CLIENTS, avail=0.8,
                                mode="ge", burst=4.0, erase_block=8)
_WL = chan_mod.ChannelConfig(n_clients=N_CLIENTS, pmax=10.0, gmin=0.05,
                             rho_f=0.5, csi_err=0.1, block=8)


def combo_configs() -> Dict[str, FLConfig]:
    """Name -> FLConfig for the full pin/parity matrix.  Every wireless-off
    x chaos x population combination appears, every backend, the one-bit
    and EF uplinks and the adaptive controller."""
    base = dict(n_clients=N_CLIENTS, local_steps=2, batch_size=3,
                local_lr=0.05, global_lr=0.05, rounds=3,
                compression_ratio=0.2, channel=_OAC_CH, seed=0)
    combos = {
        "exact": FLConfig(**base),
        "threshold": FLConfig(backend="threshold", **base),
        "packed": FLConfig(backend="packed", **base),
        "exact_onebit": FLConfig(one_bit=True, **base),
        "exact_ef": FLConfig(error_feedback=True, **base),
        "exact_onebit_ef": FLConfig(one_bit=True, error_feedback=True,
                                    **base),
        "exact_adaptive": FLConfig(adaptive_km=True, **base),
        "threshold_onebit": FLConfig(backend="threshold", one_bit=True,
                                     **base),
        "threshold_ef": FLConfig(backend="threshold", error_feedback=True,
                                 **base),
        "packed_onebit": FLConfig(backend="packed", one_bit=True, **base),
        "chaos": FLConfig(faults=_FAULTS, **base),
        "chaos_packed": FLConfig(backend="packed", faults=_FAULTS, **base),
        "pop": FLConfig(population=_POP, **base),
        "wl": FLConfig(wireless=_WL, **base),
        "wl_onebit": FLConfig(wireless=_WL, one_bit=True, **base),
        "chaos_wl": FLConfig(faults=_FAULTS, wireless=_WL, **base),
        "pop_chaos": FLConfig(population=_POP, faults=_FAULTS_NODROP,
                              **base),
        "pop_wl": FLConfig(population=_POP, wireless=_WL, **base),
        "pop_chaos_wl": FLConfig(population=_POP, faults=_FAULTS_NODROP,
                                 wireless=_WL, **base),
    }
    return combos


def make_problem(n_clients: int = N_CLIENTS, d: int = D, h: int = 2,
                 b: int = 3, seed: int = 0):
    """(params0, loss_fn, xs, ys): a tiny linear regression whose client
    batches are pre-drawn as stacked (N, H, B, ...) arrays."""
    rng = np.random.default_rng(seed)
    params0 = {"a": jnp.asarray(rng.normal(size=(d,)).astype("f4"))}
    xs = jnp.asarray(rng.normal(size=(n_clients, h, b, d)).astype("f4"))
    ys = jnp.asarray(rng.normal(size=(n_clients, h, b)).astype("f4"))

    def loss_fn(p, x, y):
        return 0.5 * jnp.mean((x @ p["a"] - y) ** 2)

    return params0, loss_fn, xs, ys


def run_rounds(fl: FLConfig, rounds: int = 3
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Walk ``make_fl_step`` like ``trainer.train`` (same per-round key
    split) and return the final (w, g, age, residual)."""
    params0, loss_fn, xs, ys = make_problem(fl.n_clients)
    state, unravel = fl_trainer.init_server(params0, fl)
    d = state.w.shape[0]
    step = fl_trainer.make_fl_step(fl, unravel, loss_fn, d)
    has_fstate = (fl.chaos or fl.watchdog is not None
                  or fl.population is not None or fl.wireless is not None)
    fstate = (fl_trainer.init_fault_state(fl, state) if has_fstate
              else None)
    key = jax.random.PRNGKey(fl.seed)
    w, g, age, sel = state.w, state.g, state.age, state.sel_count
    residual, tstate, cstate = state.residual, state.theta, state.ctrl
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        args = (sub, w, g, age, sel, xs, ys, residual, tstate, cstate)
        if has_fstate:
            (w, g, age, sel, residual, _, tstate, cstate, _,
             fstate) = step(*args, fstate)
        else:
            w, g, age, sel, residual, _, tstate, cstate, _ = step(*args)
    return (np.asarray(w), np.asarray(g), np.asarray(age),
            np.asarray(residual))


def capture_goldens(path: str = GOLDEN_PATH) -> Dict[str, Dict]:
    """Run every combo and write the trajectory fingerprints (full final
    vectors — d is tiny) to ``path``."""
    out = {}
    for name, fl in combo_configs().items():
        w, g, age, res = run_rounds(fl)
        out[name] = {"w": w.tolist(), "g": g.tolist(),
                     "age": age.tolist(), "res": res.tolist()}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    return out


def load_goldens(path: str = GOLDEN_PATH) -> Dict[str, Dict]:
    with open(path) as f:
        return json.load(f)


if __name__ == "__main__":
    capture_goldens()
    print(f"wrote {GOLDEN_PATH}")

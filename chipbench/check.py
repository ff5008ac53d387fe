"""What decides ``correct``: the checked rounds against the plain reference.

A cell compares the numbers its ``limits/<cell>.json`` gives a limit (a
number whose sound runs and control cannot be told apart has none there,
see PERF.md).  Of the training step as a whole:

- ``loss_gap``: the largest |program loss - reference loss| over the
  checked rounds, in nats;
- ``grad_norm_gap``: over parameter leaves, the largest gap between the
  program's and the reference's norm of the first non-zero gradient AdamW
  received, over the reference's norm of that leaf or of the median leaf,
  whichever is larger;
- ``update_norm_gap``: the same for the parameters' change over the
  checked rounds, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move under Adam by round-off).

Of the FAIR-k server, from what it stores after each checked round
(``server_state``), the worst round:

- ``sel_count_gap``: |refreshed coordinates - the reference's| over the
  reference's count;
- ``refresh_energy_gap``: the same for the stored merged gradient's sum of
  squares over the refreshed coordinates (a selection that misses the
  largest scores reads low);
- ``merged_norm_gap``, and where the server keeps them ``residual_gap``,
  ``shadow_gap``, ``pending_gap``: the worst leaf's norm gap, as above, of
  the stored merged gradient, error-feedback residual, straggler shadow
  and pending update;
- ``age_hist_gap``: the sum of |count gaps| over the age bins, over twice
  the reference's refreshed count;
- ``ctrl_state_gap`` (adaptive split): the largest gap of the split, its
  damped step, seen flag and round counter, which sound runs hold exactly;
- ``ctrl_ema_gap``: the total variation between the controller's age-EMA
  distributions.

The reference runs after the program's state is freed, from the weights
and batches the benchmark made (``feed``)."""

from __future__ import annotations

import functools
import importlib
import json
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import server_state

NUMBERS = ("loss_gap", "grad_norm_gap", "update_norm_gap", "sel_count_gap",
           "refresh_energy_gap", "merged_norm_gap", "age_hist_gap",
           "residual_gap", "shadow_gap", "pending_gap", "ctrl_state_gap",
           "ctrl_ema_gap")
BUFFER_GAPS = {"merged": "merged_norm_gap", "res": "residual_gap",
               "shadow": "shadow_gap", "pending": "pending_gap"}
NEGLIGIBLE = 1e-3


def fp8(x):
    """float8 (4 exponent, 3 mantissa bits) with a per-tensor scale: the
    lower-precision control's rounding of what the program holds in
    bfloat16.  The backward pass sees the rounded values and passes
    cotangents through unrounded (they would flush to zero at float8's
    range)."""
    import jax
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x)) / 128.0 + 1e-30
    q = jax.lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3) * s
    return x + jax.lax.stop_gradient(q - x)


@functools.lru_cache(maxsize=None)
def _loss_fn(reference: str, model_json: str, quant):
    """One loss function object for each model and rounding, so that the
    reference's jitted programs are built once a process."""
    model = importlib.import_module(f"chipbench.reference.{reference}")
    m = json.loads(model_json)
    kw = {} if quant is None else {"quant": quant}
    return lambda p, t, l: model.loss(p, t, l, m, **kw)


def reference_readings(cell, seed: int, batches, abstract, *,
                       shardings=None, quant=None,
                       micro: Optional[int] = None, exchange: bool = True,
                       selection: str = "exact") -> Dict[str, Any]:
    """The reference's losses and norms over the checked rounds.  The
    weights are placed by ``shardings`` (the program's parameter
    shardings), and so are the gradients, the optimizer's moments and the
    server's trees that follow them: the plain code is spread over the
    program's devices.  ``quant`` rounds what the program holds in its
    compute dtype (the control); ``micro`` keeps only that many
    microbatches a round, ``exchange=False`` leaves out the exchange of
    gradients between devices, ``selection`` another server selection
    (planted faults)."""
    import dataclasses
    import jax
    from chipbench import feed
    from chipbench.reference import fairk_adamw
    loss_fn = _loss_fn(cell.config["reference"],
                       json.dumps(cell.config["model"], sort_keys=True), quant)
    params = jax.jit(feed.init_weights, out_shardings=shardings)(
        jax.random.PRNGKey(feed.seed32(seed, 0)), abstract)
    srv = dataclasses.replace(
        fairk_adamw.parse_server(cell.traffic["server_flags"]),
        selection=selection)
    train = functools.partial(
        fairk_adamw.train, batches=batches, loss_fn=loss_fn, srv=srv,
        lr=cell.traffic["lr"], steps=len(batches), micro=micro,
        exchange=exchange, seed=feed.seed32(seed, 2))
    with jax.default_matmul_precision("highest"):
        # sharded over several devices, the gradient program cannot load
        # beside the moments and the server's trees once the program's step
        # has run (PERF.md, Open questions): those wait on the host then
        out = train(params, park=shardings is not None)
    out["delta"] = feed.delta_norms(out.pop("params"), seed, abstract)
    return out


def worst(p: Dict[str, float], r: Dict[str, float], keys=None):
    """(largest per-leaf norm gap over the reference's norm of that leaf
    or of the median leaf, whichever is larger; that leaf)."""
    keys = list(r) if keys is None else keys
    med = float(np.median([r[k] for k in keys]))
    return max((abs(p[k] - r[k]) / max(r[k], med, 1e-30), k) for k in keys)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def server_gaps(got: List[Dict[str, Any]], want: List[Dict[str, Any]]
                ) -> Dict[str, Any]:
    """The server's numbers, the worst over the checked rounds."""
    out: Dict[str, Any] = {}

    def put(name, value, where=None):
        if name not in out or value > out[name]:
            out[name] = value
            if where is not None:
                out[name.replace("_gap", "_leaf")] = where
    for t, (p, r) in enumerate(zip(got, want)):
        put("sel_count_gap", _rel(p["n_sel"], r["n_sel"]))
        put("refresh_energy_gap", _rel(p["energy"], r["energy"]))
        hp, hr = np.asarray(p["age_counts"]), np.asarray(r["age_counts"])
        put("age_hist_gap",
            float(np.abs(hp - hr).sum()) / max(2 * r["n_sel"], 1.0))
        for buf, name in BUFFER_GAPS.items():
            if buf in r:
                v, leaf = worst(p.get(buf) or dict.fromkeys(r[buf], 0.0),
                                r[buf])
                put(name, v, f"{t}:{leaf}")
        if "ctrl" in r:
            cp, cr = p["ctrl"], r["ctrl"]
            put("ctrl_state_gap", max(abs(cp[k] - cr[k])
                                      for k in server_state.CTRL_SCALARS))
            put("ctrl_ema_gap", 0.5 * float(np.abs(
                np.asarray(cp["age_pmf"]) - np.asarray(cr["age_pmf"])).sum()))
    return out


def gaps(got: Dict[str, Any], want: Dict[str, Any]) -> Dict[str, float]:
    """The compared numbers of ``got`` against ``want``."""
    loss = max(abs(a - b) for a, b in zip(got["losses"], want["losses"]))
    g_ref = want["first_grad"]
    med_g = float(np.median(list(g_ref.values())))

    # a program whose optimizer never received a gradient reads as zeros
    grad, grad_leaf = worst(got["first_grad"] or dict.fromkeys(g_ref, 0.0),
                            g_ref, list(g_ref))
    moving = [k for k in g_ref if g_ref[k] >= NEGLIGIBLE * med_g]
    upd, upd_leaf = worst(got["delta"], want["delta"], moving)
    return {"loss_gap": loss, "grad_norm_gap": grad, "update_norm_gap": upd,
            "median_grad_norm": med_g, "grad_leaf": grad_leaf,
            "update_leaf": upd_leaf,
            **server_gaps(got["server"], want["server"])}


def compare(cell, seed: int, prog_read, batches, abstract, shardings=None
            ) -> Dict[str, Dict[str, float]]:
    want = reference_readings(cell, seed, batches, abstract,
                              shardings=shardings)
    got = gaps(prog_read, want)
    return {k: {"value": got[k], "limit": cell.limits[k]} for k in NUMBERS
            if k in cell.limits}

"""Distributed tests: run in subprocesses with 8 placeholder host devices
(the main pytest process must keep the real single-device view)."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.sharded, pytest.mark.slow]

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run_sub(code: str, timeout=560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_train_step_runs_and_learns_sharded():
    out = _run_sub(r"""
import jax, jax.numpy as jnp, numpy as np, json
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.launch.steps import make_train_step, init_server_state
from repro.models import transformer as tr
from repro.optim import make_optimizer
from repro.data.tokens import lm_batch

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("qwen2.5-32b", reduced_variant=True)
shape = InputShape("t", 128, 8, "train")
bundle = make_train_step(cfg, shape, mesh)
params = tr.init_lm(jax.random.PRNGKey(0), cfg)
opt = make_optimizer(bundle.meta["optimizer"], 3e-3)
opt_state = opt.init(params)
server = init_server_state(params, mesh=mesh, cfg=cfg)
step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
               out_shardings=bundle.out_shardings)
nm = bundle.meta["n_micro"]
losses, fresh = [], []
with mesh:
    for t in range(25):
        toks, labels = lm_batch(t % 3, 8, 128, cfg.vocab)  # few repeated batches
        batch = {"tokens": jnp.asarray(toks).reshape(nm, 8 // nm, 128),
                 "labels": jnp.asarray(labels).reshape(nm, 8 // nm, 128)}
        params, opt_state, server, loss = step(params, opt_state, server,
                                               batch, jnp.asarray(t, jnp.int32))
        losses.append(float(loss))
        # persisted packed server state: flat int8 age buffer, PAD_AGE (-1)
        # pads; age 0 = selected this round
        ages = np.asarray(server["age"])
        fresh.append(float((ages[ages >= 0] == 0).mean()))
print(json.dumps({"first": losses[0], "last": losses[-1],
                  "frac_fresh": float(np.mean(fresh[-12:])),
                  "max_age": int(ages.max())}))
""")
    res = json.loads(out.strip().splitlines()[-1])
    assert res["last"] < res["first"] - 0.05, res
    # rho = 0.1 target, as the mean fresh share of the last 12 rounds.  One
    # round's share swings with the 3-batch cycle (0.04-0.15: the magnitude
    # threshold comes from the previous round's histogram), so a single
    # round sat on either side of 0.05 with the numerics of the JAX
    # release.  The mean is ~0.078 on (1,1), (2,4) and (8,1) meshes alike:
    # about k_M/d = 0.075, because the age stage is starved (ROADMAP,
    # Reach: age-stage budget).
    assert 0.05 < res["frac_fresh"] < 0.35, res
    assert res["max_age"] <= 25, res


def test_decode_parity_sharded_vs_single():
    """serve_step on the mesh must match the unsharded decode."""
    out = _run_sub(r"""
import jax, jax.numpy as jnp, numpy as np, json
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.launch.steps import make_serve_step
from repro.models import transformer as tr

errs = {}
for name in ("qwen2.5-32b", "mamba2-370m", "granite-moe-3b-a800m"):
    cfg = get_config(name, reduced_variant=True)
    params = tr.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (8, 1)).astype("i4"))
    caches = tr.init_caches(cfg, 8, capacity=64)
    ref_logits, _ = tr.decode_step(params, cfg, toks, jnp.asarray(0), caches)
    mesh = make_mesh((2, 4), ("data", "model"))
    bundle = make_serve_step(cfg, InputShape("d", 64, 8, "decode"), mesh)
    with mesh:
        step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                       out_shardings=bundle.out_shardings)
        caches2 = tr.init_caches(cfg, 8, capacity=64)
        sh_logits, _ = step(params, caches2, toks, jnp.asarray(0, jnp.int32))
    errs[name] = float(np.abs(np.asarray(ref_logits, np.float32)
                              - np.asarray(sh_logits, np.float32)).max())
print(json.dumps(errs))
""")
    res = json.loads(out.strip().splitlines()[-1])
    assert res["qwen2.5-32b"] < 0.05, res
    assert res["mamba2-370m"] < 0.05, res
    # MoE: bf16 resharding can flip near-tie router top-k picks -> looser
    assert res["granite-moe-3b-a800m"] < 0.5, res


def test_fl_oac_collective_reduction():
    """The FL-OAC step's all-reduce volume must be ~rho of the baseline's
    (the paper's waveform-budget saving, measured in the compiled HLO)."""
    out = _run_sub(r"""
import jax, json
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.launch.steps import make_fl_oac_step
from repro.roofline import analyze_hlo

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("mamba2-370m", reduced_variant=True)
res = {}
for base in (False, True):
    b = make_fl_oac_step(cfg, mesh, seq_len=64, rho=0.1, baseline=base)
    with mesh:
        c = jax.jit(b.fn, in_shardings=b.in_shardings,
                    out_shardings=b.out_shardings).lower(*b.input_specs).compile()
    res["base" if base else "fairk"] = analyze_hlo(
        c.as_text())["collective_bytes_per_device"]
print(json.dumps(res))
""")
    res = json.loads(out.strip().splitlines()[-1])
    ratio = res["fairk"] / res["base"]
    assert ratio < 0.2, res      # rho=0.1 plus small fixed overheads


def test_fl_oac_step_executes():
    """Run two FL-OAC rounds for real on the 8-device mesh."""
    out = _run_sub(r"""
import jax, jax.numpy as jnp, numpy as np, json
from repro.launch.mesh import make_mesh
from jax.flatten_util import ravel_pytree
from repro.configs import get_config
from repro.launch.steps import make_fl_oac_step
from repro.models import transformer as tr
from repro.data.tokens import lm_batch

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("mamba2-370m", reduced_variant=True)
b = make_fl_oac_step(cfg, mesh, seq_len=64, rho=0.1)
params = tr.init_lm(jax.random.PRNGKey(0), cfg)
w, _ = ravel_pytree(params)
d = b.meta["d"]; nb = b.meta["blocks"]
g_prev = jnp.zeros((d,), jnp.float32)
age = jnp.zeros((nb,), jnp.float32)
with mesh:
    fn = jax.jit(b.fn, in_shardings=b.in_shardings,
                 out_shardings=b.out_shardings)
    losses = []
    for t in range(3):
        toks, labels = lm_batch(t, 8, 64, cfg.vocab)
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}
        w, g_prev, age, loss = fn(w, g_prev, age, batch,
                                  jnp.asarray(t, jnp.int32))
        losses.append(float(loss))
frac_fresh = float((np.asarray(age) == 0).mean())
print(json.dumps({"losses": losses, "frac_fresh": frac_fresh,
                  "kb_over_nb": b.meta["kb"] / nb}))
""")
    res = json.loads(out.strip().splitlines()[-1])
    assert all(np.isfinite(l) for l in res["losses"])
    # after a round, ~rho of blocks are fresh (age 0)
    assert abs(res["frac_fresh"] - res["kb_over_nb"]) < 0.05


def test_engine_sharded_parity_multi_device():
    """SelectionEngine sharded backend on a REAL 8-device mesh: must match
    the exact backend on tie-free ages, and must match the single-device
    threshold backend bit-exactly even under heavy integer-age ties (the
    global-index jitter property a 1-device parity test cannot see)."""
    out = _run_sub(r"""
import jax, jax.numpy as jnp, numpy as np, json
from repro.launch.mesh import make_mesh
from repro.core.engine import EngineConfig, SelectionEngine

d = 4096
rng = np.random.default_rng(0)
g = jnp.asarray(rng.normal(size=d).astype("f4"))
gp = jnp.asarray(rng.normal(size=d).astype("f4"))
common = dict(policy="fairk", rho=0.1, k_m_frac=0.75, exact_theta=True)
mesh = make_mesh((8,), ("shard",))
ex = SelectionEngine(EngineConfig(backend="exact", **common), d)
th = SelectionEngine(EngineConfig(backend="threshold", **common), d)
sh = SelectionEngine(EngineConfig(backend="sharded", **common), d,
                     mesh=mesh)
out = {}
# (a) tie-free ages: sharded == exact (the documented parity guarantee)
age = jnp.asarray(rng.permutation(d).astype("f4"))
g1, a1, _ = jax.jit(ex.select_and_merge)(g, gp, age)
with mesh:
    g2, a2, _ = jax.jit(sh.select_and_merge)(g, gp, age)
out["exact_mismatch"] = int((np.asarray(g1) != np.asarray(g2)).sum()
                            + (np.asarray(a1) != np.asarray(a2)).sum())
# (b) heavy ties: sharded == threshold (same global-index jitter)
age_t = jnp.asarray(rng.integers(0, 8, d).astype("f4"))
g3, a3, s3 = th.select_and_merge(g, gp, age_t)
with mesh:
    g4, a4, s4 = jax.jit(sh.select_and_merge)(g, gp, age_t)
out["thresh_mismatch"] = int((np.asarray(g3) != np.asarray(g4)).sum()
                             + (np.asarray(a3) != np.asarray(a4)).sum())
out["n_thresh"] = float(s3["n_selected"])
out["n_sharded"] = float(s4["n_selected"])
print(json.dumps(out))
""")
    res = json.loads(out.strip().splitlines()[-1])
    assert res["exact_mismatch"] == 0, res
    assert res["thresh_mismatch"] == 0, res
    assert res["n_thresh"] == res["n_sharded"], res


import numpy as np  # noqa: E402  (used in asserts above)

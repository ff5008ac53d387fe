"""Tests of the benchmark harness that need no chip.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

The trace reduction on a recorded trace, the FLOP and byte counts, finding
a cell's files and its reference by name, the refusal of a CPU device and
of a reference that cannot count its configuration, reading the program's
flat server buffers by leaf, the lower-precision control and planted
selection faults failing the comparison, and a whole run (without the look
for a chip) failing when the timed step is broken underneath.  The sound
run of each configuration's small case is ``test_reference_parity.py``'s."""

from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys
import time
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import check, flops, harness, tinycell, trace  # noqa: E402
from chipbench.tests import faults  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
CELL = "mamba2-370m.default.s2048x8"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
MAMBA = "mamba2-370m"


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def _ctx(tr, rounds=1):
    cell = harness.load_cell(CELL)
    return trace.Context(cell=cell, trace=tr, rounds=rounds, chips=1,
                         device_kind="TPU v5 lite")


def test_reduction_on_hand_made_trace():
    dev = "/device:TPU:0"
    ops = [("%while.1 = (...)", 100, 80),          # [100, 180)
           ("%fusion.2 = f32[8]", 120, 30),        # inside the while
           ("%fairk_stats_update_pallas.3 = (...)", 200, 40),
           ("%fusion.4 = f32[8]", 400, 100)]       # ends past the window
    host = [("feed", 90, 5), ("dispatch", 95, 10), ("block", 105, 345)]
    ctx = _ctx({"devices": {dev: ops}, "host": host})
    assert ctx.bounds() == (90, 450)
    assert [e[0] for e in ctx.ops(dev)] == [ops[0][0], ops[2][0], ops[3][0]]
    # busy: [100, 180) + [200, 240) + [400, 450) clipped = 170 of 360
    assert ctx.busy_s() == pytest.approx(170e-9)
    assert ctx.idle_share() == pytest.approx(1 - 170 / 360)
    assert ctx.per_round_ms(trace.in_client_phase) == pytest.approx(80e-6)
    assert ctx.per_round_ms(trace.in_update_phase) == pytest.approx(140e-6)
    assert ctx.per_round_ms(trace.is_fairk_kernel) == pytest.approx(40e-6)
    b = ctx.breakdown()
    assert b["device_ops"][0] == [ops[3][0], pytest.approx(100e-9)]
    assert b["idle_gaps"][0] == ["block", pytest.approx(160e-9)]


def test_reduction_on_recorded_trace():
    """Two rounds of the one-chip full-stack cell, recorded on a TPU v5e:
    every top-level op, and the ops the first 2 ms of the microbatch scan
    holds.  The expected numbers are those of the whole recording."""
    rec = json.loads(gzip.open(DATA / "trace_full_s512x4.json.gz").read())
    tr = {"devices": {k: [tuple(e) for e in v]
                      for k, v in rec["trace"]["devices"].items()},
          "host": [tuple(h) for h in rec["trace"]["host"]]}
    ctx = _ctx(tr, rounds=rec["rounds"])
    for name, want in rec["expect"].items():
        got = {"busy_s": ctx.busy_s(),
               "idle_share": ctx.idle_share(),
               "fairk_kernel_ms": ctx.per_round_ms(trace.is_fairk_kernel),
               "client_phase_ms": ctx.per_round_ms(trace.in_client_phase),
               "update_phase_ms": ctx.per_round_ms(trace.in_update_phase),
               }[name]
        assert got == pytest.approx(want, rel=1e-9), name


# ---------------------------------------------------------------------------
# the work a round needs
# ---------------------------------------------------------------------------

def _small_model(**kw):
    m = dict(n_layers=1, d_model=256, vocab=512, ssm_state=16, ssm_expand=2,
             ssm_head_dim=32, ssm_groups=1, ssm_conv=4, ssm_chunk=64,
             tie_embeddings=True)
    m.update(kw)
    return m


@pytest.mark.parametrize("kw", [{}, {"d_model": 512, "ssm_state": 64,
                                     "ssm_head_dim": 64, "vocab": 1024}])
def test_flops_against_xla_cost_analysis(kw):
    """The architecture's count bounds XLA's from below: XLA adds the
    elementwise work and the chunked SSD's quadratic terms.  One layer, as
    XLA's cost analysis counts a scan's body once; remat off."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import transformer as tr
    m = _small_model(**kw)
    cfg = dataclasses.replace(get_config("mamba2-370m"), remat=False, **{
        k: m[k] for k in ("n_layers", "d_model", "vocab", "ssm_state",
                          "ssm_head_dim", "ssm_chunk")})
    seq = 256
    p = jax.eval_shape(lambda k: tr.init_lm(k, cfg), jax.random.PRNGKey(0))
    b = {k: jax.ShapeDtypeStruct((1, seq), jnp.int32)
         for k in ("tokens", "labels")}
    cost = jax.jit(jax.grad(lambda p, b: tr.loss_fn(p, cfg, b)[0])).lower(
        p, b).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    mine = flops.train_flops_per_round({"reference": "ssd_lm", "model": m},
                                       {"seq_len": seq, "batch": 1})
    assert mine <= cost["flops"] <= 1.4 * mine


def test_flops_and_params_of_the_cell():
    from chipbench.reference import ssd_lm
    conf = json.loads((ROOT / "chipbench/configs/mamba2-370m.json")
                      .read_text())
    assert ssd_lm.param_count(conf["model"]) == conf["params"] == 368387584
    assert ssd_lm.forward_flops_per_token(conf["model"], 2048) == 786481152


@pytest.mark.parametrize("cell,per_round", [
    ("mamba2-370m.default.s2048x8", 38_657_121_583_104),
    ("mamba2-370m.full.s512x4", 4_832_140_197_888),
    ("granite-34b-fsdp4.default.s2048x8", 145_187_074_473_984)])
def test_flops_per_round_of_each_cell(cell, per_round):
    c = harness.load_cell(cell)
    assert flops.train_flops_per_round(c.config, c.traffic) == per_round


def test_fairk_bytes():
    assert flops.fairk_bytes(1000, False) == 14000
    assert flops.fairk_bytes(1000, True) == 22000
    assert flops.fairk_bytes(368387584, False) == 5157426176


def test_peaks_table():
    assert trace.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# cells found by name
# ---------------------------------------------------------------------------

def test_cell_found_by_name(tmp_path):
    root = tinycell.make_root(tmp_path, MAMBA)
    (root / "chipbench/metrics/rounds_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.rounds)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append(dict(bench["per_layer"][0],
                                   name="rounds_seen", unit="rounds"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("tiny.t", root)
    assert cell.config["model"]["d_model"] == 64
    assert cell.traffic["seq_len"] == 128
    assert {m["name"] for m in cell.per_layer} >= {"rounds_seen", "step_mfu"}
    reader = harness.load_reader(cell, "rounds_seen")
    assert reader(trace.Context(cell, {}, 7, 1, "cpu")) == 7.0
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell", root)


def _fresh_reference(root, monkeypatch, drop=None, params_off=0):
    """A reference module of a fresh name, ``chipbench.reference.fresh_lm``
    (delegating to ``ssd_lm``), that the root's configuration names;
    without ``drop``, and with ``params`` off by ``params_off``."""
    from chipbench.reference import ssd_lm
    mod = types.ModuleType("chipbench.reference.fresh_lm")
    mod.calls = []

    def loss(*args, **kw):
        mod.calls.append(args)
        return "fresh"
    mod.loss = loss
    mod.param_count = ssd_lm.param_count
    mod.forward_flops_per_token = ssd_lm.forward_flops_per_token
    if drop:
        delattr(mod, drop)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    path = root / "chipbench/configs/tiny.json"
    conf = json.loads(path.read_text())
    conf["reference"] = "fresh_lm"
    conf["params"] += params_off
    path.write_text(json.dumps(conf))
    return mod


def test_reference_found_by_name(tmp_path, monkeypatch):
    """A configuration whose reference module is new is found by
    ``load_cell``, ``train_flops_per_round`` and ``check._loss_fn`` by its
    name alone."""
    from chipbench.reference import ssd_lm
    root = tinycell.make_root(tmp_path, MAMBA)
    mod = _fresh_reference(root, monkeypatch)
    cell = harness.load_cell("tiny.t", root)
    m, seq = cell.config["model"], cell.traffic["seq_len"]
    assert flops.train_flops_per_round(cell.config, cell.traffic) == (
        3 * ssd_lm.forward_flops_per_token(m, seq) * seq
        * cell.traffic["batch"])
    fn = check._loss_fn("fresh_lm", json.dumps(m, sort_keys=True), None)
    assert fn("p", "t", "l") == "fresh"
    assert mod.calls == [("p", "t", "l", m)]


@pytest.mark.parametrize("drop,params_off,message", [
    ("forward_flops_per_token", 0,
     "chipbench.reference.fresh_lm has no forward_flops_per_token"),
    (None, 1, "chipbench.reference.fresh_lm.param_count gives "
     r"\d+ parameters, the configuration's params \d+")])
def test_reference_refused_by_load_cell(tmp_path, monkeypatch, drop,
                                        params_off, message):
    """A reference that lacks a count, or whose parameter count is not
    the configuration's ``params``, is refused when the cell loads."""
    root = tinycell.make_root(tmp_path, MAMBA)
    _fresh_reference(root, monkeypatch, drop=drop, params_off=params_off)
    with pytest.raises(ValueError, match=message):
        harness.load_cell("tiny.t", root)


def test_cpu_device_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chipbench/run.py"),
                        "--workload", CELL, "--seed", "3", "--seconds", "1"],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


# ---------------------------------------------------------------------------
# the program's server state, read by leaf
# ---------------------------------------------------------------------------

def test_server_buffers_read_by_the_program_layout():
    """``server_state.flat_norms`` reads a flat buffer by leaf as the
    program packs it: the full-size cell's parameter tree and the
    two-layer one."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from chipbench import server_state
    from repro.configs import get_config
    from repro.core import packing
    from repro.models import transformer as tr
    cell = harness.load_cell(CELL)
    for m in (cell.config["model"], _small_model(n_layers=2, d_model=64,
                                                 vocab=256, ssm_head_dim=16)):
        cfg = dataclasses.replace(get_config("mamba2-370m"), **{
            k: m[k] for k in ("n_layers", "d_model", "vocab", "ssm_state",
                              "ssm_head_dim")})
        abstract = jax.eval_shape(lambda k: tr.init_lm(k, cfg),
                                  jax.random.PRNGKey(0))
        lay = packing.PackedLayout.from_tree(abstract)
        offsets, at = [], 0
        for leaf in jax.tree.leaves(abstract):
            offsets.append(at)
            at += -(-int(np.prod(leaf.shape)) // server_state.LANE) \
                * server_state.LANE
        assert at == lay.d_packed
        assert offsets == [e.offset for e in lay.table]
    tree = jax.tree.map(lambda l: jnp.arange(l.size, dtype=jnp.float32)
                        .reshape(l.shape) / l.size, abstract)
    got = server_state.flat_norms(lay.pack(tree), abstract)
    want = server_state.norms(jax.tree.leaves(tree))
    assert [float(x) for x in got] == pytest.approx(
        [float(x) for x in want], rel=1e-6)
    with pytest.raises(ValueError):
        server_state.flat_norms(jnp.zeros(lay.d_packed + server_state.LANE),
                                abstract)


# ---------------------------------------------------------------------------
# what decides `correct`
# ---------------------------------------------------------------------------

def _over(compared) -> list:
    return [k for k, c in compared.items() if c["value"] > c["limit"]]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = tinycell.make_root(tmp_path_factory.mktemp("tiny"), MAMBA)
    cell = harness.load_cell("tiny.t", root)
    return cell, harness.Program(cell)


def test_control_fails_the_comparison(tiny):
    """The reference in float8 put in the program's place fails one of the
    cell's numbers."""
    cell, prog = tiny
    seed = 2 ** 33 + 17
    pool = prog.pool(seed, cell.config["model"]["vocab"], 128)[:3]
    want = check.reference_readings(cell, seed, pool, prog.abstract)
    got = check.reference_readings(cell, seed, pool, prog.abstract,
                                   quant=check.fp8)
    g = check.gaps(got, want)
    assert _over({k: {"value": g[k], "limit": v}
                  for k, v in cell.limits.items()})


@pytest.mark.parametrize("selection,number,reading", [
    ("none", "sel_count_gap", 1.0), ("none", "refresh_energy_gap", 1.0),
    ("random", "refresh_energy_gap", None)])
def test_selection_fault_fails_the_comparison(tiny, selection, number,
                                              reading):
    """The reference put in the program's place with its selection
    broken: nothing refreshed after the first round reads 1 on the count
    and the energy; a uniform draw of k coordinates misses the largest
    scores and fails the energy."""
    cell, prog = tiny
    seed = 2 ** 33 + 17
    pool = prog.pool(seed, cell.config["model"]["vocab"], 128)[:3]
    want = check.reference_readings(cell, seed, pool, prog.abstract)
    got = check.reference_readings(cell, seed, pool, prog.abstract,
                                   selection=selection)
    g = check.gaps(got, want)
    assert g[number] > cell.limits[number]
    if reading is not None:
        assert g[number] == pytest.approx(reading)


class NoMagnitudeStage(harness.Program):
    """A server whose magnitude stage selects nothing after the first
    round: only the oldest coordinates are refreshed."""

    def compile(self, state, batch):
        import jax.numpy as jnp
        from repro.core import engine
        real = engine.SelectionEngine._packed_thresholds

        def broken(eng, g, age, tstate, *a, **kw):
            tm, ta, streak = real(eng, g, age, tstate, *a, **kw)
            return (jnp.where(tstate["init"] > 0, jnp.inf, tm), ta, streak)
        engine.SelectionEngine._packed_thresholds = broken
        try:
            return super().compile(state, batch)
        finally:
            engine.SelectionEngine._packed_thresholds = real


@pytest.mark.parametrize("broken", [faults.Unchanged, faults.HalfBatch,
                                    NoMagnitudeStage])
def test_broken_step_is_not_correct(tiny, broken):
    """A whole run, with the look for a chip skipped and the timed step
    broken underneath, reports correct false."""
    cell, _ = tiny
    out = harness.run_cell(cell, 2 ** 33 + 5, 0.5, False, CPU,
                           time.perf_counter(), program_cls=broken)
    assert out["correct"] is False
    assert _over(out["compared"])


FULL_SERVER = ("--ef", "--sanitize", "--async-agg", "--adaptive-km")


@pytest.mark.parametrize("broken,reads", [(harness.Program, 0.0),
                                          (faults.Unchanged, 3.0)])
def test_controller_state_is_held_exactly(tmp_path, broken, reads):
    """Under the full server the adaptive split's state after the checked
    rounds (split, damped step, seen flag, round counter) matches the
    reference's exactly; a step that returns its state unchanged leaves
    the round counter three rounds behind."""
    root = tinycell.make_root(tmp_path, MAMBA, flags=FULL_SERVER,
                              limits={"ctrl_state_gap": 0.0})
    cell = harness.load_cell("tiny.t", root)
    out = harness.run_cell(cell, 2 ** 33 + 5, 0.5, False, CPU,
                           time.perf_counter(), program_cls=broken)
    assert out["compared"]["ctrl_state_gap"]["value"] == reads
    assert out["correct"] is (reads == 0.0)


"""The scope readers (``chipbench/scopes.py`` and the metrics that call
it) and the set-up span reader, without a chip.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

On a trace recorded on a TPU v5e with the scope table of the executable
that ran (``record_scopes.py``), on a hand-made trace, and on a program
without scopes (the readers then read nothing)."""

from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness, scopes, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
FULL = "mamba2-370m.full.s512x4"
PARTITION = tuple(scopes.METRICS) + ("fairk_kernel_ms", "unattributed_ms")


@pytest.fixture
def tables():
    """The per-process table cache, restored after the test."""
    saved = dict(scopes._tables)
    yield scopes._tables
    scopes._tables.clear()
    scopes._tables.update(saved)


def _read(cell, ctx, names):
    return {m: harness.load_reader(cell, m)(ctx) for m in names}


def test_readers_on_recorded_trace(tables):
    """Two rounds of the one-chip full-stack cell on a TPU v5e: every
    top-level op of the window and the scope table of the step (its
    ``source`` says where each came from).  The readers give the recorded
    values, and the scope metrics, the kernel and the unattributed ops
    partition the busy time."""
    rec = json.loads(gzip.open(DATA / "scopes_full_s512x4.json.gz").read())
    cell = harness.load_cell(FULL)
    tables[FULL] = rec["table"]
    ctx = trace.Context(
        cell=cell, rounds=rec["rounds"], chips=1,
        device_kind=rec["device_kind"],
        trace={"devices": {k: [tuple(e) for e in v]
                           for k, v in rec["trace"]["devices"].items()},
               "host": [tuple(h) for h in rec["trace"]["host"]]})
    got = _read(cell, ctx, PARTITION + ("client_phase_ms",
                                        "update_phase_ms"))
    for name, value in got.items():
        assert value == pytest.approx(rec["expect"][name], rel=1e-9), name
    parts = sum(got[m] for m in PARTITION)
    assert parts == pytest.approx(rec["expect"]["all_ops_ms"], rel=1e-9)
    busy_ms = ctx.busy_s() * 1e3 / rec["rounds"]
    assert parts == pytest.approx(busy_ms, rel=5e-3)
    assert got["client_scope_ms"] > 0.5 * got["client_phase_ms"]
    assert got["fairk_kernel_ms"] > 0.0


def _hand_made():
    dev = "/device:TPU:0"
    ops = [("%while.1 = (...)", 100, 80),           # client, holds the next
           ("%fusion.2 = f32[8]", 120, 30),
           ("%fusion.3 = f32[8]", 200, 10),         # pack
           ("%fairk_update.4 = (...)", 210, 40),    # the kernel
           ("%reshape.5 = f32[8]", 250, 20),        # fairk, not the kernel
           ("%multiply_fusion.6 = f32[8]", 270, 15),  # mixed
           ("%copy.7 = f32[8]", 285, 5),            # not in the table
           ("%fusion.8 = f32[8]", 290, 25)]         # adamw
    host = [("feed", 90, 5), ("dispatch", 95, 10), ("block", 105, 215)]
    table = {"while.1": "client", "fusion.2": "client", "fusion.3": "pack",
             "fairk_update.4": "fairk", "reshape.5": "fairk",
             "multiply_fusion.6": None, "fusion.8": "adamw"}
    return {"devices": {dev: ops}, "host": host}, table


def test_readers_on_hand_made_trace(tables):
    cell = harness.load_cell(FULL)
    tr, tables[FULL] = _hand_made()
    ctx = trace.Context(cell=cell, trace=tr, rounds=1, chips=1,
                        device_kind="TPU v5 lite")
    got = _read(cell, ctx, PARTITION)
    assert got == pytest.approx(
        {"client_scope_ms": 80e-6, "pack_ms": 10e-6, "fairk_kernel_ms": 40e-6,
         "fairk_other_ms": 20e-6, "unattributed_ms": 20e-6,
         "adamw_ms": 25e-6, "server_stages_ms": 0.0, "state_cast_ms": 0.0,
         "unpack_ms": 0.0})
    # a scope that owns no op of its own reads 0, not nothing
    assert got["unpack_ms"] == 0.0
    assert sum(got.values()) == pytest.approx(ctx.busy_s() * 1e3)


def test_readers_read_nothing_without_scopes(tables):
    """A program without ``repro.obs`` (the parent of the scopes) has no
    table: every scope reader returns None, so the run leaves the metric
    out of its line."""
    cell = harness.load_cell(FULL)
    tr, _ = _hand_made()
    tables[FULL] = None
    ctx = trace.Context(cell=cell, trace=tr, rounds=1, chips=1,
                        device_kind="TPU v5 lite")
    got = _read(cell, ctx, tuple(scopes.METRICS) + ("unattributed_ms",))
    assert set(got.values()) == {None}
    assert _read(cell, ctx, ["fairk_kernel_ms"])["fairk_kernel_ms"] > 0.0


def test_table_is_none_where_the_program_has_no_obs(tables, monkeypatch):
    import repro.obs  # noqa: F401 — so that there is an attribute to hide
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(sys.modules["repro"], "obs", raising=False)
    cell = harness.load_cell(FULL)
    tables.pop(FULL, None)
    assert scopes.table(cell) is None


def test_server_init_reader():
    from repro import obs
    cell = harness.load_cell(FULL)
    read = harness.load_reader(cell, "server_init_s")
    ctx = trace.Context(cell=cell, trace={}, rounds=1, chips=1,
                        device_kind="TPU v5 lite")
    obs.reset()
    assert read(ctx) is None
    with obs.span("server_init") as sp:
        pass
    with obs.span("server_init"):
        pass
    assert read(ctx) == pytest.approx(sp.ms / 1e3)
    obs.reset()


def test_rebuilt_table_is_the_executed_one(tmp_path, tables):
    """The table ``scopes.table`` builds by compiling the cell's step at
    the window's shapes equals the one of the executable the run drove
    (two-layer model on the CPU)."""
    from chipbench import tinycell
    from repro import obs
    root = tinycell.make_root(tmp_path, "mamba2-370m",
                              flags=("--ef", "--sanitize", "--async-agg",
                                     "--adaptive-km"))
    cell = harness.load_cell("tiny.t", root)
    prog = harness.Program(cell)
    state = prog.init(5)
    pool = prog.pool(5, cell.config["model"]["vocab"], 128)
    executed = obs.scope_table(prog.compile(state, pool[0]))
    tables.pop(cell.name, None)
    rebuilt = scopes.table(cell)
    assert rebuilt == executed
    assert set(rebuilt.values()) >= {"client", "pack", "fairk", "adamw",
                                     "server_stages"}
    assert scopes.table(cell) is rebuilt

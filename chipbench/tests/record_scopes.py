#!/usr/bin/env python3
"""Record the scope fixture of ``test_scopes.py`` on a TPU.

  python3 chipbench/tests/record_scopes.py --seed <n> \
      --out scopes_full_s512x4.json.gz

Drives the one-chip full-stack cell as a run does (set-up, three rounds,
then the window's own loop with the profiler on for its first two rounds)
and writes, gzipped JSON: the traced rounds' count, the top-level device
ops of the window and its host spans, the scope table of the executable
that ran (``repro.obs.scope_table``, for the instructions in the trace),
the scope set of each of those instructions, and what every
scope reader and the busy time read.  It also checks that the table
``chipbench/scopes.py`` builds by compiling the cell again equals the one
of the executable that ran, and prints the ops no scope owns, by time."""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import harness, scopes, trace  # noqa: E402

CELL = "mamba2-370m.full.s512x4"
TRACED_S = 0.3              # the profiler stops after two rounds of 0.24 s
READ = tuple(scopes.METRICS) + ("unattributed_ms", "fairk_kernel_ms",
                                "client_phase_ms", "update_phase_ms")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    opts = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from repro import obs
    cell = harness.load_cell(CELL)
    harness.compile_cache(cell.root)
    device = harness.require_chips(cell.chips)
    prog = harness.Program(cell)
    state = prog.init(opts.seed)
    pool = prog.pool(opts.seed, cell.config["model"]["vocab"],
                     cell.traffic["seq_len"])
    trace_dir = str(cell.root / harness.TRACE_DIR)
    shutil.rmtree(trace_dir, ignore_errors=True)
    with prog.mesh:
        prog.compile(state, pool[0])
        for t in range(harness.CHECKED_STEPS):
            state, _ = prog(state, pool[t], jnp.asarray(t, jnp.int32))
        jax.block_until_ready(state)
        harness.TRACE_SECONDS = TRACED_S
        state, win = harness.window(prog, state, pool, 2 * TRACED_S,
                                    trace_dir)
    sets = obs.scope_sets(prog.compiled)
    table = obs.scope_table(prog.compiled)
    tr = trace.load(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = trace.Context(cell=cell, trace=tr, rounds=win["traced"],
                        chips=cell.chips, device_kind=device["kind"])
    ops = {dev: [list(e) for e in ctx.ops(dev)] for dev in tr["devices"]}
    names = {scopes.instruction(e) for evs in ops.values() for e in evs}
    scopes._tables[cell.name] = {k: table[k] for k in names if k in table}
    expect = {m: harness.load_reader(cell, m)(ctx) for m in READ}
    expect["busy_s"] = ctx.busy_s()
    expect["all_ops_ms"] = ctx.per_round_ms(lambda ev: True)
    del scopes._tables[cell.name]
    rebuilt = scopes.table(cell)
    rec = {"rounds": win["traced"], "device_kind": device["kind"],
           "source": f"recorded on the chip by record_scopes.py, seed "
                     f"{opts.seed}; table of the executable that ran",
           "trace": {"devices": ops, "host": [list(h) for h in tr["host"]]},
           "table": {k: table.get(k) for k in sorted(names)},
           "sets": {k: sorted(map(str, sets[k])) for k in sorted(names)
                    if k in sets},
           "expect": expect,
           "rebuilt_table_equal": rebuilt == table}
    Path(opts.out).parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(opts.out, "wt") as f:
        json.dump(rec, f)
    unowned = {}
    for dev, evs in ops.items():
        for e in evs:
            name = scopes.instruction(e)
            if not trace.is_fairk_kernel(e) and table.get(name) is None:
                unowned[name] = unowned.get(name, 0) + e[2]
    n = len(ops) * win["traced"]
    for name, ns in sorted(unowned.items(), key=lambda kv: -kv[1])[:40]:
        print(f"{ns / 1e6 / n:10.4f} ms  {name}  "
              f"{sorted(map(str, sets.get(name, {'?'})))}")
    print(json.dumps({"rounds": win["traced"], "expect": expect,
                      "rebuilt_table_equal": rec["rebuilt_table_equal"]}))
    return 0 if rec["rebuilt_table_equal"] else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip at the cell's size.

  python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
      [--faults 3] [--budget-s 1200] [--out readings.jsonl]

For each seed, in one process: the program's checked rounds against the
reference (the lower readings).  For the first ``--faults`` seeds also,
against the same reference: the lower-precision control (the reference
with every value the program holds in bfloat16 rounded to float8,
``check.fp8``), and planted faults in the reference put in the program's
place: half the batch left out (the first half of each round's
microbatches, the mean taken over them) and a server that refreshes a
uniform draw of about k coordinates instead of its selection.  A step
that returns its state unchanged reads 1 on the norm gaps by definition
and needs no run.  No seed is begun once ``--budget-s`` seconds have
passed.  One JSON line per reading."""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--budget-s", type=float, default=float("inf"))
    ap.add_argument("--out", default=None)
    opts = ap.parse_args(argv)
    from chipbench import check, harness
    cell = harness.load_cell(opts.workload)
    harness.compile_cache(harness.ROOT)
    harness.require_chips(cell.chips)
    prog = harness.Program(cell)
    vocab, seq = cell.config["model"]["vocab"], cell.traffic["seq_len"]
    out = open(opts.out, "a") if opts.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    t_start = time.perf_counter()
    for i, seed in enumerate(opts.seeds):
        if time.perf_counter() - t_start > opts.budget_s:
            break
        t0 = time.perf_counter()
        state = prog.init(seed)
        pool = prog.pool(seed, vocab, seq)[:harness.CHECKED_STEPS]
        with prog.mesh:
            prog.compile(state, pool[0])
            state, got = harness.program_readings(prog, state, pool, seed)
        del state
        gc.collect()
        t_ref = time.perf_counter()
        want = check.reference_readings(cell, seed, pool, prog.abstract)
        emit({"seed": seed, "kind": "program", **check.gaps(got, want),
              "losses": got["losses"], "ref_losses": want["losses"],
              "n_sel": [r["n_sel"] for r in got["server"]],
              "ref_n_sel": [r["n_sel"] for r in want["server"]],
              "s": time.perf_counter() - t0,
              "reference_s": time.perf_counter() - t_ref})
        if i < opts.faults:
            for kind, kw in (("control_fp8", {"quant": check.fp8}),
                             ("half_batch", {"micro": prog.n_micro // 2}),
                             ("random_selection", {"selection": "random"})):
                t0 = time.perf_counter()
                bad = check.reference_readings(cell, seed, pool,
                                               prog.abstract, **kw)
                emit({"seed": seed, "kind": kind, **check.gaps(bad, want),
                      "s": time.perf_counter() - t0})
        del pool
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Check the program's host spans against the profiler trace, and time one.

  PYTHONPATH=src python tools/span_clock.py --out span_clock.json -- \
      --arch mamba2-370m --full --seq 512 --batch 4 --steps 6 \
      --ef --sanitize --async-agg --adaptive-km

Runs ``repro.launch.train.run`` with the arguments after ``--`` under the
JAX profiler.  Every ``repro.obs.span`` is both an in-memory record
(``time.time_ns``) and a ``TraceAnnotation`` in the trace; the trace's
host events are matched to the records by name and order, and each pair's
start offset is taken.  One clock means one constant offset: the script
reports the offsets' spread (largest less least) and the largest gap
between a record's and its event's duration.  Then it times ``obs.span``
with the profiler off.  Prints one JSON object and writes it to
``--out``."""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402

from repro import obs  # noqa: E402
from repro.launch import train  # noqa: E402

SPANS = ("server_init",) + train.HOST_PHASES


def host_events(trace_dir: str):
    """{span name: [(start_ns, duration_ns)]} of the trace's host events,
    in time order (starts relative to the trace's own origin)."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns))
    return {k: sorted(v) for k, v in out.items()}


def span_cost_us(n: int) -> float:
    """Host microseconds of one empty ``obs.span``, profiler off."""
    obs.reset()
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("tick"):
            pass
    us = (time.perf_counter() - t0) / n * 1e6
    obs.reset()
    return us


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    own, run_args = (argv[:argv.index("--")], argv[argv.index("--") + 1:]
                     ) if "--" in argv else (argv, [])
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=None)
    ap.add_argument("--cost-n", type=int, default=100_000)
    opts = ap.parse_args(own)
    args = train.parse_args(run_args)
    train.enable_compile_cache()
    obs.reset()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            train.run(args)
        finally:
            jax.profiler.stop_trace()
        events = host_events(d)
    offsets, dur_gap, unmatched = [], 0.0, {}
    for name in SPANS:
        recs = [s for s in obs.spans() if s.name == name]
        evs = events.get(name, [])
        if len(recs) != len(evs):
            unmatched[name] = [len(recs), len(evs)]
            continue
        for rec, (start, dur) in zip(recs, evs):
            offsets.append(rec.start_ns - start)
            dur_gap = max(dur_gap, abs((rec.end_ns - rec.start_ns) - dur))
    out = {"device": jax.devices()[0].device_kind,
           "spans_matched": len(offsets), "unmatched": unmatched,
           "offset_spread_us": ((max(offsets) - min(offsets)) / 1e3
                                if offsets else None),
           "duration_gap_us": dur_gap / 1e3,
           "span_cost_us": span_cost_us(opts.cost_n)}
    print(json.dumps(out), flush=True)
    if opts.out:
        Path(opts.out).write_text(json.dumps(out) + "\n")
    return 0 if offsets and not unmatched else 1


if __name__ == "__main__":
    sys.exit(main())

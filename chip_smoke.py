#!/usr/bin/env python3
"""Chip smoke test: the FAIR-k training step end to end on a TPU.

  python chip_smoke.py            # one chip
  python chip_smoke.py --chips 4  # four chips only: the sharded phase

One chip.  First the fused server kernel (Pallas, lowered by Mosaic) is
checked against its pure-JAX oracle on the chip.  Then ``mamba2-370m`` at
its published widths (random weights from a seed) trains ``STEPS`` steps at
seq 2048 x batch 8 through the launcher's own loop
(``repro.launch.train.run``), under two OAC server configurations: the
default (packed, fused stats) and ``--ef --sanitize --async-agg
--adaptive-km``.  For each it prints the compile seconds, every step's
loss, time and selected fraction, the steady step time, the device's peak
bytes in use and whether the compiled step holds the Mosaic kernel
(``tpu_custom_call``).  It fails on a non-finite loss, a first loss far
from ln(vocab) (the loss of a random init), a selected fraction outside
(0, 0.5] after the cold-start step, or a step without the kernel (the
server phase would then have run on the XLA reference path).

Four chips.  The same model trains on a ``data=4, model=1`` mesh — four FL
clients, one per chip, FSDP parameters, thresholds and histograms
``pmean``'d across shards — and then, after those arrays are freed, on one
device with the same global batches.  Losses and selected fractions must
agree step by step within ``LOSS_TOL`` / ``SEL_TOL`` (justified below).

Refuses to run anywhere but on a TPU: it exits non-zero without printing
the result line.  The last line of a passing run is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Compiled
programs are cached as ``repro.launch.train.enable_compile_cache`` says.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH_ARGS = ["--arch", "mamba2-370m", "--full", "--seq", "2048",
             "--batch", "8"]
STEPS = 5
CONFIGS = {
    "default": [],
    "ef+sanitize+async+adaptive_km": ["--ef", "--sanitize", "--async-agg",
                                      "--adaptive-km"],
}
# A random init predicts near-uniformly: its first loss is ln(vocab).
INIT_LOSS_TOL = 0.5
# Sharded vs one-device.  The first step sees the same weights and batch,
# so its loss differs only by reduction order (bf16 matmuls, the gradient
# mean over four shards vs eight microsteps).  Later steps also differ by
# the thresholds: each shard estimates them from a strided sample of its
# own quarter of the buffer before the pmean, so the selected sets (and
# through AdamW the weights) part slightly.  The selected fraction is a
# budget-tracking controller's output at rho = 0.1: a threshold error of a
# few percent of the budget moves it by well under 0.01.
LOSS_TOL = 0.02
SEL_TOL = 0.01


def fail(msg: str, code: int = 1):
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def log(msg: str):
    print(msg, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def kernel_phase(d: int) -> None:
    """The fused stats kernel against its oracle on the device: merged
    values, ages, residuals and counts exactly; the histograms to within a
    few boundary samples (the kernel's and XLA's log2 may round apart at a
    bin edge)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import packing
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    g = rng.normal(size=d).astype("f4")
    g[rng.integers(0, d, 500)] = np.nan
    age = (rng.permutation(d) % 120).astype("f4")
    age[1000:5000] = packing.PAD_AGE
    args = [jnp.asarray(x) for x in (
        g, rng.normal(size=d).astype("f4"), age)]
    res = jnp.asarray(rng.normal(size=d).astype("f4"))
    kw = dict(residual=res, sanitize=True)
    want = ops.fairk_stats_update(*args, 1.1, 60.0, mode="ref", **kw)
    got = ops.fairk_stats_update(*args, 1.1, 60.0, mode="pallas", **kw)
    for name, a, b in zip(("g_t", "age", "residual"), want[:3], got[:3]):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            fail(f"kernel {name} differs from the oracle")
    for name in ("n_sel", "n_sel_m"):
        if float(want[3][name]) != float(got[3][name]):
            fail(f"kernel {name} {float(got[3][name])} != oracle "
                 f"{float(want[3][name])}")
    for name in ("mag_hist", "age_hist"):
        a, b = np.asarray(want[3][name]), np.asarray(got[3][name])
        off = float(np.abs(a - b).sum())
        if a.sum() != b.sum() or off > 1e-3 * a.sum() + 2:
            fail(f"kernel {name} off the oracle by {off} of {a.sum()}")
    log(f"[kernel] fairk_stats_update (pallas) matches the oracle at d={d} "
        f"(sample stride {packing.hist_stride(d)})")


def train_phase(name: str, extra, mesh: str = "1x1") -> dict:
    """Train through the launcher's loop; print and return what it saw."""
    import numpy as np
    from repro.configs import get_config
    from repro.launch import train
    args = train.parse_args(ARCH_ARGS + ["--steps", str(STEPS), "--mesh", mesh]
                            + list(extra))
    out = train.run(args)
    out["kernel"] = "tpu_custom_call" in out.pop("compiled").as_text()
    out["steady_step_s"] = statistics.median(out["step_s"][1:]
                                             or out["step_s"])
    out["vocab"] = get_config(args.arch, reduced_variant=args.reduced).vocab
    log(f"[{name}] mesh {mesh} compile_s {out['compile_s']:.2f} "
        f"steady_step_s {out['steady_step_s']:.4f} kernel {out['kernel']}")
    log(f"[{name}] losses {out['losses']}")
    log(f"[{name}] selected {out['sel_frac']}")
    if not all(math.isfinite(x) for x in out["losses"]):
        fail(f"{name}: non-finite loss {out['losses']}")
    if abs(out["losses"][0] - np.log(out["vocab"])) > INIT_LOSS_TOL:
        fail(f"{name}: first loss {out['losses'][0]} is not near "
             f"ln(vocab) = {np.log(out['vocab']):.3f}")
    if not all(0.0 < s <= 0.5 for s in out["sel_frac"][1:]):
        fail(f"{name}: selected fraction out of (0, 0.5]: "
             f"{out['sel_frac']}")
    return out


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def one_chip() -> None:
    # sample strides 32 (lanes of the kernel tile) and 256 (rows, as on the
    # full model's buffer), each with a partial tail block of pads
    for d in (16 * 65536 + 3000, 256 * 65536 + 3000):
        kernel_phase(d)
    for name, extra in CONFIGS.items():
        out = train_phase(name, extra)
        log(f"[{name}] peak_bytes_in_use {peak_bytes()}")
        if not out["kernel"]:
            fail(f"{name}: the compiled step holds no tpu_custom_call — "
                 "the server phase ran on the reference path")
        del out
        gc.collect()


def four_chips() -> None:
    import jax
    sharded = train_phase("sharded", [], mesh="4x1")
    gc.collect()
    jax.clear_caches()
    single = train_phase("single", [], mesh="1x1")
    for name, out in (("sharded", sharded), ("single", single)):
        if not out["kernel"]:
            fail(f"{name}: the compiled step holds no tpu_custom_call")
    dl = [abs(a - b) for a, b in zip(sharded["losses"], single["losses"])]
    ds = [abs(a - b) for a, b in zip(sharded["sel_frac"],
                                     single["sel_frac"])]
    log(f"[compare] |loss diff| {dl} (tol {LOSS_TOL})")
    log(f"[compare] |selected diff| {ds} (tol {SEL_TOL})")
    if max(dl) > LOSS_TOL or max(ds) > SEL_TOL:
        fail("sharded and one-device runs disagree")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernel check + two server configurations; "
                         "4: only the sharded run vs the one-device run")
    opts = ap.parse_args()
    info = device_info()
    if info["platform"] != "tpu":
        fail(f"no TPU: JAX found {info['platform']} devices", code=2)
    if info["count"] < opts.chips:
        fail(f"--chips {opts.chips} needs {opts.chips} chips, "
             f"{info['count']} present", code=2)
    from repro.launch.train import enable_compile_cache
    log(f"[chip_smoke] {info}; compile cache {enable_compile_cache()}")
    if opts.chips == 4:
        four_chips()
    else:
        one_chip()
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()

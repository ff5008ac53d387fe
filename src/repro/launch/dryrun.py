import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, prove memory/sharding coherence, and dump the roofline
artifacts (memory_analysis, cost_analysis, loop-aware parsed HLO metrics).

A CPU-placeholder tool: the 512 "devices" are host CPU devices, so what it
reports is sharding coherence and compiled-program structure, never a chip
measurement.  The two environment lines above MUST precede every other
import (jax locks the platform and device count at first init) — that is
why they sit before the docstring's siblings here and why nothing else in
the repo sets them globally.  ``JAX_PLATFORMS=cpu`` keeps it off an
attached accelerator, which one process at a time may hold.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--fl-mode]
Artifacts land in benchmarks/artifacts/dryrun/<arch>__<shape>__<mesh>.json.
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (make_fl_oac_step, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.roofline import analyze_hlo, build_report, suggestion

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")


def make_step(cfg, shape, mesh, oac_packed: bool = True):
    if shape.kind == "train":
        from repro.launch.steps import OacServerConfig
        return make_train_step(cfg, shape, mesh,
                               oac=OacServerConfig(packed=oac_packed))
    if shape.kind == "prefill":
        return make_prefill_step(cfg, shape, mesh)
    return make_serve_step(cfg, shape, mesh)


def run_one(arch: str, shape_name: str, multi_pod: bool,
            out_dir: str, fl_mode: bool = False, fl_baseline: bool = False,
            fl_one_bit: bool = False, force: bool = False,
            oac_packed: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in
                         (mesh.devices.shape if hasattr(mesh, "devices")
                          else ()))
    mesh_name = ("2x16x16" if multi_pod else "16x16")
    tag = f"{arch}__{shape_name}__{mesh_name}" + (
        "__flbase" if fl_baseline else
        "__fl1bit" if fl_one_bit else "__fl" if fl_mode else "") + (
        "" if oac_packed else "__perleaf")
    out_path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            cached = json.load(f)
        # artifacts written before the packed server phase share the
        # default tag — only reuse a train artifact if it records the same
        # server-phase flavour (stale per-leaf stats must not masquerade
        # as the packed configuration)
        meta = cached.get("meta", {})
        if (meta.get("kind") != "train"
                or meta.get("oac_packed") == oac_packed):
            return cached

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    t0 = time.time()
    if fl_mode:
        bundle = make_fl_oac_step(cfg, mesh, baseline=fl_baseline,
                                  one_bit=fl_one_bit)
    else:
        bundle = make_step(cfg, shape, mesh, oac_packed=oac_packed)
    with mesh:
        lowered = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                          out_shardings=bundle.out_shardings
                          ).lower(*bundle.input_specs)
        compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    print(mem)                               # proves it fits
    cost = compiled.cost_analysis()
    print({k: v for k, v in cost.items()
           if k in ("flops", "bytes accessed", "transcendentals")})
    parsed = analyze_hlo(compiled.as_text())
    chips = 512 if multi_pod else 256
    report = build_report(cfg, shape, mesh_name, chips, parsed)

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "fl_mode": fl_mode, "fl_baseline": fl_baseline,
        "meta": {k: v for k, v in bundle.meta.items() if k != "scans"}
        | {"scans": bundle.meta.get("scans", {})},
        "compile_s": t_compile,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "per_device_total": (mem.argument_size_in_bytes
                                 + mem.temp_size_in_bytes),
        },
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "parsed": parsed,
        "roofline": report.as_dict(),
        "suggestion": suggestion(report),
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[dryrun] {tag}: compile {t_compile:.1f}s, "
          f"dominant={report.dominant}, step={report.step_time_s*1e3:.2f}ms")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape) on the chosen mesh")
    ap.add_argument("--fl-mode", action="store_true",
                    help="paper-technique FL-OAC step (clients = devices)")
    ap.add_argument("--fl-baseline", action="store_true",
                    help="FL-OAC without compression (full all-reduce)")
    ap.add_argument("--fl-onebit", action="store_true",
                    help="FL-OAC with the one-bit FSK-MV uplink (Sec. V-B, "
                         "sign_mv majority vote); the FL simulator's "
                         "FLConfig.one_bit likewise runs on every backend "
                         "(exact / threshold / packed)")
    ap.add_argument("--per-leaf-server", action="store_true",
                    help="historical per-leaf OAC server phase (default: "
                         "persisted packed fused pass with in-kernel selection statistics, DESIGN.md §9-§11)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=os.path.abspath(ART_DIR))
    args = ap.parse_args()

    combos = []
    if args.all:
        for arch in sorted(ARCHS):
            for shape in ("train_4k", "prefill_32k", "decode_32k",
                          "long_500k"):
                combos.append((arch, shape))
    else:
        combos.append((args.arch or "qwen2.5-32b",
                       args.shape or "train_4k"))

    failures = []
    for arch, shape in combos:
        try:
            run_one(arch, shape, args.multi_pod, args.out,
                    fl_mode=args.fl_mode, fl_baseline=args.fl_baseline,
                    fl_one_bit=args.fl_onebit, force=args.force,
                    oac_packed=not args.per_leaf_server)
        except Exception as e:
            failures.append((arch, shape, repr(e)))
            traceback.print_exc()
    if failures:
        print(f"\n[dryrun] {len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"\n[dryrun] all {len(combos)} combination(s) compiled OK")


if __name__ == "__main__":
    main()

"""Training launcher.

Runs real steps of a registered architecture on this host's devices: on
the CPU for tests (reduced configs), on a TPU through the same loop
(``chip_smoke.py`` at the repo root calls ``run`` below).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-32b --reduced \
      --steps 20 --policy fairk

``--mesh DxM`` lays the devices out as ``data`` (FL clients, FSDP) x
``model`` (tensor parallelism); the default is one device.

Checkpointing (packed server phase): ``--ckpt-every N`` saves the
persisted flat server buffers (incl. the warm-start theta vector and the
adaptive-``k_M`` controller state) every N steps via
``repro.checkpoint.save_server_state``; a SIGTERM lands one final save
before the loop exits; ``--resume`` restores the latest checkpoint from
``--ckpt-dir`` and continues at the following step.

Compiled programs persist across processes (``enable_compile_cache``):
where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps them there;
otherwise they go to ``.jax_cache/`` at the repository root.
"""

from __future__ import annotations

import argparse
import os
import signal
import time
import zipfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation

from repro import checkpoint, obs
from repro.configs import ARCHS, get_config
from repro.configs.base import InputShape
from repro.core import packing
from repro.data.tokens import lm_batch
from repro.launch import sharding as shlib
from repro.launch.mesh import make_mesh, parse_mesh
from repro.launch.steps import (OacServerConfig, init_server_state,
                                make_train_step, server_layout)
from repro.models import transformer as tr

REPO_ROOT = Path(__file__).resolve().parents[3]
# the host spans of one step of ``run``, in the order they run
HOST_PHASES = ("batch", "dispatch", "block", "readback", "ckpt")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own default for the
    cache directory, and no other is set here.  Otherwise the cache goes to
    the fixed ``.jax_cache/`` at the repository root: the directory is part
    of an entry's key, so it must not move between runs.  Call it from an
    entry point, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen2.5-32b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1",
                    help="device mesh DxM: D data shards (FL clients, "
                         "FSDP) x M model shards, over the first D*M "
                         "devices")
    ap.add_argument("--oac", action="store_true", default=True,
                    help="enable the FAIR-k OAC server phase")
    ap.add_argument("--no-oac", dest="oac", action="store_false")
    ap.add_argument("--rho", type=float, default=0.1)
    ap.add_argument("--per-leaf-server", action="store_true",
                    help="historical per-leaf OAC server phase (default: "
                         "persisted packed fused pass with in-kernel selection statistics, DESIGN.md §9-§11)")
    ap.add_argument("--ef", action="store_true",
                    help="error feedback: persist the unselected gradient "
                         "mass in a flat residual buffer and fold it back "
                         "next step (packed server phase only)")
    ap.add_argument("--one-bit", action="store_true",
                    help="one-bit server uplink: merge sign_mv-detected "
                         "signs of the effective gradient (combine with "
                         "--ef; packed server phase only)")
    ap.add_argument("--legacy-stats", action="store_true",
                    help="disable the fused in-kernel selection statistics "
                         "(restores the two-pass count accounting + "
                         "sampled-quantile bootstrap)")
    ap.add_argument("--async-agg", action="store_true",
                    help="asynchronous double-buffered server rounds "
                         "(DESIGN.md §13): the optimizer consumes the "
                         "previous round's merged gradient so the fused "
                         "pass overlaps the next round's compute; "
                         "straggler contributions defer one round via the "
                         "shadow buffer (packed server phase only)")
    ap.add_argument("--straggler-frac", type=float, default=0.25,
                    help="fraction of coordinates whose uplink arrives one "
                         "aggregation late under --async-agg")
    ap.add_argument("--adaptive-km", action="store_true",
                    help="adapt the k_M/k split online INSIDE the compiled "
                         "step (core/controller.py: the kernel-emitted age "
                         "histogram drives a traced split — zero host "
                         "syncs, zero recompiles; packed server phase "
                         "only)")
    ap.add_argument("--sanitize", action="store_true",
                    help="graceful degradation (DESIGN.md §14): mask "
                         "non-finite gradient coordinates out of the "
                         "fused selection — a crashed host's NaN/Inf "
                         "uplink is 'unsent' (age climbs, EF residual "
                         "rides through) instead of poisoning the model "
                         "(packed server phase only)")
    ap.add_argument("--fade", type=float, default=0.0,
                    help="per-round deep-fade erasure probability on the "
                         "aggregated uplink, at --fade-block granularity "
                         "(needs --sanitize)")
    ap.add_argument("--fade-block", type=int, default=128,
                    help="coordinates per deep-fade block (one OFDM "
                         "symbol group's worth)")
    ap.add_argument("--population", type=int, default=0,
                    help="virtual client-population size (DESIGN.md §15): "
                         "per-round availability, cohort participation, "
                         "mid-round churn erasures and (under --async-agg) "
                         "the traced straggler share all derive from a "
                         "stateless population of this many clients "
                         "(0 = off; needs --sanitize)")
    ap.add_argument("--cohorts", type=int, default=4096,
                    help="cohort batch size of the packed population "
                         "state (clients per packed row)")
    ap.add_argument("--participants", type=int, default=16,
                    help="clients the server samples per round from the "
                         "live population")
    ap.add_argument("--avail", type=float, default=0.9,
                    help="stationary per-client availability of the "
                         "population")
    ap.add_argument("--diurnal", action="store_true",
                    help="diurnal availability: the population's rate "
                         "rides a sinusoid (period --diurnal-period, "
                         "swing --diurnal-depth) whose time-average stays "
                         "at --avail")
    ap.add_argument("--diurnal-period", type=int, default=96,
                    help="rounds per diurnal cycle")
    ap.add_argument("--diurnal-depth", type=float, default=0.1,
                    help="relative swing of the diurnal availability rate")
    ap.add_argument("--channel", action="store_true",
                    help="geometric wireless channel (DESIGN.md §16): "
                         "per-block AR(1) Rayleigh fading with truncated "
                         "channel inversion — blocks in outage erase "
                         "through the sanitize path and the persisted "
                         "fading chain rides the server checkpoints "
                         "(needs --sanitize)")
    ap.add_argument("--pmax", type=float, default=10.0,
                    help="per-client transmit power budget of --channel "
                         "(inverting a gain below 1/pmax is infeasible)")
    ap.add_argument("--gmin", type=float, default=0.05,
                    help="designed truncation threshold of --channel on "
                         "the instantaneous gain")
    ap.add_argument("--csi-err", type=float, default=0.0,
                    help="residual channel-estimation error std of "
                         "--channel: multiplicative per-block "
                         "misalignment on the fresh aggregate")
    ap.add_argument("--fading-corr", type=float, default=0.5,
                    help="Gauss-Markov AR(1) fading correlation of "
                         "--channel in [0, 1) (0 = memoryless)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save the packed server state every N steps "
                         "(0 = off; a SIGTERM always lands one final "
                         "save when > 0)")
    ap.add_argument("--ckpt-dir", default="checkpoints",
                    help="directory for server_<step>.npz checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest server checkpoint from "
                         "--ckpt-dir and continue at the next step")
    ap.add_argument("--client-chunk", type=int, default=0,
                    help="streaming client aggregation (DESIGN.md §17): "
                         "split the global batch into this many simulated "
                         "client microbatches and accumulate their "
                         "gradients chunk by chunk inside the compiled "
                         "step — gradient memory scales with the chunk, "
                         "not the client count (0 = one fused batch)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build_oac(args) -> "OacServerConfig | None":
    """The OAC server configuration the CLI flags describe."""
    population = None
    if args.population > 0:
        from repro.core.population import PopulationConfig
        population = PopulationConfig(
            n_clients=args.population, cohort_size=args.cohorts,
            participants=args.participants, avail=args.avail,
            mode="diurnal" if args.diurnal else "iid",
            period=args.diurnal_period, depth=args.diurnal_depth,
            slow_frac=(args.straggler_frac if args.async_agg else 0.0))
    wireless = None
    if args.channel:
        from repro.core.channel import ChannelConfig
        wireless = ChannelConfig(pmax=args.pmax, gmin=args.gmin,
                                 csi_err=args.csi_err,
                                 rho_f=args.fading_corr,
                                 block=args.fade_block)
    return (OacServerConfig(rho=args.rho, packed=not args.per_leaf_server,
                            error_feedback=args.ef, one_bit=args.one_bit,
                            fused_stats=not args.legacy_stats,
                            adaptive_km=args.adaptive_km,
                            async_agg=args.async_agg,
                            straggler_frac=args.straggler_frac,
                            sanitize=args.sanitize, fade=args.fade,
                            fade_block=args.fade_block,
                            population=population, wireless=wireless)
            if args.oac else None)


def run(args) -> dict:
    """The training loop behind the CLI: build the mesh, the step and the
    state, compile once, then take ``args.steps`` steps.

    Returns what a caller measuring the run needs: ``compile_s`` (lower +
    compile of the step), per-step ``losses``, ``step_s`` (host clock
    around each step up to ``block_until_ready``), ``sel_frac`` (the
    share of this shard's coordinates the server phase selected, from the
    fused kernel's counts carried in the threshold state; None without the
    packed phase), ``host_ms`` (for each of ``HOST_PHASES`` the ms of its
    host span in each step: building the batch, dispatching the step,
    waiting for the device, reading ``sel_frac`` back, checkpointing; 0
    where a step saved nothing) and the ``compiled`` step."""
    cfg = get_config(args.arch, reduced_variant=args.reduced)
    data, model = parse_mesh(args.mesh)
    if data * model > len(jax.devices()):
        raise ValueError(f"--mesh {args.mesh} needs {data * model} devices, "
                         f"{len(jax.devices())} present")
    mesh = make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:data * model])
    shape = InputShape("custom", args.seq, args.batch, "train")
    oac = build_oac(args)
    # --client-chunk C: C client microbatches, vmapped as one chunk;
    # otherwise the step builder's default of one sample per data shard
    # per microstep
    if args.client_chunk and args.batch % args.client_chunk:
        raise ValueError(f"--client-chunk {args.client_chunk} must divide "
                         f"--batch {args.batch}")
    bundle = make_train_step(cfg, shape, mesh,
                             n_micro=(args.client_chunk or None),
                             client_chunk=(args.client_chunk or None),
                             oac=oac, lr=1e-3)
    n_micro = bundle.meta["n_micro"]
    in_sh, out_sh = bundle.in_shardings, bundle.out_shardings

    key = jax.random.PRNGKey(args.seed)
    params = tr.init_lm(key, cfg)
    from repro.optim import make_optimizer
    opt = make_optimizer(bundle.meta["optimizer"], bundle.meta["lr"])
    opt_state = opt.init(params)
    server = init_server_state(params, mesh=mesh, cfg=cfg, oac=oac)

    # checkpointing (packed server state only: the flat persisted buffers
    # ARE the cross-step state worth resuming; params/opt ride the generic
    # repro.checkpoint.save when needed)
    ckpt_on = args.ckpt_every > 0 or args.resume
    if ckpt_on and (oac is None or not oac.packed):
        raise ValueError("--ckpt-every/--resume checkpoint the PACKED "
                         "server buffers — they need --oac and are "
                         "incompatible with --per-leaf-server")
    layout = (server_layout(params, shlib.param_pspecs(params, cfg, mesh),
                            mesh) if ckpt_on else None)
    start = 0
    if args.resume:
        candidates = checkpoint.server_steps(args.ckpt_dir)
        if not candidates:
            # legitimate on the FIRST launch of a preemptible job, but
            # never silent: a mistyped --ckpt-dir must not masquerade as
            # a continued trajectory
            print(f"[train] --resume: no server checkpoint under "
                  f"{args.ckpt_dir!r} — starting fresh at step 0",
                  flush=True)
        else:
            # newest first, walking back past corrupt checkpoints: the
            # content checksums (checkpoint.io) catch bit rot / torn
            # writes, and a server_<N>.npz without its params/opt
            # companion is the same torn-save species.  Config
            # mismatches (layout / field-set ValueErrors) still raise —
            # falling back cannot fix a wrong flag.
            restored = False
            for last in candidates:
                srv_path = os.path.join(args.ckpt_dir,
                                        f"server_{last:08d}.npz")
                step_path = os.path.join(args.ckpt_dir,
                                         f"step_{last:08d}.npz")
                try:
                    srv_np, _ = checkpoint.restore_server_state(
                        srv_path, layout=layout)
                    if not os.path.exists(step_path):
                        raise checkpoint.CorruptCheckpointError(
                            f"{srv_path} has no matching "
                            f"step_{last:08d}.npz (params/optimizer) — "
                            "torn save")
                    tree = checkpoint.restore(step_path,
                                              like={"params": params,
                                                    "opt": opt_state})
                except (checkpoint.CorruptCheckpointError,
                        zipfile.BadZipFile, OSError) as err:
                    print(f"[train] --resume: checkpoint step {last} "
                          f"failed validation ({err}); falling back to "
                          "the previous checkpoint", flush=True)
                    continue
                # reconcile the checkpoint field set with the configured
                # one: pre-async checkpoints migrate (cold zero
                # double-buffers) when resuming under --async-agg; any
                # other flag mismatch raises with the offending fields
                # named
                srv_np = checkpoint.migrate_server_state(srv_np,
                                                         like=server)
                server = {k: jnp.asarray(v) for k, v in srv_np.items()}
                # the server buffers describe the OLD model's gradient
                # stream — resuming them onto re-randomized weights would
                # merge a stale trajectory into a fresh one, so
                # params/opt ride the same checkpoint step
                params = jax.tree.map(jnp.asarray, tree["params"])
                opt_state = jax.tree.map(jnp.asarray, tree["opt"])
                start = last
                restored = True
                print(f"[train] resumed server + params/opt state from "
                      f"step {last} ({args.ckpt_dir})", flush=True)
                break
            if not restored:
                raise ValueError(
                    f"--resume: every checkpoint under "
                    f"{args.ckpt_dir!r} failed validation "
                    f"(tried steps {candidates}) — refusing to silently "
                    "restart the trajectory from scratch")

    # a SIGTERM (preemption) finishes the in-flight step, saves once, and
    # exits the loop cleanly; the caller's handler is back once run() ends
    stop = {"sig": False}

    def _on_term(signum, frame):
        stop["sig"] = True

    def save(step):
        path = checkpoint.save_server_state(args.ckpt_dir, server,
                                            layout=layout, step=step)
        # params/opt accompany every server checkpoint (closure reads the
        # loop's latest bindings) so --resume continues ONE trajectory
        checkpoint.save(args.ckpt_dir, {"params": params,
                                        "opt": opt_state}, step=step)
        print(f"  [ckpt] saved {path} (+ step_{step:08d}.npz)", flush=True)

    def make_batch(t):
        toks, labels = lm_batch(args.seed * 1000 + t, args.batch, args.seq,
                                cfg.vocab)
        mb = args.batch // n_micro
        batch = {"tokens": jnp.asarray(toks).reshape((n_micro, mb, args.seq)),
                 "labels": jnp.asarray(labels).reshape(
                     (n_micro, mb, args.seq))}
        if cfg.family == "vlm":
            batch["embeds"] = jnp.zeros(
                (n_micro, mb, cfg.n_patches, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (n_micro, mb, cfg.encoder_seq, cfg.d_model),
                jnp.dtype(cfg.compute_dtype))
        return batch

    # donate (params, opt_state, server): the persisted packed server
    # buffers (flat g_prev bf16 / age int8 / EF residual f32 / controller
    # vec) are consumed and rebuilt every step — donation makes the
    # update fully in place
    params, opt_state, server = jax.device_put((params, opt_state, server),
                                               in_sh[:3])
    step_fn = jax.jit(bundle.fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=(0, 1, 2))
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M-param family "
          f"variant, mesh {args.mesh}, {args.steps} steps, "
          f"oac={'on' if args.oac else 'off'}", flush=True)
    # the share of this shard's valid coordinates the fused pass selected:
    # the kernel's count (pmean'd over shards) rides the threshold state
    d_valid = (server_layout(params, shlib.param_pspecs(params, cfg, mesh),
                             mesh).d_valid
               if oac is not None and oac.packed else None)
    out = {"losses": [], "step_s": [], "sel_frac": [],
           "host_ms": {phase: [] for phase in HOST_PHASES}}
    prev_term = signal.signal(signal.SIGTERM, _on_term)
    try:
        with mesh:
            t0 = time.perf_counter()
            compiled = step_fn.lower(
                params, opt_state, server, make_batch(start),
                jnp.asarray(start, jnp.int32)).compile()
            out["compile_s"] = time.perf_counter() - t0
            out["compiled"] = compiled
            print(f"[train] compiled the step in {out['compile_s']:.1f}s",
                  flush=True)
            for t in range(start, start + args.steps):
                spans = {}
                with StepTraceAnnotation("train_step", step_num=t):
                    with obs.span("batch") as spans["batch"]:
                        batch = make_batch(t)
                    t0 = time.perf_counter()
                    with obs.span("dispatch") as spans["dispatch"]:
                        seed = jnp.asarray(t, jnp.int32)
                        params, opt_state, server, loss = compiled(
                            params, opt_state, server, batch, seed)
                    with obs.span("block") as spans["block"]:
                        jax.block_until_ready((params, opt_state, server,
                                               loss))
                    dt = time.perf_counter() - t0
                    out["losses"].append(float(loss))
                    out["step_s"].append(dt)
                    sel = None
                    with obs.span("readback") as spans["readback"]:
                        if d_valid:
                            n_sel = np.asarray(server["theta"])[
                                packing.THRESHOLD_STATE_FIELDS.index(
                                    "n_sel")]
                            sel = float(n_sel) / d_valid
                    out["sel_frac"].append(sel)
                    print(f"  step {t:3d} loss {out['losses'][-1]:.4f} "
                          f"({dt:.2f}s, batch {spans['batch'].ms:.1f} ms, "
                          f"block {spans['block'].ms:.1f} ms)"
                          + (f" selected {sel:.4f}" if sel is not None
                             else ""), flush=True)
                    if ckpt_on and args.ckpt_every > 0 and (
                            (t + 1 - start) % args.ckpt_every == 0):
                        with obs.span("ckpt") as spans["ckpt"]:
                            save(t + 1)
                for phase in HOST_PHASES:
                    out["host_ms"][phase].append(
                        spans[phase].ms if phase in spans else 0.0)
                if stop["sig"]:
                    if ckpt_on:
                        save(t + 1)
                    print("[train] SIGTERM — state saved, exiting",
                          flush=True)
                    break
    finally:
        signal.signal(signal.SIGTERM, prev_term)
    print("[train] done", flush=True)
    return out


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()

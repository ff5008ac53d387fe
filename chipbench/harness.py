"""The benchmark harness: one run of one cell.

A cell is found by name in ``BENCHMARK.json``: its configuration file
(``configs/``), its traffic file (``traffic/<traffic>.json``), its limits
(``limits/<cell>.json``) and, in a traced run, one reader per per-layer
metric (``metrics/<metric>.py``).  Adding a cell, a configuration, a
traffic mix or a metric adds files and entries; nothing here changes.

The run drives the program's own compiled train step
(``repro.launch.steps.make_train_step``, jitted as ``repro.launch.train.run``
jits it: the bundle's shardings, donated state, a ``data x model`` mesh,
the OAC server ``train.build_oac`` makes from the traffic's flags).

- Set-up: weights, optimizer and server state on the device from the
  seed; a pool of distinct batches; the step compiled once; three checked
  rounds through the window's own call and feed, read for the comparison.
- Window: whole rounds, each ended by ``block_until_ready``, until the
  seconds have passed.  Compilations inside the window are counted.
- After the window: peak device memory, then the program's state is freed
  and the plain reference replays the three checked rounds (``check``).
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import hashlib
import importlib.util
import json
import math
import os
import resource
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parent.parent
CHECKED_STEPS = 3
POOL = 8
TRACE_SECONDS = 2.0          # the traced part of a --trace 1 window
TRACE_DIR = ".chipbench_trace"


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Everything one cell needs, found by name from ``BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    base = root / bench["paths"][0]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in names]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((base / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        limits=json.loads((base / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e, per_layer=per_layer, root=root)


def load_reader(cell: Cell, metric: str):
    """The ``read(ctx)`` function of one per-layer metric's file."""
    path = cell.root / "chipbench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(n: int) -> Dict[str, Any]:
    """The device description, or ``NoChip`` off a TPU or short of chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, {len(devs)} present")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

class Program:
    """The program's compiled train step for one cell, with its state."""

    def __init__(self, cell: Cell):
        import jax
        from repro.configs import get_config
        from repro.configs.base import InputShape
        from repro.launch import train
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_train_step
        from repro.optim import make_optimizer
        conf, tr = cell.config, cell.traffic
        cfg = get_config(conf["arch"])
        cfg = dataclasses.replace(cfg, **conf["model"])
        args = train.parse_args(
            ["--arch", conf["arch"], "--full", "--seq", str(tr["seq_len"]),
             "--batch", str(tr["batch"]), "--mesh", f"{cell.chips}x1"]
            + list(tr["server_flags"]))
        self.cfg, self.oac = cfg, train.build_oac(args)
        self.mesh = make_mesh((cell.chips, 1), ("data", "model"),
                              devices=jax.devices()[:cell.chips])
        shape = InputShape("custom", tr["seq_len"], tr["batch"], "train")
        bundle = make_train_step(cfg, shape, self.mesh, oac=self.oac,
                                 lr=tr["lr"])
        self.in_sh, self.out_sh = bundle.in_shardings, bundle.out_shardings
        self.abstract = bundle.input_specs[0]
        self.n_micro = bundle.meta["n_micro"]
        self.micro_batch = bundle.meta["micro_batch"]
        self.opt = make_optimizer(bundle.meta["optimizer"], bundle.meta["lr"])
        self.jitted = jax.jit(bundle.fn, in_shardings=self.in_sh,
                              out_shardings=self.out_sh,
                              donate_argnums=(0, 1, 2))
        self.compiled = None
        self.root = cell.root

    def init(self, seed: int):
        """(params, opt_state, server) for ``seed``, on the device."""
        import jax
        from chipbench import feed
        from repro.launch.steps import init_server_state
        params = feed.make_weights(seed, self.abstract, self.in_sh[0])
        opt = jax.jit(self.opt.init, out_shardings=self.in_sh[1])(params)
        server = init_server_state(self.abstract, mesh=self.mesh,
                                   cfg=self.cfg, oac=self.oac)
        server = jax.device_put(server, self.in_sh[2])
        return params, opt, server

    def pool(self, seed: int, vocab: int, seq_len: int):
        from chipbench import feed
        return feed.batch_pool(seed, POOL, self.n_micro, self.micro_batch,
                               seq_len, vocab, self.in_sh[3])

    def compile(self, state, batch):
        import jax.numpy as jnp
        if self.compiled is None:
            with self.mesh:
                lowered = self.jitted.lower(*state, batch,
                                            jnp.asarray(0, jnp.int32))
                self.compiled = lowered.compile()
            keep_module_text(self.root, lowered, self.compiled)
        return self.compiled

    def release(self):
        """Drop the compiled step before the reference (``compile`` builds
        it again)."""
        self.compiled = None
        gc.collect()

    def __call__(self, state, batch, seed):
        params, opt, server, loss = self.compiled(*state, batch, seed)
        return (params, opt, server), loss


def module_text_path(root: Path, lowered) -> Path:
    """Where the optimised module text of a lowered program is kept, under
    the compilation cache's directory, by a hash of its lowered text."""
    key = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:40]
    return root / ".jax_cache" / "module_text" / f"{key}.txt"


def keep_module_text(root: Path, lowered, compiled) -> None:
    """Keep the optimised module text of a program this process compiled.
    An executable the persistent cache serves comes without it, and the
    scope and collective readers of a traced run need it (``scopes``)."""
    path = module_text_path(root, lowered)
    text = compiled.as_text()
    if "ENTRY" in text and not path.exists():
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f".{os.getpid()}.tmp")
        tmp.write_text(text)
        tmp.replace(path)


def server_reader(prog: Program):
    """A jitted reading (``server_state.reading``) of the program's stored
    server state.  The flat buffers are read by their layout: one chip's
    whole, or each device's local buffer by its leaves' shard shapes under
    the parameter shardings (``server_state.sharded_reading``)."""
    import jax
    from chipbench import server_state as ss
    from repro.core.controller import controller_state_from_vec
    lag = prog.oac.straggler_lag if prog.oac.async_agg else 0
    leaves = jax.tree.leaves(prog.abstract)
    local = [sh.shard_shape(l.shape)
             for l, sh in zip(leaves, jax.tree.leaves(prog.in_sh[0]))]
    spec = prog.in_sh[2]["g"].spec

    def fn(server):
        if prog.mesh.size != 1:
            return ss.sharded_reading(
                {b: server[b] for b in ("g", "age") + ss.BUFFERS
                 if b in server}, lag, local, [l.shape for l in leaves],
                prog.mesh, spec, controller_state_from_vec(server["ctrl"])
                if "ctrl" in server else None)
        norms = {("merged" if b == "g" else b):
                 ss.flat_norms(server[b], prog.abstract)
                 for b in ("g",) + ss.BUFFERS if b in server}
        ctrl = (controller_state_from_vec(server["ctrl"])
                if "ctrl" in server else None)
        return ss.reading(server["g"], server["age"], norms, lag, ctrl)
    return jax.jit(fn)


def reference_shardings(prog: Program):
    """Where the reference places its weights: as the program's
    parameters over several devices; on one chip, where JAX puts them."""
    return prog.in_sh[0] if prog.mesh.size > 1 else None


def program_readings(prog: Program, state, pool, seed: int):
    """Drive the program through the checked rounds; read the losses, the
    first non-zero gradient AdamW received (from its first moment:
    m = (1 - b1) g after a step from m = 0), the stored server state after
    each round and the parameters' change.  Returns (state after the
    rounds, readings)."""
    import jax
    import jax.numpy as jnp
    from chipbench import feed, server_state
    from chipbench.reference import fairk_adamw as ref
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x)))
                               for x in jax.tree.leaves(t)])
    read = server_reader(prog)
    keys = server_state.leaf_keys(prog.abstract)
    losses, first, server = [], None, []
    for t in range(CHECKED_STEPS):
        state, loss = prog(state, pool[t], jnp.asarray(t, jnp.int32))
        losses.append(float(loss))
        server.append(server_state.to_host(read(state[2]), keys))
        if first is None:
            got = [float(n) / (1 - ref.B1) for n in norms(state[1]["m"])]
            if any(n > 0 for n in got):
                first = dict(zip(keys, got))
    return state, {"losses": losses, "first_grad": first, "server": server,
                   "delta": feed.delta_norms(state[0], seed, prog.abstract)}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts backend compilations while ``on``."""

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name.endswith("backend_compile_duration"):
            self.count += 1


def peak_bytes(n: int) -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n])


def window(prog: Program, state, pool, seconds: float, trace_dir=None):
    """Whole rounds until ``seconds`` have passed.  With ``trace_dir`` the
    profiler records the first ``TRACE_SECONDS`` of them."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    times, losses = [], []
    slowest = (0.0, None)
    traced = None
    # set-up's objects (traced programs, caches) stay out of the window's
    # garbage collections
    gc.collect()
    gc.freeze()
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    t_start = time.perf_counter()
    t = CHECKED_STEPS
    while True:
        t0 = time.perf_counter()
        use0 = resource.getrusage(resource.RUSAGE_SELF)
        with TraceAnnotation("feed"):
            batch = pool[t % len(pool)]
            seed = jnp.asarray(t, jnp.int32)
        with TraceAnnotation("dispatch"):
            state, loss = prog(state, batch, seed)
        t_disp = time.perf_counter()
        with TraceAnnotation("block"):
            jax.block_until_ready((state, loss))
        t1 = time.perf_counter()
        times.append(t1 - t0)
        if t1 - t0 > slowest[0]:
            slowest = (t1 - t0, _round_record(len(times) - 1, t0, t_disp, t1,
                                              use0))
        losses.append(loss)
        t += 1
        if trace_dir is not None and traced is None and (
                t1 - t_start >= min(TRACE_SECONDS, seconds)):
            traced = len(times)
            jax.profiler.stop_trace()
        if t1 - t_start >= seconds:
            break
    elapsed = time.perf_counter() - t_start
    gc.unfreeze()
    failed = sum(not math.isfinite(float(x)) for x in losses)
    return state, {"round_s": times, "elapsed_s": elapsed,
                   "failed": failed, "traced": traced, "slowest": slowest[1]}


def _round_record(i: int, t0: float, t_disp: float, t1: float, use0):
    """Where one round's host time went: dispatch, the wait for the
    device, and the process's page faults and context switches meanwhile
    (a switch the process did not ask for means the host was busy)."""
    use1 = resource.getrusage(resource.RUSAGE_SELF)
    return {"round": i, "dispatch_ms": 1e3 * (t_disp - t0),
            "block_ms": 1e3 * (t1 - t_disp),
            **{k: getattr(use1, f"ru_{k}") - getattr(use0, f"ru_{k}")
               for k in ("minflt", "majflt", "nvcsw", "nivcsw")}}


def compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at the fixed ``.jax_cache/``
    inside the checkout, handed to the program's own switch (which defers
    to ``JAX_COMPILATION_CACHE_DIR``); every program is cached.  Call it
    before JAX touches a device."""
    import jax
    from repro.launch.train import enable_compile_cache
    path = root / ".jax_cache"
    path.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return enable_compile_cache()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: Dict[str, Any], t_process: float,
             program_cls=Program) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line's object."""
    import jax
    from chipbench import check, trace as trace_lib
    counter = CompileCounter()
    tr = cell.traffic
    vocab = cell.config["model"]["vocab"]
    prog = program_cls(cell)
    state = prog.init(seed)
    pool = prog.pool(seed, vocab, tr["seq_len"])
    with prog.mesh:
        prog.compile(state, pool[0])
        state, prog_read = program_readings(prog, state, pool, seed)
        jax.block_until_ready(state)
        setup_s = time.perf_counter() - t_process
        trace_dir = None
        if trace:
            trace_dir = str(cell.root / TRACE_DIR)
            shutil.rmtree(trace_dir, ignore_errors=True)
        counter.on = True
        state, win = window(prog, state, pool, seconds, trace_dir)
        counter.on = False
    peak = peak_bytes(cell.chips)
    del state
    prog.release()
    t_check = time.perf_counter()
    compared = check.compare(cell, seed, prog_read, pool[:CHECKED_STEPS],
                             prog.abstract, reference_shardings(prog))
    check_s = time.perf_counter() - t_check
    del pool
    tokens = tr["seq_len"] * tr["batch"]
    rounds = len(win["round_s"])
    values = {
        "tokens_per_s": rounds * tokens / win["elapsed_s"],
        "round_ms_p95": (1e3 * statistics.quantiles(win["round_s"], n=100)[94]
                         if rounds >= 2 else None),
        "peak_hbm_gib": peak / 2 ** 30,
        "setup_s": setup_s,
    }
    out: Dict[str, Any] = {"correct": all(c["value"] <= c["limit"]
                                          for c in compared.values())
                           and win["failed"] == 0 and counter.count == 0,
                           "attempted": rounds, "failed": win["failed"]}
    dev = dict(device, memory_peak_bytes=peak)
    metrics: Dict[str, Any] = {}
    if trace:
        t0 = time.perf_counter()
        tr_data = trace_lib.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = trace_lib.Context(cell=cell, trace=tr_data,
                                rounds=win["traced"], chips=cell.chips,
                                device_kind=device["kind"])
        for m in cell.per_layer:
            v = load_reader(cell, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev |= {"busy_s": ctx.busy_s(), "window_s": ctx.trace_window_s()}
        out["breakdown"] = ctx.breakdown()
        out["trace_read_s"] = time.perf_counter() - t0
    else:
        for m in cell.end_to_end:
            v = values[m["name"]]
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = dev
    out["compiles_in_window"] = counter.count
    med = statistics.median(win["round_s"])
    out["rounds_ms"] = {"median": 1e3 * med, "max": 1e3 * max(win["round_s"]),
                        "over_2x_median": sum(t > 2 * med
                                              for t in win["round_s"]),
                        "slowest": win["slowest"]}
    out["check_s"] = check_s
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    cell = load_cell(opts.workload)
    compile_cache(cell.root)
    try:
        device = require_chips(cell.chips)
    except NoChip as err:
        print(f"[chipbench] {err}", file=sys.stderr, flush=True)
        return 2
    out = run_cell(cell, opts.seed, opts.seconds, bool(opts.trace), device,
                   t_process)
    for name, c in out["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0

"""Tests of the harness on a step sharded over four devices, without a chip.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

The dense transformer's FLOP and parameter counts, the collective readers
on a hand-made module and trace, and, in subprocesses with four host
devices (``--xla_force_host_platform_device_count=4``; this process keeps
its one device): the four-shard server reading against the one-chip
reading of the same values gathered, and whole runs of a two-layer
``granite-34b`` cell on a ``(4, 1)`` mesh, sound and broken."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import flops, harness, scopes, trace  # noqa: E402

GRANITE = "granite-34b-fsdp4.default.s2048x8"
TINY = dict(n_layers=2, d_model=256, n_heads=2, n_kv_heads=1, d_ff=512,
            vocab=512)


# ---------------------------------------------------------------------------
# the work a round needs
# ---------------------------------------------------------------------------

def _granite_cfg(**kw):
    import dataclasses
    from repro.configs import get_config
    conf = harness.load_cell(GRANITE).config
    return dataclasses.replace(get_config(conf["arch"]),
                               **dict(conf["model"], **kw))


@pytest.mark.parametrize("kw", [{}, TINY])
def test_mqa_counts_agree_with_the_program(kw):
    """``mqa_param_count`` counts every leaf of the program's parameter
    tree; the forward FLOPs without attention are twice the matmul
    weights that ``ModelConfig.param_count`` counts (all but the
    embedding, which is a gather)."""
    import jax
    import numpy as np
    from repro.models import transformer as tr
    cfg = _granite_cfg(**kw)
    m = dict(harness.load_cell(GRANITE).config["model"], **kw)
    abstract = jax.eval_shape(lambda k: tr.init_lm(k, cfg),
                              jax.random.PRNGKey(0))
    assert flops.mqa_param_count(m) == sum(
        int(np.prod(l.shape)) for l in jax.tree.leaves(abstract))
    seq = 2048
    hd = m["d_model"] // m["n_heads"]
    attn = m["n_layers"] * 2 * 2 * (seq // 2) * m["n_heads"] * hd
    fwd = flops.FORWARD["mqa_lm"](m, seq)
    assert (fwd - attn) // 2 == cfg.param_count() - m["vocab"] * m["d_model"]


def test_flops_and_params_of_the_granite_cell():
    conf = harness.load_cell(GRANITE).config
    assert flops.mqa_param_count(conf["model"]) == conf["params"] \
        == 1741338624
    # 3 layers of 2 x 379,060,224 weights + 25,165,824 attention, and the
    # head's 2 x 301,989,888
    assert flops.mqa_forward_flops_per_token(conf["model"], 2048) \
        == 2953838592
    assert flops.FORWARD["ssd_lm"](
        harness.load_cell("mamba2-370m.default.s2048x8").config["model"],
        2048) == 786481152


def test_mqa_flops_against_xla_cost_analysis():
    """The count bounds XLA's from below: XLA adds the elementwise work and
    the attention's masked upper half, which the program computes and the
    count leaves out.  One layer (XLA's cost analysis counts a scan's body
    once), remat off."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tr
    cfg = _granite_cfg(**dict(TINY, n_layers=1), remat=False)
    m = dict(harness.load_cell(GRANITE).config["model"],
             **dict(TINY, n_layers=1))
    seq = 256
    p = jax.eval_shape(lambda k: tr.init_lm(k, cfg), jax.random.PRNGKey(0))
    b = {k: jax.ShapeDtypeStruct((1, seq), jnp.int32)
         for k in ("tokens", "labels")}
    cost = jax.jit(jax.grad(lambda p, b: tr.loss_fn(p, cfg, b)[0])).lower(
        p, b).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    mine = flops.train_flops_per_round({"reference": "mqa_lm", "model": m},
                                       {"seq_len": seq, "batch": 1})
    assert mine <= cost["flops"] <= 1.4 * mine


# ---------------------------------------------------------------------------
# collectives, at any depth
# ---------------------------------------------------------------------------

HLO = """\
HloModule m

%fused_computation.1 (p: bf16[4]) -> bf16[16] {
  %p = bf16[4]{0} parameter(0)
  ROOT %all-gather.9 = bf16[16]{0} all-gather(%p), dimensions={0}
}

%fused_computation.2 (p: f32[8], q: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %q = f32[8]{0} parameter(1)
  ROOT %add.1 = f32[8]{0} add(%p, %q)
}

%fused_computation.3 (p: bf16[4], w: bf16[16,16]) -> bf16[16] {
  %p = bf16[4]{0} parameter(0)
  %w = bf16[16,16]{1,0} parameter(1)
  %all-gather.10 = bf16[16]{0} all-gather(%p), dimensions={0}
  ROOT %dot.1 = bf16[16]{0} dot(%all-gather.10, %w), lhs_contracting_dims={0}, rhs_contracting_dims={0}
}

%body (c: (s32[], bf16[4])) -> (s32[], bf16[4]) {
  %c = (s32[], bf16[4]{0}) parameter(0)
  %all-gather-start.1 = (bf16[4]{0}, bf16[16]{0}) all-gather-start(%x), dimensions={0}
  %all-gather-done.1 = bf16[16]{0} all-gather-done(%all-gather-start.1)
  %async-collective-start = (bf16[4]{0}, bf16[16]{0}, /*index=2*/u32[]) fusion(%y), kind=kCustom, calls=%fused_computation.1
  %fusion.5 = f32[8]{0} fusion(%a, %b), kind=kLoop, calls=%fused_computation.2
  %reduce-scatter.3 = f32[2]{0} reduce-scatter(%fusion.5), dimensions={0}, to_apply=%add
  %fusion.7 = bf16[16]{0} fusion(%x, %w), kind=kOutput, calls=%fused_computation.3
  ROOT %tuple = (s32[], bf16[4]{0}) tuple(%i, %x)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %while.1 = (s32[], bf16[4]{0}) while(%t), condition=%cond, body=%body
  %all-reduce.7 = f32[8]{0} all-reduce(%a), to_apply=%add
  %collective-permute-start.2 = (f32[8]{0}, f32[8]{0}) collective-permute-start(%a), source_target_pairs={{0,1}}
  %collective-permute-done.2 = f32[8]{0} collective-permute-done(%collective-permute-start.2)
  ROOT %fusion.6 = f32[8]{0} fusion(%a, %a), kind=kLoop, calls=%fused_computation.2
}
"""


def test_collective_instructions():
    """Collective opcodes, their async halves and the fusions that hold
    one and nothing that computes, in any computation, move data; a fusion
    that holds a gather and the matmul it feeds is a collective matmul;
    the loop that holds them is a loop; a fusion of plain arithmetic is
    work."""
    kinds = trace.instruction_kinds(HLO)
    assert {n for n, k in kinds.items() if k == "collective"} == {
        "all-gather.9", "all-gather.10", "all-gather-start.1",
        "all-gather-done.1", "async-collective-start", "reduce-scatter.3",
        "all-reduce.7", "collective-permute-start.2",
        "collective-permute-done.2"}
    assert {n for n, k in kinds.items() if k == "collective_matmul"} == {
        "fusion.7"}
    assert {n for n, k in kinds.items() if k == "loop"} == {"while.1"}
    assert kinds["fusion.5"] == kinds["fusion.6"] == kinds["dot.1"] == "work"


def test_collective_reader_sums_nested_collectives(monkeypatch):
    """``collective_ms`` reads the time of the collectives inside the loop
    and outside it in which no compute runs, and nothing else: not the
    loop, not the compute beside the collectives, not the collective
    matmul, and not the part of a collective that compute overlaps; a
    collective that ends past the window is clipped; on each of two
    devices (averaged)."""
    cell = harness.load_cell(GRANITE)
    monkeypatch.setattr(scopes, "compiled_text", lambda c: HLO)
    ops = [("%while.1 = (s32[], bf16[4])", 100, 200),           # the loop
           ("%all-gather-start.1 = (bf16[4]", 110, 5),
           ("%fusion.5 = f32[8]{0} fusion", 115, 40),
           ("%all-gather-done.1 = bf16[16]", 155, 20),
           ("%async-collective-start = (bf16[4]", 180, 10),
           ("%reduce-scatter.3 = f32[2]", 190, 10),
           ("%fusion.7 = bf16[16]{0} fusion", 195, 50),  # 5 over the last
           ("%all-reduce.7 = f32[8]{0} all-reduce", 300, 30),
           ("%fusion.6 = f32[8]{0} fusion", 320, 30),    # 10 over the last
           ("%collective-permute-done.2 = f32[8]", 390, 40)]  # to 430
    host = [("feed", 90, 5), ("dispatch", 95, 5), ("block", 100, 310)]
    tr = {"devices": {"/device:TPU:0": ops, "/device:TPU:1": ops},
          "host": host}
    ctx = trace.Context(cell=cell, trace=tr, rounds=2, chips=4,
                        device_kind="TPU v5 lite")
    read = harness.load_reader(cell, "collective_ms")
    # 5 + 20 + 10 + (10 - 5) + (30 - 10) + (410 - 390) = 80 ns a device,
    # over 2 rounds
    assert read(ctx) == pytest.approx(80e-6 / 2)
    # the top-level view sees the loop and the outer ops only
    assert ctx.per_round_ms(lambda e: "all-gather" in e[0]) is None
    one_chip = trace.Context(cell=cell, trace=tr, rounds=2, chips=1,
                             device_kind="TPU v5 lite")
    assert read(one_chip) is None


# ---------------------------------------------------------------------------
# four host devices, in a subprocess
# ---------------------------------------------------------------------------

def _run4(code: str, timeout: int = 900) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout, cwd=str(ROOT))
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_shard_reading_equals_the_gathered_one():
    """A server packed as the program packs it over four devices (each
    device's local shards, leaf by leaf) reads as the one-chip buffer of
    the same values gathered: counts exactly, sums and norms to rounding.
    The norms and biases every device holds count once."""
    got = _run4("""
        import dataclasses, json
        from types import SimpleNamespace
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.core import packing
        from repro.launch import sharding as shlib
        from repro.launch.mesh import make_mesh
        from repro.models import transformer as tr
        from chipbench import harness, server_state as ss
        conf = harness.load_cell("%s").config
        cfg = dataclasses.replace(get_config("granite-34b"),
                                  **dict(conf["model"], **%r))
        mesh = make_mesh((4, 1), ("data", "model"))
        ab = jax.eval_shape(lambda k: tr.init_lm(k, cfg), jax.random.PRNGKey(0))
        sh = shlib.to_named(shlib.param_pspecs(ab, cfg, mesh), mesh)
        leaves, treedef = jax.tree_util.tree_flatten(ab)
        ks = jax.random.split(jax.random.PRNGKey(3), 2 * len(leaves))
        g = [jax.random.normal(ks[2 * i], l.shape).astype(jnp.bfloat16)
             for i, l in enumerate(leaves)]
        age = [jax.random.randint(ks[2 * i + 1], l.shape, 0, 12
                                  ).astype(jnp.int8)
               for i, l in enumerate(leaves)]
        res = [x.astype(jnp.float32) * 0.5 for x in g]
        tree = lambda xs: jax.tree_util.tree_unflatten(treedef, xs)
        spec = P(("data", "model"))

        pspecs = shlib.param_pspecs(ab, cfg, mesh)

        def pack(t, fill=0.0):
            def local(t):
                lay = packing.PackedLayout.from_tree(t)
                return (lay.pack_age(t, jnp.int8) if fill else
                        lay.pack(t, jax.tree.leaves(t)[0].dtype))
            return jax.shard_map(local, mesh=mesh, in_specs=(pspecs,),
                                 out_specs=spec)(jax.device_put(t, sh))
        server = {"g": pack(tree(g)), "age": pack(tree(age), fill=1),
                  "res": pack(tree(res))}
        prog = SimpleNamespace(mesh=mesh, abstract=ab,
                               in_sh=(sh, None, {"g": NamedSharding(mesh, spec)}),
                               oac=SimpleNamespace(async_agg=False))
        keys = ss.leaf_keys(ab)
        four = ss.to_host(harness.server_reader(prog)(server), keys)
        one = ss.to_host(jax.jit(lambda g, a, r: ss.reading(
            g, a, {"merged": ss.norms(g), "res": ss.norms(r)}, 0))(
            g, age, res), keys)
        lay = packing.PackedLayout.from_tree(ab)
        print(json.dumps({"four": four, "one": one,
                          "d_local": lay.d_packed,
                          "d": int(server["g"].shape[0])}))
    """ % (GRANITE, TINY))
    four, one = got["four"], got["one"]
    assert got["d"] > 0 and got["d"] % 4 == 0
    assert four["n_sel"] == one["n_sel"] > 0
    assert four["age_counts"] == one["age_counts"]
    assert four["energy"] == pytest.approx(one["energy"], rel=1e-5)
    for buf in ("merged", "res"):
        assert four[buf].keys() == one[buf].keys()
        for k in one[buf]:
            assert four[buf][k] == pytest.approx(one[buf][k], rel=1e-5), k


RUN = """
    import json, shutil, sys, time
    from pathlib import Path
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from chipbench import check, flops, harness
    from chipbench.reference import mqa_lm
    tmp = Path(%(tmp)r)
    for d in ("configs", "traffic", "limits", "metrics"):
        (tmp / "chipbench" / d).mkdir(parents=True)
    bench = json.loads(Path("BENCHMARK.json").read_text())
    w = dict([x for x in bench["workloads"] if x["chips"] == 4][0],
             name="tinyg.t", config="tinyg", traffic="t")
    bench["configs"] = [dict(bench["configs"][1], name="tinyg",
                             file="chipbench/configs/tinyg.json")]
    bench["workloads"] = [w]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    conf = harness.load_cell("%(cell)s").config
    conf["model"] = dict(conf["model"], **%(tiny)r)
    conf["params"] = flops.mqa_param_count(conf["model"])
    (tmp / "chipbench/configs/tinyg.json").write_text(json.dumps(conf))
    (tmp / "chipbench/traffic/t.json").write_text(json.dumps(
        {"seq_len": 128, "batch": 8, "server_flags": [], "lr": 1e-3}))
    (tmp / "chipbench/limits/tinyg.t.json").write_text(json.dumps(%(limits)r))
    cell = harness.load_cell("tinyg.t", tmp)


    class Unchanged(harness.Program):
        def __call__(self, state, batch, seed):
            _, loss = super().__call__(jax.tree.map(jnp.copy, state), batch,
                                       seed)
            return state, loss


    class HalfBatch(harness.Program):
        def __init__(self, cell):
            half = dict(cell.traffic, batch=cell.traffic["batch"] // 2)
            super().__init__(harness.Cell(**dict(vars(cell), traffic=half)))
            self.full = cell.traffic["batch"] // self.micro_batch

        def pool(self, seed, vocab, seq_len):
            from chipbench import feed
            return feed.batch_pool(seed, harness.POOL, self.full,
                                   self.micro_batch, seq_len, vocab,
                                   self.in_sh[3])

        def _half(self, batch):
            return {k: v[:self.n_micro] for k, v in batch.items()}

        def compile(self, state, batch):
            return super().compile(state, self._half(batch))

        def __call__(self, state, batch, seed):
            return super().__call__(state, self._half(batch), seed)


    class NoExchange(harness.Program):
        # the model's loss taken on each device over its own rows, on its
        # own whole copy of the weights; in the backward pass each device
        # keeps, of the gradient its rows give, the shard it holds, and
        # nothing is summed between devices
        def compile(self, state, batch):
            from repro.models import transformer as tr
            plain, mesh = tr.loss_fn, self.mesh
            n = mesh.shape["data"]
            dims = [next((i for i, e in enumerate(sh.spec) if e is not None
                          and "data" in (e if isinstance(e, tuple) else (e,))),
                         None) for sh in jax.tree.leaves(self.in_sh[0])]

            @jax.custom_vjp
            def spread(p):
                return jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (n,) + x.shape), p)

            def own(c, d):
                if d is None:
                    return c[0]
                k = c.shape[d + 1] // n
                return jnp.concatenate([jax.lax.slice_in_dim(
                    c[i], i * k, (i + 1) * k, axis=d) for i in range(n)], d)

            def bwd(_, ct):
                leaves, treedef = jax.tree_util.tree_flatten(ct)
                return (jax.tree_util.tree_unflatten(
                    treedef, [own(c, d) for c, d in zip(leaves, dims)]),)
            spread.defvjp(lambda p: (spread(p), None), bwd)

            def local(params, cfg, mbatch, residual_fn=None):
                return jax.shard_map(
                    lambda p, b: plain(jax.tree.map(lambda x: x[0], p),
                                       cfg, b),
                    mesh=mesh, in_specs=(P("data"), P("data")),
                    out_specs=P(), check_vma=False)(spread(params), mbatch)
            tr.loss_fn = local
            try:
                return super().compile(state, batch)
            finally:
                tr.loss_fn = plain


    if %(mutate)r == "no_rope":
        mqa_lm.rope = lambda x, theta: x
    elif %(mutate)r == "second_kv_head":
        # the reference built for two key/value heads of the same width
        plain = mqa_lm.loss
        mqa_lm.loss = lambda p, t, l, m, **kw: plain(
            p, t, l, dict(m, n_kv_heads=2), **kw)
    if %(broken)r == "control":
        # the reference, rounded to float8 where the program holds bfloat16,
        # in the program's place
        prog = harness.Program(cell)
        seed = 2 ** 33 + 5
        pool = prog.pool(seed, conf["model"]["vocab"], 128)[:3]
        kw = dict(shardings=harness.reference_shardings(prog))
        want = check.reference_readings(cell, seed, pool, prog.abstract, **kw)
        got = check.reference_readings(cell, seed, pool, prog.abstract,
                                       quant=check.fp8, **kw)
        g = check.gaps(got, want)
        compared = {k: {"value": g[k], "limit": v}
                    for k, v in cell.limits.items()}
        print(json.dumps({"compared": compared, "correct": all(
            c["value"] <= c["limit"] for c in compared.values())}))
        sys.exit(0)
    broken = {"sound": harness.Program, "unchanged": Unchanged,
              "half_batch": HalfBatch, "no_exchange": NoExchange}[%(broken)r]
    try:
        out = harness.run_cell(cell, 2 ** 33 + 5, 0.5, False,
                               {"platform": "cpu", "kind": "cpu", "count": 4},
                               time.perf_counter(), program_cls=broken)
    except Exception as err:
        out = {"correct": False, "error": repr(err), "compared": {}}
    print(json.dumps({k: out.get(k) for k in
                      ("correct", "compared", "compiles_in_window", "error")}))
"""

# limits for the two-layer model on four CPU devices, set like a cell's
# from its own readings: sound runs read at most grad 0.0014, update
# 0.017; the float8 control reads grad 0.0048-0.0078, half the batch
# 0.36-0.45, the exchange left out 1.0
TINY4_LIMITS = {"grad_norm_gap": 0.003, "update_norm_gap": 0.05}


def _run_cell4(tmp_path, broken="sound", mutate=None):
    return _run4(RUN % {"tmp": str(tmp_path), "cell": GRANITE, "tiny": TINY,
                        "limits": TINY4_LIMITS, "broken": broken,
                        "mutate": mutate})


def test_four_device_run_agrees_with_the_reference(tmp_path):
    """The program's step on a ``(4, 1)`` mesh against the plain MQA
    reference and FAIR-k server, sharded alike, at a two-layer
    ``granite-34b``: the loss, each leaf's first gradient and the three
    rounds' change agree within the limits."""
    out = _run_cell4(tmp_path)
    assert out["correct"] is True, out
    assert out["compiles_in_window"] == 0
    assert set(out["compared"]) == set(TINY4_LIMITS)


@pytest.mark.parametrize("broken", ["unchanged", "half_batch",
                                    "no_exchange", "control"])
def test_four_device_broken_step_is_not_correct(tmp_path, broken):
    """A whole run with the look for a chip skipped and the timed step
    broken underneath reports correct false: a step that returns its state
    unchanged, half of the batch left out, the exchange between the
    devices left out.  And the lower-precision control, the reference in
    float8 on the same four devices, fails the comparison."""
    out = _run_cell4(tmp_path, broken=broken)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["compared"].values())


@pytest.mark.parametrize("mutate", ["no_rope", "second_kv_head"])
def test_reference_without_the_program_block_fails(tmp_path, mutate):
    """The comparison is tight enough to see the architecture: a reference
    that drops the rotary positions disagrees past the limits, and one
    built for two key/value heads cannot run on the program's one-head
    weights."""
    out = _run_cell4(tmp_path, mutate=mutate)
    assert out["correct"] is False
    if mutate == "no_rope":
        assert any(c["value"] > c["limit"]
                   for c in out["compared"].values())
    else:
        assert "reshape" in out["error"].lower() or "shape" in \
            out["error"].lower()

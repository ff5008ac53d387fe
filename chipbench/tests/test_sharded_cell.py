"""Tests of the harness on a step sharded over four devices, without a chip.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

The dense transformer's FLOP and parameter counts, the collective readers
on a hand-made module and trace, and, in subprocesses with four host
devices (``--xla_force_host_platform_device_count=4``; this process keeps
its one device): the four-shard server reading against the one-chip
reading of the same values gathered, and whole runs of a two-layer
``granite-34b`` cell on a ``(4, 1)`` mesh, broken (its sound run is
``test_reference_parity.py``'s)."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import flops, harness, scopes, tinycell, trace  # noqa: E402

GRANITE = "granite-34b-fsdp4.default.s2048x8"
CONFIG = "granite-34b-fsdp4"
TINY = tinycell.case(CONFIG)["model"]


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
    """``mqa_lm.param_count`` counts every leaf of the program's parameter
    tree; the forward FLOPs without attention are twice the matmul
    weights that ``ModelConfig.param_count`` counts (all but the
    embedding, which is a gather)."""
    import jax
    import numpy as np
    from chipbench.reference import mqa_lm
    from repro.models import transformer as tr
    cfg = _granite_cfg(**kw)
    m = dict(harness.load_cell(GRANITE).config["model"], **kw)
    abstract = jax.eval_shape(lambda k: tr.init_lm(k, cfg),
                              jax.random.PRNGKey(0))
    assert mqa_lm.param_count(m) == sum(
        int(np.prod(l.shape)) for l in jax.tree.leaves(abstract))
    seq = 2048
    hd = m["d_model"] // m["n_heads"]
    attn = m["n_layers"] * 2 * 2 * (seq // 2) * m["n_heads"] * hd
    fwd = mqa_lm.forward_flops_per_token(m, seq)
    assert (fwd - attn) // 2 == cfg.param_count() - m["vocab"] * m["d_model"]


def test_flops_and_params_of_the_granite_cell():
    from chipbench.reference import mqa_lm, ssd_lm
    conf = harness.load_cell(GRANITE).config
    assert mqa_lm.param_count(conf["model"]) == conf["params"] \
        == 1741338624
    # 3 layers of 2 x 379,060,224 weights + 25,165,824 attention, and the
    # head's 2 x 301,989,888
    assert mqa_lm.forward_flops_per_token(conf["model"], 2048) \
        == 2953838592
    assert ssd_lm.forward_flops_per_token(
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

def test_four_shard_reading_equals_the_gathered_one():
    """A server packed as the program packs it over four devices (each
    device's local shards, leaf by leaf) reads as the one-chip buffer of
    the same values gathered: counts exactly, sums and norms to rounding.
    The norms and biases every device holds count once."""
    got = tinycell.in_subprocess("""
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
    """ % (GRANITE, TINY), 4, 900)
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
    import json
    from pathlib import Path
    from chipbench import check, harness, tinycell
    from chipbench.reference import mqa_lm
    from chipbench.tests import faults
    root = Path(%(root)r)
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
        cell = harness.load_cell(tinycell.CELL, root)
        prog = harness.Program(cell)
        seed = tinycell.SEED
        pool = prog.pool(seed, cell.config["model"]["vocab"], 128)[:3]
        kw = dict(shardings=harness.reference_shardings(prog))
        want = check.reference_readings(cell, seed, pool, prog.abstract, **kw)
        got = check.reference_readings(cell, seed, pool, prog.abstract,
                                       quant=check.fp8, **kw)
        g = check.gaps(got, want)
        compared = {k: {"value": g[k], "limit": v}
                    for k, v in cell.limits.items()}
        out = {"compared": compared, "correct": all(
            c["value"] <= c["limit"] for c in compared.values())}
    else:
        out = tinycell.run(root, faults.BROKEN[%(broken)r])
    print(json.dumps({k: out.get(k) for k in
                      ("correct", "compared", "compiles_in_window", "error")}))
"""


def _run_cell4(tmp_path, broken="sound", mutate=None):
    root = tinycell.make_root(tmp_path, CONFIG)
    return tinycell.in_subprocess(
        RUN % {"root": str(root), "broken": broken, "mutate": mutate}, 4,
        900)


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

"""Mosaic lowering of the server-phase Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) runs the kernel body as plain JAX
and cannot see the TPU's tiling and layout rules.  These tests compile the
kernels of the launch path at a real width for a *described* v5e chip —
the TPU compiler is installed, no chip is attached — and assert the
compiled program really holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest-xdist
worker imports this file."""

from __future__ import annotations

import os
import re
import sys
from math import prod

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.core import packing
from repro.kernels.fairk_update import (fairk_ef_update_pallas,
                                        fairk_stats_update_pallas)
from repro.kernels.sign_mv import sign_from_energy_pallas, sign_mv_pallas
from repro.optim import make_optimizer

D = 64 * 65536          # 64 grid steps of the production block
BLOCK = 65536
D_PACKED = D + 3 * 256  # a packed buffer's length: a partial last block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("residual,sanitize", [(False, False), (True, True)],
                         ids=["plain", "residual-sanitize"])
@pytest.mark.parametrize("stored", [False, True], ids=["f32", "bf16-int8"])
def test_fairk_stats_update_lowers(one_chip, residual, sanitize, stored):
    """``stored``: g_prev and age in the packed server state's dtypes."""
    stride = packing.hist_stride(D_PACKED)
    vec = _sds(one_chip, (D_PACKED,))
    scalar = _sds(one_chip, ())
    g_prev = _sds(one_chip, (D_PACKED,), jnp.bfloat16) if stored else vec
    age = _sds(one_chip, (D_PACKED,), jnp.int8) if stored else vec

    def fn(g, gp, age, tm, ta, res):
        return fairk_stats_update_pallas(
            g, gp, age, tm, ta, residual=res if residual else None,
            block_size=BLOCK, stats_stride=stride, sanitize=sanitize)

    _assert_kernel(fn, vec, g_prev, age, scalar, scalar, vec)


@pytest.mark.parametrize("stride", [1, 64, 256])
def test_fairk_stats_update_lowers_every_sample_stride(one_chip, stride):
    """The histogram sample walks lanes (stride <= 128) or rows (256)."""
    vec = _sds(one_chip, (D,))
    scalar = _sds(one_chip, ())
    _assert_kernel(
        lambda g, gp, age, tm, ta: fairk_stats_update_pallas(
            g, gp, age, tm, ta, block_size=BLOCK, stats_stride=stride),
        vec, vec, vec, scalar, scalar)


def test_fairk_ef_update_lowers(one_chip):
    vec = _sds(one_chip, (D,))
    scalar = _sds(one_chip, ())
    _assert_kernel(
        lambda g, gp, age, tm, ta, res: fairk_ef_update_pallas(
            g, gp, age, tm, ta, residual=res, block_size=BLOCK),
        vec, vec, vec, scalar, scalar, vec)


def test_sign_mv_lowers(one_chip):
    _assert_kernel(lambda v: sign_mv_pallas(v, None, block_k=2048),
                   _sds(one_chip, (1, D)))


def test_sign_from_energy_lowers(one_chip):
    vec = _sds(one_chip, (D,))
    _assert_kernel(lambda e, z: sign_from_energy_pallas(e, z, block_k=2048),
                   vec, vec)


def test_scope_table_of_the_step_for_the_chip(one_chip, monkeypatch):
    """Every scope owns work of its own in the chip's compile of a reduced
    full-server train step (the CPU fuses two of them away)."""
    from test_obs import compile_step
    # the host is a CPU: steer the step onto its chip path (Pallas kernels)
    monkeypatch.setattr(sys.modules["repro.kernels.ops"], "_on_tpu",
                        lambda: True)
    compiled = compile_step(devices=list(one_chip.device_set))
    assert "fairk_update" in compiled.as_text()
    sets = obs.scope_sets(compiled)
    table = obs.scope_table(compiled)
    none = sorted((k, sorted(map(str, s))) for k, s in sets.items()
                  if table[k] is None and s)
    assert set(table.values()) >= set(obs.SCOPES), (
        f"scopes with no instruction of their own: "
        f"{set(obs.SCOPES) - set(table.values())}; top-level instructions "
        f"with None: {none}")


# the leaf classes of mamba2-370m's packed server buffer: minor widths 32,
# 256, 1024 and 2048 and a 1-D leaf, 48 rows a stack (at 8 rows XLA keeps
# the per-leaf slices); the buffer's (d/128, 128) view has a row count that
# is not a multiple of 8, as the cell's 2,878,028 rows
UNPACK_LEAVES = [(48, 32), (48, 256), (48, 1024), (48, 1024, 32),
                 (48, 1024, 256), (48, 1024, 2048), (1024,)]
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s+=\s+(.*)$")


def _instructions(text):
    """(name, opcode, [output dims]) of every instruction of an HLO module
    (a tuple-shaped output gives each of its elements)."""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        end = rest.find(" ")
        if rest.startswith("("):
            depth = 0
            for end, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            end += 1
        opcode = rest[end:].lstrip().split("(", 1)[0]
        dims = [tuple(int(x) for x in d.split(",") if x)
                for d in re.findall(r"\w+\[([\d,]*)\]", rest[:end])]
        yield name, opcode, dims


@pytest.mark.parametrize("stored", ["bf16", "kernel-f32"])
def test_unpack_relays_each_leaf_not_the_buffer(one_chip, stored):
    """The packed update phase (unpack, then AdamW) moves each leaf out of
    its own slot: no op holds the whole buffer in a shape other than the
    stored 1-D buffer or the kernel's (d/128, 128) view, and each leaf is
    relaid once.  ``stored``: ``pending`` (bf16, 1-D) or the kernel's f32
    output."""
    tree = [jax.ShapeDtypeStruct(s, jnp.float32) for s in UNPACK_LEAVES]
    layout = packing.PackedLayout.from_tree(tree)
    d, rows = layout.d_packed, layout.d_packed // 128
    assert rows % 8
    opt = make_optimizer("adamw", 1e-3)

    def update_phase(buf, params, opt_state):
        g = layout.unpack(buf.reshape(d))
        g = jax.tree.map(lambda gt, p: gt.astype(p.dtype), g, params)
        updates, new_opt = opt.update(g, opt_state, params)
        return jax.tree.map(lambda p, u: p + u, params, updates), new_opt

    buf = (_sds(one_chip, (d,), jnp.bfloat16) if stored == "bf16"
           else _sds(one_chip, (rows, 128)))
    params = [_sds(one_chip, s) for s in UNPACK_LEAVES]
    opt_state = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                             jax.eval_shape(opt.init, tree))
    text = jax.jit(update_phase, donate_argnums=(1, 2)).lower(
        buf, params, opt_state).compile().as_text()
    ops = list(_instructions(text))
    whole = [(name, op, dims) for name, op, out in ops for dims in out
             if prod(dims) == d and dims not in ((d,), (rows, 128))]
    assert not whole, f"whole-buffer relayouts: {whole}"
    for shape in UNPACK_LEAVES[:-1]:
        relaid = [name for name, op, out in ops
                  if op == "reshape" and out == [shape]]
        assert len(relaid) <= 1, f"{shape} relaid {len(relaid)} times"

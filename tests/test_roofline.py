"""Roofline HLO parser: loop-aware FLOP/collective accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config
from repro.roofline import analyze_hlo, build_report, model_flops
from repro.roofline.hlo import _shape_bytes, parse_computations


def test_shape_bytes():
    assert _shape_bytes("f32[8,32]{1,0}") == 8 * 32 * 4
    assert _shape_bytes("bf16[2,3]") == 12
    assert _shape_bytes("(s32[], f32[5])") == 4 + 20
    assert _shape_bytes("pred[16]") == 16


def test_scan_flops_multiplied_by_trip_count():
    """cost_analysis counts a while body once; the parser must multiply."""
    trips, n, k, m = 7, 16, 32, 24

    def body(c, w):
        return c @ w, None

    def fn(ws, x):
        out, _ = jax.lax.scan(body, x, ws)
        return out

    compiled = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((trips, k, k), jnp.float32),
        jax.ShapeDtypeStruct((n, k), jnp.float32)).compile()
    parsed = analyze_hlo(compiled.as_text())
    expected = 2 * n * k * k * trips
    assert parsed["flops_per_device"] == pytest.approx(expected, rel=0.01)
    # and confirm the raw cost_analysis really does NOT multiply
    raw = compiled.cost_analysis()["flops"]
    assert raw < expected / 2


def test_dot_flops_unrolled():
    a = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    compiled = jax.jit(lambda a, b: a @ b).lower(a, b).compile()
    parsed = analyze_hlo(compiled.as_text())
    assert parsed["flops_per_device"] == pytest.approx(2 * 8 * 64 * 32,
                                                       rel=0.01)


def test_computation_parsing():
    compiled = jax.jit(lambda x: (x * 2).sum()).lower(
        jax.ShapeDtypeStruct((128,), jnp.float32)).compile()
    comps = parse_computations(compiled.as_text())
    assert any(c.is_entry for c in comps.values())


def test_model_flops_train_6nd():
    cfg = get_config("qwen2.5-32b")
    shape = SHAPES["train_4k"]
    mf = model_flops(cfg, shape)
    expected = 6 * cfg.param_count() * shape.global_batch * shape.seq_len
    assert mf == pytest.approx(expected)


def test_model_flops_moe_uses_active():
    cfg = get_config("arctic-480b")
    mf = model_flops(cfg, SHAPES["train_4k"])
    assert mf < 6 * cfg.param_count() * 256 * 4096 * 0.2


def test_report_structure():
    cfg = get_config("qwen2.5-32b")
    parsed = {"flops_per_device": 1e12, "bytes_per_device": 1e9,
              "collective_bytes_per_device": 1e8,
              "collective_breakdown": {}, "collective_counts": {},
              "n_computations": 3}
    rep = build_report(cfg, SHAPES["train_4k"], "16x16", 256, parsed)
    assert rep.dominant in ("compute", "memory", "collective")
    assert rep.step_time_s == max(rep.compute_s, rep.memory_s,
                                  rep.collective_s)

"""The program's tracing (``repro.obs``): named scopes keep to their
vocabulary, cost nothing in the compiled step and can be read back from
it; host spans nest and stay bounded."""

from __future__ import annotations

import contextlib
import re

import jax
import pytest

from repro import obs
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.launch import train
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step

FULL_SERVER = ["--ef", "--sanitize", "--async-agg", "--adaptive-km"]
STACK_SECTIONS = ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames")


def compile_step(devices=None):
    """A reduced ``mamba2-370m`` train step under the full server
    (``FULL_SERVER``), compiled for ``devices[0]`` (default: this host's
    first device)."""
    args = train.parse_args(["--arch", "mamba2-370m", "--batch", "2",
                             "--seq", "32"] + FULL_SERVER)
    cfg = get_config("mamba2-370m", reduced_variant=True)
    devices = devices or jax.devices()[:1]
    mesh = make_mesh((1, 1), ("data", "model"), devices=devices)
    bundle = make_train_step(cfg, InputShape("custom", 32, 2, "train"),
                             mesh, oac=train.build_oac(args), lr=1e-3)
    specs = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        bundle.input_specs, bundle.in_shardings)
    jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings,
                     donate_argnums=(0, 1, 2))
    with mesh:
        return jitted.lower(*specs).compile()


def _strip_metadata(line: str) -> str:
    out, i = [], 0
    while (j := line.find(", metadata={", i)) >= 0:
        out.append(line[i:j])
        k, depth, quoted = j + len(", metadata={"), 1, False
        while depth:
            ch = line[k]
            if ch == '"' and line[k - 1] != "\\":
                quoted = not quoted
            elif not quoted:
                depth += (ch == "{") - (ch == "}")
            k += 1
        i = k
    return "".join(out) + line[i:]


def without_metadata(text: str) -> str:
    """HLO text without ``metadata={...}`` and the stack-frame tables."""
    lines, in_table = [], False
    for line in text.splitlines():
        if line.strip() in STACK_SECTIONS:
            in_table = True
            continue
        if in_table and (not line.strip() or re.match(r"^\d+ ", line)):
            continue
        in_table = False
        lines.append(_strip_metadata(line))
    return "\n".join(lines)


def test_scopes_cost_nothing(monkeypatch):
    scoped = compile_step().as_text()
    assert "fl.client" in scoped and "fl.fairk" in scoped
    monkeypatch.setattr(obs, "scope",
                        lambda name: contextlib.nullcontext())
    plain = compile_step().as_text()
    assert "fl.client" not in plain
    assert without_metadata(scoped) == without_metadata(plain)


def test_scope_table_gives_each_scope_its_work():
    compiled = compile_step()
    sets = obs.scope_sets(compiled)
    table = obs.scope_table(compiled)
    owned = set(table.values()) - {None}
    none = sorted(k for k, v in table.items() if v is None and sets[k])
    # every scope reaches the compiled step
    assert set().union(*sets.values()) >= set(obs.SCOPES)
    # XLA:CPU fuses the unpacked leaves into AdamW's fusions and the
    # stored-dtype casts into the server stages'; the TPU compile of this
    # step (tests/test_tpu_compile.py) gives those two scopes work of
    # their own
    fused_on_cpu = {"unpack", "server_cast"}
    assert owned >= set(obs.SCOPES) - fused_on_cpu, (
        f"scopes with no instruction of their own: "
        f"{set(obs.SCOPES) - owned}; top-level instructions with None: "
        f"{[(k, sorted(map(str, sets[k]))) for k in none]}")
    mixed = [sets[k] for k in none if len(sets[k]) > 1]
    assert any({"unpack", "adamw"} <= s for s in mixed)
    assert any({"server_cast", "server_stages"} <= s for s in mixed)


def test_leaf_scope_is_the_innermost():
    assert obs.leaf_scope("jit(f)/fl.client/while/body/dot") == "client"
    assert obs.leaf_scope(
        "jit(f)/shard_map/fl.fairk/transpose(jvp(fl.pack))/x") == "pack"
    assert obs.leaf_scope("jit(f)/jit(pack)/add") is None
    assert obs.leaf_scope("jit(f)/fl.nope/add") is None


def test_scope_outside_the_vocabulary_raises():
    with pytest.raises(ValueError, match="unknown scope"):
        obs.scope("controller")
    for name in obs.SCOPES:
        with obs.scope(name):
            pass


def test_spans_nest_and_stay_bounded():
    obs.reset()
    with obs.span("outer") as outer:
        with obs.span("inner") as inner:
            pass
        with obs.span("inner") as inner2:
            pass
    got = obs.spans()
    assert [s.name for s in got] == ["inner", "inner", "outer"]
    assert inner.parent == inner2.parent == outer.id and outer.parent is None
    assert outer.start_ns <= inner.start_ns <= inner.end_ns
    assert inner2.end_ns <= outer.end_ns and outer.ms >= inner.ms >= 0.0
    obs.reset()
    assert obs.spans() == []
    for _ in range(obs.SPAN_CAPACITY + 10):
        with obs.span("tick"):
            pass
    got = obs.spans()
    assert len(got) == obs.SPAN_CAPACITY
    assert got[-1].id - got[0].id == obs.SPAN_CAPACITY - 1
    obs.reset()


def test_span_closes_on_error():
    obs.reset()
    with pytest.raises(RuntimeError):
        with obs.span("outer"):
            raise RuntimeError("boom")
    with obs.span("after") as after:
        pass
    assert after.parent is None
    assert [s.name for s in obs.spans()] == ["outer", "after"]
    obs.reset()


HAND_MADE_HLO = '''HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%region_0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="reduce_sum"}
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %x = f32[8]{0} get-tuple-element(%p), index=1
  %y = f32[8]{0} multiply(%x, %x), metadata={op_name="jit(f)/fl.client/while/body/mul"}
  %w = f32[8]{0} add(%y, %y), metadata={op_name="reduce_window_sum"}
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[8]{0}) tuple(%i, %w)
}

%cond (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %c = pred[] constant(false)
}

%fused_computation (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %s = f32[8]{0} slice(%q), slice={[0:8]}, metadata={op_name="jit(f)/fl.unpack/slice"}
  ROOT %m = f32[8]{0} multiply(%s, %s), metadata={op_name="jit(f)/fl.adamw/mul"}
}

ENTRY %main (arg: f32[8]) -> f32[8] {
  %arg = f32[8]{0} parameter(0)
  %init = (s32[], f32[8]{0}) tuple(%zero, %arg)
  %while.1 = (s32[], f32[8]{0}) while(%init), condition=%cond, body=%body, metadata={op_name="jit(f)/fl.client/while"}
  %g = f32[8]{0} get-tuple-element(%while.1), index=1
  %copy.2 = f32[8]{0} copy(%g)
  %r = f32[] reduce(%copy.2, %c0), dimensions={0}, to_apply=%region_0, metadata={op_name="jit(f)/fl.fairk/reduce_sum"}
  %fusion.3 = f32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation
  ROOT %out = f32[8]{0} add(%fusion.3, %fusion.3), metadata={op_name="jit(f)/add"}
}
'''


class _Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


def test_scope_table_rules_on_hand_made_hlo():
    """A loop whose body holds an op rewritten from a combiner (un-rooted
    name) keeps its scope; a reduction keeps its caller's scope; an XLA
    copy with no op_name and a fusion of two scopes are None; data-free
    instructions are None with an empty set."""
    compiled = _Compiled(HAND_MADE_HLO)
    sets = obs.scope_sets(compiled)
    assert obs.scope_table(compiled) == {
        "arg": None, "init": None, "while.1": "client", "g": None,
        "copy.2": None, "r": "fairk", "fusion.3": None, "out": None}
    assert sets["fusion.3"] == {"unpack", "adamw"}
    assert sets["out"] == {None}
    assert sets["copy.2"] == sets["g"] == sets["arg"] == frozenset()

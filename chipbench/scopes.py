"""Which layer each device op of a traced round belongs to, by the
program's own named scopes (``repro.obs``).

The TPU trace names each op by its HLO instruction and carries no name
stack; the scopes live in the compiled module's ``op_name`` metadata.  So
``table(cell)`` compiles the cell's step once per process, from the same
``harness.Program(cell)`` the window ran and at the window's argument
shapes and shardings (the persistent compilation cache then serves the
executable the window ran), and maps each top-level instruction to its
scope with ``repro.obs.scope_table``.  A program without ``repro.obs``
has no scopes: ``table`` returns None and every scope reader reads
nothing.

A round's top-level ops fall into exactly one of: the FAIR-k Pallas kernel
(``trace.is_fairk_kernel``), one scope of ``METRICS``, or ``unattributed``
(an op that mixes scopes or holds work outside every scope, or one the
table does not know)."""

from __future__ import annotations

from typing import Dict, Optional

from chipbench import trace

# per-layer metric -> the scope whose top-level ops it sums
METRICS = {"client_scope_ms": "client", "pack_ms": "pack",
           "server_stages_ms": "server_stages", "fairk_other_ms": "fairk",
           "state_cast_ms": "server_cast", "unpack_ms": "unpack",
           "adamw_ms": "adamw"}

_tables: Dict[str, Optional[Dict[str, Optional[str]]]] = {}


def instruction(ev: trace.Event) -> str:
    """The HLO instruction name of a trace op event."""
    return ev[0].split(" = ")[0].strip().lstrip("%")


def _compile(cell):
    """The cell's train step compiled at the window's argument shapes and
    shardings."""
    import jax
    import jax.numpy as jnp
    from chipbench import harness
    from repro.configs.base import InputShape
    from repro.launch import sharding as shlib
    from repro.launch import steps
    prog = harness.Program(cell)
    tr = cell.traffic
    shape = InputShape("custom", tr["seq_len"], tr["batch"], "train")
    specs = (prog.abstract,
             jax.eval_shape(prog.opt.init, prog.abstract),
             steps.abstract_server_state(
                 prog.abstract, mesh=prog.mesh,
                 p_specs=shlib.param_pspecs(prog.abstract, prog.cfg,
                                            prog.mesh),
                 oac=prog.oac),
             steps.train_input_specs(prog.cfg, shape, prog.n_micro,
                                     prog.micro_batch),
             jax.ShapeDtypeStruct((), jnp.int32))
    args = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        specs, prog.in_sh)
    with prog.mesh:
        return prog.jitted.lower(*args).compile()


def table(cell) -> Optional[Dict[str, Optional[str]]]:
    """{instruction: scope | None} of the cell's compiled step, built once
    per process; None where the program has no scopes."""
    if cell.name in _tables:
        return _tables[cell.name]
    try:
        from repro import obs
    except ImportError:
        _tables[cell.name] = None
        return None
    import jax
    compiled = _compile(cell)
    if "ENTRY" not in compiled.as_text():
        # an executable loaded from the persistent cache may come without
        # its module's text: compile once more with the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            compiled = _compile(cell)
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
    _tables[cell.name] = obs.scope_table(compiled)
    return _tables[cell.name]


def scope_of(tab: Dict[str, Optional[str]], ev: trace.Event
             ) -> Optional[str]:
    """The scope that owns a top-level op alone, or None."""
    return tab.get(instruction(ev))


def scope_ms(ctx, scope: str) -> Optional[float]:
    """Device ms per round of the top-level ops ``scope`` owns alone,
    other than the FAIR-k kernel; 0.0 where it owns none (its work was
    fused elsewhere), None without a table."""
    tab = table(ctx.cell)
    if tab is None:
        return None
    return ctx.per_round_ms(lambda ev: not trace.is_fairk_kernel(ev)
                            and scope_of(tab, ev) == scope) or 0.0


def unattributed_ms(ctx) -> Optional[float]:
    """Device ms per round of the top-level ops no single scope owns."""
    tab = table(ctx.cell)
    if tab is None:
        return None
    return ctx.per_round_ms(lambda ev: not trace.is_fairk_kernel(ev)
                            and scope_of(tab, ev) is None) or 0.0

"""The program's tracing: named scopes on the device program, host spans.

``scope(name)`` marks where a layer's device work is traced.  It is a
``jax.named_scope`` under a fixed vocabulary (``SCOPES``), so it reaches
the compiled HLO only as the ``op_name`` metadata of the instructions it
holds (``.../fl.<name>/...``): instruction names, fusion and the device
program are unchanged.  ``scope_table`` reads it back from a compiled
executable and gives each top-level instruction of the entry computation
the one scope all its work carries, or None where XLA fused work of
several scopes (or of none) into it.

``span(name)`` times a piece of host work.  It is a
``jax.profiler.TraceAnnotation``, so it lands in any profiler trace beside
the device ops, and also a ``Span`` record (``time.time_ns`` clock, parent
id of the enclosing span) in a bounded in-memory buffer that ``spans()``
reads and ``reset()`` clears.  Nothing is written to disk.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import re
import threading
import time
from typing import Dict, FrozenSet, Iterator, List, Optional

import jax

# the layers of a round, in the order the step runs them
SCOPES = ("client", "pack", "server_stages", "fairk", "server_cast",
          "unpack", "adamw")
SCOPE_PREFIX = "fl."
_ROOTED = "jit("        # how the op_name of traced work starts
SPAN_CAPACITY = 4096

# instructions that move no data of their own: they neither give nor take
# a scope
_NEUTRAL_OPS = frozenset({"parameter", "constant", "tuple",
                          "get-tuple-element", "bitcast"})


def scope(name: str):
    """A named scope of the step's device program; ``name`` is one of
    ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; the vocabulary is "
                         f"{SCOPES}")
    return jax.named_scope(SCOPE_PREFIX + name)


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Span:
    """One finished (or open) host span; times are ``time.time_ns()``."""
    id: int
    name: str
    parent: Optional[int]
    start_ns: int = 0
    end_ns: Optional[int] = None

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6


_buffer: collections.deque = collections.deque(maxlen=SPAN_CAPACITY)
_ids = itertools.count(1)
_open = threading.local()


@contextlib.contextmanager
def span(name: str) -> Iterator[Span]:
    """Time the enclosed host work as one span; yields its record, whose
    ``ms`` is set on exit."""
    if not hasattr(_open, "stack"):
        _open.stack = []
    stack = _open.stack
    rec = Span(next(_ids), name, stack[-1].id if stack else None)
    stack.append(rec)
    try:
        with jax.profiler.TraceAnnotation(name):
            rec.start_ns = time.time_ns()
            try:
                yield rec
            finally:
                rec.end_ns = time.time_ns()
    finally:
        stack.pop()
        _buffer.append(rec)


def spans() -> List[Span]:
    """The finished spans still in the buffer, oldest first."""
    return list(_buffer)


def reset() -> None:
    _buffer.clear()


# ---------------------------------------------------------------------------
# reading the scopes back from a compiled executable
# ---------------------------------------------------------------------------

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_SCOPE_IN_NAME = re.compile(r"(?<![\w.])" + re.escape(SCOPE_PREFIX)
                            + r"(\w+)")
_REF = re.compile(r"%([\w.\-]+)")


def leaf_scope(op_name: str) -> Optional[str]:
    """The innermost scope of ``SCOPES`` an ``op_name`` path holds."""
    found = [s for s in _SCOPE_IN_NAME.findall(op_name) if s in SCOPES]
    return found[-1] if found else None


def _opcode(rest: str) -> str:
    """The opcode of an instruction line's right-hand side (after the
    shape, which may be a parenthesised tuple)."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
    tail = rest[i:].lstrip()
    return tail[:tail.find("(")] if "(" in tail else tail


def _parse(text: str):
    """(entry computation name, {computation: [(instruction, opcode,
    op_name | None, [referenced names])]})."""
    comps: Dict[str, list] = {}
    entry, cur = None, None
    for line in text.splitlines():
        if not line.strip():
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            name, rest = m.groups()
            op = _OP_NAME.search(rest)
            comps[cur].append((name, _opcode(rest),
                               op.group(1) if op else None,
                               _REF.findall(rest)))
            continue
        h = _HEADER.match(line)
        if h and not line.startswith((" ", "\t")):
            cur = h.group(2)
            comps[cur] = []
            if h.group(1):
                entry = cur
    return entry, comps


def scope_sets(compiled) -> Dict[str, FrozenSet[Optional[str]]]:
    """For each top-level instruction of the entry computation, the set of
    leaf scopes of the work it holds (its own and that of every
    computation it calls, transitively); None in the set stands for work
    outside every scope.  Data-free instructions add nothing, nor do
    those whose ``op_name`` is missing (made by XLA from nothing the
    program traced) or not rooted in the jitted function (the scalar
    combiners of reductions and scatters, lowered without the caller's
    name stack, and ops XLA rewrote from them)."""
    entry, comps = _parse(compiled.as_text())
    if entry is None:
        raise ValueError("no entry computation in the compiled module")
    memo: Dict[str, FrozenSet[Optional[str]]] = {}

    def of_instr(opcode, op_name, refs, seen):
        out = set()
        if opcode not in _NEUTRAL_OPS and (op_name or "").startswith(
                _ROOTED):
            out.add(leaf_scope(op_name))
        for r in refs:
            if r in comps:
                out |= of_comp(r, seen)
        return out

    def of_comp(comp, seen):
        if comp in memo:
            return memo[comp]
        if comp in seen:
            return frozenset()
        seen = seen | {comp}
        out = set()
        for _, opcode, op_name, refs in comps[comp]:
            out |= of_instr(opcode, op_name, refs, seen)
        memo[comp] = frozenset(out)
        return memo[comp]

    return {name: frozenset(of_instr(opcode, op_name, refs, {entry}))
            for name, opcode, op_name, refs in comps[entry]}


def scope_table(compiled) -> Dict[str, Optional[str]]:
    """{top-level instruction name: its scope | None}: an instruction gets
    a scope only when all the work it holds carries that one scope; one
    that mixes scopes, or holds work outside every scope, maps to None
    (XLA fuses across scope boundaries, and that time belongs to neither
    side alone)."""
    return {name: (next(iter(s)) if len(s) == 1 else None)
            for name, s in scope_sets(compiled).items()}

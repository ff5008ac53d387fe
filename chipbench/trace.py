"""From a profiler trace to per-layer numbers.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into plain lists:
for each TPU plane the events of its ``XLA Ops`` line (the HLO instruction
text, cut to ``NAME_CHARS``, its start and duration in ns) and the
harness's host spans (``feed``, ``dispatch``, ``block``).  The TPU trace
carries no name stack, but its op events nest: a ``while`` op's interval
holds the ops of its body.  ``Context`` works on the top-level ops: the
busy union and idle share, summed time by a predicate, and the breakdown;
``Context.exposed_per_round_ms`` alone looks inside the loops, at every op
event.  The metric readers under ``metrics/`` call it."""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from typing import Any, Callable, Dict, List, Optional, Tuple

HOST_SPANS = ("feed", "dispatch", "block")
OP_LINE = "XLA Ops"
NAME_CHARS = 100
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")

Event = Tuple[str, int, int]


def load(trace_dir: str) -> Dict[str, Any]:
    """The device op events and host spans of the one trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[plane.name] = [
                        (e.name[:NAME_CHARS], e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name in HOST_SPANS]
    if not devices:
        raise RuntimeError("the trace holds no TPU op line")
    return {"devices": devices, "host": host}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
            ) -> float:
    """Length of the overlap of two lists of sorted disjoint intervals."""
    out, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        out += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def top_level(events: List[Event]) -> List[Event]:
    """The events no other event's interval holds."""
    out, end = [], float("-inf")
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        if ev[1] >= end:
            out.append(ev)
            end = ev[1] + ev[2]
        else:
            end = max(end, ev[1] + ev[2])
    return out


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; an unknown kind is an error."""
    table = json.loads(open(PEAKS).read())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


# -- which op belongs to which layer ------------------------------------------

def is_fairk_kernel(ev: Event) -> bool:
    """The fused FAIR-k Pallas call (named after its wrapper)."""
    return "fairk" in ev[0].split(" = ")[0].lower()


def in_client_phase(ev: Event) -> bool:
    """A top-level loop: the microbatch scan of the clients' forward and
    backward (the step's only loop outside the layer scans it holds)."""
    return ev[0].startswith("%while")


def in_update_phase(ev: Event) -> bool:
    """Every other top-level op of the step: the server phase and AdamW
    under the ``shard_map`` update, and the gradient's scaling."""
    return not in_client_phase(ev)


# HLO opcodes that move data between devices; each also has an async
# ``-start`` / ``-done`` pair
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
               "collective-permute")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")
_CALLS = re.compile(r"calls=%?([\w.-]+)")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.-]+)\s+\(.*\{\s*$")


def _opcode(rhs: str) -> str:
    """The opcode of an instruction's right-hand side: the first word
    called after its result shape."""
    m = _OPCODE.search(rhs.split(", metadata=")[0])
    return m.group(1) if m else ""


def _is_collective_opcode(op: str) -> bool:
    return op in COLLECTIVES or any(op in (c + "-start", c + "-done")
                                    for c in COLLECTIVES)


# opcodes whose interval holds the ops of another computation, and those
# that compute on the matrix unit
LOOPS = ("while", "conditional", "call")
MATMULS = ("dot", "convolution")


def instruction_kinds(hlo_text: str) -> Dict[str, str]:
    """The kind of each instruction of a compiled module (``as_text()``):
    ``collective`` where it only moves data between devices (a collective
    opcode or its async start or done, or a fusion or async wrapper whose
    called computations hold one and no dot or convolution, at any depth),
    ``collective_matmul`` for a fusion that holds both, ``loop`` for a
    ``while``, ``conditional`` or ``call``, and ``work`` for the rest."""
    comps: Dict[str, List[Tuple[str, str, List[str]]]] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and cur is not None:
            rhs = m.group(2)
            comps[cur].append((m.group(1), _opcode(rhs),
                               _CALLS.findall(rhs)))
            continue
        c = _COMP.match(line)
        if c:
            cur = c.group(1)
            comps[cur] = []
    held: Dict[str, set] = {}

    def holds(name: str, seen=()) -> set:
        """What a computation holds, through its fusions: 'collective',
        'matmul'."""
        if name not in held:
            out = set()
            for _, op, calls in comps.get(name, ()):
                if _is_collective_opcode(op):
                    out.add("collective")
                elif op in MATMULS:
                    out.add("matmul")
                elif op in ("fusion", "async-start"):
                    for c in calls:
                        if c not in seen:
                            out |= holds(c, seen + (name,))
            held[name] = out
        return held[name]

    kinds = {}
    for body in comps.values():
        for name, op, calls in body:
            inner = (set().union(*(holds(c) for c in calls))
                     if op in ("fusion", "async-start", "async-done")
                     else set())
            if _is_collective_opcode(op) or inner == {"collective"}:
                kinds[name] = "collective"
            elif inner == {"collective", "matmul"}:
                kinds[name] = "collective_matmul"
            elif op in LOOPS:
                kinds[name] = "loop"
            else:
                kinds[name] = "work"
    return kinds


@dataclasses.dataclass
class Context:
    """What a metric reader sees of one traced window."""
    cell: Any
    trace: Dict[str, Any]
    rounds: int
    chips: int
    device_kind: str

    def __post_init__(self):
        self._top: Dict[str, List[Event]] = {}

    # -- the window -----------------------------------------------------------
    def bounds(self) -> Tuple[int, int]:
        """[start, end) of the traced window in the trace's clock: the
        first round's start to the last traced round's end."""
        spans = self.trace["host"]
        if not spans:
            raise RuntimeError("the trace holds no host round spans")
        return (min(s for _, s, _ in spans),
                max(s + d for _, s, d in spans))

    def trace_window_s(self) -> float:
        lo, hi = self.bounds()
        return (hi - lo) / 1e9

    def ops(self, dev: str) -> List[Event]:
        """Top-level op events of one device that overlap the window."""
        if dev not in self._top:
            lo, hi = self.bounds()
            self._top[dev] = [e for e in top_level(self.trace["devices"][dev])
                              if e[1] + e[2] > lo and e[1] < hi]
        return self._top[dev]

    def busy_s(self) -> float:
        """Seconds in which an op ran, inside the window, averaged over the
        devices."""
        lo, hi = self.bounds()
        devs = list(self.trace["devices"])
        return sum(max(0, min(e, hi) - max(s, lo)) for d in devs
                   for s, e in union([(e[1], e[1] + e[2])
                                      for e in self.ops(d)])
                   ) / len(devs) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.trace_window_s()

    # -- op sums --------------------------------------------------------------
    def per_round_ms(self, pred: Callable[[Event], bool]) -> Optional[float]:
        """Summed device time of the top-level ops ``pred`` picks, per round
        and per device, in ms; None where no op matches."""
        devs = list(self.trace["devices"])
        evs = [e for d in devs for e in self.ops(d) if pred(e)]
        if not evs:
            return None
        return sum(e[2] for e in evs) / 1e6 / self.rounds / len(devs)

    def exposed_per_round_ms(self, pred: Callable[[Event], bool],
                             work: Callable[[Event], bool]
                             ) -> Optional[float]:
        """Device time per round and per device, in ms, in which an op
        event ``pred`` picks runs, at any depth (inside loops too), and no
        event ``work`` picks does: the union of the first's intervals in
        the window less its overlap with the union of the second's.  None
        where ``pred`` picks no op."""
        lo, hi = self.bounds()
        devs = list(self.trace["devices"])
        total, found = 0, False
        for d in devs:
            evs = [(max(e[1], lo), min(e[1] + e[2], hi), e)
                   for e in self.trace["devices"][d]
                   if e[1] + e[2] > lo and e[1] < hi]
            mine = union([(s, e) for s, e, ev in evs if pred(ev)])
            busy = union([(s, e) for s, e, ev in evs
                          if work(ev) and not pred(ev)])
            found = found or bool(mine)
            total += sum(e - s for s, e in mine) - overlap(mine, busy)
        if not found:
            return None
        return total / 1e6 / self.rounds / len(devs)

    # -- breakdown ------------------------------------------------------------
    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The top-level device ops that took most time, and the longest
        idle gaps, each labelled by the host span that overlaps it most."""
        sums: Dict[str, int] = {}
        devs = list(self.trace["devices"])
        for d in devs:
            for e in self.ops(d):
                sums[e[0]] = sums.get(e[0], 0) + e[2]
        ops = sorted(sums.items(), key=lambda kv: -kv[1])[:top]
        lo, hi = self.bounds()
        gaps = []
        for d in devs:
            prev = lo
            for s, e in union([(e[1], e[1] + e[2]) for e in self.ops(d)]
                              ) + [(hi, hi)]:
                if s > prev:
                    gaps.append((prev, min(s, hi)))
                prev = max(prev, e)
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        labelled = []
        for s, e in gaps[:top]:
            best, label = 0, "none"
            for name, hs, hd in self.trace["host"]:
                ov = min(e, hs + hd) - max(s, hs)
                if ov > best:
                    best, label = ov, name
            labelled.append([label, (e - s) / 1e9])
        return {"device_ops": [[k, v / 1e9 / len(devs)] for k, v in ops],
                "idle_gaps": labelled}

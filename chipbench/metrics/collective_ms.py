"""collective_ms: device time per round and per device in which the step
waits on an exchange between chips and computes nothing, at any depth of
the step (inside the microbatch and layer scans too): the intervals of the
ops that only move data (a collective, its async start or done, the fused
async collectives) less their overlap with any op that computes
(``trace.instruction_kinds``).  A collective matmul, which fuses a gather
with the matmul it feeds, counts as compute.  On one chip there are no
exchanges and it reads nothing."""

from chipbench import scopes, trace


def read(ctx):
    if ctx.chips == 1:
        return None
    kinds = trace.instruction_kinds(scopes.compiled_text(ctx.cell))
    kind = lambda ev: kinds.get(scopes.instruction(ev))
    return ctx.exposed_per_round_ms(
        lambda ev: kind(ev) == "collective",
        lambda ev: kind(ev) in ("work", "collective_matmul"))

"""update_phase_ms: device time per round of the ops under the
``shard_map`` update (the FAIR-k server phase and AdamW)."""

from chipbench import trace


def read(ctx):
    return ctx.per_round_ms(trace.in_update_phase)

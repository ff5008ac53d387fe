"""fairk_roofline: the least bytes the round's selection needs at the
persisted dtypes (``flops.fairk_bytes``, over this chip's share of the
coordinates), at the chip's HBM peak, over the kernel's device time."""

from chipbench import flops, trace


def read(ctx):
    ms = ctx.per_round_ms(trace.is_fairk_kernel)
    if not ms:
        return None
    coords = ctx.cell.config["params"] / ctx.chips
    ef = "--ef" in ctx.cell.traffic["server_flags"]
    least_s = (flops.fairk_bytes(coords, ef)
               / trace.peaks(ctx.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms / 1e3)

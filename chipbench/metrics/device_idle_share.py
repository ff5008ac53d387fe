"""device_idle_share: 1 - (union of device op intervals) / traced window,
averaged over the chips, in %."""


def read(ctx):
    return 100.0 * ctx.idle_share()

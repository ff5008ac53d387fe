"""unpack_ms: device time per round of the top-level ops the
``unpack`` scope owns alone: slicing the merged flat gradient back into
leaves (``core/packing.py``)."""

from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "unpack")

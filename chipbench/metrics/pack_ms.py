"""pack_ms: device time per round of the top-level ops the ``pack``
scope owns alone: packing the fresh gradient tree into the flat buffer
(``core/packing.py``)."""

from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "pack")

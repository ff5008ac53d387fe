"""fairk_other_ms: device time per round of the top-level ops the
``fairk`` scope owns alone other than the Pallas kernel (which
``fairk_kernel_ms`` reads): the threshold arithmetic and the relayouts of
the kernel's tiles (``core/engine.py``, ``kernels/``)."""

from chipbench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "fairk")

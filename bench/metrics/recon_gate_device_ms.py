"""Device time per run of the exchange gate's scoring kernel: the Mosaic
custom-calls under the ``recon_gate`` scope (``kernels/ops.py``), the
kernel alone and not its wrapper's pads."""
from bench import scopes


def read(ctx):
    return scopes.kernel_ms(ctx, "recon_gate")

"""Device time per run of the K-means assignment kernel: the Mosaic
custom-calls under the ``kmeans_assign`` scope (``kernels/ops.py``), the
kernel alone and not its wrapper's pads and norms."""
from bench import scopes


def read(ctx):
    return scopes.kernel_ms(ctx, "kmeans_assign")

"""Device time per run of the ``cluster`` scope: federated PCA (the
3072x3072 ``eigh`` in CIFAR) and K-means (``core/pipeline.py::
_cluster_impl``), in segment 0 and at every re-cluster in the chunk."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "cluster")

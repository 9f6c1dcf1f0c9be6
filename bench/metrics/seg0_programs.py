"""Device programs launched per run inside segment 0: the ``XLA Modules``
events that start under the ``pipeline`` and ``segment`` span
annotations."""
from bench import scopes


def read(ctx):
    return scopes.seg0_programs(ctx)

"""Device time per run of the ``discover`` scope: the RL episodes
(``core/qlearning.py::_discover_impl``), all of them in segment 0 and
the warm-started bursts in the chunk."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "discover")

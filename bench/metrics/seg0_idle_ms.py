"""Device idle time per run inside segment 0: the window's idle time under
the host annotations of the program's ``pipeline`` and ``segment`` spans
(``core/pipeline.py::run_pipeline``, ``dynamics/orchestrator.py``), where
the eager segment 0 dispatches its programs one by one."""
from bench import scopes


def read(ctx):
    return scopes.seg0_idle_ms(ctx)

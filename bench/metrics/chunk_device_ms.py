"""Device time per run of the fused segment engine: the XLA module jitted
from ``dynamics/orchestrator.py::_chunk_fn`` (env step, re-cluster, RL
burst, re-exchange and FL of segments 1..)."""

MODULE = "_chunk_fn"


def read(ctx):
    red = ctx["trace"]
    if red.n_runs <= 0:
        return None
    ns = red.module_ns(lambda name: MODULE in name)
    return ns / 1e6 / red.n_runs if ns > 0 else None

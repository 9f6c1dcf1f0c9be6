"""Device time per run of every module other than the scan chunk: the
eager segment 0 (pretrain, clustering, full RL discovery, exchange, first
FL segment) and the run's host-side glue."""

MODULE = "_chunk_fn"


def read(ctx):
    red = ctx["trace"]
    if red.n_runs <= 0:
        return None
    ns = red.module_ns(lambda name: MODULE not in name)
    return ns / 1e6 / red.n_runs if ns > 0 else None

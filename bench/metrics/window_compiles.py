"""Backend compiles inside the measured window, from the program's own
compile counter (``repro.obs.counters``); every program should come from
the warm-up, so this reads 0."""


def read(ctx):
    return ctx["compiles"]

"""Share of the device's busy time spent inside Mosaic (Pallas) custom
calls: ``kmeans_assign`` and ``recon_gate`` on this path."""


def read(ctx):
    red = ctx["trace"]
    if red.busy_ns <= 0 or red.custom_ns <= 0:
        return None
    return 100.0 * red.custom_ns / red.busy_ns

"""Device time per run of the ``fl`` scope: FL local steps, FedAvg and the
global eval (``fl/trainer.py::_round_body``, ``::_eval_loss_fn``), in
segment 0's round programs and in the scan chunk alike."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "fl")

"""Device time per run of the ``exchange`` scope: AE pretrain, reserve
selection, the gate and the transfer (``core/exchange.py::
_pretrain_step``, ``::select_reserves_device``, ``::_exchange_device``)."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "exchange")

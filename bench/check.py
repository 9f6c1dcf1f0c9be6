"""The comparison that decides ``correct``.

One federation run of the window, drawn from the seed, is followed by the
configuration's plain reference from the run's key (see
``bench/references/``), once the window has closed.  Compared, each against
the limit of its own in ``bench/limits/<cell>.json``:

``eval_first``  relative gap of the first eval loss (after the first FedAvg
                round) between the run and the reference
``eval_worst``  the worst relative gap over every eval of the run
``change_worst_leaf``
                the worst leaf's gap between the norms of the global
                weights' change over the run (final minus initial), the
                run's against the reference's, over the larger of that
                leaf's and the median leaf's reference norm
``moved``       data points the run's exchanges delivered; the reference
                says 0 (every client is full to its cap under ``drop``)
``offline``     client-segments the run had unavailable; the reference says
                0 (the traffic has no churn)

Leaves whose first gradient in the reference is under a thousandth of the
median leaf's are left out of ``change_worst_leaf``: Adam moves them by
round-off alone.  A number whose limit is ``null`` is reported and not
compared.
"""
from __future__ import annotations

import jax
import numpy as np

NUMBERS = ("eval_first", "eval_worst", "change_worst_leaf", "moved",
           "offline")


def _leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for p, v in flat}


def compare(run: dict, ref: dict, n_clients: int) -> dict:
    """``run``: the compared run's ``eval_loss``, ``final`` global weights,
    per-segment ``moved`` and ``n_available``.  ``ref``: the reference's
    ``run`` output.  Returns the numbers above."""
    r_eval = np.asarray(ref["eval_loss"], np.float64)
    p_eval = np.asarray(run["eval_loss"], np.float64)
    if p_eval.shape != r_eval.shape:
        raise ValueError(f"the run evaluated {p_eval.shape[0]} times, the "
                         f"reference {r_eval.shape[0]}")
    gaps = np.abs(p_eval - r_eval) / np.abs(r_eval)
    init, r_fin, p_fin = (_leaves(ref["init"]), _leaves(ref["final"]),
                          _leaves(run["final"]))
    grad = {k: float(np.linalg.norm(v))
            for k, v in _leaves(ref["first_grad"]).items()}
    g_med = float(np.median(list(grad.values())))
    kept = [k for k in init if grad[k] >= 1e-3 * g_med]
    r_chg = {k: float(np.linalg.norm(r_fin[k] - init[k])) for k in kept}
    p_chg = {k: float(np.linalg.norm(p_fin[k] - init[k])) for k in kept}
    c_med = float(np.median(list(r_chg.values())))
    change = max(abs(p_chg[k] - r_chg[k]) / max(r_chg[k], c_med, 1e-30)
                 for k in kept)
    n_seg = len(run["n_available"])
    return {
        "eval_first": float(gaps[0]),
        "eval_worst": float(np.max(gaps)),
        "change_worst_leaf": float(change),
        "moved": int(np.sum(run["moved"])),
        "offline": int(n_clients * n_seg - np.sum(run["n_available"])),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): correct when every number
    with a limit is finite and at or under it."""
    table, ok = {}, True
    for name in NUMBERS:
        value, limit = numbers[name], limits[name]
        table[name] = {"value": value, "limit": limit}
        if limit is not None and not (np.isfinite(value) and value <= limit):
            ok = False
    return ok, table

"""Unsupervised FL trainer (paper Sec. IV-C + Algorithm 2, Sec. V setup).

All N clients train their own autoencoder replica with local SGD on
reconstruction MSE; every ``tau_a`` minibatch iterations the server
aggregates (FedAvg parameter mean / FedSGD gradient mean / FedProx with a
proximal pull toward the global model) and broadcasts back.  Stragglers
keep training locally but are excluded from aggregation (paper Fig. 6).

Vectorisation: client parameters are one stacked pytree with a leading
client axis, client datasets are padded into one (N, max_n, H, W, C) array,
and a whole aggregation round is a single jitted `lax.scan`.  Pass
``rules`` (:class:`repro.sharding.ShardingRules`) and the client axis
shards over the data-parallel mesh product: local steps stay shard-local,
the masked FedAvg/FedSGD mean lowers to an all-reduce over the client axis,
and the broadcast back is a replicated constraint — the collective
structure of the real system.  The round's carry is donated
(``donate_argnums``), so segmented training updates parameters and Adam
moments in place instead of double-buffering them every round.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro import sharding as sh
from repro.core.batching import as_client_data, stack_clients  # noqa: F401
from repro.models import autoencoder as ae


@dataclasses.dataclass(frozen=True)
class FLConfig:
    scheme: str = "fedavg"        # fedavg | fedsgd | fedprox
    total_iters: int = 1500       # minibatch iterations (paper Sec. V)
    tau_a: int = 10               # aggregation interval
    batch_size: int = 64
    lr: float = 5e-2
    prox_mu: float = 0.1          # FedProx proximal coefficient
    eval_every: int = 50
    seed: int = 0
    # Local update rule.  The paper's Eq. 8 is plain SGD; on the synthetic
    # stand-in data plain SGD cannot reach the class-coverage-sensitive
    # regime within CPU budget (see EXPERIMENTS.md §Repro deviations), so
    # benchmarks use per-parameter adaptive steps ("adam") applied to every
    # method equally — relative method orderings are what the paper claims.
    local_opt: str = "adam"       # "sgd" (Eq. 8 faithful) | "adam"
    adam_b1: float = 0.9
    adam_b2: float = 0.99
    adam_eps: float = 1e-8
    adam_lr: float = 1e-3
    # Minimum participation floor: if fewer than ceil(min_participation * N)
    # clients are up for aggregation (crash pulse, regional outage), the
    # round carries the last good global model forward — clients keep
    # training locally — instead of averaging over a near-empty mask (a
    # 1-client "global" model would yank the whole federation toward one
    # client's data).  0.0 (default) disables the floor: bit-identical to
    # the pre-floor trainer.
    min_participation: float = 0.0


class FLCarry(NamedTuple):
    """Full training state threaded through ``fl_train`` segments.

    The online orchestrator (``repro.dynamics``) trains in segments —
    FL rounds interleaved with channel evolution and graph re-discovery —
    by passing the previous segment's carry back in.  Resumed training is
    bit-for-bit identical to one uninterrupted run because round keys are
    derived from the *total* horizon (``cfg.total_iters``), not from the
    segment length.

    A carry handed to ``fl_train`` as ``init_carry`` is *consumed*: the
    round function donates its buffers to the next round, so the passed-in
    arrays are invalid afterwards.  Hold on to the returned
    ``FLResult.carry`` instead."""
    client_params: object        # stacked pytree, leading client axis
    global_params: object        # server model
    mu: object                   # Adam first moments (stacked)
    nu: object                   # Adam second moments (stacked)
    step: jax.Array              # () float32, local iteration counter


class FLResult(NamedTuple):
    """``global_params``/``client_params`` alias the buffers of ``carry`` —
    once ``carry`` is handed to a later ``fl_train(init_carry=...)`` call
    (which donates it), this result's params are deleted with it.  Read or
    copy them first; eval_* are host arrays and always survive."""
    global_params: object
    eval_iters: np.ndarray       # (n_evals,)
    eval_loss: np.ndarray        # (n_evals,) global reconstruction loss
    client_params: object
    carry: Optional[FLCarry] = None  # resume state for the next segment


def _broadcast(params, n):
    return jax.tree.map(lambda p: jnp.broadcast_to(p[None], (n,) + p.shape),
                        params)


def _masked_mean(tree, mask):
    w = mask / jnp.maximum(jnp.sum(mask), 1.0)
    return jax.tree.map(
        lambda p: jnp.tensordot(w, p.astype(jnp.float32), axes=1).astype(p.dtype),
        tree)


@jax.named_scope("fl")
def _round_body(cfg: FLConfig, ae_cfg, carry, data, sizes, agg_mask,
                keys_round, rules=None):
    """One aggregation round: ``tau_a`` scanned local iterations + a masked
    parameter (or per-iteration gradient) mean and broadcast."""
    cp, gp, mu, nu, t = carry
    n = data.shape[0]
    loss_grad = jax.grad(ae.recon_loss)
    # participation floor (static: cfg and n are trace-time constants, so a
    # disabled floor compiles to exactly the pre-floor program); the 1e-9
    # slack keeps ceil exact under float repr (0.5 * 6 -> 3, not 4)
    floor = (max(1, math.ceil(cfg.min_participation * n - 1e-9))
             if cfg.min_participation > 0.0 else 0)

    def cl(tree):   # pin the leading client axis to the mesh
        return sh.constrain_clients(tree, rules)

    def rep(tree):  # pin server-side tensors replicated (forces the
        if rules is None:   # all-reduce at the aggregation point)
            return tree
        return jax.tree.map(
            lambda p: sh.constrain(p, rules, (None,) * p.ndim), tree)

    cp, mu, nu, data, sizes, agg_mask = cl((cp, mu, nu, data, sizes,
                                            agg_mask))
    gp = rep(gp)

    def local_grad(params_i, data_i, size_i, key_i):
        idx = jax.random.randint(key_i, (cfg.batch_size,), 0, size_i)
        return loss_grad(params_i, data_i[idx], ae_cfg)

    # shard-local: each device convolves only its own clients' filters
    local_grads = sh.client_map(jax.vmap(local_grad), rules)

    def apply_update(cp, grads, mu, nu, t):
        if cfg.local_opt == "sgd":    # Eq. 8, paper-faithful
            new = jax.tree.map(lambda p, g: p - cfg.lr * g, cp, grads)
            return new, mu, nu
        b1, b2 = cfg.adam_b1, cfg.adam_b2
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1 = 1 - b1 ** t
        c2 = 1 - b2 ** t
        new = jax.tree.map(
            lambda p, m, v: p - cfg.adam_lr * (m / c1)
            / (jnp.sqrt(v / c2) + cfg.adam_eps), cp, mu, nu)
        return new, mu, nu

    def iter_body(state, key_t):
        cp, mu, nu, t = state
        t = t + 1.0
        keys = cl(jax.random.split(key_t, n))
        grads = local_grads(cp, data, sizes, keys)
        if cfg.scheme == "fedprox":   # prox pull toward the global model
            grads = jax.tree.map(
                lambda g, p, q: g + cfg.prox_mu * (p - q[None]),
                grads, cp, gp)
        if cfg.scheme == "fedsgd":
            # aggregate gradients every iteration; all clients share
            # the global model (stragglers' grads are dropped)
            agg = cl(_broadcast(rep(_masked_mean(grads, agg_mask)), n))
            if floor:
                # below the floor the shared step would average a handful
                # of survivors — fall back to purely local gradients
                ok = jnp.sum(agg_mask) >= floor
                grads = jax.tree.map(
                    lambda a, g: jnp.where(ok, a, g), agg, grads)
            else:
                grads = agg
        cp, mu, nu = apply_update(cp, cl(grads), mu, nu, t)
        return (cl(cp), mu, nu, t), None

    (cp, mu, nu, t), _ = jax.lax.scan(iter_body, (cp, mu, nu, t), keys_round)
    # aggregation at the end of the round (FedAvg/FedProx param mean):
    # a cross-shard reduction over the client axis — the all-reduce
    gp_cand = rep(_masked_mean(cp, agg_mask))
    if floor:
        # graceful fallback below the participation floor: carry the last
        # good global model forward and let clients keep their local params
        # (they rejoin the average once participation recovers)
        ok = jnp.sum(agg_mask) >= floor
        gp_new = rep(jax.tree.map(
            lambda cand, old: jnp.where(ok, cand, old), gp_cand, gp))
        cp = cl(jax.tree.map(lambda b, local: jnp.where(ok, b, local),
                             _broadcast(gp_new, n), cp))
    else:
        gp_new = gp_cand
        cp = cl(_broadcast(gp_new, n))
    return FLCarry(cp, gp_new, mu, nu, t)


# Jitted once per (FLConfig, AEConfig, rules, shape) signature — module-level
# so the orchestrator's once-per-segment fl_train calls hit the jit cache
# instead of recompiling the scanned round every segment.  The carry is
# donated: client params + Adam moments are the dominant live buffers and a
# round only ever needs one generation of them.  The undecorated
# ``_round_body`` stays callable so the orchestrator's fused segment scan
# can inline the round inside its own traced program.
_round_fn = functools.partial(jax.jit, static_argnums=(0, 1, 7),
                              donate_argnums=(2,))(_round_body)


@functools.partial(jax.jit, static_argnums=2)
@jax.named_scope("fl")
def _eval_loss_fn(params, eval_data, ae_cfg):
    return ae.recon_loss(params, eval_data, ae_cfg)


def eval_global_loss(params, eval_data, ae_cfg):
    """Jitted global reconstruction loss, returned as a device scalar (no
    host sync) — the orchestrator's deferred per-segment metric."""
    return _eval_loss_fn(params, eval_data, ae_cfg)


def fl_train(key, datasets, ae_cfg: ae.AEConfig, cfg: FLConfig,
             eval_data, stragglers: Sequence[int] = (),
             init_params=None, init_carry: Optional[FLCarry] = None,
             start_iter: int = 0, stop_iter: Optional[int] = None,
             rules: Optional[sh.ShardingRules] = None,
             avail_mask=None, defer_metrics: bool = False) -> FLResult:
    """Run the FL task. datasets: per-client image arrays, or one
    :class:`~repro.core.batching.ClientData` stack (the orchestrator's form
    — already padded and mesh-placed, so no re-stacking happens here; local
    minibatches sample indices in [0, size_i), so a stack whose padding
    rows were overwritten by an exchange scatter trains identically to the
    freshly tiled list conversion).

    eval_data: (n_eval, H, W, C) held-out set for the global recon loss.

    Segmented training: ``init_carry`` (a previous :class:`FLCarry`) plus
    ``start_iter``/``stop_iter`` run only the rounds in
    ``[start_iter, stop_iter)`` of the full ``cfg.total_iters`` horizon.
    Chaining segments end-to-end reproduces the uninterrupted run exactly
    (same per-round keys, same eval schedule); datasets may change between
    segments (e.g. after a D2D re-exchange) — only parameter shapes must
    stay fixed.  The passed-in carry is consumed (buffers donated to the
    round function); use the returned ``FLResult.carry``.

    ``rules`` shards the client axis over the mesh (see module docstring);
    mesh=1 placement is bit-identical to the unsharded program.
    ``avail_mask`` is a device-resident (N,) availability mask (truthy =
    participates in aggregation) that overrides ``stragglers`` without a
    host round-trip.  ``defer_metrics`` leaves ``eval_loss`` as a device
    array so a caller looping over segments can materialise all metrics in
    one transfer at the end of the run."""
    cd = as_client_data(datasets, rules=rules)
    n = cd.n_clients
    data, sizes = cd.data, cd.sizes
    if avail_mask is not None:
        agg_mask = jnp.asarray(avail_mask, jnp.float32)
    else:
        agg_mask = jnp.asarray(
            [0.0 if i in set(stragglers) else 1.0 for i in range(n)])
    agg_mask = sh.shard_clients(agg_mask, rules)

    if init_carry is not None:
        client_params, global_params, mu, nu, step0 = init_carry
    else:
        if init_params is None:
            init_params = ae.init_ae(key, ae_cfg)
        client_params = sh.shard_clients(_broadcast(init_params, n), rules)
        # fresh copy: the caller's init_params must survive the first
        # round's carry donation
        global_params = jax.tree.map(jnp.copy, init_params)
        # mu/nu need distinct buffers — aliased leaves cannot both be
        # donated
        mu = jax.tree.map(jnp.zeros_like, client_params)
        nu = jax.tree.map(jnp.zeros_like, client_params)
        step0 = jnp.zeros((), jnp.float32)

    if start_iter % cfg.tau_a or (stop_iter is not None
                                  and stop_iter % cfg.tau_a):
        raise ValueError(
            f"segment bounds [{start_iter}, {stop_iter}) must align to the "
            f"aggregation interval tau_a={cfg.tau_a} — a segment boundary "
            "inside a round would silently drop iterations")
    n_rounds = cfg.total_iters // cfg.tau_a
    start_round = start_iter // cfg.tau_a
    stop_round = n_rounds if stop_iter is None else \
        min(stop_iter // cfg.tau_a, n_rounds)
    eval_iters, eval_vals = [], []
    keys = jax.random.split(jax.random.fold_in(key, 1), n_rounds)
    carry = FLCarry(client_params, global_params, mu, nu, step0)
    with obs.span("fl", rounds=stop_round - start_round,
                  start_iter=start_iter):
        for r in range(start_round, stop_round):
            kr = jax.random.split(keys[r], cfg.tau_a)
            carry = _round_fn(cfg, ae_cfg, carry, data, sizes, agg_mask, kr,
                              rules)
            it = (r + 1) * cfg.tau_a
            if it % cfg.eval_every == 0 or r == n_rounds - 1:
                eval_iters.append(it)
                eval_vals.append(_eval_loss_fn(
                    carry.global_params, eval_data, ae_cfg))
        eval_loss = jnp.stack(eval_vals) if eval_vals else jnp.zeros((0,))
        if not defer_metrics:
            eval_loss = np.asarray(eval_loss)
    return FLResult(carry.global_params, np.asarray(eval_iters),
                    eval_loss, carry.client_params, carry)

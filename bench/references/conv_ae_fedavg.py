"""Plain reference for the paper's federation: a convolutional autoencoder
per client, trained by local Adam steps on reconstruction MSE, averaged by
FedAvg every ``tau_a`` steps, and scored on the eval split.

It imports nothing of the system under test.  It follows the documented
key schedule of one federation run from the run's key alone:

    k_pipe, k_env, k_fl = split(run_key, 3)
    initial weights:   one key per leaf, split(k_fl, n_leaves), leaves in
                       sorted-name order; He-normal kernels, zero biases
    round r keys:      split(split(fold_in(k_fl, 1), n_rounds)[r], tau_a)
    client i at step:  split(step_key, N)[i] -> randint minibatch indices

Layers are written with ``lax.conv_general_dilated`` and
``lax.conv_transpose`` ('SAME', stride 2, HWIO kernels, no kernel flip),
not with the patch-gather einsums of the system, and every contraction is
at ``Precision.HIGHEST``.  ``dtype=jnp.bfloat16`` gives the low-precision
control: weights, data, activations and optimizer state in bfloat16.

Scope: every client available in every round (scenarios without churn) and
client data unchanged by the exchange (every client full to its cap under
the ``drop`` policy, so no transfer can land).  The harness checks both
premises on the run it compares.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
_DN = ("NHWC", "HWIO", "NHWC")


def leaf_shapes(ae: dict) -> dict:
    """Parameter shapes in sorted-name order, as (group, name) -> shape."""
    c, (w1, w2), lat = ae["channels"], ae["widths"], ae["latent_dim"]
    flat = (ae["height"] // 4) * (ae["width"] // 4) * w2
    shapes = {
        ("dec", "b1"): (w1,), ("dec", "b2"): (c,), ("dec", "bp"): (flat,),
        ("dec", "conv1"): (3, 3, w2, w1), ("dec", "conv2"): (3, 3, w1, c),
        ("dec", "proj"): (lat, flat),
        ("enc", "b1"): (w1,), ("enc", "b2"): (w2,), ("enc", "bp"): (lat,),
        ("enc", "conv1"): (3, 3, c, w1), ("enc", "conv2"): (3, 3, w1, w2),
        ("enc", "proj"): (flat, lat),
    }
    return dict(sorted(shapes.items()))


def init_params(k_fl, ae: dict, dtype=jnp.float32) -> dict:
    shapes = leaf_shapes(ae)
    keys = jax.random.split(k_fl, len(shapes))
    out = {"dec": {}, "enc": {}}
    for k, ((grp, name), shape) in zip(keys, shapes.items()):
        if name.startswith("b"):
            v = jnp.zeros(shape, jnp.float32)
        else:
            v = jax.random.normal(k, shape) * math.sqrt(
                2.0 / math.prod(shape[:-1]))
        out[grp][name] = v.astype(dtype)
    return out


def _conv(x, w, b):
    return lax.conv_general_dilated(x, w, (2, 2), "SAME",
                                    dimension_numbers=_DN, precision=HI) + b


def _conv_t(x, w, b):
    return lax.conv_transpose(x, w, (2, 2), "SAME", dimension_numbers=_DN,
                              precision=HI) + b


def reconstruct(p, x, ae: dict):
    e, d = p["enc"], p["dec"]
    h = jax.nn.relu(_conv(x, e["conv1"], e["b1"]))
    h = jax.nn.relu(_conv(h, e["conv2"], e["b2"]))
    z = jnp.dot(h.reshape(h.shape[0], -1), e["proj"], precision=HI) + e["bp"]
    h = jax.nn.relu(jnp.dot(z, d["proj"], precision=HI) + d["bp"])
    h = h.reshape(-1, ae["height"] // 4, ae["width"] // 4, ae["widths"][1])
    h = jax.nn.relu(_conv_t(h, d["conv1"], d["b1"]))
    return _conv_t(h, d["conv2"], d["b2"])


def loss(p, x, ae: dict):
    return jnp.mean(jnp.square(reconstruct(p, x, ae) - x))


# Faults that can be planted in the reference when it stands in the
# system's place, to read how far each moves the compared numbers:
#   "half_batch"  each local gradient over half of the minibatch
#   "no_fedavg"   the mean is left out: clients keep their own weights and
#                 the global model is client 0's
FAULTS = ("half_batch", "no_fedavg")


def _statics(ae, fl, fault=None):
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; expected one of {FAULTS}")
    return (tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in ae.items())),
            tuple(sorted(fl.items())), fault)


@functools.partial(jax.jit, static_argnums=(0,))
def _round(statics, params, mu, nu, step, data, sizes_key):
    """One FedAvg round: tau_a local Adam steps on every client, then the
    mean of the client weights.  ``sizes_key`` = (sizes, round keys)."""
    ae, fl = dict(statics[0]), dict(statics[1])
    fault = statics[2]
    ae["widths"] = list(ae["widths"])
    sizes, keys = sizes_key
    n = data.shape[0]
    dtype = data.dtype
    grad = jax.grad(loss)

    def local(p, m, v, t, x_i, size_i, k_i):
        idx = jax.random.randint(k_i, (fl["batch_size"],), 0, size_i)
        if fault == "half_batch":
            idx = idx[:fl["batch_size"] // 2]
        g = grad(p, x_i[idx], ae)
        b1, b2 = fl["adam_b1"], fl["adam_b2"]
        m = jax.tree.map(lambda a, gg: b1 * a + (1 - b1) * gg, m, g)
        v = jax.tree.map(lambda a, gg: b2 * a + (1 - b2) * gg * gg, v, g)
        c1 = (1 - b1 ** t).astype(dtype)
        c2 = (1 - b2 ** t).astype(dtype)
        p = jax.tree.map(
            lambda pp, a, b: pp - fl["adam_lr"] * (a / c1)
            / (jnp.sqrt(b / c2) + fl["adam_eps"]), p, m, v)
        return p, m, v, g

    def step_fn(state, k_t):
        p, m, v, t = state
        t = t + 1.0
        ks = jax.random.split(k_t, n)
        p, m, v, g = jax.vmap(local, in_axes=(0, 0, 0, None, 0, 0, 0))(
            p, m, v, t, data, sizes, ks)
        return (p, m, v, t), g

    (cp, mu, nu, step), grads = lax.scan(step_fn, (params, mu, nu, step),
                                         keys)
    if fault == "no_fedavg":
        gp = jax.tree.map(lambda a: a[0], cp)
    else:
        gp = jax.tree.map(lambda a: jnp.mean(a.astype(jnp.float32), axis=0)
                          .astype(a.dtype), cp)
        cp = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape),
                          gp)
    first_grad = jax.tree.map(lambda g: jnp.mean(g[0].astype(jnp.float32),
                                                 axis=0), grads)
    return cp, mu, nu, step, gp, first_grad


@functools.partial(jax.jit, static_argnums=(0,))
def _eval(statics, gp, eval_x):
    ae = dict(statics[0])
    ae["widths"] = list(ae["widths"])
    return loss(gp, eval_x, ae).astype(jnp.float32)


def run(run_key, data, sizes, eval_x, ae: dict, fl: dict, total_iters: int,
        dtype=jnp.float32, fault=None) -> dict:
    """Follow one federation run's FL from its key: returns the eval
    iterations and losses, the initial and final global weights, and the
    norm of the first step's mean gradient per leaf."""
    _k_pipe, _k_env, k_fl = jax.random.split(run_key, 3)
    tau = fl["tau_a"]
    n_rounds = total_iters // tau
    n = data.shape[0]
    statics = _statics(ae, fl, fault)
    init = init_params(k_fl, ae, dtype)
    params = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), init)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    step = jnp.zeros((), jnp.float32)
    data = data.astype(dtype)
    eval_x = eval_x.astype(dtype)
    round_keys = jax.random.split(jax.random.fold_in(k_fl, 1), n_rounds)
    iters, losses, first_grad = [], [], None
    gp = init
    for r in range(n_rounds):
        keys = jax.random.split(round_keys[r], tau)
        params, mu, nu, step, gp, fg = _round(statics, params, mu, nu, step,
                                              data, (sizes, keys))
        if first_grad is None:
            first_grad = fg
        it = (r + 1) * tau
        if it % fl["eval_every"] == 0 or r == n_rounds - 1:
            iters.append(it)
            losses.append(_eval(statics, gp, eval_x))
    return {"eval_iters": iters, "eval_loss": jnp.stack(losses),
            "init": init, "final": gp, "first_grad": first_grad}

"""The benchmark's world: client datasets and eval split, made on the device
from the seed in one jitted call.

The construction follows the paper's Sec. V setting as this repository
encodes it (``benchmarks/common.py::make_world``): class-structured image
data (a smooth fine-grained prototype per class, plus a per-sample smooth
deformation and pixel noise, squashed by a sigmoid), N clients each holding
``classes_per_client`` classes on a circle of classes (client i sees
classes {i-1, i, i+1} mod n_classes), ``n_train_per_class // (N /
n_classes)`` samples per class per client, and an eval split drawn from the
same prototypes.  Here the whole world is generated per class on the
device, and the partition is an index table fixed by the configuration, so
the same seed gives the same world and every seed gives the same sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative seed up to 64 bits (``PRNGKey``
    alone keeps only the low 32 bits of a large Python int)."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    k = jax.random.fold_in(jax.random.PRNGKey(0),
                           np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def partition_table(n_clients: int, n_classes: int, classes_per_client: int,
                    n_train_per_class: int) -> tuple[np.ndarray, int]:
    """(N, per_client) indices into the class-major train set, and the
    per-class take.  Client i holds the circular domain {i - half + t};
    each class hands consecutive blocks of its samples to the clients that
    hold it, in client order (``repro.data.partition_by_classes`` with
    ``circular=True``, minus its shuffles: the samples of a class are
    already i.i.d., and each client's rows are permuted on the device)."""
    per_client = (n_classes * n_train_per_class // n_clients)
    take = per_client // classes_per_client
    half = classes_per_client // 2
    cursor = [0] * n_classes
    rows = []
    for i in range(n_clients):
        idx = []
        for t in range(classes_per_client):
            c = (i - half + t) % n_classes
            start = cursor[c]
            if start + take > n_train_per_class:
                raise ValueError(
                    f"class {c} has {n_train_per_class} samples, the "
                    f"partition needs more than {start + take}")
            idx.append(c * n_train_per_class + np.arange(start, start + take))
            cursor[c] = start + take
        rows.append(np.concatenate(idx))
    return np.stack(rows).astype(np.int32), take


def _smooth(key, n, h, w, c, grid):
    low = jax.random.normal(key, (n, grid, grid, c))
    return jax.image.resize(low, (n, h, w, c), method="bicubic")


@functools.partial(jax.jit, static_argnums=(1,))
def _make_world(key, statics):
    (h, w, c, n_classes, n_train, n_eval, strength, grid, deform, noise,
     table) = statics
    table = jnp.asarray(np.asarray(table, np.int32))
    n_clients, per_client = table.shape
    kp, kd, kn, ks = jax.random.split(key, 4)
    protos = _smooth(kp, n_classes, h, w, c, grid) * strength
    n_per = n_train + n_eval
    n = n_classes * n_per
    labels = jnp.repeat(jnp.arange(n_classes, dtype=jnp.int32), n_per)
    imgs = jax.nn.sigmoid(protos[labels] + _smooth(kd, n, h, w, c, 6) * deform
                          + jax.random.normal(kn, (n, h, w, c)) * noise)
    imgs = imgs.reshape(n_classes, n_per, h, w, c)
    train = imgs[:, :n_train].reshape(n_classes * n_train, h, w, c)
    ev = imgs[:, n_train:].reshape(n_classes * n_eval, h, w, c)
    train_y = jnp.repeat(jnp.arange(n_classes, dtype=jnp.int32), n_train)
    perms = jax.vmap(lambda k: jax.random.permutation(k, per_client))(
        jax.random.split(ks, n_clients))
    rows = jnp.take_along_axis(table, perms, axis=1)
    return train[rows], train_y[rows], ev


def make_world(seed_k, cfg: dict):
    """Client stack (N, per_client, H, W, C), its labels (N, per_client)
    and the eval images (n_classes * n_eval_per_class, H, W, C), made on the
    device in one jitted call from ``seed_k``."""
    w = cfg["world"]
    table, _ = partition_table(w["n_clients"], w["n_classes"],
                               w["classes_per_client"],
                               w["n_train_per_class"])
    a = cfg["ae"]
    statics = (a["height"], a["width"], a["channels"], w["n_classes"],
               w["n_train_per_class"], w["n_eval_per_class"],
               w["proto_strength"], w["proto_grid"], w["deform"], w["noise"],
               tuple(map(tuple, table.tolist())))
    return _make_world(seed_k, statics)

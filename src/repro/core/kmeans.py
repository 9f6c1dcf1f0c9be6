"""K-means++ (paper Sec. III): careful seeding + Lloyd iterations.

The assignment step (pairwise distance + argmin, the per-iteration hot spot)
routes through ``repro.kernels.ops.kmeans_assign`` — the Pallas TPU kernel
with a pure-jnp oracle fallback on CPU.

Two entry points:
  * :func:`kmeans` — the reference single-dataset fit on a ragged (n, d)
    array.
  * :func:`kmeans_masked` / :func:`kmeans_batched` — the array-first client
    plane: the same algorithm on a mask-padded (cap, d) slice, and its vmap
    over a whole (N, cap, d) client stack.  Every reduction that touches
    rows is formulated so zero-weighted padding rows append zero terms
    without re-grouping the real ones (one-hot gemms, ``where``-masked
    sums), and the k-means++ seeding draws route through the same
    ``jax.random`` calls with the *true* size as the bound — so
    ``kmeans_masked`` with ``size == cap`` is bit-identical to
    :func:`kmeans`, and the vmapped stack is bit-identical to the
    per-client loop (``tests/test_client_data.py``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import sharding as sh
from repro.kernels import ops as kops


class KMeansResult(NamedTuple):
    centroids: jax.Array    # (k, d)
    assignments: jax.Array  # (n,) int32
    inertia: jax.Array      # () sum of squared distances to assigned centroid


def kmeans_plus_plus_init(key, x, k: int):
    """k-means++ seeding [Arthur & Vassilvitskii 2007]."""
    n = x.shape[0]
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    cents = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    d2 = jnp.sum(jnp.square(x - cents[0]), axis=-1)

    def body(i, carry):
        cents, d2, key = carry
        key, kc = jax.random.split(key)
        probs = d2 / jnp.maximum(jnp.sum(d2), 1e-12)
        idx = jax.random.choice(kc, n, p=probs)
        cents = cents.at[i].set(x[idx])
        nd2 = jnp.sum(jnp.square(x - cents[i]), axis=-1)
        return cents, jnp.minimum(d2, nd2), key

    cents, _, _ = jax.lax.fori_loop(1, k, body, (cents, d2, key))
    return cents


def lloyd_step(x, centroids):
    assign, min_d2 = kops.kmeans_assign(x, centroids)
    k = centroids.shape[0]
    onehot = jax.nn.one_hot(assign, k, dtype=x.dtype)        # (n, k)
    counts = jnp.sum(onehot, axis=0)                          # (k,)
    sums = onehot.T @ x                                       # (k, d)
    new_c = jnp.where(counts[:, None] > 0,
                      sums / jnp.maximum(counts[:, None], 1.0),
                      centroids)
    return new_c, assign, jnp.sum(min_d2)


def kmeans(key, x, k: int, n_iters: int = 25) -> KMeansResult:
    """Full K-means++ fit. x: (n, d)."""
    cents = kmeans_plus_plus_init(key, x, k)

    def body(_, carry):
        cents, _, _ = carry
        return lloyd_step(x, cents)

    init = lloyd_step(x, cents)
    cents, assign, inertia = jax.lax.fori_loop(1, n_iters, body, init)
    return KMeansResult(cents, assign, inertia)


def kmeans_plus_plus_init_masked(key, x, size, k: int):
    """k-means++ seeding over the valid prefix of a padded (cap, d) slice.

    Identical draws to :func:`kmeans_plus_plus_init` on the unpadded rows:
    the first centroid is ``randint(0, size)`` and subsequent D^2 draws go
    through ``jax.random.choice`` whose cumsum/searchsorted internals are
    unaffected by trailing zero-probability padding."""
    cap = x.shape[0]
    valid = jnp.arange(cap) < size
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, size)
    cents = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    d2 = jnp.where(valid, jnp.sum(jnp.square(x - cents[0]), axis=-1), 0.0)

    def body(i, carry):
        cents, d2, key = carry
        key, kc = jax.random.split(key)
        probs = d2 / jnp.maximum(jnp.sum(d2), 1e-12)
        idx = jax.random.choice(kc, cap, p=probs)
        cents = cents.at[i].set(x[idx])
        nd2 = jnp.where(valid, jnp.sum(jnp.square(x - cents[i]), axis=-1),
                        0.0)
        return cents, jnp.minimum(d2, nd2), key

    cents, _, _ = jax.lax.fori_loop(1, k, body, (cents, d2, key))
    return cents


def lloyd_step_masked(x, valid_f, centroids):
    """One Lloyd iteration over the valid rows of a padded slice.

    valid_f: (cap,) {0,1} float mask.  Padding rows are excluded from the
    counts/sums via the one-hot mask product (an appended zero row in the
    gemm) and from the inertia via ``where`` — assignments for padding rows
    are computed but carry no weight."""
    assign, min_d2 = kops.kmeans_assign(x, centroids)
    k = centroids.shape[0]
    onehot = jax.nn.one_hot(assign, k, dtype=x.dtype) * valid_f[:, None]
    counts = jnp.sum(onehot, axis=0)
    sums = onehot.T @ x
    new_c = jnp.where(counts[:, None] > 0,
                      sums / jnp.maximum(counts[:, None], 1.0),
                      centroids)
    return new_c, assign, jnp.sum(jnp.where(valid_f > 0, min_d2, 0.0))


def kmeans_masked(key, x, size, k: int, n_iters: int = 25) -> KMeansResult:
    """Full K-means++ fit on the valid prefix of a padded (cap, d) slice.

    Assignments are returned at the padded length (cap,); entries at index
    >= ``size`` are meaningless.  With ``size == cap`` this is bit-identical
    to :func:`kmeans`."""
    cap = x.shape[0]
    valid_f = (jnp.arange(cap) < size).astype(x.dtype)
    cents = kmeans_plus_plus_init_masked(key, x, size, k)

    def body(_, carry):
        cents, _, _ = carry
        return lloyd_step_masked(x, valid_f, cents)

    init = lloyd_step_masked(x, valid_f, cents)
    cents, assign, inertia = jax.lax.fori_loop(1, n_iters, body, init)
    return KMeansResult(cents, assign, inertia)


def kmeans_batched(key, x, sizes, k: int, n_iters: int = 25,
                   rules=None) -> KMeansResult:
    """All clients' K-means++ fits in one vmapped program.

    x: (N, cap, d) padded client stack; sizes: (N,).  Returns a stacked
    :class:`KMeansResult` — centroids (N, k, d), assignments (N, cap),
    inertia (N,).  Per-client keys match the sequential
    ``jax.random.split(key, N)`` convention of the list path, and the
    assignment hot spot still routes through ``ops.kmeans_assign`` (the
    Pallas kernel batches over the grid under vmap).  Entirely row-local:
    with ``rules`` every shard fits its own clients
    (``sharding.client_map``, which the Pallas kernel needs) with zero
    collectives."""
    keys = jax.random.split(key, x.shape[0])
    fit = jax.vmap(lambda kk, xx, ss: kmeans_masked(kk, xx, ss, k, n_iters))
    return sh.client_map(fit, rules)(keys, x, sizes)


def wcss_elbow(key, x, k_candidates) -> int:
    """Elbow method over candidate k (Assumption 2 helper).

    Kneedle-style criterion: normalise (k, WCSS) to the unit square and pick
    the k with the maximum vertical distance below the chord from the first
    to the last point — the 'hinge' of the WCSS curve."""
    inertias = jnp.stack([kmeans(key, x, int(k)).inertia for k in k_candidates])
    if len(k_candidates) < 3:
        return int(k_candidates[int(jnp.argmin(inertias))])
    ks = jnp.asarray(k_candidates, jnp.float32)
    kx = (ks - ks[0]) / (ks[-1] - ks[0])
    iy = (inertias - inertias[-1]) / jnp.maximum(inertias[0] - inertias[-1],
                                                 1e-12)
    chord = 1.0 - kx                  # straight line from (0,1) to (1,0)
    return int(k_candidates[int(jnp.argmax(chord - iy))])

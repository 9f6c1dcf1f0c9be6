"""Pallas TPU kernel: fused K-means assignment (pairwise d^2 + argmin).

The paper's per-Lloyd-iteration hot spot.  For n points x (n, d) and k
centroids (k, d), computes argmin_k ||x - c_k||^2 without materialising the
(n, k) distance matrix in HBM: each grid step streams a (BLOCK_N, d) tile of
points into VMEM, computes the distances to all centroids on the MXU
(-2 c @ x^T is a matmul), reduces to (assign, min_d2) in-register and writes
only the two lane-dense (1, BLOCK_N) rows back.

Layout: the distance tile is held transposed, (k, BLOCK_N), so both
reductions run over sublanes and their results are already lane-dense rows —
no sublane-to-lane relayout, and the (1, BLOCK_N) output blocks meet the
(8, 128) tiling rule.  The squared norms ``x2`` (1, n) and ``c2`` (k, 1)
are computed by the caller with the oracle's own expression (``ops.py``), so
kernel and oracle differ only in the cross-term's accumulation order.  The
argmin is "first index attaining the minimum", as ``jnp.argmin``.

Under ``jax.vmap`` (``kmeans_batched`` fits every client at once) Pallas
prepends the batch axis to the grid and squeezes it from every block, so the
same block shapes stay legal.

VMEM budget per step (f32): BLOCK_N*d + k*d + k*BLOCK_N floats.  With
BLOCK_N=512, d=1024, k<=256: 512k + 256k + 128k floats ~= 3.5 MiB << 16 MiB.
MXU alignment: callers pad d to a multiple of 128 and k to a multiple of 8,
and give padding centroids a huge ``c2`` so they never win the argmin.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 512


def _kernel(x_ref, c_ref, x2_ref, c2_ref, assign_ref, min_d2_ref):
    cross = jax.lax.dot_general(
        c_ref[...], x_ref[...], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (k, BN) via MXU
    d2 = x2_ref[...] - 2.0 * cross + c2_ref[...]    # (1, BN), (k, 1)
    mn = jnp.min(d2, axis=0, keepdims=True)          # (1, BN)
    k_ids = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    assign_ref[...] = jnp.min(jnp.where(d2 == mn, k_ids, d2.shape[0]),
                              axis=0, keepdims=True)
    min_d2_ref[...] = jnp.maximum(mn, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def kmeans_assign_pallas(x, centroids, x2, c2, *, interpret: bool = False,
                         block_n: int = BLOCK_N):
    """x: (n, d) f32, centroids: (k, d) f32, x2: (1, n) squared point norms,
    c2: (k, 1) squared centroid norms -> (assign (1, n) int32,
    min_d2 (1, n) f32).

    n % block_n == 0, block_n % 128 == 0, d % 128 == 0 and k % 8 == 0
    assumed (use ops.kmeans_assign for automatic padding)."""
    n, d = x.shape
    k = centroids.shape[0]
    return pl.pallas_call(
        _kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
        name="kmeans_assign",
    )(x, centroids, x2, c2)

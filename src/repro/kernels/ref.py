"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret mode
on CPU, compiled on TPU) and the default execution path on CPU hosts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def kmeans_assign_ref(x, centroids):
    """x: (n, d), centroids: (k, d) -> (assign (n,) int32, min_d2 (n,) f32)."""
    x = x.astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    x2 = jnp.sum(jnp.square(x), axis=1, keepdims=True)        # (n, 1)
    c2 = jnp.sum(jnp.square(c), axis=1)                        # (k,)
    # full f32: a TPU's default matmul precision rounds f32 operands to
    # bf16, far coarser than the kernel's cross term
    cross = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
    d2 = x2 - 2.0 * cross + c2[None, :]                        # (n, k)
    assign = jnp.argmin(d2, axis=1).astype(jnp.int32)
    min_d2 = jnp.maximum(jnp.min(d2, axis=1), 0.0)
    return assign, min_d2


def recon_gate_ref(y, x, mask):
    """y, x: (..., R, P); mask: (..., R) -> (...,) masked mean MSE.

    Per-sample pixel-mean squared error, averaged over the valid (masked)
    samples of each group — the exchange gate's subset score."""
    d = (y - x).astype(jnp.float32)
    per = jnp.mean(jnp.square(d), axis=-1)
    m = mask.astype(jnp.float32)
    return jnp.sum(per * m, axis=-1) / jnp.maximum(jnp.sum(m, axis=-1), 1.0)


def flash_attention_ref(q, k, v, *, causal=True, window=None, q_offset=0):
    """q: (B,S,H,hd); k,v: (B,L,Kv,hd) -> (B,S,H,hd).

    Plain masked softmax attention with GQA head grouping."""
    b, s, h, d = q.shape
    n_kv = k.shape[2]
    qg = q.reshape(b, s, n_kv, h // n_kv, d)
    scores = jnp.einsum("bskgd,blkd->bkgsl", qg, k,
                        preferred_element_type=jnp.float32) * (d ** -0.5)
    qpos = jnp.arange(s) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((s, k.shape[1]), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgsl,blkd->bskgd", probs, v)
    return out.reshape(b, s, h, d)

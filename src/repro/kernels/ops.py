"""Jit'd public wrappers around the Pallas kernels.

Dispatch policy:
  * TPU backend           -> compiled Pallas kernel
  * CPU + REPRO_FORCE_PALLAS=1 -> Pallas in interpret mode (tests)
  * CPU otherwise         -> pure-jnp oracle (`ref.py`)

Wrappers also handle padding to kernel-friendly shapes (d -> x128 for the
MXU, sequence -> block multiples), the choice of block sizes, and
un-padding of the results, so callers never see alignment constraints.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.kmeans_assign import BLOCK_N, kmeans_assign_pallas
from repro.kernels.recon_gate import (BLOCK_BYTES, GROUP_BLOCK,
                                      recon_gate_pallas)


def _use_pallas(override):
    if override is not None:
        return override
    if jax.default_backend() == "tpu":
        return True
    return os.environ.get("REPRO_FORCE_PALLAS", "0") == "1"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _pad_to(x, axis, mult, value=0.0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), pad


# ---------------------------------------------------------------------------
# kmeans assignment
# ---------------------------------------------------------------------------

@jax.named_scope("kmeans_assign")
def kmeans_assign(x, centroids, use_pallas=None):
    """x: (n, d), centroids: (k, d) -> (assign (n,) int32, min_d2 (n,) f32)."""
    if not _use_pallas(use_pallas):
        return ref.kmeans_assign_ref(x, centroids)
    n = x.shape[0]
    x = x.astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    # squared norms with the oracle's expressions, before any padding
    x2 = jnp.sum(jnp.square(x), axis=1)[None, :]              # (1, n)
    c2 = jnp.sum(jnp.square(c), axis=1)[:, None]              # (k, 1)
    block_n = min(BLOCK_N, _round_up(n, 128))
    xp, _ = _pad_to(x, 0, block_n)
    xp, _ = _pad_to(xp, 1, 128)
    x2, _ = _pad_to(x2, 1, block_n)
    cp, _ = _pad_to(c, 1, 128)
    cp, _ = _pad_to(cp, 0, 8)
    c2, _ = _pad_to(c2, 0, 8, value=1e30)   # padding centroids never win
    assign, min_d2 = kmeans_assign_pallas(xp, cp, x2, c2,
                                          interpret=_interpret(),
                                          block_n=block_n)
    return assign[0, :n], min_d2[0, :n]


# ---------------------------------------------------------------------------
# exchange gate: masked reconstruction-MSE scoring
# ---------------------------------------------------------------------------

@jax.named_scope("recon_gate")
def recon_gate_score(y, x, mask, use_pallas=None):
    """y, x: (..., R, P); mask: (..., R) -> (...,) masked mean MSE.

    Per-sample pixel-mean squared error averaged over each group's valid
    samples — the AE exchange gate's subset score (see kernels/recon_gate.py).
    """
    if not _use_pallas(use_pallas):
        return ref.recon_gate_ref(y, x, mask)
    lead = y.shape[:-2]
    r, p = y.shape[-2:]
    g = 1
    for s in lead:
        g *= s
    block_r, block_p = _gate_blocks(r, p)
    # zero padding: equal in y and x (zero residual), mask 0 on padded
    # samples and groups, so padding never moves a score
    padded = []
    for a, shape, mults in (
            (y, (g, r, p), (GROUP_BLOCK, block_r, 128)),
            (x, (g, r, p), (GROUP_BLOCK, block_r, 128)),
            (mask, (g, r), (GROUP_BLOCK, block_r))):
        a = a.astype(jnp.float32).reshape(shape)
        for axis, mult in enumerate(mults):
            a, _ = _pad_to(a, axis, mult)
        padded.append(a)
    out = recon_gate_pallas(*padded, p_true=p, block_r=block_r,
                            block_p=block_p, interpret=_interpret())
    return out[:g, 0].reshape(lead)


def _gate_blocks(r: int, p: int) -> tuple[int, int]:
    """(block_r, block_p) for the gate kernel: the whole (8-padded) sample
    axis in one block when a 128-pixel tile of it fits ``BLOCK_BYTES``,
    else 128-multiple sample blocks; then the widest 128-multiple pixel
    block that divides the padded P and keeps the tile within budget."""
    row_bytes = GROUP_BLOCK * 128 * 4        # one sample's 128-pixel slab
    block_r = _round_up(r, 8)
    if block_r * row_bytes > BLOCK_BYTES:
        block_r = BLOCK_BYTES // row_bytes // 128 * 128
    p_tiles = _round_up(p, 128) // 128
    fit = max(1, BLOCK_BYTES // (block_r * row_bytes))
    block_p = 128 * max(t for t in range(1, p_tiles + 1)
                        if p_tiles % t == 0 and t <= fit)
    return block_r, block_p


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    use_pallas=None, block_q=None, block_k=None):
    """q: (B,S,H,hd); k,v: (B,L,Kv,hd) -> (B,S,H,hd)."""
    if not _use_pallas(use_pallas):
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       q_offset=q_offset)
    b, s, h, hd = q.shape
    lk = k.shape[1]
    bq = block_q or min(512, max(8, 1 << (s - 1).bit_length()))
    bk = block_k or min(512, max(8, 1 << (lk - 1).bit_length()))
    qp, pad_q = _pad_to(q, 1, bq)
    kp, pad_k = _pad_to(k, 1, bk)
    vp, _ = _pad_to(v, 1, bk)
    # padded KV positions are masked out by the causal test only if they are
    # in the future; mask them explicitly by pushing them past every query.
    if pad_k and not causal:
        # give padded keys -inf by exploiting the window test
        raise NotImplementedError("non-causal padded flash attention")
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 q_offset=q_offset, interpret=_interpret(),
                                 block_q=min(bq, qp.shape[1]),
                                 block_k=min(bk, kp.shape[1]))
    return out[:, :s] if pad_q else out

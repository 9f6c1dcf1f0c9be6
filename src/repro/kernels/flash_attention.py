"""Pallas TPU kernel: flash attention (online softmax), causal + sliding
window, GQA-aware.

Layout: q (B,S,H,hd), k/v (B,L,Kv,hd).  Grid = (B*H, S/BQ, L/BK); the KV
axis is the innermost ("arbitrary") dimension so the running (m, l, acc)
accumulators live in VMEM scratch across KV steps and the output tile is
written once on the last step — K/V stream HBM->VMEM exactly once per query
block.  GQA maps query head h to KV head h // (H/Kv) in the BlockSpec index
maps, so no KV replication ever materialises.

VMEM per step (f32): BQ*hd + 2*BK*hd + BQ*BK + BQ*(hd+2).  With BQ=BK=512,
hd=128: ~1.8 MiB — comfortably inside 16 MiB with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_Q = 512
BLOCK_K = 512


def _make_kernel(*, scale, causal, window, q_offset, block_q, block_k,
                 n_kv_blocks):
    def kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        qi = pl.program_id(1)
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        q = q_ref[0].astype(jnp.float32) * scale         # (BQ, hd)
        k = k_ref[0].astype(jnp.float32)                 # (BK, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        qpos = (qi * block_q + q_offset
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
        kpos = (ki * block_k
                + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_scr[...] + jnp.sum(p, axis=1)
        v = v_ref[0].astype(jnp.float32)                 # (BK, hd)
        acc = acc_scr[...] * alpha[:, None] + p @ v
        m_scr[...] = m_cur
        l_scr[...] = l_cur
        acc_scr[...] = acc

        @pl.when(ki == n_kv_blocks - 1)
        def _finalize():
            o_ref[0] = (acc_scr[...]
                        / jnp.maximum(l_scr[...], 1e-30)[:, None]
                        ).astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "interpret",
                     "block_q", "block_k"))
def flash_attention_pallas(q, k, v, *, causal=True, window=None, q_offset=0,
                           interpret=False, block_q=BLOCK_Q, block_k=BLOCK_K):
    """q: (B,S,H,hd); k,v: (B,L,Kv,hd). S % block_q == L % block_k == 0
    assumed (ops.flash_attention pads)."""
    b, sq, h, hd = q.shape
    _, lk, n_kv, _ = k.shape
    group = h // n_kv
    n_kv_blocks = lk // block_k

    kern = _make_kernel(scale=hd ** -0.5, causal=causal, window=window,
                        q_offset=q_offset, block_q=block_q, block_k=block_k,
                        n_kv_blocks=n_kv_blocks)

    # flatten (B, H) into the first grid axis; kv head = head // group
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * n_kv, lk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * n_kv, lk, hd)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        return ((bh // h) * n_kv + (bh % h) // group, ki, 0)

    of = pl.pallas_call(
        kern,
        grid=(b * h, sq // block_q, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return of.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)

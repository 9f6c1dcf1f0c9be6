"""Pallas TPU kernel: fused masked reconstruction-MSE gate scoring.

The exchange gate (paper Sec. III-B) scores every (receiver, cluster)
reserve subset with the receiver's autoencoder: score = mean over the
subset's *valid* samples of the per-sample reconstruction MSE.  The AE
forward pass stays in XLA; this kernel fuses the tail — squared error,
per-sample pixel mean, masked sample mean — so the (G, R, P) residual
tensor is never materialised in HBM.

Layout: reconstructions ``y`` and targets ``x`` arrive flattened to
(G, R, P) where G = groups (receiver x cluster pairs, or receivers for the
base score), R = samples per group, P = pixels per sample; ``mask`` (G, R)
weights the samples (1 = valid, 0 = padding).  The grid is
(G / GB, R / RB, P / PB): each step streams a (GB, RB, PB) tile of both
operands, reduces it to per-sample partial MSEs (GB, RB), weights them by
the (GB, RB) mask tile and adds the result into a (GB, 1) accumulator; the
mask weights are counted once per (group, sample) tile.  The last step of
a group block writes ``num / max(cnt, 1)`` broadcast over a lane-dense
(GB, 128) output block.  GB = 8 and the 2-D mask/output layouts keep every
block within the (8, 128) tiling rule.

The score is linear in the per-pixel squared error, so splitting P and R
over grid steps only reorders the sums: the kernel computes the oracle's
function (``ref.recon_gate_ref``) up to float reassociation.

VMEM: the caller sizes RB and PB so one operand tile is at most
``BLOCK_BYTES`` (2 MiB); the two operands double-buffered then take
<= 8 MiB, inside v5e's 16 MiB scoped default at any R and P — the base
score streams a whole client stack (R = the client cap), not just a
reserve.  Callers pad P and R with zeros in both ``y`` and ``x`` (zero
residual) and with mask 0, so padding never moves the score; the true
pixel count is baked in statically via ``inv_p``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

GROUP_BLOCK = 8
LANES = 128
BLOCK_BYTES = 2 * 1024 * 1024


def _kernel(y_ref, x_ref, m_ref, out_ref, num_ref, cnt_ref, *, inv_p):
    r, p = pl.program_id(1), pl.program_id(2)

    @pl.when((r == 0) & (p == 0))
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    d = y_ref[...] - x_ref[...]                     # (GB, RB, PB)
    m = m_ref[...]                                  # (GB, RB)
    per = jnp.sum(d * d, axis=2) * inv_p            # per-sample partial MSE
    num_ref[...] += jnp.sum(per * m, axis=1, keepdims=True)

    @pl.when(p == 0)
    def _count():
        cnt_ref[...] += jnp.sum(m, axis=1, keepdims=True)

    @pl.when((r == pl.num_programs(1) - 1) & (p == pl.num_programs(2) - 1))
    def _finish():
        score = num_ref[...] / jnp.maximum(cnt_ref[...], 1.0)
        out_ref[...] = jnp.broadcast_to(score, out_ref.shape)


@functools.partial(jax.jit,
                   static_argnames=("p_true", "block_r", "block_p",
                                    "interpret"))
def recon_gate_pallas(y, x, mask, *, p_true: int, block_r: int,
                      block_p: int, interpret: bool = False):
    """y, x: (G, R, P) f32; mask: (G, R) f32 -> (G, 128) f32, every lane of
    row g holding group g's masked mean per-sample MSE.

    G % 8 == 0; R % block_r == 0 with block_r == R or block_r % 128 == 0;
    P % block_p == 0 with block_p % 128 == 0.  ``p_true`` is the unpadded
    pixel count (use ops.recon_gate_score for automatic padding)."""
    g, r, p = y.shape
    tile = pl.BlockSpec((GROUP_BLOCK, block_r, block_p),
                        lambda i, j, k: (i, j, k))
    return pl.pallas_call(
        functools.partial(_kernel, inv_p=1.0 / float(p_true)),
        grid=(g // GROUP_BLOCK, r // block_r, p // block_p),
        in_specs=[
            tile,
            tile,
            pl.BlockSpec((GROUP_BLOCK, block_r), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((GROUP_BLOCK, LANES), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((GROUP_BLOCK, 1), jnp.float32),
                        pltpu.VMEM((GROUP_BLOCK, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="recon_gate",
    )(y, x, mask)

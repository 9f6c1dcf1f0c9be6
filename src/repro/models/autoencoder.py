"""The paper's unsupervised learning model: a convolutional autoencoder.

Matches the paper's setup (Sec. V): a small CNN AE per client trained on
reconstruction MSE; the encoder embedding feeds the linear-evaluation probe.
Works for FMNIST-like (28x28x1) and CIFAR-like (32x32x3) inputs (NHWC).

The layers are XLA convolutions on every backend.  The client paths (FL
trainer, AE pretrain, exchange gate) vmap them over stacked per-client
filters, which XLA lowers to one convolution over all clients; a program
sharded over the client axis maps those calls with ``sharding.client_map``
so that each device convolves only its own clients.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import common as cm


@dataclasses.dataclass(frozen=True)
class AEConfig:
    height: int = 28
    width: int = 28
    channels: int = 1
    widths: tuple = (32, 64)
    latent_dim: int = 64

    @property
    def h4(self):
        return self.height // 4

    @property
    def w4(self):
        return self.width // 4


def ae_specs(cfg: AEConfig):
    c = cfg.channels
    w1, w2 = cfg.widths
    flat = cfg.h4 * cfg.w4 * w2
    return {
        "enc": {
            "conv1": cm.Spec((3, 3, c, w1), (None, None, None, None), "he"),
            "b1": cm.Spec((w1,), (None,), "zeros"),
            "conv2": cm.Spec((3, 3, w1, w2), (None, None, None, None), "he"),
            "b2": cm.Spec((w2,), (None,), "zeros"),
            "proj": cm.Spec((flat, cfg.latent_dim), (None, None), "he"),
            "bp": cm.Spec((cfg.latent_dim,), (None,), "zeros"),
        },
        "dec": {
            "proj": cm.Spec((cfg.latent_dim, flat), (None, None), "he"),
            "bp": cm.Spec((flat,), (None,), "zeros"),
            "conv1": cm.Spec((3, 3, w2, w1), (None, None, None, None), "he"),
            "b1": cm.Spec((w1,), (None,), "zeros"),
            "conv2": cm.Spec((3, 3, w1, c), (None, None, None, None), "he"),
            "b2": cm.Spec((c,), (None,), "zeros"),
        },
    }


def init_ae(key, cfg: AEConfig, dtype=jnp.float32):
    return cm.init_params(key, ae_specs(cfg), dtype)


_DN = ("NHWC", "HWIO", "NHWC")


def _conv(x, w, b, stride=1):
    """'SAME' conv, NHWC activations and HWIO filters."""
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=_DN) + b


def _conv_t(x, w, b, stride=2):
    """'SAME' transposed conv: ``lax.conv_transpose``'s padding rule, no
    kernel flip."""
    return jax.lax.conv_transpose(
        x, w, (stride, stride), "SAME", dimension_numbers=_DN) + b


def encode(params, x, cfg: AEConfig):
    """x: (B,H,W,C) -> (B, latent)."""
    e = params["enc"]
    h = jax.nn.relu(_conv(x, e["conv1"], e["b1"], 2))
    h = jax.nn.relu(_conv(h, e["conv2"], e["b2"], 2))
    h = h.reshape(h.shape[0], -1)
    return h @ e["proj"] + e["bp"]


def decode(params, z, cfg: AEConfig):
    d = params["dec"]
    h = jax.nn.relu(z @ d["proj"] + d["bp"])
    h = h.reshape(-1, cfg.h4, cfg.w4, cfg.widths[1])
    h = jax.nn.relu(_conv_t(h, d["conv1"], d["b1"], 2))
    # linear output head: an output sigmoid + MSE saturates against the
    # near-binary targets and stalls the paper's plain-SGD local steps
    return _conv_t(h, d["conv2"], d["b2"], 2)


def reconstruct(params, x, cfg: AEConfig):
    return decode(params, encode(params, x, cfg), cfg)


def recon_loss(params, x, cfg: AEConfig):
    """Mean-squared reconstruction error, the paper's L(phi, D)."""
    y = reconstruct(params, x, cfg)
    return jnp.mean(jnp.square(y - x))


def per_sample_loss(params, x, cfg: AEConfig):
    """(B,) per-sample MSE — the exchange gate's anomaly score."""
    y = reconstruct(params, x, cfg)
    return jnp.mean(jnp.square(y - x), axis=(1, 2, 3))


def masked_recon_loss(params, x, mask, cfg: AEConfig):
    """Masked mean per-sample MSE over a padded client stack.

    With ``mask`` selecting each real sample exactly once this equals
    :func:`recon_loss` on the unpadded data (every sample has the same pixel
    count), so gradients through padded stacks are exact.
    """
    per = per_sample_loss(params, x, cfg)
    m = mask.astype(per.dtype)
    return jnp.sum(per * m) / jnp.maximum(jnp.sum(m), 1.0)

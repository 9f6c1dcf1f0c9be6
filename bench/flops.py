"""Operations of the AE training step, counted from the configuration's
shapes, and the chips' peaks.

A step's FLOPs are forward + backward = 3x the forward, and the forward is
2 x its multiply-accumulates.  A transposed convolution counts only the
products of kernel taps with real input pixels: the system computes it as
a stride-1 convolution over a zero-stuffed input, whose products with the
stuffed zeros are not work the layer needs.  Bias adds, activations and
the loss are left out (under 0.1% of the total).
"""
from __future__ import annotations

import json
import pathlib

_PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def _same_pads(size: int, k: int, s: int) -> tuple[int, int]:
    out = -(-size // s)
    pad = max((out - 1) * s + k - size, 0)
    return pad // 2, pad - pad // 2


def conv_taps_1d(size: int, k: int = 3, s: int = 2) -> int:
    """(output position, kernel tap) pairs that read a real input pixel in
    a 'SAME' strided convolution along one axis."""
    lo, _ = _same_pads(size, k, s)
    out = -(-size // s)
    return sum(1 for o in range(out) for t in range(k)
               if 0 <= o * s + t - lo < size)


def conv_t_taps_1d(size: int, k: int = 3, s: int = 2) -> int:
    """Same count for a 'SAME' transposed convolution (input length
    ``size``, output ``size * s``): the input is dilated by ``s`` and padded
    as ``lax.conv_transpose`` pads it; only taps on a real pixel count."""
    pad_len = k + s - 2
    pad_a = k - 1 if s > k - 1 else -(-pad_len // 2)
    real = {pad_a + s * i for i in range(size)}
    return sum(1 for o in range(size * s) for t in range(k) if o + t in real)


def ae_forward_macs(ae: dict) -> dict:
    """Multiply-accumulates of one sample's forward pass, per layer."""
    h, w, c = ae["height"], ae["width"], ae["channels"]
    w1, w2 = ae["widths"]
    lat = ae["latent_dim"]
    h2, w2_ = -(-h // 2), -(-w // 2)
    h4, w4 = -(-h2 // 2), -(-w2_ // 2)
    flat = h4 * w4 * w2
    return {
        "enc.conv1": conv_taps_1d(h) * conv_taps_1d(w) * c * w1,
        "enc.conv2": conv_taps_1d(h2) * conv_taps_1d(w2_) * w1 * w2,
        "enc.proj": flat * lat,
        "dec.proj": lat * flat,
        "dec.conv1": conv_t_taps_1d(h4) * conv_t_taps_1d(w4) * w2 * w1,
        "dec.conv2": conv_t_taps_1d(h2) * conv_t_taps_1d(w2_) * w1 * c,
    }


def train_flops_per_sample(ae: dict) -> int:
    """Forward + backward FLOPs of one sample of one local step."""
    return 3 * 2 * sum(ae_forward_macs(ae).values())


def run_train_flops(cfg: dict, traffic: dict) -> int:
    """AE training FLOPs of one whole federation run: every client's local
    steps (eval and gate forwards excluded)."""
    iters = traffic["n_segments"] * traffic["iters_per_segment"]
    return (train_flops_per_sample(cfg["ae"]) * cfg["fl"]["batch_size"]
            * cfg["world"]["n_clients"] * iters)


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip of this kind; a kind missing
    from the table is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]

"""The AE's XLA convolutions against the patch-gather layers they replaced.

The oracle below is the former program code: each 'SAME' conv gathers the
3x3 shifted views of its padded input into a (B, ho, wo, 9, C) tensor and
contracts it with one einsum; the transposed conv zero-stuffs its input to
full resolution first.  Both sides run at ``Precision.HIGHEST``, so they
agree to float32 rounding.  Shapes are every layer of the two benchmark
configurations: 28x28x1 and 32x32x3 autoencoders with widths (32, 64).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import autoencoder as ae

HI = jax.lax.Precision.HIGHEST
RTOL = 1e-5   # of the output's largest magnitude


def _same_pads(size, k, s):
    out = -(-size // s)
    pad = max((out - 1) * s + k - size, 0)
    return pad // 2, pad - pad // 2


def _patch_conv(xp, w, stride):
    kh, kw, c, f = w.shape
    ho = (xp.shape[1] - kh) // stride + 1
    wo = (xp.shape[2] - kw) // stride + 1
    patches = jnp.stack(
        [xp[:, dy:dy + (ho - 1) * stride + 1:stride,
             dx:dx + (wo - 1) * stride + 1:stride]
         for dy in range(kh) for dx in range(kw)], axis=3)
    return jnp.einsum("bhwpc,pcf->bhwf", patches, w.reshape(kh * kw, c, f),
                      precision=HI)


def patch_conv(x, w, b, stride=1):
    kh, kw = w.shape[:2]
    plo, phi = _same_pads(x.shape[1], kh, stride)
    qlo, qhi = _same_pads(x.shape[2], kw, stride)
    xp = jnp.pad(x, ((0, 0), (plo, phi), (qlo, qhi), (0, 0)))
    return _patch_conv(xp, w, stride) + b


def patch_conv_t(x, w, b, stride=2):
    bsz, h, wd, c = x.shape
    kh = w.shape[0]
    xd = jnp.zeros(
        (bsz, stride * h - (stride - 1), stride * wd - (stride - 1), c),
        x.dtype).at[:, ::stride, ::stride].set(x)
    pad_len = kh + stride - 2
    pad_a = kh - 1 if stride > kh - 1 else -(-pad_len // 2)
    xp = jnp.pad(xd, ((0, 0), (pad_a, pad_len - pad_a),
                      (pad_a, pad_len - pad_a), (0, 0)))
    return _patch_conv(xp, w, 1) + b


ORACLE = {"conv": (ae._conv, patch_conv), "conv_t": (ae._conv_t, patch_conv_t)}


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    scale = float(np.max(np.abs(want)))
    assert scale > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=RTOL * scale)


def _operands(key, batch, size, c, f, lead=()):
    kx, kw, kb = jax.random.split(key, 3)
    x = jax.random.normal(kx, lead + (batch, size, size, c))
    w = jax.random.normal(kw, lead + (3, 3, c, f)) * np.sqrt(2.0 / (9 * c))
    b = jax.random.normal(kb, lead + (f,)) * 0.1
    return x, w, b


@pytest.mark.parametrize("kind,size,c,f,out", [
    pytest.param("conv", 28, 1, 32, 14, id="fmnist-enc1-28to14"),
    pytest.param("conv", 14, 32, 64, 7, id="fmnist-enc2-14to7"),
    pytest.param("conv", 32, 3, 32, 16, id="cifar-enc1-32to16"),
    pytest.param("conv", 16, 32, 64, 8, id="cifar-enc2-16to8"),
    pytest.param("conv_t", 7, 64, 32, 14, id="fmnist-dec1-7to14"),
    pytest.param("conv_t", 14, 32, 1, 28, id="fmnist-dec2-14to28"),
    pytest.param("conv_t", 8, 64, 32, 16, id="cifar-dec1-8to16"),
    pytest.param("conv_t", 16, 32, 3, 32, id="cifar-dec2-16to32"),
])
def test_layer_matches_patch_gather(kind, size, c, f, out):
    layer, oracle = ORACLE[kind]
    x, w, b = _operands(jax.random.PRNGKey(size * 100 + c), 4, size, c, f)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(layer, static_argnums=3)(x, w, b, 2)
    want = oracle(x, w, b, 2)
    assert got.shape == (4, out, out, f)
    _close(got, want)


@pytest.mark.parametrize("kind,size,c,f", [
    pytest.param("conv", 28, 1, 32, id="conv-28x1"),
    pytest.param("conv_t", 16, 32, 3, id="conv_t-16to32x3"),
])
def test_vmapped_over_stacked_client_filters(kind, size, c, f):
    """The client paths' form: one filter set per client, vmapped."""
    layer, oracle = ORACLE[kind]
    x, w, b = _operands(jax.random.PRNGKey(7), 8, size, c, f, lead=(5,))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.vmap(lambda x, w, b: layer(x, w, b, 2)))(x, w, b)
    want = jnp.stack([oracle(x[i], w[i], b[i], 2) for i in range(5)])
    _close(got, want)


def _patch_recon_loss(params, x, cfg):
    e, d = params["enc"], params["dec"]
    h = jax.nn.relu(patch_conv(x, e["conv1"], e["b1"], 2))
    h = jax.nn.relu(patch_conv(h, e["conv2"], e["b2"], 2))
    z = jnp.dot(h.reshape(h.shape[0], -1), e["proj"], precision=HI) + e["bp"]
    h = jax.nn.relu(jnp.dot(z, d["proj"], precision=HI) + d["bp"])
    h = h.reshape(-1, cfg.h4, cfg.w4, cfg.widths[1])
    h = jax.nn.relu(patch_conv_t(h, d["conv1"], d["b1"], 2))
    y = patch_conv_t(h, d["conv2"], d["b2"], 2)
    return jnp.mean(jnp.square(y - x))


@pytest.mark.parametrize("cfg", [
    pytest.param(ae.AEConfig(), id="fmnist"),
    pytest.param(ae.AEConfig(height=32, width=32, channels=3), id="cifar"),
])
def test_recon_loss_gradient_matches_patch_gather(cfg):
    params = ae.init_ae(jax.random.PRNGKey(3), cfg)
    x = jax.random.uniform(jax.random.PRNGKey(4),
                           (6, cfg.height, cfg.width, cfg.channels))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(ae.recon_loss), static_argnums=2)(
            params, x, cfg)
    want = jax.grad(_patch_recon_loss)(params, x, cfg)
    jax.tree.map(_close, got, want)

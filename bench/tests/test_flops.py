"""The AE FLOP count against a hand count, for both configurations."""
import pytest

from bench import flops, spec

# Hand count.  A 'SAME' stride-2 3x3 conv over 28 pixels has 14 outputs x
# 3 taps = 42 pairs, less the one tap that falls on the padding: 41; over
# 14 pixels, 21 - 1 = 20.  A 'SAME' stride-2 transposed 3x3 conv from 7 to
# 14 pixels: 7 real inputs x 3 taps = 21, less one tap cropped at the end:
# 20; from 14 to 28: 42 - 1 = 41.  For 32 and 16 pixels: 47 and 23.
FMNIST = {"enc.conv1": 41 * 41 * 1 * 32, "enc.conv2": 20 * 20 * 32 * 64,
          "enc.proj": 7 * 7 * 64 * 64, "dec.proj": 64 * 7 * 7 * 64,
          "dec.conv1": 20 * 20 * 64 * 32, "dec.conv2": 41 * 41 * 32 * 1}
CIFAR = {"enc.conv1": 47 * 47 * 3 * 32, "enc.conv2": 23 * 23 * 32 * 64,
         "enc.proj": 8 * 8 * 64 * 64, "dec.proj": 64 * 8 * 8 * 64,
         "dec.conv1": 23 * 23 * 64 * 32, "dec.conv2": 47 * 47 * 32 * 3}


@pytest.mark.parametrize("name,hand", [("ae-fmnist-n30", FMNIST),
                                       ("ae-cifar-n30", CIFAR)])
def test_forward_macs_match_hand_count(name, hand):
    ae = spec.load_config(name)["ae"]
    assert flops.ae_forward_macs(ae) == hand
    assert flops.train_flops_per_sample(ae) == 6 * sum(hand.values())


@pytest.mark.parametrize("size", [7, 8, 14, 16, 28, 32])
def test_tap_counts_match_brute_force(size):
    # brute force: build the padded dilated input and count real pixels
    # under every output's window
    pad_a = 2
    real = [pad_a + 2 * i for i in range(size)]
    brute = sum(1 for o in range(2 * size) for t in range(3)
                if o + t in real)
    assert flops.conv_t_taps_1d(size) == brute
    lo = 0 if size % 2 == 0 else 1
    out = -(-size // 2)
    brute = sum(1 for o in range(out) for t in range(3)
                if 0 <= 2 * o + t - lo < size)
    assert flops.conv_taps_1d(size) == brute


def test_run_flops_scale_with_traffic():
    cfg = spec.load_config("ae-fmnist-n30")
    r100 = spec.load_traffic("online-fading-r100")
    r10 = dict(r100, n_segments=6, iters_per_segment=10)
    per = flops.train_flops_per_sample(cfg["ae"]) * 32 * 30
    assert flops.run_train_flops(cfg, r100) == per * 300
    assert flops.run_train_flops(cfg, r10) == per * 60


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")

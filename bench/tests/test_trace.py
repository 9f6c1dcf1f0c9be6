"""The trace reduction on a synthesised trace with known intervals."""
import pytest

from bench import spec, trace

CHUNK = "jit__chunk_fn(123)"
ROUND = "jit__round_body(77)"
KERNEL = ("%custom-call.3 = f32[8,128]{1,0} custom-call(f32[8,784]{1,0} "
          "%p0), custom_call_target=\"tpu_custom_call\"")
FUSION = "%fusion.72 = f32[30]{0} fusion(f32[30,30]{1,0} %p), kind=kLoop"
CONV = "%convolution.4 = f32[8,14,14,32]{3,2,1,0} convolution(%a, %b)"
LOOP = "%while.373 = (s32[], f32[30]{0}) while(%t), condition=%c, body=%b"


def synthetic():
    """Two runs, [100, 300] and [400, 600] ns; an op before the window that
    must not count; two overlapping ops that count once."""
    chip = trace.Chip(
        ops=[(50, 90, FUSION),                     # before the window
             (100, 140, FUSION), (130, 160, CONV),  # overlap: 100..160
             (200, 220, KERNEL),
             (400, 450, CONV), (500, 530, KERNEL), (495, 540, LOOP),
             (590, 650, FUSION)],                  # clipped to 600
        modules=[(100, 160, ROUND), (200, 220, CHUNK), (400, 450, ROUND),
                 (500, 650, CHUNK)])
    host = [(160, 200, "Wait for donation holds"), (0, 1000, "main")]
    return trace.Raw(chips=[chip], runs=[(100, 300), (400, 600)], host=host)


def test_busy_and_idle_share():
    red = trace.reduce(synthetic())
    assert red.window_ns == 500
    assert red.n_runs == 2
    # 100..160, 200..220, 400..450, 495..540, 590..600
    assert red.busy_ns == 60 + 20 + 50 + 45 + 10
    ctx = {"trace": red}
    idle = spec.load_metric("device_idle_share").read(ctx)
    assert idle == pytest.approx(100 * (1 - 185 / 500))


def test_attribution_by_module_name():
    red = trace.reduce(synthetic())
    assert red.module_total == {"jit__round_body": 110.0,
                                "jit__chunk_fn": 20.0 + 100.0}
    ctx = {"trace": red}
    chunk = spec.load_metric("chunk_device_ms").read(ctx)
    seg0 = spec.load_metric("seg0_device_ms").read(ctx)
    assert chunk == pytest.approx(120 / 1e6 / 2)
    assert seg0 == pytest.approx(110 / 1e6 / 2)
    assert red.op_total["jit__chunk_fn:custom-call.3"] == 20 + 30
    assert red.op_total["jit__round_body:convolution.4"] == 30 + 50
    assert not any("while" in k for k in red.op_total)   # a container


def test_mosaic_custom_call_share():
    red = trace.reduce(synthetic())
    assert red.custom_ns == 20 + 30
    share = spec.load_metric("pallas_busy_share").read({"trace": red})
    assert share == pytest.approx(100 * 50 / 185)
    assert trace.is_custom_call(KERNEL)
    assert not trace.is_custom_call(FUSION)


def test_idle_gaps_are_labelled_and_longest_first():
    red = trace.reduce(synthetic())
    lengths = [ns for _, ns in red.idle]
    assert lengths == sorted(lengths, reverse=True)
    assert lengths[0] == 180     # 220..400, between the runs too
    assert sum(lengths) == 500 - red.busy_ns
    label = dict((ns, lab) for lab, ns in red.idle)[40]   # 160..200
    assert label == ("Wait for donation holds: jit__round_body -> "
                     "jit__chunk_fn")
    b = trace.breakdown(red)
    assert b["device_ops"][0] == ["jit__round_body:convolution.4", 80e-9]
    assert len(b["idle_gaps"]) <= 10


def test_several_chips_average():
    raw = synthetic()
    other = trace.Chip(ops=[(100, 600, FUSION)], modules=[(100, 600, CHUNK)])
    raw.chips.append(other)
    red = trace.reduce(raw)
    assert red.busy_ns == (185 + 500) / 2


def test_mfu_reads_flops_over_window_and_peak():
    red = trace.reduce(synthetic())
    ctx = {"trace": red, "run_train_flops": 1000.0,
           "peaks": {"flops_per_s": 1e12}}
    mfu = spec.load_metric("mfu").read(ctx)
    # 2 runs x 1000 FLOP over 500 ns at 1e12 FLOP/s
    assert mfu == pytest.approx(100 * 2000 / (500e-9 * 1e12))


def test_nothing_to_read_gives_no_number():
    chip = trace.Chip(ops=[], modules=[])
    red = trace.reduce(trace.Raw([chip], [(0, 10)], []))
    for name in ("device_idle_share", "pallas_busy_share",
                 "chunk_device_ms", "seg0_device_ms"):
        assert spec.load_metric(name).read({"trace": red}) is None

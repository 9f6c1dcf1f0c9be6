"""No CPU fallback: a machine without a TPU, with too few chips, or with a
``device_kind`` missing from the peak table gets no result."""
import os
import subprocess
import sys
import types

import pytest

from bench import run as bench_run
from bench import spec


def fake_jax(platform, kind, count=1):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev] * count)


def test_tpu_in_the_table_passes():
    info = bench_run.device_info(fake_jax("tpu", "TPU v5 lite"), 1)
    assert info == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.mark.parametrize("platform,kind,count,chips", [
    ("cpu", "cpu", 1, 1), ("gpu", "NVIDIA H100", 1, 1),
    ("tpu", "TPU v9 imaginary", 1, 1), ("tpu", "TPU v5 lite", 1, 4)])
def test_wrong_device_is_refused(platform, kind, count, chips):
    with pytest.raises(bench_run.NoChip):
        bench_run.device_info(fake_jax(platform, kind, count), chips)


def test_cpu_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fmnist-r100",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "not a TPU" in p.stderr

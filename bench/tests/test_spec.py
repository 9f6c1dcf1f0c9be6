"""The harness finds each configuration, traffic mix, metric, reference and
limit file by the name ``BENCHMARK.json`` gives it, and a new one added
under a new name with no edit to an existing file."""
import json
import shutil
import types

import pytest

from bench import spec


def test_every_named_file_exists():
    bench = spec.load_benchmark()
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        cfg = spec.load_config(cell["config"])
        assert configs[cell["config"]]["file"] == \
            f"bench/configs/{cell['config']}.json"
        spec.load_reference(cfg["reference"])
        traffic = spec.load_traffic(cell["traffic"])
        assert traffic["n_segments"] >= 1
        spec.load_limits(cell["name"])
        for m in spec.cell_metrics(bench, cell, "per_layer"):
            assert callable(spec.load_metric(m["name"]).read)


def test_new_files_are_found_by_name(tmp_path):
    bench_dir = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR / "configs", bench_dir / "configs")
    shutil.copytree(spec.BENCH_DIR / "traffic", bench_dir / "traffic")
    shutil.copytree(spec.BENCH_DIR / "metrics", bench_dir / "metrics")
    cfg = spec.load_config("ae-fmnist-n30", bench_dir)
    cfg["world"]["n_clients"] = 120
    (bench_dir / "configs" / "ae-fmnist-n120.json").write_text(
        json.dumps(cfg))
    traffic = spec.load_traffic("online-fading-r100", bench_dir)
    traffic["scenario"] = "churn"
    (bench_dir / "traffic" / "online-churn-r100.json").write_text(
        json.dumps(traffic))
    (bench_dir / "metrics" / "runs_in_window.py").write_text(
        "def read(ctx):\n    return ctx['trace'].n_runs\n")

    assert spec.load_config("ae-fmnist-n120", bench_dir)["world"][
        "n_clients"] == 120
    assert spec.load_traffic("online-churn-r100", bench_dir)[
        "scenario"] == "churn"
    red = types.SimpleNamespace(n_runs=4)
    assert spec.load_metric("runs_in_window", bench_dir).read(
        {"trace": red}) == 4

    bench = spec.load_benchmark()
    new_cell = {"name": "fmnist120-churn", "config": "ae-fmnist-n120",
                "traffic": "online-churn-r100", "chips": 1, "why": "x"}
    bench["workloads"].append(new_cell)
    bench["per_layer"].append({"name": "runs_in_window", "unit": "runs",
                               "better": "higher", "source": "host_clock",
                               "layer": "device",
                               "moves": "client_iters_per_s"})
    assert spec.find_cell(bench, "fmnist120-churn") is new_cell
    names = [m["name"] for m in spec.cell_metrics(bench, new_cell,
                                                  "per_layer")]
    assert names == [m["name"] for m in bench["per_layer"]]
    old = spec.find_cell(bench, "fmnist-r100")
    assert "runs_in_window" in [m["name"] for m in
                                spec.cell_metrics(bench, old, "per_layer")]


@pytest.mark.parametrize("bad", ["../etc", "a/b", "", "x" * 65, "a b"])
def test_names_cannot_leave_their_directory(bad):
    with pytest.raises(ValueError):
        spec.load_config(bad)

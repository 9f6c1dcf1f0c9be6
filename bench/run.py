"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks for.
A run:

1. builds the cell's world on the device from ``--seed`` (client stack,
   labels, eval split; ``bench/world.py``);
2. warms up with one whole federation run of the cell's own shapes, every
   program coming from the persistent compile cache at
   ``bench/_cache/jax`` after a checkout's first run; the time from process
   start to here is ``setup_s``;
3. in the measured window calls ``repro.dynamics.run_orchestrator`` back
   to back, one whole federation run per call, each ending in
   ``block_until_ready`` on the global weights, the carry and the client
   data, until ``--seconds`` have passed; the run in flight then finishes
   and counts;
4. follows one run of the window, drawn from the seed, with the
   configuration's plain reference (``bench/check.py``), and prints the
   numbers compared beside their limits;
5. prints one JSON line: the cell's end-to-end metrics with ``--trace 0``,
   its per-layer metrics (read from a profiler trace of the window) with
   ``--trace 1``.

A machine whose first device is not a TPU, that holds fewer chips than the
cell asks for, or whose ``device_kind`` is missing from
``bench/peaks.json`` gets exit code 2 and no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

CACHE_DIR = ROOT / "bench" / "_cache" / "jax"
TRACE_DIR = ROOT / "bench" / "_cache" / "trace"
RUN_ANNOTATION = "federation_run"


class NoChip(RuntimeError):
    """The machine lacks the device the cell needs."""


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache(jax) -> None:
    """Every program of the cell goes to the in-checkout cache, however
    short its compile: the set-up of a later run then compiles nothing."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no cap on an entry's size: the CIFAR clustering and chunk programs
    # are over 200 MB each, and a cap below that recompiles them every run
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_info(jax, chips: int) -> dict:
    from bench import flops

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"JAX's first device is {d0.platform!r}, not a TPU; "
                     "the benchmark never falls back to another backend")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    try:
        flops.peaks(d0.device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from e
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def build_run_config(cfg: dict, traffic: dict):
    """The system's own config objects for one cell."""
    from repro.core.channel import ChannelConfig
    from repro.core.exchange import ExchangeConfig
    from repro.core.pipeline import PipelineConfig
    from repro.core.qlearning import RLConfig
    from repro.core.rewards import RewardConfig
    from repro.dynamics import OrchestratorConfig
    from repro.fl import FLConfig
    from repro.models.autoencoder import AEConfig

    a = cfg["ae"]
    ae_cfg = AEConfig(a["height"], a["width"], a["channels"],
                      tuple(a["widths"]), a["latent_dim"])
    pipe = PipelineConfig(**cfg["pipeline"],
                          reward=RewardConfig(**cfg["reward"]),
                          rl=RLConfig(**cfg["rl"]),
                          channel=ChannelConfig(**cfg["channel"]),
                          exchange=ExchangeConfig(**cfg["exchange"]))
    orch = OrchestratorConfig(
        n_segments=traffic["n_segments"],
        iters_per_segment=traffic["iters_per_segment"],
        mode=traffic["mode"], rediscover_every=traffic["rediscover_every"],
        burst_episodes=cfg["burst_episodes"],
        exchange_on_rediscover=traffic["exchange_on_rediscover"],
        pipeline=pipe, fl=FLConfig(**cfg["fl"]),
        segment_impl=traffic["segment_impl"])
    return ae_cfg, orch


@dataclasses.dataclass
class Cell:
    """Everything one run of a cell needs, built once in set-up."""
    name: str
    cfg: dict
    traffic: dict
    ae_cfg: object
    orch: object
    seed_key: object
    client_data: object
    eval_x: object

    @property
    def iters_per_run(self) -> int:
        return (self.cfg["world"]["n_clients"] * self.traffic["n_segments"]
                * self.traffic["iters_per_segment"])


def make_world(jax, cfg: dict, seed_key):
    from bench import world

    x, y, ev = world.make_world(jax.random.fold_in(seed_key, 0), cfg)
    jax.block_until_ready((x, y, ev))
    return x, y, ev


def make_cell(jax, name: str, cfg: dict, traffic: dict, seed: int) -> Cell:
    import jax.numpy as jnp

    from bench import world
    from repro.core.batching import ClientData

    ae_cfg, orch = build_run_config(cfg, traffic)
    seed_key = world.seed_key(seed)
    x, y, ev = make_world(jax, cfg, seed_key)
    cd = ClientData(x, jnp.full((x.shape[0],), x.shape[1], jnp.int32), y)
    return Cell(name, cfg, traffic, ae_cfg, orch, seed_key, cd, ev)


def setup_cell(jax, bench: dict, workload: str, seed: int) -> Cell:
    from bench import spec

    entry = spec.find_cell(bench, workload)
    return make_cell(jax, workload, spec.load_config(entry["config"]),
                     spec.load_traffic(entry["traffic"]), seed)


def run_key(jax, cell: Cell, index: int):
    """Run ``index`` of the window; -1 is the warm-up run."""
    if index < 0:
        return jax.random.fold_in(cell.seed_key, 1)
    return jax.random.fold_in(jax.random.fold_in(cell.seed_key, 2), index)


def federation_run(jax, cell: Cell, index: int, runner=None) -> dict:
    """One whole federation run, ended on the device; returns what the
    check needs of it (the weights stay on the device)."""
    from repro.dynamics import run_orchestrator

    runner = runner or run_orchestrator
    res = runner(run_key(jax, cell, index), cell.client_data, None,
                 cell.ae_cfg, cell.orch, cell.traffic["scenario"],
                 cell.eval_x)
    jax.block_until_ready((res.global_params, res.carry,
                           res.client_data.data))
    segs = res.trace.segments
    return {"index": index, "final": res.global_params,
            "eval_loss": res.eval_loss,
            "moved": [s.moved for s in segs],
            "n_available": [s.n_available for s in segs]}


def measure(jax, cell: Cell, seconds: float, trace_dir=None, runner=None):
    """The measured window: whole runs back to back until ``seconds`` have
    passed; the run in flight finishes and counts."""
    # the submodule; ``repro.obs.counters`` the attribute is a function
    counters = importlib.import_module("repro.obs.counters")
    counters.install()
    counters.set_active(True)
    c0 = counters.snapshot()[0]
    if trace_dir is not None:
        # host events of the runtime and the run annotations, but no
        # Python call tracing: that would slow the host it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    runs, walls = [], []
    t0 = time.perf_counter()
    while True:
        i = len(runs)
        with jax.profiler.TraceAnnotation(RUN_ANNOTATION):
            t = time.perf_counter()
            runs.append(federation_run(jax, cell, i, runner))
            walls.append(time.perf_counter() - t)
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    if trace_dir is not None:
        jax.profiler.stop_trace()
    compiles = counters.snapshot()[0] - c0
    counters.set_active(False)
    return {"runs": runs, "walls": walls, "window_s": window_s,
            "compiles": compiles}


def memory_peak(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def check_run(jax, cell: Cell, run: dict) -> dict:
    """Follow ``run`` with the configuration's reference and compare."""
    import jax.numpy as jnp
    import numpy as np

    from bench import check, spec

    ref_mod = spec.load_reference(cell.cfg["reference"])
    x, _y, ev = make_world(jax, cell.cfg, cell.seed_key)
    total = cell.traffic["n_segments"] * cell.traffic["iters_per_segment"]
    ref = ref_mod.run(run_key(jax, cell, run["index"]), x,
                      jnp.full((x.shape[0],), x.shape[1], jnp.int32), ev,
                      cell.cfg["ae"], cell.cfg["fl"], total)
    ref = jax.device_get(ref)
    prog = {"eval_loss": np.asarray(run["eval_loss"]),
            "final": jax.device_get(run["final"]),
            "moved": run["moved"], "n_available": run["n_available"]}
    return check.compare(prog, ref, cell.cfg["world"]["n_clients"])


def sample_index(seed: int, n_runs: int) -> int:
    import numpy as np

    return int(np.random.default_rng([seed, 0xC4EC]).integers(n_runs))


def per_layer(jax, cell: Cell, bench: dict, win: dict, trace_path,
              device: dict) -> tuple[dict, dict, dict]:
    """Read the per-layer metrics of the cell from the window's trace."""
    from bench import flops, spec, trace

    cell_entry = spec.find_cell(bench, cell.name)
    red = trace.reduce(trace.load(trace_path, RUN_ANNOTATION))
    ctx = {"trace": red, "compiles": win["compiles"],
           "run_train_flops": flops.run_train_flops(cell.cfg, cell.traffic),
           "peaks": flops.peaks(device["kind"])}
    metrics = {}
    for m in spec.cell_metrics(bench, cell_entry, "per_layer"):
        value = spec.load_metric(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = {"busy_s": red.busy_ns / 1e9, "window_s": red.window_ns / 1e9}
    return metrics, busy, trace.breakdown(red)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        import jax
    except ImportError as e:
        print(f"bench: cannot import jax: {e}", file=sys.stderr)
        return 3
    enable_cache(jax)
    from bench import check, spec

    bench = spec.load_benchmark()
    cell_entry = spec.find_cell(bench, args.workload)
    try:
        device = device_info(jax, cell_entry["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the system under test is missing: {e}",
              file=sys.stderr)
        return 3
    limits = spec.load_limits(args.workload)

    cell = setup_cell(jax, bench, args.workload, args.seed)
    federation_run(jax, cell, -1)
    setup_s = time.perf_counter() - T_START

    trace_dir = None
    if args.trace:
        trace_dir = TRACE_DIR / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    win = measure(jax, cell, args.seconds, trace_dir)
    device["memory_peak_bytes"] = memory_peak(jax)
    n_runs = len(win["runs"])
    pick = win["runs"][sample_index(args.seed, n_runs)]
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "runs": n_runs, "run_wall_s": win["walls"],
                      "window_s": win["window_s"], "setup_s": setup_s,
                      "window_compiles": win["compiles"],
                      "memory_peak_bytes": device["memory_peak_bytes"],
                      "checked_run": pick["index"],
                      "device": device}), flush=True)

    breakdown = None
    if args.trace:
        metrics, busy, breakdown = per_layer(jax, cell, bench, win,
                                             trace_dir, device)
        device.update(busy)
    else:
        rate = n_runs * cell.iters_per_run / win["window_s"]
        values = {"client_iters_per_s": rate, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec.cell_metrics(bench, cell_entry,
                                              "end_to_end")}

    # the program's state goes before the reference runs
    win["runs"] = [pick]
    cell.client_data = cell.eval_x = None
    gc.collect()
    numbers = check_run(jax, cell, pick)
    correct, table = check.judge(numbers, limits)
    for name, row in table.items():
        print(f"check {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    result = {"correct": correct, "attempted": n_runs,
              "failed": 0 if correct else 1, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = table
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

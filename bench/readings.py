"""Readings that the correctness limits of a cell are set from.

    python3 bench/readings.py --workload <cell> --seeds 11 12 13 ... [--out file.json]

On the chip, for each seed: one whole federation run of the cell (run 0 of
that seed's window, at the cell's own sizes, through the timed path),
compared with the float32 reference; the control (the reference in
bfloat16, put in the system's place); and each fault that the cell can
have, planted in the reference put in the system's place or in the run's
own answer:

``half_batch``  each local gradient over half of the minibatch
``no_fedavg``   the FedAvg mean left out (the exchange between clients)
``stale_eval``  an answer altered where it is produced: the first eval
                reports the loss of the model before the round
``unchanged``   the training step returns its state unchanged: the
                weights never move, so ``change_worst_leaf`` reads 1 on
                every leaf; no run is needed

The lower reading of a number is the largest the sound runs give, the
upper the smallest the control and the faults give (``bench/check.py``).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run  # bench/run.py, which also sets up sys.path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import check, spec

    bench_run.enable_cache(jax)
    bench = spec.load_benchmark()
    entry = spec.find_cell(bench, args.workload)
    try:
        device = bench_run.device_info(jax, entry["chips"])
    except bench_run.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    cfg = spec.load_config(entry["config"])
    traffic = spec.load_traffic(entry["traffic"])
    ref_mod = spec.load_reference(cfg["reference"])
    total = traffic["n_segments"] * traffic["iters_per_segment"]
    n_clients = cfg["world"]["n_clients"]
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        cell = bench_run.make_cell(jax, args.workload, cfg, traffic, seed)
        run = bench_run.federation_run(jax, cell, 0)
        t_run = time.perf_counter() - t0
        prog = {"eval_loss": np.asarray(run["eval_loss"]),
                "final": jax.device_get(run["final"]),
                "moved": run["moved"], "n_available": run["n_available"]}
        x, sizes, ev = cell.client_data.data, cell.client_data.sizes, \
            cell.eval_x
        key = bench_run.run_key(jax, cell, 0)

        def ref(dtype=jnp.float32, fault=None):
            out = ref_mod.run(key, x, sizes, ev, cfg["ae"], cfg["fl"], total,
                              dtype=dtype, fault=fault)
            return jax.device_get(out)

        t1 = time.perf_counter()
        r32 = ref()
        t_ref = time.perf_counter() - t1

        def as_run(r):
            return {"eval_loss": np.asarray(r["eval_loss"], np.float32),
                    "final": r["final"], "moved": [0] * len(run["moved"]),
                    "n_available": [n_clients] * len(run["n_available"])}

        stale = dict(prog)
        stale["eval_loss"] = prog["eval_loss"].copy()
        stale["eval_loss"][0] = float(ref_mod.loss(r32["init"], ev,
                                                   cfg["ae"]))
        row = {"seed": seed, "run_s": t_run, "ref_s": t_ref,
               "program": check.compare(prog, r32, n_clients),
               "control": check.compare(as_run(ref(jnp.bfloat16)), r32,
                                        n_clients),
               "half_batch": check.compare(as_run(ref(fault="half_batch")),
                                           r32, n_clients),
               "no_fedavg": check.compare(as_run(ref(fault="no_fedavg")),
                                          r32, n_clients),
               "stale_eval": check.compare(stale, r32, n_clients)}
        print(json.dumps(row), flush=True)
        rows.append(row)
        del cell, run, x, ev, sizes
    summary = {}
    for name in check.NUMBERS:
        summary[name] = {
            "lower": max(r["program"][name] for r in rows),
            **{k: min(r[k][name] for r in rows)
               for k in ("control", "half_batch", "no_fedavg",
                         "stale_eval")}}
    out = {"workload": args.workload, "device": device, "rows": rows,
           "summary": summary}
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

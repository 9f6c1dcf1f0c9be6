"""Benchmark harness: one entry per paper table/figure + kernel micro-bench +
the roofline table + the dynamic-deployment scenarios.  Prints
``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig5] \\
        [--json runs/bench/BENCH_quick.json] [--profile runs/prof]

--full uses the paper-scale settings (30 clients, 1500 iterations); the
default quick settings preserve every claim's *ordering* at ~10x less CPU.
--json additionally records every emitted CSV row as a JSON artifact so the
perf trajectory across PRs is machine-diffable.
--profile DIR captures a jax.profiler (TensorBoard) trace per instrumented
bench region under DIR (equivalent to REPRO_PROFILE=DIR); the dynamic rows
additionally stream obs span manifests under runs/obs/ — see
tools/trace_report.py.
"""
import argparse
import io
import json
import os
import re
import sys
import time
import traceback

from benchmarks import (beyond_paper, cluster_bench, dryrun_table,
                        dynamic_scenarios, fig3_heatmap, fig4_links,
                        fig5_convergence, fig6_stragglers, kernel_bench,
                        roofline_table, shard_scaling)
from repro.launch.compile_cache import enable_compile_cache

BENCHES = {
    "fig3": fig3_heatmap.main,
    "fig4": fig4_links.main,
    "fig5": fig5_convergence.main,
    "fig6": fig6_stragglers.main,
    "kernels": kernel_bench.main,
    "cluster": cluster_bench.main,
    "roofline": roofline_table.main,
    "dryrun": dryrun_table.main,
    "beyond": beyond_paper.main,
    "dynamic": dynamic_scenarios.main,
    "dynamic-smoke": dynamic_scenarios.smoke,   # CI: tiny online rows
                                                # (eager + fused engine)
    "scanfuse": dynamic_scenarios.scanfuse,
    "faults": dynamic_scenarios.faults,
    "chaos": dynamic_scenarios.chaos,           # CI: kill+resume identity
    "shard": shard_scaling.main,
}

CI_ONLY = ("dynamic-smoke", "chaos")

# a result row: bench_name,<int-or-float us>,<derived k=v fields>
_ROW_RE = re.compile(r"^([A-Za-z][\w.-]*),(\d+(?:\.\d+)?),(.*)$")


class _RowTee(io.TextIOBase):
    """stdout tee that records the benchmark CSV rows as they stream by."""

    def __init__(self, real):
        self.real = real
        self.rows = []
        self._buf = ""

    def write(self, s):
        self.real.write(s)
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            m = _ROW_RE.match(line.strip())
            if m and m.group(1) != "name":
                self.rows.append({"name": m.group(1),
                                  "us_per_call": float(m.group(2)),
                                  "derived": m.group(3)})
        return len(s)

    def flush(self):
        self.real.flush()


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None, metavar="NAME[,NAME...]",
                    help="comma-separated subset of: " + ",".join(BENCHES))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the emitted rows to a BENCH_*.json "
                         "artifact at PATH; a bare filename (no directory "
                         "component) lands in runs/bench/")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture jax.profiler traces of instrumented "
                         "regions under DIR (sets REPRO_PROFILE)")
    args = ap.parse_args()
    if args.json and not os.path.dirname(args.json):
        # bench artifacts live under runs/bench/ — a bare filename is a
        # request for the canonical location, not the repo root
        args.json = os.path.join("runs", "bench", args.json)
    if args.profile:
        os.environ["REPRO_PROFILE"] = args.profile
    if args.only:
        names = args.only.split(",")
        unknown = [n for n in names if n not in BENCHES]
        if unknown:
            ap.error(f"unknown bench(es) {unknown}; choose from "
                     + ",".join(BENCHES))
    else:
        names = [n for n in BENCHES if n not in CI_ONLY]  # CI-only rows

    tee = _RowTee(sys.stdout) if args.json else None
    if tee is not None:
        sys.stdout = tee
    print("name,us_per_call,derived")
    failed = 0
    try:
        for name in names:
            try:
                BENCHES[name](quick=not args.full)
            except Exception:
                failed += 1
                traceback.print_exc()
                print(f"{name},0,FAILED")
    finally:
        if tee is not None:
            sys.stdout = tee.real
            payload = {
                "schema": "bench-rows/v1",
                "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime()),
                "mode": "full" if args.full else "quick",
                "benches": names,
                "failed": failed,
                "rows": tee.rows,
            }
            d = os.path.dirname(args.json)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=1)
            print(f"wrote {len(tee.rows)} rows -> {args.json}",
                  file=sys.stderr)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()

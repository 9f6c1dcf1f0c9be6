"""Shard-scaling bench: the client-stacked data plane over growing meshes.

Weak scaling sweep: mesh size m in {1, 2, 4, 8} with N = base_n * m clients,
one child process per mesh size (the XLA host-platform device count is fixed
at backend init, so ``XLA_FLAGS=--xla_force_host_platform_device_count=m``
must be set before the child imports jax).  The children run on the CPU
only (``JAX_PLATFORMS=cpu``): on a TPU host the parent already holds the
chip.  Each child times the jitted
exchange-gate scoring program and a full FL segment (stacking + donated
rounds) with the client axis sharded per ``ShardingRules``, and checks
parity against the unsharded single-device program in-process.

Rows (per mesh size, own wall time per row):

    shard_gate_mesh{m}_n{N},<us>,mesh=..;clients=..;us_per_client=..;...
    shard_fl_mesh{m}_n{N},<us>,...
    shard_disc_mesh{m}_n{N},<us>,...

Derived fields carry the per-client cost ratio vs the mesh=1 row (weak
scaling: ~1.0 is flat) and the parity verdict — gate/pretrain are expected
*bit-identical* under sharding (per-client scoring has no cross-client
reduction); the FL round's FedAvg all-reduce and the discovery burst's two
reward collectives reassociate float sums, so their verdicts report max
float deltas instead (~1e-7), plus final-graph agreement for discovery.
The discovery row normalises by agent*episode (each episode is one scan
step of Algorithm 1), so ``per_agent_ep_vs_mesh1`` ~ 1.0 means flat weak
scaling of the re-discovery bursts.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

MESHES_QUICK = (1, 2, 4)
MESHES_FULL = (1, 2, 4, 8)
BASE_N_QUICK = 8
BASE_N_FULL = 16

_TAG = "SHARD_CHILD "


def _lab_cfg(n_clients: int, quick: bool):
    from repro.meshlab import LabConfig
    if quick:
        return LabConfig(n_clients=n_clients, n_per_client=40)
    return LabConfig(n_clients=n_clients, n_per_client=80, hw=28,
                     widths=(8, 16), latent=16, n_rounds=4)


def child_main(mesh: int, n_clients: int, quick: bool, iters: int) -> None:
    """Runs inside the subprocess with ``mesh`` visible devices."""
    from repro import meshlab as ML
    cfg = _lab_cfg(n_clients, quick)
    rep = ML.timing_report(cfg, mesh, iters=iters)
    par = ML.parity_report(cfg, mesh)
    tag = f"mesh{mesh}"
    rep["gate_bitwise"] = (par[f"gate_digest_{tag}"]
                           == par["gate_digest_base"])
    rep["pretrain_bitwise"] = (par[f"pretrain_digest_{tag}"]
                               == par["pretrain_digest_base"])
    rep["mesh1_bitwise"] = all(
        par[f"{p}_digest_mesh1"] == par[f"{p}_digest_base"]
        for p in ("gate", "pretrain", "fl", "cluster",
                  "disc", "disc_ucb", "disc_warm"))
    rep["fl_maxdiff"] = par[f"fl_maxdiff_{tag}"]
    rep["disc_q_maxdiff"] = par[f"disc_q_maxdiff_{tag}"]
    rep["disc_edge_agree"] = par[f"disc_edge_agree_{tag}"]
    rep["cluster_loop_bitwise"] = par["cluster_loop_bitwise"]
    rep["cluster_cents_maxdiff"] = par[f"cluster_cents_maxdiff_{tag}"]
    print(_TAG + json.dumps(rep), flush=True)


def _spawn(mesh: int, n_clients: int, quick: bool, iters: int) -> dict:
    # Children run on forced host devices, on the CPU only: they are spawned
    # after the parent's other benches touched JAX, so a parent on a chip
    # holds it and a child that asked for it would fail or hang.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={mesh}")
    cmd = [sys.executable, "-m", "benchmarks.shard_scaling", "--child",
           "--mesh", str(mesh), "--clients", str(n_clients),
           "--iters", str(iters)] + ([] if quick else ["--full"])
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1800)
    for line in proc.stdout.splitlines():
        if line.startswith(_TAG):
            return json.loads(line[len(_TAG):])
    raise RuntimeError(
        f"shard_scaling child (mesh={mesh}) produced no report:\n"
        f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")


def main(quick: bool = True) -> None:
    meshes = MESHES_QUICK if quick else MESHES_FULL
    base_n = BASE_N_QUICK if quick else BASE_N_FULL
    iters = 5 if quick else 10
    reports = {m: _spawn(m, base_n * m, quick, iters) for m in meshes}
    ref = reports[meshes[0]]
    for m in meshes:
        r = reports[m]
        n = r["n_clients"]
        gate_ratio = r["gate_us_per_client"] / ref["gate_us_per_client"]
        fl_ratio = r["fl_us_per_client"] / ref["fl_us_per_client"]
        common = (f"mesh={m};clients={n};devices={r['device_count']};"
                  f"mesh1_bitwise={r['mesh1_bitwise']}")
        print(f"shard_gate_mesh{m}_n{n},{r['gate_us']:.0f},{common};"
              f"us_per_client={r['gate_us_per_client']:.1f};"
              f"per_client_vs_mesh1={gate_ratio:.2f};"
              f"sharded_bitwise={r['gate_bitwise']};"
              f"pretrain_bitwise={r['pretrain_bitwise']}")
        disc_ratio = (r["disc_us_per_agent_episode"]
                      / ref["disc_us_per_agent_episode"])
        cluster_ratio = (r["cluster_us_per_client"]
                         / ref["cluster_us_per_client"])
        print(f"shard_fl_mesh{m}_n{n},{r['fl_segment_us']:.0f},{common};"
              f"us_per_client={r['fl_us_per_client']:.1f};"
              f"per_client_vs_mesh1={fl_ratio:.2f};"
              f"fl_maxdiff_vs_single={r['fl_maxdiff']:.2e}")
        print(f"shard_cluster_mesh{m}_n{n},{r['cluster_us']:.0f},{common};"
              f"us_per_client={r['cluster_us_per_client']:.1f};"
              f"per_client_vs_mesh1={cluster_ratio:.2f};"
              f"loop_bitwise={r['cluster_loop_bitwise']};"
              f"cents_maxdiff_vs_single={r['cluster_cents_maxdiff']:.2e}")
        print(f"shard_disc_mesh{m}_n{n},{r['disc_us']:.0f},{common};"
              f"episodes={r['rl_episodes']};"
              f"us_per_agent_ep={r['disc_us_per_agent_episode']:.2f};"
              f"per_agent_ep_vs_mesh1={disc_ratio:.2f};"
              f"disc_q_maxdiff_vs_single={r['disc_q_maxdiff']:.2e};"
              f"disc_edge_agree={r['disc_edge_agree']}/{n}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--mesh", type=int, default=1)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.child:
        child_main(args.mesh, args.clients, not args.full, args.iters)
    else:
        main(quick=not args.full)

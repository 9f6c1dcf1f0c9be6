"""Chip smoke test: the paper's federation loop on a TPU, in one process.

    python chip_smoke.py              # one chip: device, kernels, loop
    python chip_smoke.py --chips 4    # CLIENTS-sharded loop over 4 chips

One chip, three phases; any failed phase exits nonzero:

  a. device  — the first JAX device must be a TPU.  There is no CPU
               fallback: on any other backend the script stops here.
  b. kernels — ``ops.kmeans_assign`` and ``ops.recon_gate_score`` against
               their ``kernels/ref.py`` oracles at the loop's shapes
               (assignments equal, gate scores within ``GATE_RTOL``), and
               the jitted clustering and gate programs must contain
               ``tpu_custom_call``: the compiled Pallas kernel runs, neither
               interpreted nor replaced by the oracle.
  c. loop    — ``run_orchestrator(segment_impl="scan", scenario="fading")``
               at the paper's setting (ROADMAP D1): 30 clients holding 3
               FMNIST-shaped classes each (``BenchConfig.full()`` data), the
               AE at its own widths (``AEConfig()``), RL with E=600, M=90.
               Run cold, then warm, then on the eager engine with the same
               key and exchange config; the final eval losses of the two
               engines must agree within ``LOSS_ATOL``.

``--chips 4`` runs only the sharded path and its comparison: the same loop
with 32 clients (a count that divides the 4-way mesh) under
``ShardingRules.default(make_client_mesh())``, and on one device.  The
client stack must be split over all four chips, and the two runs must meet
the README's mesh=4 parity bounds.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
it is printed only when every phase passed.  The persistent compilation
cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` in
the checkout (``repro.launch.compile_cache``), so a second run in the same
checkout compiles less.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

# The loop's engines are one computation fused two ways; the repo's own
# scan-vs-eager contract (benchmarks/dynamic_scenarios.py) bounds the final
# loss difference by 1e-4.
LOSS_ATOL = 1e-4
# Gate scores: the kernel sums the same squared errors in another order.
GATE_RTOL, GATE_ATOL = 1e-5, 1e-6
# min_d2 of the assignment kernel: only the MXU cross term's accumulation
# order differs from the oracle.
KMEANS_D2_RTOL, KMEANS_D2_ATOL = 1e-4, 1e-4
N_SEGMENTS, ITERS_PER_SEGMENT = 3, 20

# README "Running multi-device", mesh=4: the discovered graph is unchanged,
# the gate is exact (so the same datapoints move), and the FedAvg
# all-reduce reassociates: tests/test_mesh_parity.py bounds the parameter
# delta of one FL segment by 1e-5, so a run of N_SEGMENTS segments is held
# to N_SEGMENTS times that.
MESH4_PARAM_ATOL = 1e-5 * N_SEGMENTS


class PhaseFailed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def say(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# compile accounting: JAX fires this event around every compile-or-load of
# a program it has not yet traced in this process, persistent-cache hits
# included, so the count stays put and the seconds show what the cache saved
# ---------------------------------------------------------------------------

class CompileMeter:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **_):
        if event == self.EVENT:
            self.n += 1
            self.secs += duration_secs

    def snapshot(self):
        return self.n, self.secs


# ---------------------------------------------------------------------------
# phase a: the device
# ---------------------------------------------------------------------------

def phase_device(jax, chips: int):
    devs = jax.devices()
    d0 = devs[0]
    say(f"[a] devices: {len(devs)} x {d0.platform} ({d0.device_kind})")
    check(d0.platform == "tpu",
          f"no TPU found: JAX's first device is {d0.platform!r}; this "
          "script never falls back to another backend")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} TPU devices, "
          f"found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# the world (ROADMAP D1) and the loop's config
# ---------------------------------------------------------------------------

def make_d1_world(n_clients: int):
    from benchmarks import common as C
    from repro.core.batching import as_client_data
    from repro.models.autoencoder import AEConfig

    bc = dataclasses.replace(C.BenchConfig.full(), n_clients=n_clients)
    key, xs, ys, ev, _cut_ae = C.make_world(bc, "fmnist")
    return {"bc": bc, "key": key, "cd": as_client_data(xs, ys),
            "eval": ev.images, "ae_cfg": AEConfig()}


def loop_config(bc, segment_impl: str):
    from repro.core.exchange import ExchangeConfig
    from repro.core.pipeline import PipelineConfig
    from repro.core.qlearning import RLConfig
    from repro.dynamics import OrchestratorConfig
    from repro.fl import FLConfig

    return OrchestratorConfig(
        n_segments=N_SEGMENTS, iters_per_segment=ITERS_PER_SEGMENT,
        mode="online", burst_episodes=max(bc.rl_episodes // 4, 50),
        pipeline=PipelineConfig(
            rl=RLConfig(n_episodes=bc.rl_episodes, buffer_size=bc.rl_buffer),
            exchange=ExchangeConfig(apply_channel_failure=True,
                                    overflow="drop",
                                    reserve_selector="device")),
        fl=FLConfig(tau_a=bc.tau_a, eval_every=bc.tau_a,
                    batch_size=bc.batch_size),
        segment_impl=segment_impl)


# ---------------------------------------------------------------------------
# phase b: kernels against their oracles, and present in the programs
# ---------------------------------------------------------------------------

def phase_kernels(jax, world):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import exchange as ex
    from repro.core import pipeline
    from repro.kernels import ops, ref
    from repro.models import autoencoder as ae

    cd, ae_cfg = world["cd"], world["ae_cfg"]
    pcfg = loop_config(world["bc"], "scan").pipeline
    n, cap = cd.n_clients, cd.cap
    k, r = pcfg.n_clusters, pcfg.exchange.reserve_per_cluster
    key = jax.random.PRNGKey(1)

    # -- K-means assignment at the clustering program's shapes: the
    # (N, cap, n_pca) PCA features of the client stack and the centroids the
    # clustering program fits (the loop's segment 0 reuses this compile)
    cluster_args = (key, cd.data, cd.sizes, pcfg.n_pca, k, pcfg.kmeans_iters,
                    None)
    pca, cents, _ = pipeline._cluster_impl(*cluster_args)
    flats = cd.data.reshape(n, cap, -1)
    mask = cd.mask(flats.dtype)
    z = pca.transform(flats)
    a_k, d_k = jax.jit(jax.vmap(ops.kmeans_assign))(z, cents)
    a_r, d_r = jax.jit(jax.vmap(ref.kmeans_assign_ref))(z, cents)
    a_k, a_r = np.asarray(a_k), np.asarray(a_r)
    n_diff = int(np.sum(a_k != a_r))
    d2_err = float(np.max(np.abs(np.asarray(d_k) - np.asarray(d_r))))
    say(f"[b] kmeans_assign (N={n}, cap={cap}, d={pcfg.n_pca}, k={k}): "
        f"{a_k.size - n_diff}/{a_k.size} assignments equal the oracle, "
        f"max |min_d2 diff| = {d2_err:.3e}")
    check(n_diff == 0, f"{n_diff} kmeans assignments differ from the oracle")
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                               rtol=KMEANS_D2_RTOL, atol=KMEANS_D2_ATOL)

    # -- gate scores at both call sites: the base score over each client's
    # whole stack (R = cap) and the (receiver, cluster) reserve scores
    params = jax.vmap(lambda kk: ae.init_ae(kk, ae_cfg))(
        jax.random.split(key, n))
    recon = jax.jit(jax.vmap(lambda p, x: ae.reconstruct(p, x, ae_cfg)))
    own = cd.data
    y_own = recon(params, own)
    picks = jax.random.randint(key, (k * r,), 0, cap)   # reserves may
    cand = own[:, picks].reshape((n, k, r) + own.shape[2:])  # repeat rows
    y_cand = recon(params, cand.reshape((n, k * r) + own.shape[2:])
                   ).reshape(cand.shape)
    cand_mask = (jax.random.uniform(key, (n, k, r)) < 0.8).astype(
        jnp.float32)
    for name, y, x, m in (
            ("base", y_own.reshape(n, cap, -1), own.reshape(n, cap, -1),
             mask),
            ("reserve", y_cand.reshape(n, k, r, -1),
             cand.reshape(n, k, r, -1), cand_mask)):
        s_k = np.asarray(jax.jit(ops.recon_gate_score)(y, x, m))
        s_r = np.asarray(jax.jit(ref.recon_gate_ref)(y, x, m))
        rel = float(np.max(np.abs(s_k - s_r) / np.maximum(np.abs(s_r),
                                                           1e-30)))
        say(f"[b] recon_gate_score {name} {tuple(y.shape)}: max rel diff "
            f"{rel:.3e} (rtol {GATE_RTOL}, atol {GATE_ATOL})")
        np.testing.assert_allclose(s_k, s_r, rtol=GATE_RTOL, atol=GATE_ATOL)

    # -- the kernels inside the jitted programs the loop runs
    cluster_text = pipeline._cluster_impl.lower(*cluster_args).as_text()
    gate_text = ex._gate_scores.lower(
        params, own, mask, cand, cand_mask, jnp.ones((n, k), bool),
        jnp.zeros((n,)), jnp.zeros((n, n)), jnp.arange(n), ae_cfg, True,
        None).as_text()
    for name, text in (("clustering", cluster_text), ("gate", gate_text)):
        found = "tpu_custom_call" in text
        say(f"[b] {name} program contains tpu_custom_call: {found}")
        check(found, f"the jitted {name} program has no Pallas kernel")


# ---------------------------------------------------------------------------
# phase c: the loop
# ---------------------------------------------------------------------------

def run_loop(jax, meter, world, segment_impl, rules=None, label=""):
    import numpy as np

    from repro.dynamics import run_orchestrator

    cfg = loop_config(world["bc"], segment_impl)
    n0, s0 = meter.snapshot()
    t0 = time.perf_counter()
    res = run_orchestrator(world["key"], world["cd"], None, world["ae_cfg"],
                           cfg, "fading", world["eval"], rules=rules)
    jax.block_until_ready((res.global_params, res.carry,
                           res.client_data.data))
    wall = time.perf_counter() - t0
    n1, s1 = meter.snapshot()
    summ = res.trace.summary()
    loss = float(summ["final_loss"])
    say(f"[c] {label or segment_impl}: wall {wall:.3f} s, compiles "
        f"{n1 - n0} ({s1 - s0:.3f} s compiling), moved "
        f"{summ['total_moved']}, final eval loss {loss!r}, "
        f"eval losses {[float(v) for v in np.asarray(res.eval_loss)]}")
    check(np.isfinite(loss), f"{label}: final loss is not finite")
    return res, summ


def phase_loop(jax, meter, world):
    from repro.dynamics.orchestrator import _chunk_bounds

    n_chunks = len(_chunk_bounds(loop_config(world["bc"], "scan"), None, 1,
                                 None))
    say(f"[c] loop: {world['cd'].n_clients} clients, cap {world['cd'].cap},"
        f" AE widths {world['ae_cfg'].widths} latent "
        f"{world['ae_cfg'].latent_dim}, RL E={world['bc'].rl_episodes} "
        f"M={world['bc'].rl_buffer}, {N_SEGMENTS} segments x "
        f"{ITERS_PER_SEGMENT} iters ({n_chunks} scan chunk)")
    _, cold = run_loop(jax, meter, world, "scan", label="scan cold")
    _, warm = run_loop(jax, meter, world, "scan", label="scan warm")
    _, eager = run_loop(jax, meter, world, "eager", label="eager")
    diff = abs(warm["final_loss"] - eager["final_loss"])
    say(f"[c] scan vs eager final loss: |diff| = {diff:.3e} "
        f"(atol {LOSS_ATOL})")
    check(cold["final_loss"] == warm["final_loss"],
          "cold and warm scan runs disagree on the same key")
    check(diff <= LOSS_ATOL, "scan engine diverged from the eager engine")


# ---------------------------------------------------------------------------
# --chips 4: the CLIENTS-sharded loop and its one-device comparison
# ---------------------------------------------------------------------------

def phase_mesh4(jax, meter):
    import numpy as np

    from repro import sharding as sh
    from repro.launch.mesh import make_client_mesh

    world = make_d1_world(32)
    mesh = make_client_mesh(4)
    rules = sh.ShardingRules.default(mesh)
    say(f"[m] mesh {dict(mesh.shape)} over "
        f"{[d.id for d in mesh.devices.flat]}, {world['cd'].n_clients} "
        f"clients, cap {world['cd'].cap}")
    res4, s4 = run_loop(jax, meter, world, "scan", rules, "scan mesh=4")
    res1, s1 = run_loop(jax, meter, world, "scan", None, "scan 1 device")

    stack = res4.client_data.data
    per_dev = {s.device.id: s.data.nbytes for s in stack.addressable_shards}
    say(f"[m] client stack {tuple(stack.shape)} {stack.nbytes} B; bytes per "
        f"device: {per_dev}")
    check(len(per_dev) == 4 and
          all(b == stack.nbytes // 4 for b in per_dev.values()),
          "the client stack is not split evenly over the four chips")

    p_diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(jax.tree.leaves(res4.global_params),
                                 jax.tree.leaves(res1.global_params)))
    edges_equal = bool(np.array_equal(np.asarray(res4.in_edge),
                                      np.asarray(res1.in_edge)))
    say(f"[m] mesh=4 vs 1 device: max |param diff| {p_diff:.3e} "
        f"(atol {MESH4_PARAM_ATOL:g}), final graph equal: {edges_equal}, "
        f"moved {s4['total_moved']} vs {s1['total_moved']}, final loss "
        f"{s4['final_loss']!r} vs {s1['final_loss']!r}")
    check(edges_equal, "mesh=4 discovered a different graph")
    check(s4["total_moved"] == s1["total_moved"],
          "mesh=4 moved a different number of datapoints")
    check(p_diff <= MESH4_PARAM_ATOL, "mesh=4 params beyond the FL bound")
    check(abs(s4["final_loss"] - s1["final_loss"]) <= LOSS_ATOL,
          "mesh=4 final loss diverged from the one-device run")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    try:
        device = phase_device(jax, args.chips)
        from repro.launch.compile_cache import enable_compile_cache
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    except ImportError as e:
        print(f"chip_smoke: FAILED: the repo's sources are not next to "
              f"this script ({e})", file=sys.stderr)
        return 1

    say(f"[a] compilation cache: {enable_compile_cache()}")
    meter = CompileMeter(jax)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_mesh4(jax, meter)
        else:
            world = make_d1_world(30)
            phase_kernels(jax, world)
            phase_loop(jax, meter, world)
    except (PhaseFailed, AssertionError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    n, secs = meter.snapshot()
    say(f"[-] total: {time.perf_counter() - t0:.3f} s, {n} backend "
        f"compiles, {secs:.3f} s compiling")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

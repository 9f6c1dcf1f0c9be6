"""Mesh lab: a deterministic client world + the client-stacked hot paths
(clustering, AE pretraining, exchange-gate scoring, FL rounds, RL graph
discovery) runnable with or without :class:`~repro.sharding.ShardingRules`.

Shared by ``benchmarks/shard_scaling.py`` and the multi-device parity tests
(``tests/test_mesh_parity.py``): both spawn children under
``XLA_FLAGS=--xla_force_host_platform_device_count=K`` — the device count is
baked into the process at backend init, so sweeping mesh sizes means one
process per size — and compare outputs / wall time across mesh sizes.

All randomness flows from ``jax.random`` (counter-based), so the same
``LabConfig`` builds bit-identical worlds in every child regardless of its
device count.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as sh
from repro.core import channel as ch
from repro.core import exchange as ex
from repro.core import qlearning as ql
from repro.core import rewards as rw
from repro.core import trust as tr
from repro.core.batching import as_client_data
from repro.core.pipeline import (PipelineConfig, cluster_clients,
                                 cluster_clients_loop)
from repro.core.qlearning import uniform_graph
from repro.fl.trainer import FLConfig, fl_train
from repro.launch.mesh import make_client_mesh
from repro.models.autoencoder import AEConfig


@dataclasses.dataclass(frozen=True)
class LabConfig:
    n_clients: int = 8
    n_per_client: int = 40
    n_clusters: int = 3
    reserve: int = 8
    hw: int = 16                   # image height == width
    widths: tuple = (4, 8)
    latent: int = 8
    tau_a: int = 5
    n_rounds: int = 2
    batch_size: int = 16
    rl_episodes: int = 120         # discovery program length (one burst)
    rl_buffer: int = 30
    seed: int = 0

    @property
    def ae_cfg(self) -> AEConfig:
        return AEConfig(self.hw, self.hw, 1, widths=self.widths,
                        latent_dim=self.latent)


def make_rules(mesh_size: int | None) -> sh.ShardingRules | None:
    """ShardingRules over a (data=mesh_size,) mesh; ``None`` -> unsharded."""
    if mesh_size is None:
        return None
    return sh.ShardingRules.default(make_client_mesh(mesh_size))


def build_world(cfg: LabConfig) -> dict:
    """Datasets, cluster assignments, trust, graph and channel for N
    clients — everything the exchange gate and the FL trainer consume.

    Client sizes are *ragged* (n_per_client minus a per-client offset) so
    every stacked program exercises the mask-padded plane, not just the
    trivially rectangular case."""
    key = jax.random.PRNGKey(cfg.seed)
    k_data, k_assign, k_tr, k_ch, k_g, k_ex, k_fl = jax.random.split(key, 7)
    n = cfg.n_clients
    sizes = [max(cfg.n_per_client - 3 * (i % 4), 4) for i in range(n)]
    datasets = [
        jax.random.uniform(jax.random.fold_in(k_data, i),
                           (sizes[i], cfg.hw, cfg.hw, 1))
        for i in range(n)]
    assignments = [
        jax.random.randint(jax.random.fold_in(k_assign, i),
                           (sizes[i],), 0, cfg.n_clusters)
        for i in range(n)]
    trust = tr.make_trust(k_tr, n, cfg.n_clusters, 0.9)
    rss = ch.make_rss(k_ch, n)
    p_fail = ch.failure_prob(rss)
    in_edge = uniform_graph(k_g, n)
    eval_data = jax.random.uniform(jax.random.fold_in(k_data, n),
                                   (32, cfg.hw, cfg.hw, 1))
    # Discovery-plane operands (keys folded, not split, so the draws above
    # stay identical to pre-discovery lab worlds): a synthetic dissimilarity
    # matrix through the real Eq. 2 reward map.
    lam = jax.random.uniform(jax.random.fold_in(key, 100), (n, n))
    local_r = rw.local_reward_matrix(lam, p_fail)
    return {"cfg": cfg, "datasets": datasets, "assignments": assignments,
            "trust": trust, "p_fail": p_fail, "in_edge": in_edge,
            "eval_data": eval_data, "local_r": local_r,
            "k_ex": k_ex, "k_fl": k_fl,
            "k_rl": jax.random.fold_in(key, 101),
            "k_cl": jax.random.fold_in(key, 102),
            "cluster_data": _cluster_world(jax.random.fold_in(key, 103),
                                           cfg, sizes)}


def _cluster_world(key, cfg: LabConfig, sizes) -> list:
    """Structured (blobby) ragged datasets for the clustering programs.

    The cluster parity contract at mesh>1 is a <=1e-6 centroid drift under
    the PCA moment all-reduce's float reassociation.  That bound is only
    meaningful on data whose covariance has healthy eigengaps: pure uniform
    noise (the gate/FL world) has a near-degenerate spectrum whose eigh
    basis rotates wholesale under 1e-7 moment perturbations.  Six shared
    prototype patterns + small noise give a rank-5 between-proto scatter
    with generically separated eigenvalues, so the retained basis — and
    everything downstream of it — is stable under the collective.  Samples
    are scaled to ~unit flattened norm: the reassociation drift in the
    basis projection is relative (~1e-7 of the sample norm), so unit scale
    is what makes the absolute <=1e-6 centroid bound the tight, meaningful
    statement of that contract."""
    scale = 1.0 / cfg.hw
    protos = jax.random.normal(jax.random.fold_in(key, 0),
                               (6, cfg.hw, cfg.hw, 1)) * scale
    out = []
    for i, s in enumerate(sizes):
        ids = jax.random.randint(jax.random.fold_in(key, 1 + i), (s,), 0, 6)
        noise = jax.random.normal(jax.random.fold_in(key, 100 + i),
                                  (s, cfg.hw, cfg.hw, 1))
        out.append(protos[ids] + 0.05 * scale * noise)
    return out


# ---------------------------------------------------------------------------
# the three hot paths
# ---------------------------------------------------------------------------

def run_pretrain(world, rules):
    """Vmapped one-step AE pretraining over the (sharded) client stack."""
    cfg: LabConfig = world["cfg"]
    return ex.pretrain_autoencoders_batched(
        world["k_ex"], world["datasets"], cfg.ae_cfg,
        ex.ExchangeConfig(reserve_per_cluster=cfg.reserve), rules)


def gate_operands(world, rules):
    """Assemble the exchange program's operands once (host-side work is
    index-only: reserve indices, the stacked trust tensor and the placed
    ClientData)."""
    cfg: LabConfig = world["cfg"]
    n = cfg.n_clients
    _k_pre, k_sel, k_ch = jax.random.split(world["k_ex"], 3)
    trust_np = [np.asarray(t) for t in world["trust"]]
    k_max = max(t.shape[1] for t in trust_np)
    sel = ex._select_reserves(k_sel, world["assignments"],
                              [t.shape[1] for t in trust_np], cfg.reserve)
    sel_idx, sel_mask = ex._sel_tensors(sel, n, k_max, cfg.reserve)
    trust_s = ex._stack_trust_padded(trust_np, n, k_max)
    fail_u = jax.random.uniform(k_ch, (n,))
    cd = as_client_data(world["datasets"], rules=rules)
    # grow-policy headroom, from the host mask *before* placement (same
    # formula as _gate_batched)
    out_cap = cd.cap + int(sel_mask.sum(axis=(1, 2)).max(initial=0))
    sel_idx, sel_mask, trust_s, fail_u, in_edge = sh.shard_clients(
        (jnp.asarray(sel_idx), jnp.asarray(sel_mask), jnp.asarray(trust_s),
         fail_u, jnp.asarray(world["in_edge"])), rules)
    return (cd, sel_idx, sel_mask, trust_s, fail_u, in_edge, out_cap)


def run_gate(world, params, operands, rules):
    """One jitted exchange program (gather reserves -> score the gate ->
    scatter accepted rows): returns (new ClientData, moved, base, scores,
    fail, accept, overflowed)."""
    cfg: LabConfig = world["cfg"]
    cd, sel_idx, sel_mask, trust_s, fail_u, in_edge, out_cap = operands
    return ex._exchange_device(cfg.ae_cfg, False, out_cap, rules, params,
                               cd.data, cd.sizes, cd.labels, sel_idx,
                               sel_mask, trust_s, fail_u, world["p_fail"],
                               in_edge)


def run_fl_segment(world, rules):
    """A short FL segment (``n_rounds`` aggregation rounds) from scratch."""
    cfg: LabConfig = world["cfg"]
    flcfg = FLConfig(total_iters=cfg.tau_a * cfg.n_rounds, tau_a=cfg.tau_a,
                     eval_every=cfg.tau_a * cfg.n_rounds,
                     batch_size=cfg.batch_size)
    res = fl_train(world["k_fl"], world["datasets"], cfg.ae_cfg, flcfg,
                   world["eval_data"], rules=rules)
    return res.global_params, res.client_params


def _pipe_cfg(cfg: LabConfig) -> PipelineConfig:
    # n_pca=4 < the cluster world's rank-5 proto scatter, so every retained
    # component sits above the noise floor (see _cluster_world)
    return PipelineConfig(n_pca=4, n_clusters=cfg.n_clusters,
                          kmeans_iters=10)


def run_cluster(world, rules):
    """The jitted stacked clustering program (masked federated PCA +
    vmapped K-means++) on the ragged structured lab datasets.  Returns
    (components, centroids, assignments)."""
    cfg: LabConfig = world["cfg"]
    pca, cents, assigns = cluster_clients(world["k_cl"],
                                          world["cluster_data"],
                                          _pipe_cfg(cfg), rules=rules)
    return pca.components, cents, assigns


def run_cluster_loop(world):
    """The per-client host-loop reference of the same masked math — the
    stacked program must match it bit-for-bit."""
    cfg: LabConfig = world["cfg"]
    pca, cents, assigns = cluster_clients_loop(world["k_cl"],
                                               world["cluster_data"],
                                               _pipe_cfg(cfg))
    return pca.components, cents, assigns


def _rl_cfg(cfg: LabConfig, policy: str, episodes=None) -> ql.RLConfig:
    return ql.RLConfig(n_episodes=cfg.rl_episodes if episodes is None
                       else episodes,
                       buffer_size=cfg.rl_buffer, policy=policy)


def run_discovery(world, rules, policy: str = "mixed") -> ql.GraphResult:
    """One cold-start RL discovery burst (Algorithm 1) with the agent axis
    placed per ``rules``."""
    cfg: LabConfig = world["cfg"]
    return ql.discover_graph(world["k_rl"], world["local_r"],
                             world["p_fail"], _rl_cfg(cfg, policy),
                             rules=rules)


def run_discovery_warm(world, rules, policy: str = "mixed") -> ql.GraphResult:
    """Two chained bursts: a cold half followed by a burst warm-started
    from its mesh-placed ``GraphResult.state`` — the online orchestrator's
    re-discovery pattern."""
    cfg: LabConfig = world["cfg"]
    half = cfg.rl_episodes // 2
    first = ql.discover_graph(world["k_rl"], world["local_r"],
                              world["p_fail"], _rl_cfg(cfg, policy),
                              n_episodes=half, rules=rules)
    return ql.discover_graph(jax.random.fold_in(world["k_rl"], 1),
                             world["local_r"], world["p_fail"],
                             _rl_cfg(cfg, policy), init_state=first.state,
                             n_episodes=cfg.rl_episodes - half, rules=rules)


# ---------------------------------------------------------------------------
# parity + timing harness (runs inside one child process)
# ---------------------------------------------------------------------------

def digest(tree) -> str:
    """sha256 over the concatenated little-endian bytes of all leaves."""
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    return h.hexdigest()


def max_abs_diff(a, b) -> float:
    return max(float(jnp.max(jnp.abs(jnp.asarray(x) - jnp.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def parity_report(cfg: LabConfig, mesh_size: int) -> dict:
    """Run every path unsharded, at mesh=1, and at ``mesh_size``; report
    bit-parity (digests) and max float deltas.

    Discovery programs: ``disc`` (paper Eq. 4 mixed policy), ``disc_ucb``
    (deterministic UCB1) and ``disc_warm`` (a burst resumed from a
    mesh-placed warm-start state).  At mesh>1 their two collectives (the
    episode-mean reward and r_net) reassociate float sums, so — like the FL
    round — parity there is a Q-table delta plus final-edge agreement, not
    bit equality.  The clustering program's single collective (the PCA
    moment ``client_sum``) reassociates the same way at mesh>1, so its
    sharded verdict is a centroid delta + assignment agreement; at mesh=1
    (and vs the per-client host loop, ``cluster_loop_bitwise``) it is
    bit-identical."""
    world = build_world(cfg)
    out = {"device_count": len(jax.devices()), "mesh_size": mesh_size}
    discoveries = (("disc", lambda r: run_discovery(world, r, "mixed")),
                   ("disc_ucb", lambda r: run_discovery(world, r, "ucb")),
                   ("disc_warm", lambda r: run_discovery_warm(world, r)))

    ref = {}
    for tag, rules in (("base", None), ("mesh1", make_rules(1)),
                       (f"mesh{mesh_size}", make_rules(mesh_size))):
        params = run_pretrain(world, rules)
        operands = gate_operands(world, rules)
        gate = run_gate(world, params, operands, rules)
        gp, cp = run_fl_segment(world, rules)
        cluster = run_cluster(world, rules)
        graphs = {name: fn(rules) for name, fn in discoveries}
        out[f"pretrain_digest_{tag}"] = digest(params)
        out[f"gate_digest_{tag}"] = digest(gate)
        out[f"fl_digest_{tag}"] = digest((gp, cp))
        out[f"cluster_digest_{tag}"] = digest(cluster)
        for name, g in graphs.items():
            out[f"{name}_digest_{tag}"] = digest((g.in_edge, g.state))
        if tag == "base":
            ref = {"params": params, "gate": gate, "gp": gp,
                   "cluster": cluster, "graphs": graphs}
            out["cluster_loop_bitwise"] = (digest(run_cluster_loop(world))
                                           == out["cluster_digest_base"])
        else:
            out[f"pretrain_maxdiff_{tag}"] = max_abs_diff(ref["params"],
                                                          params)
            out[f"gate_maxdiff_{tag}"] = max_abs_diff(ref["gate"][2:4],
                                                      gate[2:4])
            out[f"fl_maxdiff_{tag}"] = max_abs_diff(ref["gp"], gp)
            out[f"cluster_cents_maxdiff_{tag}"] = float(
                jnp.max(jnp.abs(ref["cluster"][1] - cluster[1])))
            out[f"cluster_assign_agree_{tag}"] = int(
                jnp.sum(ref["cluster"][2] == cluster[2]))
            out[f"cluster_assign_total_{tag}"] = int(cluster[2].size)
            for name, g in graphs.items():
                rg = ref["graphs"][name]
                out[f"{name}_q_maxdiff_{tag}"] = float(
                    jnp.max(jnp.abs(rg.q - g.q)))
                out[f"{name}_edge_agree_{tag}"] = int(
                    jnp.sum(rg.in_edge == g.in_edge))
    return out


def time_path(fn, *, iters: int = 5) -> float:
    """Mean wall-clock us per call after one warmup (compile) call."""
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def timing_report(cfg: LabConfig, mesh_size: int | None,
                  iters: int = 5) -> dict:
    """Wall-time the gate program, one FL round and a discovery burst at
    the given mesh size (None -> the plain unsharded path)."""
    world = build_world(cfg)
    rules = make_rules(mesh_size)
    params = run_pretrain(world, rules)
    operands = gate_operands(world, rules)

    gate_us = time_path(
        lambda: run_gate(world, params, operands, rules), iters=iters)

    # FL: time a full fl_train segment (stacking + n_rounds donated rounds)
    fl_us = time_path(lambda: run_fl_segment(world, rules)[0],
                      iters=max(iters // 2, 2))

    # Discovery: one cold RL burst (the orchestrator's re-discovery shape)
    disc_us = time_path(lambda: run_discovery(world, rules),
                        iters=max(iters // 2, 2))

    # Clustering: the jitted stacked program (the re-discovery segment's
    # first stage — previously a host-side per-client loop)
    cluster_us = time_path(lambda: run_cluster(world, rules), iters=iters)

    return {"device_count": len(jax.devices()),
            "mesh_size": 0 if mesh_size is None else mesh_size,
            "n_clients": cfg.n_clients,
            "gate_us": gate_us, "fl_segment_us": fl_us,
            "disc_us": disc_us, "rl_episodes": cfg.rl_episodes,
            "cluster_us": cluster_us,
            "gate_us_per_client": gate_us / cfg.n_clients,
            "fl_us_per_client": fl_us / cfg.n_clients,
            "cluster_us_per_client": cluster_us / cfg.n_clients,
            "disc_us_per_agent_episode":
                disc_us / (cfg.n_clients * cfg.rl_episodes)}

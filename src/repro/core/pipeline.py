"""End-to-end smart-exchange pipeline (paper Algorithms 1 + 2 wiring).

    PCA (federated basis) -> K-means++ per client -> trust + channel ->
    lambda matrix -> rewards -> RL graph discovery -> AE-gated exchange.

Array-first client plane: the canonical client representation is a
:class:`repro.core.batching.ClientData` stack built **once** at the API
boundary (ragged lists are accepted for compatibility and converted exactly
once) and threaded through clustering, exchange and back out.  The whole
clustering stage — masked federated PCA moments + vmapped K-means++ —
is one jitted device program (:func:`cluster_clients`) whose client axis
shards over the CLIENTS mesh: per-client fits stay on their shard and the
only collective is the PCA moment all-reduce (``sharding.client_sum``).

Returns everything the benchmarks need (heatmaps, link stats, new datasets).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro import sharding as sh
from repro.core import channel as ch
from repro.core import dissimilarity as ds
from repro.core import exchange as ex
from repro.core import kmeans as km
from repro.core import pca as pca_lib
from repro.core import qlearning as ql
from repro.core import rewards as rw
from repro.core import trust as tr
from repro.core.batching import ClientData, as_client_data
from repro.models.autoencoder import AEConfig


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    n_pca: int = 32
    n_clusters: int = 3            # k_i (paper: 3 classes per device)
    kmeans_iters: int = 25
    beta: Optional[float] = None   # None -> median heuristic
    beta_scale: float = 0.8
    p_trust: float = 0.9
    reward: rw.RewardConfig = dataclasses.field(default_factory=rw.RewardConfig)
    rl: ql.RLConfig = dataclasses.field(default_factory=ql.RLConfig)
    channel: ch.ChannelConfig = dataclasses.field(default_factory=ch.ChannelConfig)
    exchange: ex.ExchangeConfig = dataclasses.field(default_factory=ex.ExchangeConfig)


@dataclasses.dataclass
class PipelineResult:
    """One-shot pipeline output.  ``client_data`` is the device-resident
    post-exchange stack (the orchestrator threads it onward without a host
    round-trip); ``datasets``/``labels`` lazily materialise the ragged list
    view for host-side consumers."""
    client_data: ClientData
    in_edge: jax.Array
    lam_before: jax.Array
    lam_after: jax.Array
    p_fail: jax.Array
    graph: ql.GraphResult
    centroids: jax.Array           # (N, k, d) pre-exchange stacked centroids
    trust: Optional[list] = None       # per-transmitter T_j matrices
    exchange: Optional[object] = None  # full ExchangeResult (gate decisions)

    @property
    def datasets(self) -> list:
        return self.client_data.data_list()

    @property
    def labels(self) -> Optional[list]:
        return self.client_data.label_list()

    @property
    def moved_counts(self):
        return self.exchange.moved_counts


class PipelineKeys(NamedTuple):
    """The five sub-keys ``run_pipeline`` derives from its key, exposed so
    external drivers (the dynamics orchestrator) can reproduce individual
    draws — e.g. seed the channel environment with ``k_ch`` and hand the
    resulting RSS back via ``run_pipeline(..., rss=...)`` bit-for-bit."""
    k_cl: jax.Array
    k_tr: jax.Array
    k_ch: jax.Array
    k_rl: jax.Array
    k_ex: jax.Array


def split_pipeline_keys(key) -> PipelineKeys:
    return PipelineKeys(*jax.random.split(key, 5))


# ---------------------------------------------------------------------------
# clustering plane (paper Sec. III): one jitted, CLIENTS-sharded program
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
@jax.named_scope("cluster")
def _cluster_impl(key, data, sizes, n_pca, n_clusters, kmeans_iters, rules):
    n, cap = data.shape[:2]
    flats = sh.constrain_clients(data.reshape(n, cap, -1), rules)
    mask = sh.constrain_clients(
        (jnp.arange(cap)[None, :] < sizes[:, None]).astype(flats.dtype),
        rules)
    pca = pca_lib.fit_pca_federated_stacked(flats, mask, n_pca, rules)
    z = sh.constrain_clients(pca.transform(flats), rules)
    res = km.kmeans_batched(key, z, sizes, n_clusters, kmeans_iters, rules)
    return pca, sh.constrain_clients(res.centroids, rules), \
        sh.constrain_clients(res.assignments, rules)


def cluster_clients(key, datasets, cfg: PipelineConfig, rules=None):
    """Shared-basis federated PCA + per-client K-means++ over the stacked
    client plane.

    ``datasets`` may be a ragged per-client list (converted once) or a
    :class:`ClientData`.  Returns ``(pca, centroids, assignments)``:

      * ``pca`` — the shared :class:`repro.core.pca.PCA` basis fitted from
        the masked per-client moment sums.  The orchestrator re-runs this
        whole function on the *current* (post-exchange) datasets at every
        re-discovery, so each segment's centroids live in that segment's own
        refreshed basis — the returned PCA is what keeps the Eq. 7
        lambda comparison meaningful as the data distribution drifts.
      * ``centroids`` — (N, k, d) stacked per-client centroids.
      * ``assignments`` — (N, cap) stacked cluster ids; entries at index >=
        ``sizes[i]`` are padding.

    The whole stage is one jitted device program; with ``rules`` the client
    axis shards over the mesh (per-client K-means fits are shard-local, the
    PCA moment aggregation is the single ``client_sum`` all-reduce).
    """
    with obs.span("cluster"):
        cd = as_client_data(datasets, rules=rules)
        return _cluster_impl(key, cd.data, cd.sizes, cfg.n_pca,
                             cfg.n_clusters, cfg.kmeans_iters, rules)


def cluster_clients_loop(key, datasets, cfg: PipelineConfig):
    """Reference host loop: the same masked per-client math as
    :func:`cluster_clients`, one client at a time (kept for parity tests —
    the vmapped program must match it bit-for-bit at mesh=1).

    The PCA moments are looped per client and folded exactly like the
    stacked path, each client's moments one jitted call: op-by-op dispatch
    materialises ``xm.T`` before the gemm, and XLA:CPU then sums the second
    moment in another order (~1e-6 off) than the fused ``xm.T @ xm`` the
    vmapped program and a jitted per-client call both compile to.  The
    basis *projection* ``pca.transform`` is one shared batched call in both
    paths, because XLA:CPU's gemm reduction order is not
    batch-layout-invariant — a per-client (cap, d) @ (d, k) projection
    lands ~1e-6 off the batched one.  Either difference would smear an
    arbitrary bit difference over everything downstream without testing any
    of the masking machinery this reference exists to pin down."""
    cd = as_client_data(datasets)
    n, cap = cd.n_clients, cd.cap
    flats = cd.data.reshape(n, cap, -1)
    mask = cd.mask(flats.dtype)
    moments_fn = jax.jit(pca_lib.client_moments)
    moments = [moments_fn(flats[i], mask[i]) for i in range(n)]
    s1 = jnp.sum(jnp.stack([m[0] for m in moments]), axis=0)
    s2 = jnp.sum(jnp.stack([m[1] for m in moments]), axis=0)
    pca = pca_lib._pca_from_moments(s1, s2, jnp.sum(mask), cfg.n_pca)
    z = pca.transform(flats)
    keys = jax.random.split(key, n)
    cents, assigns = [], []
    for i in range(n):
        res = km.kmeans_masked(keys[i], z[i], cd.sizes[i],
                               cfg.n_clusters, cfg.kmeans_iters)
        cents.append(res.centroids)
        assigns.append(res.assignments)
    return pca, jnp.stack(cents), jnp.stack(assigns)


def link_rewards(cents, trust, p_fail, cfg: PipelineConfig):
    """beta + Eq. 7 lambda matrix + Eq. 2 local reward matrix, from stacked
    (N, k, d) centroids (or the legacy ragged list).

    The single shared helper behind both graph-discovery call sites —
    ``run_pipeline`` and the orchestrator's ``_rediscover`` — which had
    drifted apart as two hand-maintained copies.  Returns
    ``(beta, lam, local_r)``."""
    beta = cfg.beta if cfg.beta is not None else \
        ds.median_heuristic_beta(cents, cfg.beta_scale)
    lam = ds.lambda_matrix(cents, trust, beta)
    return beta, lam, rw.local_reward_matrix(lam, p_fail, cfg.reward)


def run_pipeline(key, datasets, labels=None, ae_cfg: AEConfig = None,
                 cfg: PipelineConfig = PipelineConfig(),
                 in_edge=None, exchange_method=None, rss=None,
                 rules=None) -> PipelineResult:
    """Full smart-exchange. Pass ``in_edge`` to skip RL (e.g. uniform
    baseline graphs) while keeping the same exchange machinery.

    ``datasets``/``labels`` may be ragged per-client lists or one
    :class:`ClientData` (then pass ``labels=None``); the list form is
    converted exactly once and every stage works on the stack.

    ``exchange_method`` overrides ``cfg.exchange.method``: "batched" runs
    the device-resident gate engine (default), "loop" the reference
    host-side plane (parity testing) — see ``core/exchange.py``.

    ``rss`` supplies a precomputed channel snapshot (the dynamics
    orchestrator owns the channel state); omitted, one is drawn from the
    pipeline key exactly as before.

    ``rules`` (:class:`repro.sharding.ShardingRules`) shards the client
    axis over the mesh for all three device planes: the jitted clustering
    program (``cluster_clients``), the RL discovery loop's agent-major
    Q-tables/buffers (``core/qlearning.py``) and the exchange engine's
    stacked gate scoring + scatter (``core/exchange.py``)."""
    with obs.span("pipeline"):
        k_cl, k_tr, k_ch, k_rl, k_ex = split_pipeline_keys(key)
        cd = as_client_data(datasets, labels, rules=rules)
        n = cd.n_clients

        pca, cents, assigns = cluster_clients(k_cl, cd, cfg, rules=rules)
        with obs.span("trust-channel"):
            trust = tr.make_trust(k_tr, n, cfg.n_clusters, cfg.p_trust)
            if rss is None:
                rss = ch.make_rss(k_ch, n, cfg.channel)
            p_fail = ch.failure_prob(rss, cfg.channel)
            beta, lam_before, local_r = link_rewards(cents, trust, p_fail,
                                                     cfg)

        if in_edge is None:
            graph = ql.discover_graph(k_rl, local_r, p_fail, cfg.rl,
                                      rules=rules)
            in_edge = graph.in_edge
        else:
            in_edge = jnp.asarray(in_edge)
            graph = ql.GraphResult(in_edge, jnp.zeros((n, n)),
                                   jnp.zeros((0,)), jnp.zeros((0,)))

        res = ex.run_exchange(k_ex, cd, None, assigns, trust, in_edge,
                              p_fail, ae_cfg, cfg.exchange,
                              method=exchange_method, rules=rules)

        # Recompute dissimilarity on the post-exchange datasets (Fig. 3).
        _, cents_after, _ = cluster_clients(k_cl, res.client_data, cfg,
                                            rules=rules)
        lam_after = ds.lambda_matrix(cents_after, trust, beta)

        return PipelineResult(res.client_data, in_edge, lam_before,
                              lam_after, p_fail, graph, cents, trust, res)

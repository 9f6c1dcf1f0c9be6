"""Autoencoder-gated D2D data exchange (paper Sec. III-B / IV-B).

After graph discovery, each formed link (transmitter j -> receiver i) moves
data as follows:

  1. j builds per-cluster *reserve* subsets K^{jk}_reserve — a seeded random
     subset of the cluster's members — only for clusters k that the trust
     matrix permits (T_j[i, k] = 1).
  2. i scores each reserve subset with its own (pre-trained-one-GD-step)
     autoencoder: if the receiver reconstructs the subset *worse* than its
     own data — L(phi_i, D_i)/|D_i| < L(phi_i, K)/|K| — the subset contains
     information i's model lacks, and the transfer happens.
  3. Optionally the physical channel is sampled: with probability P_D(i, j)
     the transmission fails and nothing moves (straggler/robustness runs).

Two interchangeable data planes implement the gate (``ExchangeConfig.method``
or the ``method=`` argument of :func:`run_exchange`):

``"batched"`` (default)
    The device-resident engine over the :class:`~repro.core.batching
    .ClientData` stack.  AE pretraining is vmapped across all N clients in
    one jit with exact masked-mean grads; reserve rows are *gathered* from
    the stack on device (transmitter-side row lookup, then a receiver-side
    gather along the client axis — the D2D communication), every
    (receiver, cluster) pair is scored in one vmapped call whose masked
    reconstruction-MSE tail is a fused Pallas kernel on TPU
    (``kernels/recon_gate.py``; jnp oracle on CPU), and accepted subsets
    are *scattered* straight into each receiver's ``ClientData`` slot — a
    capacity-masked compaction (cumsum of the keep mask -> destination
    rows) with an explicit overflow policy (``ExchangeConfig.overflow``).
    Channel failures are sampled with ``jax.random`` inside the same
    program.  No client datapoint touches the host: the only host work is
    deriving the reserve *indices* (a few ints per cluster).

``"loop"``
    The reference host-side triple loop, one jitted reconstruction-loss
    dispatch per (receiver, cluster) pair, with a ragged numpy concat.
    Kept for parity testing: both planes derive reserves, channel draws and
    pretraining keys identically, so gate decisions, ``moved_counts`` and
    the post-exchange datasets match bit-for-bit on a fixed seed.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro import sharding as sh
from repro.core import batching
from repro.core.batching import ClientData, as_client_data, \
    client_data_from_lists
from repro.kernels import ops
from repro.models import autoencoder as ae

OVERFLOW_POLICIES = ("grow", "drop", "error")
RESERVE_SELECTORS = ("host", "device")


@dataclasses.dataclass(frozen=True)
class ExchangeConfig:
    reserve_per_cluster: int = 40   # |K^{jk}_reserve|
    pretrain_steps: int = 1         # paper: one full-batch GD iteration
    pretrain_lr: float = 1e-2
    apply_channel_failure: bool = False
    method: str = "batched"         # "batched" | "loop"
    # Receiver-capacity policy of the batched plane's device scatter:
    #   "grow"  — (default) the output ClientData's cap grows by the round's
    #             largest possible transfer, so nothing is ever dropped
    #             (list-plane semantics; the shape is static per call).
    #   "drop"  — cap is fixed; accepted samples that would land past it are
    #             dropped deterministically from the tail of the transfer.
    #   "error" — cap is fixed and any overflow raises (host-checks the
    #             overflow flag, so this policy synchronises).
    overflow: str = "grow"
    # Where reserve *indices* are drawn:
    #   "host"   — (default) the reference numpy path (`_select_reserves`):
    #              an np.random choice seeded off a device randint — the
    #              seeds the loop-plane parity suite was recorded against.
    #   "device" — :func:`select_reserves_device`: a masked top-k over
    #              per-(transmitter, cluster) `jax.random.uniform` draws,
    #              entirely on device.  Same distribution (uniform subsets
    #              without replacement), *different* concrete subsets for a
    #              given key — the two selectors are not bit-comparable.
    #              Required by the orchestrator's fused scan path, which
    #              needs the whole per-segment chain to be a closed device
    #              program.
    reserve_selector: str = "host"


@dataclasses.dataclass
class ExchangeResult:
    """Exchange output.  ``client_data`` is the device-resident truth;
    ``datasets``/``labels``/``moved_counts``/``gate_decisions`` are lazy
    host views so an online driver that only threads ``client_data`` onward
    never forces a transfer."""
    client_data: ClientData
    moved_dev: object                    # (N,) datapoints received, device
    fail: Optional[jax.Array] = None     # (N,) sampled channel failures
    accept: Optional[jax.Array] = None   # (N, K) gate decisions, device
    _decisions: Optional[list] = None    # eager for the loop plane
    _ctx: Optional[tuple] = None         # lazy-decision inputs (batched)

    @property
    def datasets(self) -> list:
        return self.client_data.data_list()

    @property
    def labels(self) -> Optional[list]:
        return self.client_data.label_list()

    @property
    def moved_counts(self) -> np.ndarray:
        return np.asarray(self.moved_dev)

    @property
    def gate_decisions(self) -> list:
        """Per-link decisions ``(rx, tx, cluster, accepted)`` in loop-plane
        order (``cluster == -1``: the sampled channel failed).  Materialised
        on first access for the batched plane."""
        if self._decisions is None and self._ctx is not None:
            trust_np, sel, in_edge, apply_channel = self._ctx
            if isinstance(sel, tuple) and sel and sel[0] == "tensors":
                # device-selector runs carry (sel_idx, sel_mask) tensors;
                # normalise to the ragged loop-plane layout on first access
                si = np.asarray(sel[1])
                sm = np.asarray(sel[2])
                sel = [[si[j, m][sm[j, m] > 0]
                        for m in range(trust_np[j].shape[1])]
                       for j in range(len(trust_np))]
            self._decisions = _build_decisions(
                trust_np, sel, np.asarray(in_edge),
                np.asarray(self.fail), np.asarray(self.accept),
                apply_channel)
        return self._decisions

    def failed_links(self) -> list:
        """Live links whose sampled channel failed this round, as host
        ``(rx, tx)`` pairs — the orchestrator's retry queue feeds on this.
        Empty when the channel wasn't sampled.  Syncs via ``np.asarray``
        (not ``jax.device_get``), and only the tiny (N,) fail mask — client
        data stays on device and the one-transfer-per-run metrics contract
        is untouched."""
        if self.fail is not None and self._ctx is not None:  # batched plane
            in_edge = np.asarray(self._ctx[2])
            fail = np.asarray(self.fail)
            live = in_edge != np.arange(in_edge.shape[0])
            return [(int(i), int(in_edge[i]))
                    for i in np.nonzero(fail & live)[0]]
        if self._decisions is not None:                      # loop plane
            return [(d[0], d[1]) for d in self._decisions if d[2] == -1]
        return []


# ---------------------------------------------------------------------------
# AE pretraining (paper Sec. III-B: one full-batch GD iteration per client)
# ---------------------------------------------------------------------------

def pretrain_autoencoders(key, datasets, ae_cfg, cfg: ExchangeConfig):
    """Reference path: one jitted grad call per client (retraces per shape)."""
    params_list = []
    keys = jax.random.split(key, len(datasets))
    grad_fn = jax.jit(jax.grad(ae.recon_loss), static_argnums=2)
    for kk, x in zip(keys, datasets):
        params = ae.init_ae(kk, ae_cfg)
        for _ in range(cfg.pretrain_steps):
            g = grad_fn(params, x, ae_cfg)
            params = jax.tree.map(lambda p, gg: p - cfg.pretrain_lr * gg,
                                  params, g)
        params_list.append(params)
    return params_list


# Module-level jit: the online orchestrator re-exchanges every segment and
# previously paid a full retrace per call (the step was a closure defined
# inside the pretrain function).  (ae_cfg, lr, rules) key the cache.
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
@jax.named_scope("exchange")
def _pretrain_step(p, x, m, ae_cfg, lr, rules):
    p = sh.constrain_clients(p, rules)
    x = sh.constrain_clients(x, rules)
    m = sh.constrain_clients(m, rules)
    g = sh.client_map(jax.vmap(
        lambda pp, xx, mm: jax.grad(ae.masked_recon_loss)(pp, xx, mm, ae_cfg)
    ), rules)(p, x, m)
    new = jax.tree.map(lambda pp, gg: pp - lr * gg, p, g)
    return sh.constrain_clients(new, rules)


def pretrain_autoencoders_batched(key, datasets, ae_cfg, cfg: ExchangeConfig,
                                  rules: sh.ShardingRules | None = None):
    """All N clients in one jit: vmapped init + vmapped masked-mean grads
    over the client stack (a ragged list converts once, a
    :class:`ClientData` is consumed as-is).  Returns a stacked-params pytree
    with a leading client axis.  Per-client keys and the masked loss match
    the reference path's math exactly (padding carries zero weight).  With
    ``rules`` the client stack (data, masks, params) shards over the mesh;
    pretraining has no cross-client reduction, so each shard trains its
    clients entirely locally."""
    cd = as_client_data(datasets, rules=rules)
    n = cd.n_clients
    mask = sh.constrain_clients(cd.mask(), rules) if rules else cd.mask()
    keys = sh.shard_clients(jax.random.split(key, n), rules)
    params = sh.shard_clients(
        jax.vmap(lambda k: ae.init_ae(k, ae_cfg))(keys), rules)

    for _ in range(cfg.pretrain_steps):
        params = _pretrain_step(params, cd.data, mask, ae_cfg,
                                cfg.pretrain_lr, rules)
    return params


# ---------------------------------------------------------------------------
# shared plumbing: reserve selection + channel draws (identical in both
# data planes, so gate decisions are bit-comparable across them)
# ---------------------------------------------------------------------------

def _select_reserves(key, assignments, n_clusters_list, r: int, sizes=None):
    """Seeded random reserve subsets, per (transmitter j, cluster m).

    ``assignments`` is a per-client list of (n_j,) arrays or the stacked
    (N, cap) form (then ``sizes`` marks each client's valid prefix).
    Clusters larger than ``r`` contribute a uniform random subset (sorted,
    sampled without replacement from the exchange key); smaller clusters
    contribute all members.  Only *indices* ever reach the host.
    """
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
    if isinstance(assignments, (list, tuple)):
        rows = [np.asarray(a) for a in assignments]
    else:
        assignments = np.asarray(assignments)
        sizes = np.asarray(sizes)
        rows = [assignments[j, :int(sizes[j])]
                for j in range(assignments.shape[0])]
    sel = []
    for j, a in enumerate(rows):
        row = []
        for m in range(n_clusters_list[j]):
            idx = np.nonzero(a == m)[0]
            if idx.size > r:
                idx = np.sort(rng.choice(idx, size=r, replace=False))
            row.append(idx)
        sel.append(row)
    return sel


@jax.named_scope("exchange")
def select_reserves_device(key, assignments, sizes, k_max: int, r: int):
    """On-device reserve selection: the traced counterpart of
    :func:`_select_reserves`, returning the ``_sel_tensors`` layout directly.

    assignments: stacked (N, cap) cluster ids (entries past ``sizes[j]`` are
    padding and never selected); returns ``(sel_idx, sel_mask)`` as
    ((N, K, R) int32, (N, K, R) float32) with each (transmitter, cluster)
    row holding min(r, |members|) distinct member indices, sorted ascending
    in a valid-prefix layout — exactly the shape contract the batched gate
    (`_exchange_device`) consumes.

    Mechanism: one uniform draw per (transmitter, cluster, slot), masked to
    -inf off-cluster, then ``top_k`` — a uniform subset without replacement.
    Same distribution as the host selector but *different* concrete subsets
    for a given key (top-k over uniforms vs np.random choice); parity suites
    that pin exact subsets keep ``reserve_selector="host"``.  Traceable:
    this is what lets the orchestrator's scan path keep reserve selection
    inside the fused per-segment device program."""
    assignments = jnp.asarray(assignments)
    sizes = jnp.asarray(sizes)
    n, cap = assignments.shape
    valid = jnp.arange(cap)[None, :] < sizes[:, None]            # (N, cap)
    member = valid[:, None, :] & (
        assignments[:, None, :] == jnp.arange(k_max)[None, :, None])
    u = jax.random.uniform(key, (n, k_max, cap))
    score = jnp.where(member, u, -jnp.inf)
    r_eff = min(int(r), int(cap))
    top_val, top_idx = jax.lax.top_k(score, r_eff)
    # non-members surface as -inf scores: map them past the cap, sort so
    # real picks form an ascending valid prefix (the host selector's order)
    idx = jnp.where(jnp.isinf(top_val), cap, top_idx)
    idx = jnp.sort(idx, axis=-1)
    mask = (idx < cap).astype(jnp.float32)
    idx = jnp.where(idx < cap, idx, 0).astype(jnp.int32)
    if r_eff < r:   # cap smaller than the reserve budget: pad dead slots
        pad = ((0, 0), (0, 0), (0, r - r_eff))
        idx = jnp.pad(idx, pad)
        mask = jnp.pad(mask, pad)
    return idx, mask


def _sel_tensors(sel, n: int, k_max: int, r: int):
    """Ragged reserve indices -> ((N, K, R) int32 rows, (N, K, R) mask)."""
    sel_idx = np.zeros((n, k_max, r), np.int32)
    sel_mask = np.zeros((n, k_max, r), np.float32)
    for j, row in enumerate(sel):
        for m, idx in enumerate(row):
            if idx.size:
                sel_idx[j, m, :idx.size] = idx
                sel_mask[j, m, :idx.size] = 1.0
    return sel_idx, sel_mask


def _stack_trust_padded(trust_np, n: int, k_max: int):
    """(N_tx, N_rx, K) stacked trust, zero-padded over ragged k_j."""
    t = np.zeros((n, n, k_max), np.int8)
    for j, tj in enumerate(trust_np):
        t[j, :, :tj.shape[1]] = tj
    return t


def _build_decisions(trust_np, sel, in_edge, fail, accept, apply_channel):
    """Decision tuples in loop-plane order from the device gate outputs."""
    decisions = []
    for i in range(len(trust_np)):
        j = int(in_edge[i])
        if j == i:
            continue
        if apply_channel and fail[i]:
            decisions.append((i, j, -1, False))
            continue
        for m in range(trust_np[j].shape[1]):
            if int(trust_np[j][i, m]) == 0:
                continue
            if sel[j][m].size == 0:
                continue
            decisions.append((i, j, m, bool(accept[i, m])))
    return decisions


# ---------------------------------------------------------------------------
# data planes
# ---------------------------------------------------------------------------

def _gate_loop(datasets, labels, trust, in_edge, sel, fail_u, p_fail,
               ae_params, ae_cfg, cfg: ExchangeConfig) -> ExchangeResult:
    n = len(datasets)
    mean_loss = jax.jit(ae.recon_loss, static_argnums=2)
    new_data = [np.asarray(d) for d in datasets]
    new_labels = [np.asarray(l) for l in labels]
    moved = np.zeros(n, np.int64)
    decisions = []
    p_fail = np.asarray(p_fail)

    for i in range(n):
        j = int(in_edge[i])
        if j == i:
            continue
        if cfg.apply_channel_failure and float(fail_u[i]) < float(p_fail[i, j]):
            decisions.append((i, j, -1, False))
            continue
        base = float(mean_loss(ae_params[i], jnp.asarray(datasets[i]), ae_cfg))
        data_j = np.asarray(datasets[j])
        labels_j = np.asarray(labels[j])
        tj = np.asarray(trust[j])
        for m in range(tj.shape[1]):
            if int(tj[i, m]) == 0:
                continue  # transmitter does not permit this cluster
            idx = sel[j][m]
            if idx.size == 0:
                continue
            reserve = jnp.asarray(data_j[idx])
            score = float(mean_loss(ae_params[i], reserve, ae_cfg))
            accepted = base < score   # receiver's AE is *worse* on reserve
            decisions.append((i, j, m, bool(accepted)))
            if accepted:
                new_data[i] = np.concatenate([new_data[i], data_j[idx]])
                new_labels[i] = np.concatenate([new_labels[i], labels_j[idx]])
                moved[i] += idx.size
    return ExchangeResult(client_data_from_lists(new_data, new_labels),
                          moved, _decisions=decisions)


@functools.partial(jax.jit, static_argnums=(9, 10, 11))
def _gate_scores(params, own, own_mask, cand, cand_mask, allowed, fail_u,
                 p_fail, in_edge, ae_cfg, apply_channel, rules=None):
    """One device program scoring the whole gate.

    params: stacked AE pytree (leading client axis); own: (N, cap, H, W, C)
    padded client stack with own_mask (N, cap); cand: (N, K, R, H, W, C)
    receiver-aligned reserve tensor with cand_mask (N, K, R).
    Returns (base (N,), scores (N, K), fail (N,), accept (N, K)).

    With ``rules`` every operand keeps its leading client axis pinned to the
    mesh: per-(receiver, cluster) scoring is embarrassingly parallel over
    receivers, so each shard scores its own clients with zero collectives —
    sharded output bits match the single-device program exactly.
    """
    params, own, own_mask, cand, cand_mask, allowed, fail_u, in_edge = \
        sh.constrain_clients(
            (params, own, own_mask, cand, cand_mask, allowed, fail_u,
             in_edge), rules)
    p_fail = sh.constrain_clients(p_fail, rules)
    n, max_n = own.shape[:2]
    k, r = cand.shape[1:3]

    recon = sh.client_map(
        jax.vmap(lambda p, x: ae.reconstruct(p, x, ae_cfg)), rules)
    gate_score = sh.client_map(ops.recon_gate_score, rules)
    y_own = recon(params, own)
    base = gate_score(y_own.reshape(n, max_n, -1), own.reshape(n, max_n, -1),
                      own_mask)

    cand_flat = cand.reshape((n, k * r) + cand.shape[3:])
    y_cand = recon(params, cand_flat)
    scores = gate_score(y_cand.reshape(n, k, r, -1),
                        cand.reshape(n, k, r, -1), cand_mask)

    if apply_channel:
        fail = fail_u < p_fail[jnp.arange(n), in_edge]
    else:
        fail = jnp.zeros((n,), bool)
    accept = allowed & (base[:, None] < scores) & ~fail[:, None]
    return base, scores, fail, accept


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
@jax.named_scope("exchange")
def _exchange_device(ae_cfg, apply_channel, out_cap, rules, params, data,
                     sizes, labels, sel_idx, sel_mask, trust_s, fail_u,
                     p_fail, in_edge):
    """The whole batched exchange as one device program.

    Gathers each transmitter's reserve rows from the stack (row-local
    ``take_along_axis``), gathers them receiver-side along the client axis
    (the D2D communication — on a mesh, the only cross-shard data movement),
    scores every (receiver, cluster) pair with :func:`_gate_scores`, and
    scatters accepted subsets into each receiver's slot: the keep mask's
    exclusive cumsum assigns destination rows ``sizes[i] + offset`` in
    cluster-major order (identical to the loop plane's concat order), and
    rows past ``out_cap`` fall off the scatter (``mode="drop"``) — the
    capacity mask.  Returns (new ClientData, moved, base, scores, fail,
    accept, overflowed).
    """
    (data, sizes, labels, sel_idx, sel_mask, fail_u, in_edge) = \
        sh.constrain_clients(
            (data, sizes, labels, sel_idx, sel_mask, fail_u, in_edge), rules)
    n, cap = data.shape[:2]
    k, r = sel_idx.shape[1:3]
    own_mask = (jnp.arange(cap)[None, :] < sizes[:, None]).astype(jnp.float32)

    # transmitter-side reserve gather: row lookups within each client's slot
    flat_idx = sel_idx.reshape(n, k * r)
    res_data = jnp.take_along_axis(
        data, flat_idx.reshape((n, k * r) + (1,) * (data.ndim - 2)), axis=1)
    # receiver-side gather along the client axis (the D2D transfer)
    cand = sh.constrain_clients(jnp.take(res_data, in_edge, axis=0), rules)
    cand = cand.reshape((n, k, r) + data.shape[2:])
    cand_mask = sh.constrain_clients(
        jnp.take(sel_mask, in_edge, axis=0), rules)

    # trust gate, receiver-aligned: allowed[i, m] = T_{in_edge[i]}[i, m]
    trust_rx = jnp.swapaxes(trust_s, 0, 1)              # (N_rx, N_tx, K)
    allowed = jnp.take_along_axis(
        trust_rx, in_edge[:, None, None], axis=1)[:, 0] != 0
    allowed &= (in_edge != jnp.arange(n))[:, None]
    allowed &= cand_mask.any(-1)

    base, scores, fail, accept = _gate_scores(
        params, data, own_mask, cand, cand_mask, allowed, fail_u, p_fail,
        in_edge, ae_cfg, apply_channel, rules)

    # capacity-masked scatter: compact kept rows to sizes[i] + offset
    keep = (accept[:, :, None] & (cand_mask > 0)).reshape(n, k * r)
    dest = sizes[:, None] + jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    moved_full = jnp.sum(keep, axis=1, dtype=jnp.int32)
    if out_cap > cap:
        pad = [(0, 0), (0, out_cap - cap)] + [(0, 0)] * (data.ndim - 2)
        data = jnp.pad(data, pad)
        if labels is not None:
            labels = jnp.pad(labels, pad[:2])
    rows = jnp.arange(n)[:, None]
    dest_safe = jnp.where(keep & (dest < out_cap), dest, out_cap)
    cand_flat = cand.reshape((n, k * r) + data.shape[2:])
    new_data = sh.constrain_clients(
        data.at[rows, dest_safe].set(cand_flat, mode="drop"), rules)
    new_labels = None
    if labels is not None:
        lab_res = jnp.take_along_axis(labels[:, :cap], flat_idx, axis=1)
        cand_lab = jnp.take(lab_res, in_edge, axis=0)
        new_labels = sh.constrain_clients(
            labels.at[rows, dest_safe].set(cand_lab, mode="drop"), rules)
    new_sizes = jnp.minimum(sizes + moved_full, out_cap)
    moved = new_sizes - sizes
    overflowed = jnp.any(sizes + moved_full > out_cap)
    return (ClientData(new_data, new_sizes, new_labels), moved, base,
            scores, fail, accept, overflowed)


def _gate_batched(cd: ClientData, trust, in_edge, sel, fail_u, p_fail,
                  params, ae_cfg, cfg: ExchangeConfig,
                  rules: sh.ShardingRules | None = None,
                  sel_tensors=None) -> ExchangeResult:
    n, cap = cd.n_clients, cd.cap
    trust_np = [np.asarray(t) for t in trust]
    k_max = max(t.shape[1] for t in trust_np)
    trust_s = _stack_trust_padded(trust_np, n, k_max)

    if sel_tensors is not None:
        # device selector: (sel_idx, sel_mask) already in tensor layout
        sel_idx, sel_mask = sel_tensors
        sel_ctx = ("tensors", sel_idx, sel_mask)
        if cfg.overflow == "grow":
            # grow needs a host-known cap: sync only the tiny index mask
            out_cap = cap + int(np.asarray(
                jnp.max(jnp.sum(sel_mask, axis=(1, 2)))))
        else:
            out_cap = cap
    else:
        sel_idx, sel_mask = _sel_tensors(sel, n, k_max,
                                         cfg.reserve_per_cluster)
        sel_ctx = sel
        if cfg.overflow == "grow":
            # static headroom: the largest reserve payload any transmitter
            # offers this round (host-known — indices only, no data)
            out_cap = cap + int(sel_mask.sum(axis=(1, 2)).max(initial=0))
        else:
            out_cap = cap

    sel_idx_d, sel_mask_d, trust_d = sh.shard_clients(
        (jnp.asarray(sel_idx), jnp.asarray(sel_mask), jnp.asarray(trust_s)),
        rules)
    new_cd, moved, _base, _scores, fail, accept, overflowed = \
        _exchange_device(ae_cfg, cfg.apply_channel_failure, out_cap, rules,
                         params, cd.data, cd.sizes, cd.labels, sel_idx_d,
                         sel_mask_d, trust_d, fail_u, p_fail, in_edge)
    if cfg.overflow == "error" and bool(overflowed):
        raise ValueError(
            "exchange overflow: accepted transfers exceed the ClientData "
            f"cap ({cap}); raise the cap or use overflow='grow'/'drop'")
    return ExchangeResult(new_cd, moved, fail, accept,
                          _ctx=(trust_np, sel_ctx, in_edge,
                                cfg.apply_channel_failure))


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def run_exchange(key, datasets, labels, assignments, trust, in_edge, p_fail,
                 ae_cfg, cfg: ExchangeConfig = ExchangeConfig(),
                 ae_params=None, method: str | None = None,
                 rules: sh.ShardingRules | None = None) -> ExchangeResult:
    """Execute Algorithm 2's data-plane step over the discovered graph.

    datasets/labels: ragged per-client lists, or one :class:`ClientData` as
    ``datasets`` (then ``labels`` must be None — the stack carries them);
    the list form converts exactly once.  assignments: per-client (n_i,)
    cluster ids from K-means, or the stacked (N, cap) form; in_edge: (N,)
    transmitter for each receiver.  ``method`` (default ``cfg.method``)
    picks the data plane — see the module docstring.  ``ae_params`` may be
    a per-client list or a stacked pytree; omitted, it is pretrained here
    from the exchange key.  ``rules`` shards the batched plane's client
    axis over the mesh (ignored by the reference loop plane); mesh=1
    placement is bit-identical to the unsharded program.
    """
    method = (method or cfg.method).lower()
    if cfg.overflow not in OVERFLOW_POLICIES:
        raise ValueError(f"unknown overflow policy {cfg.overflow!r}; "
                         f"expected one of {OVERFLOW_POLICIES}")
    if cfg.reserve_selector not in RESERVE_SELECTORS:
        raise ValueError(
            f"unknown reserve selector {cfg.reserve_selector!r}; "
            f"expected one of {RESERVE_SELECTORS}")
    if method == "loop" and cfg.overflow != "grow":
        raise ValueError(
            "the loop plane only implements the 'grow' semantics (its "
            "ragged concat has no capacity); use the batched plane for "
            f"overflow={cfg.overflow!r}")
    if method == "loop" and cfg.reserve_selector != "host":
        raise ValueError(
            "the loop plane is the host-selector reference; "
            "reserve_selector='device' requires the batched plane")
    with obs.span("exchange", method=method):
        cd = as_client_data(datasets, labels, rules=rules)
        n = cd.n_clients
        k_pre, k_sel, k_ch = jax.random.split(key, 3)
        sel = sel_tensors = None
        if cfg.reserve_selector == "device":
            if isinstance(assignments, (list, tuple)):
                stacked = np.full((n, cd.cap), -1, np.int32)
                for j, a in enumerate(assignments):
                    a = np.asarray(a)
                    stacked[j, :a.shape[0]] = a
                assignments = stacked
            k_max = max(t.shape[1] for t in trust)
            sel_tensors = select_reserves_device(
                k_sel, assignments, cd.sizes, k_max,
                cfg.reserve_per_cluster)
        else:
            sel = _select_reserves(k_sel, assignments,
                                   [t.shape[1] for t in trust],
                                   cfg.reserve_per_cluster, sizes=cd.sizes)
        fail_u = jax.random.uniform(k_ch, (n,))

        if method == "loop":
            data_l = cd.data_list()
            labels_l = cd.label_list()
            if labels_l is None:
                raise ValueError(
                    "the loop plane needs labels; pass them (the batched "
                    "plane accepts unlabeled ClientData)")
            if ae_params is not None:
                params = ae_params
            else:
                with obs.span("pretrain", method=method):
                    params = pretrain_autoencoders(k_pre, data_l, ae_cfg,
                                                   cfg)
            if not isinstance(params, (list, tuple)):
                params = batching.unstack_pytree(params, n)
            with obs.span("gate", method=method):
                return _gate_loop(data_l, labels_l, trust, in_edge, sel,
                                  np.asarray(fail_u, np.float32), p_fail,
                                  list(params), ae_cfg, cfg)
        if method != "batched":
            raise ValueError(f"unknown exchange method: {method!r}")
        if ae_params is not None:
            params = ae_params
        else:
            with obs.span("pretrain", method=method):
                params = pretrain_autoencoders_batched(k_pre, cd, ae_cfg,
                                                       cfg, rules)
        if isinstance(params, (list, tuple)):
            params = batching.stack_pytrees(list(params), rules)
        with obs.span("gate", method=method):
            return _gate_batched(cd, trust, in_edge, sel, fail_u, p_fail,
                                 params, ae_cfg, cfg, rules,
                                 sel_tensors=sel_tensors)

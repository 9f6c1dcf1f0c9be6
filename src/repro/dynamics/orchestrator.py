"""Online orchestrator: interleave FL training with graph re-discovery.

The one-shot pipeline is   discover → exchange → train to completion.
A real D2D deployment never gets that luxury: the channel fades, devices
move, clients drop out.  The orchestrator turns the repo's top-level API
from "run once" into "simulate a deployment":

    segment 0:  initial discovery + exchange (the one-shot pipeline, fed
                the environment's RSS), then ``iters_per_segment`` FL iters
    segment s:  advance the environment (fading / mobility / churn) →
                optionally re-discover the graph with a short warm-started
                RL burst and re-exchange over the new links → resume FL
                from the previous segment's full carry

Three modes, matching the benchmark baselines:

``"oneshot"``   never re-discovers — the initial graph is used throughout
                (the paper's protocol, exposed to a moving world).
``"online"``    periodic RL re-discovery, warm-starting each burst from the
                previous epoch's Q-tables (``GraphResult.state``), plus a
                re-exchange over the updated graph.
``"uniform"``   re-draws a uniform random graph on the same cadence —
                the ablation separating "any re-exchange helps" from
                "RL-chosen links help".

Device residency: the client datasets themselves now live on device as one
:class:`~repro.core.batching.ClientData` stack threaded across segments —
re-clustering is a jitted stacked program (``cluster_clients``), the
re-exchange gathers reserves and scatters accepted subsets inside one
device program, and the FL segments consume the stack directly.  Channel
state (``EnvState``), the FL carry, the graph and availability masks stay
on device too; per-segment metrics (eval loss, churn, delivery, moved
counts, availability) are accumulated as *deferred* device scalars and
materialised in a single transfer after the last segment.  The only
per-segment host work left is deriving reserve *indices* (a few ints per
cluster) — no client datapoint crosses to the host inside the loop.  Pass
``rules`` to shard every client-stacked tensor (the data stack, FL carry,
clustering/exchange programs, and the RL bursts' agent-major
Q-tables/buffers) over the mesh.

Determinism contract (tested in ``tests/test_dynamics_parity.py``): under
the ``static`` scenario with mode ``"oneshot"``, the run is bit-for-bit
``run_pipeline(k_pipe) + fl_train(k_fl)`` where
``k_pipe, k_env, k_fl = jax.random.split(key, 3)``.

Fault tolerance (``repro.faults``): a scenario may carry a declarative
:class:`~repro.faults.FaultPlan` — crash pulses, regional outages, link
bursts, simulated preemption — which the orchestrator overlays onto the
environment deterministically (the fault key is ``fold_in(k_env, salt)``,
so fault-free runs keep their exact key stream).  With
``cfg.checkpoint_dir`` set, the full run state is persisted atomically at
segment boundaries (:mod:`repro.dynamics.runstate`) and a killed run
resumes **bit-identical** via ``run_orchestrator(..., resume_from=path)``.
With ``cfg.retry.enabled``, failed exchange transfers re-offer through a
bounded backoff queue instead of being dropped (retries ride the
re-discovery cadence — they need fresh cluster assignments).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro import sharding as sh
from repro.core import exchange as ex
from repro.core import qlearning as ql
from repro.core.batching import ClientData
from repro.core.channel import failure_prob
from repro.core.pipeline import (PipelineConfig, _cluster_impl,
                                 cluster_clients, link_rewards, run_pipeline,
                                 split_pipeline_keys)
from repro.dynamics.environment import EnvState, env_init, env_step
from repro.dynamics.metrics import (PendingSegment, SegmentRecord, Trace,
                                    delivery_stats_dev, link_churn_dev,
                                    realized_delivery, realized_delivery_dev)
from repro.dynamics.runstate import RunState, load_run_state, save_run_state
from repro.dynamics.scenarios import get_scenario
from repro.faults import (Preempted, RetryPolicy, apply_availability,
                          apply_pfail)
from repro.faults.retry import RetryQueue
from repro.fl import trainer as fl_trainer
from repro.fl.trainer import FLConfig, eval_global_loss, fl_train
from repro.models import autoencoder as ae

MODES = ("oneshot", "online", "uniform")
SEGMENT_IMPLS = ("eager", "scan")

# salt separating the fault plane's key stream from the env process; the
# run's own split (k_pipe, k_env, k_fl) is untouched, so fault-free runs
# are bit-identical to the pre-fault-plane runtime
_FAULT_SALT = 0xFA

CHECKPOINT_NAME = "ckpt_latest.npz"


@dataclasses.dataclass(frozen=True)
class OrchestratorConfig:
    n_segments: int = 5
    iters_per_segment: int = 100       # FL iterations per segment
    mode: str = "online"               # see MODES
    rediscover_every: int = 1          # segments between re-discoveries
    burst_episodes: int = 150          # RL episodes per warm-started burst
    exchange_on_rediscover: bool = True
    pipeline: PipelineConfig = dataclasses.field(
        default_factory=PipelineConfig)
    fl: FLConfig = dataclasses.field(default_factory=FLConfig)
    # fl.total_iters is derived (n_segments * iters_per_segment); the field
    # in `fl` is ignored so presets can share one FLConfig.
    # fault-tolerance plane (all off by default):
    retry: RetryPolicy = dataclasses.field(default_factory=RetryPolicy)
    checkpoint_dir: Optional[str] = None   # None = no checkpointing
    checkpoint_every: int = 1              # segments between checkpoints
    # Segment execution engine:
    #   "eager" — (default) one Python iteration per segment, per-phase obs
    #             spans, any exchange config.  The parity oracle.
    #   "scan"  — segments [1, n) fused into jax.lax.scan chunks (one device
    #             program per chunk; chunk boundaries fall on the
    #             checkpoint/retry/preemption cadence).  Requires the whole
    #             per-segment chain to be a closed device program: with
    #             re-exchange enabled, exchange method "batched",
    #             overflow "drop" (static shapes) and
    #             reserve_selector "device".  Segment 0 (the one-shot
    #             pipeline) always runs eagerly.
    segment_impl: str = "eager"

    @property
    def total_iters(self) -> int:
        return self.n_segments * self.iters_per_segment

    @property
    def checkpoint_path(self) -> Optional[str]:
        if self.checkpoint_dir is None:
            return None
        return os.path.join(self.checkpoint_dir, CHECKPOINT_NAME)


class OrchestratorResult(NamedTuple):
    trace: Trace
    global_params: object
    carry: object                  # final FLCarry
    in_edge: jax.Array             # graph in force at the end
    env: object                    # final EnvState
    datasets: list                 # post-all-exchanges client data
    labels: list
    eval_iters: np.ndarray         # concatenated fl_train eval schedule
    eval_loss: np.ndarray
    client_data: object = None     # the final device-resident ClientData


def _rediscover(key, cd, trust, p_fail, cfg: OrchestratorConfig,
                rl_state: Optional[ql.RLState], rules=None):
    """Re-cluster the *current* ClientData stack and run a warm-started RL
    burst (or a uniform re-draw).  Returns (in_edge, rl_state, assigns).

    Re-clustering is the jitted stacked program (``cluster_clients`` fits a
    fresh federated PCA basis + per-client K-means on device); the reward
    map is the shared ``link_rewards`` helper — the same code path
    ``run_pipeline`` uses, so the two call sites cannot drift.  ``rules``
    shards the burst's agent axis; a warm-start ``rl_state`` from a
    previous sharded burst is already mesh-placed and stays device-resident
    across segments (re-placement inside ``discover_graph`` is a no-op)."""
    k_cl, k_rl = jax.random.split(key)
    pcfg = cfg.pipeline
    with obs.span("re-cluster"):
        _, cents, assigns = cluster_clients(k_cl, cd, pcfg, rules=rules)
    with obs.span("re-discover", mode=cfg.mode):
        if cfg.mode == "uniform":
            return ql.uniform_graph(k_rl, cd.n_clients), rl_state, assigns
        _beta, _lam, local_r = link_rewards(cents, trust, p_fail, pcfg)
        graph = ql.discover_graph(k_rl, local_r, p_fail, pcfg.rl,
                                  init_state=rl_state,
                                  n_episodes=cfg.burst_episodes, rules=rules)
    return graph.in_edge, graph.state, assigns


def run_orchestrator(key, datasets, labels, ae_cfg,
                     cfg: OrchestratorConfig = OrchestratorConfig(),
                     scenario="static", eval_data=None,
                     rules=None, resume_from=None) -> OrchestratorResult:
    """Simulate a deployment: ``cfg.n_segments`` FL segments over an
    evolving environment (see module docstring for the protocol).

    ``datasets``/``labels`` may be ragged per-client lists or one
    :class:`~repro.core.batching.ClientData` (as ``datasets``, with
    ``labels=None``).

    ``resume_from``: path of a run-state checkpoint written by a previous
    (killed) invocation with ``cfg.checkpoint_dir`` set.  The call must
    pass the *same* key, configs, scenario and eval data; the run skips
    the completed segments and continues bit-identically to the
    uninterrupted run.  A resumed run ignores the scenario's
    ``preempt_at`` (otherwise it would re-preempt forever)."""
    if cfg.mode not in MODES:
        raise ValueError(f"unknown mode {cfg.mode!r}; expected one of {MODES}")
    if cfg.segment_impl not in SEGMENT_IMPLS:
        raise ValueError(f"unknown segment_impl {cfg.segment_impl!r}; "
                         f"expected one of {SEGMENT_IMPLS}")
    if (cfg.segment_impl == "scan" and cfg.mode != "oneshot"
            and cfg.exchange_on_rediscover):
        exc = cfg.pipeline.exchange
        if exc.method != "batched":
            raise ValueError(
                "segment_impl='scan' fuses the re-exchange into the scanned "
                f"device program; exchange method {exc.method!r} is host-"
                "side — use method='batched'")
        if exc.overflow != "drop":
            raise ValueError(
                "segment_impl='scan' needs static shapes across segments; "
                f"overflow={exc.overflow!r} grows the ClientData cap per "
                "round — use overflow='drop'")
        if exc.reserve_selector != "device":
            raise ValueError(
                "segment_impl='scan' needs reserve selection on device "
                "(the host selector round-trips through np.random); set "
                "ExchangeConfig(reserve_selector='device')")
    if eval_data is None:
        raise ValueError("eval_data is required: the per-segment trace is "
                         "built around the global eval reconstruction loss")
    if cfg.iters_per_segment % cfg.fl.tau_a != 0:
        raise ValueError(
            f"iters_per_segment={cfg.iters_per_segment} must be a multiple "
            f"of the aggregation interval tau_a={cfg.fl.tau_a}: segment "
            "boundaries fall between rounds otherwise (iterations would be "
            "silently dropped and straggler masks applied to shifted "
            "windows)")
    scn = get_scenario(scenario)
    with obs.span("orchestrator", mode=cfg.mode, scenario=scn.name,
                  n_segments=cfg.n_segments, resumed=resume_from is not None):
        return _orchestrate(key, datasets, labels, ae_cfg, cfg, scn,
                            eval_data, rules, resume_from)


def _orchestrate(key, datasets, labels, ae_cfg, cfg: OrchestratorConfig,
                 scn, eval_data, rules, resume_from=None) -> OrchestratorResult:
    k_pipe, k_env, k_fl = jax.random.split(key, 3)
    plan = scn.faults
    k_fault = (jax.random.fold_in(k_env, _FAULT_SALT)
               if plan is not None else None)
    pcfg = cfg.pipeline
    flcfg = dataclasses.replace(cfg.fl, total_iters=cfg.total_iters)
    ckpt_path = cfg.checkpoint_path

    retry_q = RetryQueue()
    if resume_from is not None:
        with obs.span("checkpoint-load"):
            rs = load_run_state(resume_from, ae_cfg, cfg.n_segments,
                                cfg.iters_per_segment)
        if not np.array_equal(np.asarray(rs.key), np.asarray(key)):
            raise ValueError(
                "resume key mismatch: the checkpoint was written by a run "
                "with a different PRNG key — resuming would silently "
                "diverge from the original run")
        env, cd, trust = rs.env, rs.cd, rs.trust
        in_edge, prev_edge, p_fail = rs.in_edge, rs.prev_edge, rs.p_fail
        rl_state, carry, retry_q = rs.rl_state, rs.carry, rs.retry
        pending = list(rs.pending)
        exch = None
        start_segment = rs.segment + 1
    else:
        # ClientData is a NamedTuple: test for it before the list form
        n = datasets.n_clients if isinstance(datasets, ClientData) else \
            len(datasets)
        # The environment owns the channel; seeding it with the pipeline's
        # channel sub-key makes segment 0's RSS the one-shot draw
        # bit-for-bit.  (The fault plane leaves segment 0 untouched by
        # construction: its windows overlay env_step, which first runs at
        # segment 1 — segment 0's channel/availability feed run_pipeline.)
        env = env_init(split_pipeline_keys(k_pipe).k_ch, n, pcfg.channel,
                       scn)

        init_edge = None
        if cfg.mode == "uniform":
            # same convention as the one-shot uniform baseline (benchmarks)
            init_edge = ql.uniform_graph(jax.random.fold_in(k_pipe, 7), n)
        pipe = run_pipeline(k_pipe, datasets, labels, ae_cfg, pcfg,
                            in_edge=init_edge, rss=env.rss, rules=rules)

        cd = pipe.client_data          # the device-resident client plane
        trust = pipe.trust
        in_edge = pipe.in_edge
        rl_state = pipe.graph.state
        p_fail = pipe.p_fail
        exch = pipe.exchange

        pending = []
        carry = None
        prev_edge = None
        start_segment = 0

    n = int(env.available.shape[0])
    # Under the fused engine only segment 0 (the one-shot pipeline feed-in)
    # runs eagerly; everything after it goes through the chunked lax.scan.
    # The eager loop below is byte-identical to the segment_impl="eager"
    # path — it is the parity oracle the scan is tested against.
    eager_end = cfg.n_segments if cfg.segment_impl == "eager" else \
        max(start_segment, 1)
    for s in range(start_segment, eager_end):
        if (plan is not None and plan.preempt_at == s
                and resume_from is None):
            # simulated host preemption at the segment boundary: the
            # previous segment's checkpoint (if enabled) is already on disk
            raise Preempted(s, ckpt_path)
        with obs.span("segment", segment=s):
            rediscovered = s == 0
            assigns = None
            if s > 0:
                with obs.span("env-step", segment=s):
                    env = env_step(jax.random.fold_in(k_env, s), env, scn,
                                   pcfg.channel)
                    p_fail = failure_prob(env.rss, pcfg.channel)
                if plan is not None:
                    # deterministic fault overlay; the op sequence is
                    # identical every segment (windows enter as array
                    # constants), keeping steady-state segments compile-free
                    with obs.span("fault-inject", segment=s,
                                  events=",".join(plan.active(s)) or "none"):
                        env = env._replace(available=apply_availability(
                            k_fault, plan, s, env.positions, env.available))
                        p_fail = apply_pfail(k_fault, plan, s, p_fail)
                exch = None
                if cfg.mode != "oneshot" and s % cfg.rediscover_every == 0:
                    new_edge, rl_state, assigns = _rediscover(
                        jax.random.fold_in(k_pipe, 100 + s), cd,
                        trust, p_fail, cfg, rl_state, rules=rules)
                    if cfg.exchange_on_rediscover:
                        with obs.span("re-exchange", segment=s):
                            exch = ex.run_exchange(
                                jax.random.fold_in(k_pipe, 200 + s), cd,
                                None, assigns, trust, new_edge, p_fail,
                                ae_cfg, pcfg.exchange, rules=rules)
                            cd = exch.client_data
                    prev_edge, in_edge = in_edge, new_edge
                    rediscovered = True

            retried = retry_delivered = 0
            retry_moved = jnp.zeros((), jnp.int32)
            if cfg.retry.enabled:
                if exch is not None:
                    retry_q.offer(s, exch.failed_links(), cfg.retry)
                if assigns is not None and len(retry_q):
                    cd, retry_moved, retried, retry_delivered = \
                        _retry_exchange(
                            jax.random.fold_in(k_pipe, 300 + s), s, cd,
                            assigns, trust, p_fail, ae_cfg, cfg, retry_q,
                            rules)

            with obs.span("fl-segment", segment=s):
                fl = fl_train(k_fl, cd, ae_cfg, flcfg, eval_data,
                              avail_mask=env.available, init_carry=carry,
                              start_iter=s * cfg.iters_per_segment,
                              stop_iter=(s + 1) * cfg.iters_per_segment,
                              rules=rules, defer_metrics=True)
                carry = fl.carry

            sampled = (pcfg.exchange.apply_channel_failure and rediscovered
                       and exch is not None)
            realized_dev = jnp.nan
            host_realized = None
            n_live_dev = n_failed_dev = jnp.zeros((), jnp.int32)
            if sampled:
                if exch.fail is not None:   # batched plane: stay on device
                    realized_dev = realized_delivery_dev(in_edge, exch.fail)
                    live = jnp.asarray(in_edge) != jnp.arange(n)
                    n_live_dev = jnp.sum(live.astype(jnp.int32))
                    n_failed_dev = jnp.sum(
                        (jnp.asarray(exch.fail) & live).astype(jnp.int32))
                else:                       # loop plane: host decisions
                    host_realized = realized_delivery(in_edge,
                                                      exch.gate_decisions)
            pf_dev, expected_dev = delivery_stats_dev(in_edge, p_fail)
            seg_loss = (fl.eval_loss[-1] if fl.eval_loss.size else
                        eval_global_loss(carry.global_params, eval_data,
                                         ae_cfg))
            pending.append(PendingSegment(
                segment=s, rediscovered=rediscovered, sampled=sampled,
                host_realized=host_realized,
                eval_iters=np.asarray(fl.eval_iters),
                retried=retried, retry_delivered=retry_delivered,
                dev={
                    "eval_loss": seg_loss,
                    "in_edge": jnp.asarray(in_edge),
                    "link_churn": link_churn_dev(
                        prev_edge if rediscovered and s > 0 else None,
                        in_edge),
                    "mean_pfail": pf_dev,
                    "expected_delivery": expected_dev,
                    "n_available": jnp.sum(env.available),
                    "moved": (jnp.sum(exch.moved_dev) if exch is not None
                              else jnp.zeros((), jnp.int32)) + retry_moved,
                    "realized": realized_dev,
                    "eval_curve": fl.eval_loss,
                    "n_live": n_live_dev,
                    "n_failed": n_failed_dev,
                }))

            if ckpt_path is not None and (
                    (s + 1) % cfg.checkpoint_every == 0
                    or s == cfg.n_segments - 1):
                # persists *before* the next segment's fl_train donates the
                # carry buffers (save materialises them to host first)
                with obs.span("checkpoint-save", segment=s):
                    save_run_state(ckpt_path, RunState(
                        segment=s, key=np.asarray(key), env=env, cd=cd,
                        trust=trust, in_edge=in_edge, prev_edge=prev_edge,
                        p_fail=p_fail, rl_state=rl_state, carry=carry,
                        retry=retry_q, pending=pending),
                        cfg.n_segments, cfg.iters_per_segment)

    if eager_end < cfg.n_segments:
        env, p_fail, cd, in_edge, prev_edge, rl_state, carry = \
            _scan_segments(key, cfg, scn, ae_cfg, eval_data, rules,
                           resume_from, k_pipe, k_env, k_fl, k_fault,
                           trust, retry_q, pending, env, p_fail, cd,
                           in_edge, prev_edge, rl_state, carry, eager_end,
                           ckpt_path)

    # One host transfer for every per-segment metric of the whole run: the
    # loop above never blocked on a device value.  (The transfer counter
    # pins this contract: tests assert exactly one device_get per run.
    # Restored segments' dev values are already host arrays and pass
    # through unchanged — a resumed run replays them bit-identically.)
    with obs.span("metrics-materialize"):
        host = jax.device_get([p.dev for p in pending])
    trace = Trace()
    for p, h in zip(pending, host):
        realized = p.host_realized
        if realized is None and p.sampled and np.isfinite(h["realized"]):
            realized = float(h["realized"])
        trace.add(SegmentRecord(
            segment=p.segment, eval_loss=float(h["eval_loss"]),
            in_edge=np.asarray(h["in_edge"]),
            link_churn=float(h["link_churn"]),
            mean_pfail=float(h["mean_pfail"]),
            expected_delivery=float(h["expected_delivery"]),
            realized_delivery=realized,
            n_available=int(h["n_available"]),
            moved=int(h["moved"]), rediscovered=p.rediscovered,
            eval_iters=p.eval_iters,
            eval_curve=np.asarray(h["eval_curve"]),
            n_live=int(h["n_live"]), n_failed=int(h["n_failed"]),
            retried=p.retried, retry_delivered=p.retry_delivered))

    return OrchestratorResult(trace, carry.global_params, carry, in_edge,
                              env, cd.data_list(), cd.label_list(),
                              trace.eval_curve_iters, trace.eval_curve,
                              cd)


def _retry_exchange(key, s, cd, assigns, trust, p_fail, ae_cfg,
                    cfg: OrchestratorConfig, retry_q: RetryQueue, rules):
    """Re-offer the due failed links through the standard exchange program.

    The retry edge maps each due receiver to its original transmitter and
    everyone else to themselves (a self-link is a no-op for the device
    gate), so the retry reuses the exact jit cache of the per-segment
    re-exchange — same statics, no new compiles under ``overflow="drop"``.
    A retried transfer faces the *current* channel and the receiver's
    current gate; delivery means the channel held (the gate may still
    decline the payload — that is a receiver decision, not a lost link)."""
    due = retry_q.take_due(s)
    if not due:
        return cd, jnp.zeros((), jnp.int32), 0, 0
    with obs.span("retry-exchange", segment=s, n_links=len(due)):
        n = cd.n_clients
        retry_edge = np.arange(n)
        for e in due:
            retry_edge[e.rx] = e.tx
        r_exch = ex.run_exchange(key, cd, None, assigns, trust,
                                 jnp.asarray(retry_edge), p_fail, ae_cfg,
                                 cfg.pipeline.exchange, rules=rules)
        # the (N,) fail sync is np.asarray-based (failed_links), keeping
        # the one-device_get-per-run metrics contract intact
        failed = set(r_exch.failed_links())
        delivered = 0
        for e in due:
            ok = (e.rx, e.tx) not in failed
            retry_q.resolve(s, e, ok, cfg.retry)
            delivered += int(ok)
        return (r_exch.client_data, jnp.sum(r_exch.moved_dev), len(due),
                delivered)


# ---------------------------------------------------------------------------
# fused segment engine (segment_impl="scan"): segments [1, n) run as chunked
# jax.lax.scan device programs.  Chunk boundaries are the host-interaction
# points — checkpoint writes, retry-queue offers/drains and simulated
# preemption happen *between* chunks only; inside a chunk no host code runs.
# ---------------------------------------------------------------------------


class _ScanCarry(NamedTuple):
    """Cross-segment device state threaded through the fused scan — the
    array image of what the eager loop keeps in Python locals.  ``assigns``
    holds the last rediscovery's stacked cluster ids (zeros until the first
    one; only read at a boundary drain, which always follows a rediscovery,
    and inside the rediscovery branch, which overwrites it first).
    ``prev_edge`` starts as a copy of ``in_edge`` (the eager loop's None):
    churn is derived from the pre-update edge inside the rediscovery
    branch, so the placeholder is never observable in metrics."""
    env: EnvState
    p_fail: jax.Array
    cd: object                   # ClientData
    assigns: jax.Array           # (N, cap) int32
    in_edge: jax.Array           # (N,) int32
    prev_edge: jax.Array         # (N,) int32
    rl_state: object             # RLState, or None (uniform/oneshot)
    fc: object                   # FLCarry


def _eval_rounds(cfg: OrchestratorConfig, s: int) -> list:
    """Local round indices of segment ``s`` that the eager fl_train would
    evaluate at — the host-side mirror of the traced eval gate (both are
    pure functions of the static config, so they cannot drift)."""
    rps = cfg.iters_per_segment // cfg.fl.tau_a
    n_rounds = cfg.total_iters // cfg.fl.tau_a
    out = []
    for rl in range(rps):
        r = s * rps + rl
        it = (r + 1) * cfg.fl.tau_a
        if it % cfg.fl.eval_every == 0 or r == n_rounds - 1:
            out.append(rl)
    return out


def _chunk_bounds(cfg: OrchestratorConfig, plan, start: int,
                  ckpt_path) -> list:
    """Split segments [start, n_segments) into scan chunks.  A boundary
    falls after segment ``s`` iff host interaction is due there: a
    checkpoint write, a retry offer/drain (retries ride the re-discovery
    cadence), a simulated preemption at ``s + 1``, or the end of the run.
    Boundaries are absolute functions of (cfg, plan) — independent of
    ``start`` — so a resumed run re-derives exactly the chunking the
    uninterrupted run used (resume stays bit-identical scan-vs-scan)."""
    bounds, c0 = [], start
    for s in range(start, cfg.n_segments):
        cut = s == cfg.n_segments - 1
        if ckpt_path is not None and (s + 1) % cfg.checkpoint_every == 0:
            cut = True
        if (cfg.retry.enabled and cfg.mode != "oneshot"
                and s % cfg.rediscover_every == 0):
            cut = True
        if plan is not None and plan.preempt_at == s + 1:
            cut = True
        if cut:
            bounds.append((c0, s + 1))
            c0 = s + 1
    return bounds


# One compile per (statics, chunk-length) signature: every same-length chunk
# of a run is a cache hit (tests/test_obs.py pins this with the compile
# counter).  The carry is donated — client data, FL params and Adam moments
# are the dominant buffers and each chunk consumes exactly one generation
# of them (checkpoint saves materialise to host before the next chunk).
@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def _chunk_fn(statics, carry, xs, trust_s, eval_data, k_pipe, k_env,
              k_fault):
    cfg, scn, ae_cfg, rules = statics
    pcfg = cfg.pipeline
    excfg = pcfg.exchange
    plan = scn.faults
    flcfg = dataclasses.replace(cfg.fl, total_iters=cfg.total_iters)
    uniform = cfg.mode == "uniform"
    rediscovers = cfg.mode != "oneshot"
    do_exchange = rediscovers and cfg.exchange_on_rediscover
    sampled_possible = do_exchange and excfg.apply_channel_failure
    nanf = jnp.full((), jnp.nan, jnp.float32)

    def body(c, x):
        s, re_flag, kr, eflags = x["seg"], x["re"], x["kr"], x["eflags"]
        env = env_step(jax.random.fold_in(k_env, s), c.env, scn,
                       pcfg.channel)
        p_fail = failure_prob(env.rss, pcfg.channel)
        if plan is not None:
            env = env._replace(available=apply_availability(
                k_fault, plan, s, env.positions, env.available))
            p_fail = apply_pfail(k_fault, plan, s, p_fail)
        n = env.available.shape[0]
        zero_i = jnp.zeros((), jnp.int32)

        if rediscovers:
            def redisc(op):
                cd, assigns, in_edge, rl_state = op
                k_cl, k_rl = jax.random.split(
                    jax.random.fold_in(k_pipe, 100 + s))
                _pca, cents, new_assigns = _cluster_impl(
                    k_cl, cd.data, cd.sizes, pcfg.n_pca, pcfg.n_clusters,
                    pcfg.kmeans_iters, rules)
                new_assigns = new_assigns.astype(jnp.int32)
                if uniform:
                    new_edge = ql.uniform_graph(k_rl, n)
                    new_state = rl_state
                else:
                    _beta, _lam, local_r = link_rewards(cents, trust_s,
                                                        p_fail, pcfg)
                    local_r, pf_c, st = sh.constrain_clients(
                        (local_r, p_fail, rl_state), rules)
                    graph = ql._discover_impl(k_rl, local_r, pf_c, st,
                                              pcfg.rl, cfg.burst_episodes,
                                              rules)
                    new_edge, new_state = graph.in_edge, graph.state
                new_edge = new_edge.astype(jnp.int32)
                churn = jnp.mean((in_edge != new_edge).astype(jnp.float32))
                if do_exchange:
                    k_pre, k_sel, k_ch = jax.random.split(
                        jax.random.fold_in(k_pipe, 200 + s), 3)
                    mask = sh.constrain_clients(cd.mask(), rules) \
                        if rules else cd.mask()
                    keys = sh.constrain_clients(
                        jax.random.split(k_pre, n), rules)
                    params = sh.constrain_clients(
                        jax.vmap(lambda k: ae.init_ae(k, ae_cfg))(keys),
                        rules)
                    for _ in range(excfg.pretrain_steps):
                        params = ex._pretrain_step(
                            params, cd.data, mask, ae_cfg,
                            excfg.pretrain_lr, rules)
                    sel_idx, sel_mask = ex.select_reserves_device(
                        k_sel, new_assigns, cd.sizes, trust_s.shape[2],
                        excfg.reserve_per_cluster)
                    fail_u = jax.random.uniform(k_ch, (n,))
                    new_cd, moved, _b, _sc, fail, _acc, _ovf = \
                        ex._exchange_device(
                            ae_cfg, excfg.apply_channel_failure, cd.cap,
                            rules, params, cd.data, cd.sizes, cd.labels,
                            sel_idx, sel_mask, trust_s, fail_u, p_fail,
                            new_edge)
                else:
                    new_cd = cd
                    moved = jnp.zeros((n,), jnp.int32)
                    fail = jnp.zeros((n,), bool)
                return (new_cd, new_assigns, new_edge, new_state, moved,
                        fail, churn)

            def skip(op):
                cd, assigns, in_edge, rl_state = op
                return (cd, assigns, in_edge, rl_state,
                        jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool),
                        jnp.zeros((), jnp.float32))

            cd, assigns, in_edge, rl_state, moved, fail, churn = \
                jax.lax.cond(re_flag, redisc, skip,
                             (c.cd, c.assigns, c.in_edge, c.rl_state))
            prev_edge = jnp.where(re_flag, c.in_edge, c.prev_edge)
        else:
            cd, assigns, in_edge, rl_state = (c.cd, c.assigns, c.in_edge,
                                              c.rl_state)
            prev_edge = c.prev_edge
            moved = jnp.zeros((n,), jnp.int32)
            fail = jnp.zeros((n,), bool)
            churn = jnp.zeros((), jnp.float32)

        # -- FL segment: nested round scan over the same per-round keys and
        # eval schedule the eager fl_train derives
        agg_mask = sh.constrain_clients(
            env.available.astype(jnp.float32), rules)

        def round_body(rc, xr):
            fc, last = rc
            kr_r, eflag = xr
            fc = fl_trainer._round_body(flcfg, ae_cfg, fc, cd.data,
                                        cd.sizes, agg_mask, kr_r, rules)
            val = jax.lax.cond(
                eflag,
                lambda gp: fl_trainer._eval_loss_fn(gp, eval_data, ae_cfg),
                lambda gp: nanf, fc.global_params)
            return (fc, jnp.where(eflag, val, last)), val

        (fc, last), curve = jax.lax.scan(round_body, (c.fc, nanf),
                                         (kr, eflags))
        # segment-end loss: the last scheduled eval, or (no eval scheduled
        # this segment) an extra end-of-segment evaluation — the eager
        # loop's `fl.eval_loss[-1] or eval_global_loss(...)` fallback
        seen = jnp.any(eflags)
        end_loss = jax.lax.cond(
            seen, lambda gp: nanf,
            lambda gp: fl_trainer._eval_loss_fn(gp, eval_data, ae_cfg),
            fc.global_params)
        seg_loss = jnp.where(seen, last, end_loss)

        # -- deferred metrics (masked so non-sampled segments record the
        # exact zeros/NaN the eager loop records)
        pf_dev, expected_dev = delivery_stats_dev(in_edge, p_fail)
        live = in_edge != jnp.arange(n)
        sampled_flag = re_flag if sampled_possible else \
            jnp.zeros((), bool)
        ys = {
            "eval_loss": seg_loss,
            "in_edge": in_edge,
            "link_churn": churn,
            "mean_pfail": pf_dev,
            "expected_delivery": expected_dev,
            "n_available": jnp.sum(env.available),
            "moved": jnp.sum(moved),
            "realized": jnp.where(sampled_flag,
                                  realized_delivery_dev(in_edge, fail),
                                  nanf),
            "eval_curve": curve,
            "n_live": jnp.where(sampled_flag,
                                jnp.sum(live.astype(jnp.int32)), zero_i),
            "n_failed": jnp.where(
                sampled_flag, jnp.sum((fail & live).astype(jnp.int32)),
                zero_i),
            "fail_row": fail,
        }
        return _ScanCarry(env, p_fail, cd, assigns, in_edge, prev_edge,
                          rl_state, fc), ys

    return jax.lax.scan(body, carry, xs)


def _scan_segments(key, cfg: OrchestratorConfig, scn, ae_cfg, eval_data,
                   rules, resume_from, k_pipe, k_env, k_fl, k_fault, trust,
                   retry_q: RetryQueue, pending: list, env, p_fail, cd,
                   in_edge, prev_edge, rl_state, carry, start: int,
                   ckpt_path):
    """Drive the fused engine over segments [start, n_segments): launch one
    ``_chunk_fn`` per chunk and do the host work — retry offers/drains,
    PendingSegment assembly, checkpoint writes, simulated preemption — at
    the boundaries.  Appends to ``pending`` in place and returns the final
    cross-segment state in the eager loop's variable layout."""
    plan = scn.faults
    pcfg = cfg.pipeline
    n = int(env.available.shape[0])
    rps = cfg.iters_per_segment // cfg.fl.tau_a
    n_rounds = cfg.total_iters // cfg.fl.tau_a
    statics = (cfg, scn, ae_cfg, rules)
    sampled_possible = (cfg.mode != "oneshot" and cfg.exchange_on_rediscover
                        and pcfg.exchange.apply_channel_failure)

    # all per-round FL keys up front (bit-identical to fl_train's
    # per-round derivation: split(fold_in(k_fl, 1)) then split(keys[r]))
    keys_r = jax.random.split(jax.random.fold_in(k_fl, 1), n_rounds)
    kr_all = jax.vmap(lambda k: jax.random.split(k, cfg.fl.tau_a))(keys_r)
    trust_np = [np.asarray(t) for t in trust]
    trust_s = jnp.asarray(ex._stack_trust_padded(
        trust_np, n, max(t.shape[1] for t in trust_np)))

    sc = _ScanCarry(
        env=env, p_fail=jnp.asarray(p_fail), cd=cd,
        assigns=jnp.zeros((n, cd.cap), jnp.int32),
        in_edge=jnp.asarray(in_edge).astype(jnp.int32),
        prev_edge=jnp.asarray(prev_edge if prev_edge is not None
                              else in_edge).astype(jnp.int32),
        rl_state=rl_state, fc=carry)
    # Copy every carry leaf before the first chunk: the chunk donates its
    # carry, and the eager prefix's deferred metrics (and prev_edge's
    # fallback to in_edge) still reference these buffers.  One device-side
    # copy per run; later chunks donate freshly-produced outputs.
    sc = jax.tree_util.tree_map(jnp.copy, sc)

    for c0, c1 in _chunk_bounds(cfg, plan, start, ckpt_path):
        if (plan is not None and plan.preempt_at == c0
                and resume_from is None):
            raise Preempted(c0, ckpt_path)
        segs = list(range(c0, c1))
        re_flags = [cfg.mode != "oneshot" and s % cfg.rediscover_every == 0
                    for s in segs]
        evals = [_eval_rounds(cfg, s) for s in segs]
        xs = {
            "seg": jnp.asarray(segs, jnp.int32),
            "re": jnp.asarray(re_flags),
            "kr": kr_all[c0 * rps:c1 * rps].reshape(
                (len(segs), rps) + kr_all.shape[1:]),
            "eflags": jnp.asarray([[r in ev for r in range(rps)]
                                   for ev in evals]),
        }
        with obs.span("scan-chunk", start=c0, n_segments=len(segs)):
            sc, ys = _chunk_fn(statics, sc, xs, trust_s, eval_data, k_pipe,
                               k_env, k_fault if k_fault is not None
                               else k_env)
            jax.block_until_ready(sc)

        # -- boundary host work: retry offers (from the chunk's sampled
        # failure masks) and one drain — both np.asarray syncs of tiny
        # arrays, invisible to the one-device_get metrics contract
        b = c1 - 1
        retried = retry_delivered = 0
        retry_moved = None
        if cfg.retry.enabled and sampled_possible:
            fail_np = np.asarray(ys["fail_row"])
            edge_np = np.asarray(ys["in_edge"])
            for i, s in enumerate(segs):
                if not re_flags[i]:
                    continue
                live = edge_np[i] != np.arange(n)
                retry_q.offer(
                    s, [(int(rx), int(edge_np[i][rx]))
                        for rx in np.nonzero(fail_np[i] & live)[0]],
                    cfg.retry)
        if cfg.retry.enabled and any(re_flags) and len(retry_q):
            # drains ride the chunk boundary (the boundary segment is the
            # chunk's rediscovery — _chunk_bounds cuts there): one segment
            # later than the eager engine's pre-FL drain, documented in
            # the README chunk-boundary contract
            new_cd, retry_moved, retried, retry_delivered = \
                _retry_exchange(jax.random.fold_in(k_pipe, 300 + b), b,
                                sc.cd, sc.assigns, trust, sc.p_fail,
                                ae_cfg, cfg, retry_q, rules)
            sc = sc._replace(cd=new_cd)

        for i, s in enumerate(segs):
            ev = evals[i]
            moved_dev = ys["moved"][i]
            if s == b and retry_moved is not None:
                moved_dev = moved_dev + retry_moved
            pending.append(PendingSegment(
                segment=s, rediscovered=re_flags[i],
                sampled=sampled_possible and re_flags[i],
                host_realized=None,
                eval_iters=np.asarray(
                    [(s * rps + r + 1) * cfg.fl.tau_a for r in ev]),
                retried=retried if s == b else 0,
                retry_delivered=retry_delivered if s == b else 0,
                dev={
                    "eval_loss": ys["eval_loss"][i],
                    "in_edge": ys["in_edge"][i],
                    "link_churn": ys["link_churn"][i],
                    "mean_pfail": ys["mean_pfail"][i],
                    "expected_delivery": ys["expected_delivery"][i],
                    "n_available": ys["n_available"][i],
                    "moved": moved_dev,
                    "realized": ys["realized"][i],
                    "eval_curve": (ys["eval_curve"][i][np.asarray(ev)]
                                   if ev else jnp.zeros((0,))),
                    "n_live": ys["n_live"][i],
                    "n_failed": ys["n_failed"][i],
                }))

        if ckpt_path is not None and ((b + 1) % cfg.checkpoint_every == 0
                                      or b == cfg.n_segments - 1):
            with obs.span("checkpoint-save", segment=b):
                save_run_state(ckpt_path, RunState(
                    segment=b, key=np.asarray(key), env=sc.env, cd=sc.cd,
                    trust=trust, in_edge=sc.in_edge,
                    prev_edge=sc.prev_edge, p_fail=sc.p_fail,
                    rl_state=sc.rl_state, carry=sc.fc, retry=retry_q,
                    pending=pending), cfg.n_segments,
                    cfg.iters_per_segment)

    return (sc.env, sc.p_fail, sc.cd, sc.in_edge, sc.prev_edge,
            sc.rl_state, sc.fc)

"""Decentralized multi-agent Q-learning for D2D graph discovery
(paper Sec. III + Algorithm 1).

Each client is an agent choosing its *incoming* edge (Assumption 3: exactly
one).  The whole loop is a `lax.scan` over episodes with all N agents
vectorised — decentralisation is preserved semantically (each agent reads
only its own Q row; the only shared quantities are the episode-mean reward
and r_net, which the paper explicitly lets devices exchange).

Sharding: pass ``rules`` (:class:`repro.sharding.ShardingRules`) and every
agent-major array — the Q-tables, pick counts and replay buffers in
:class:`RLState`, plus the ``local_r``/``p_fail`` reward matrices — is
placed on the CLIENTS mesh axis.  The decentralised structure is exactly
the sharded structure: action selection, buffer writes and the Eq. 6 update
are row-wise (shard-local), and the two genuinely shared scalars (the
Eq. 3 episode-mean reward and Eq. 5 r_net) lower to psum-style collectives
(``sharding.client_mean``).  ``rules=None`` is bit-identical to the
pre-sharding program, and a 1-device mesh is bit-identical to ``None``.

Deviation note: Eq. 4 normalises raw Q values, which is ill-defined once
rewards (hence Q) can be negative (r_ij = a1*lam - a2*P_D can be < 0).  We
use a shifted normalisation Q~ = Q - min(Q) + eps per row, which equals the
paper's expression whenever Q >= 0 elementwise.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro import sharding as sh
from repro.core import rewards as rw


@dataclasses.dataclass(frozen=True)
class RLConfig:
    n_episodes: int = 600      # E (paper Sec. V)
    buffer_size: int = 90      # M (paper Sec. V)
    q_init: float = 0.1        # "small equal values"
    gamma0: float = 0.3        # exploration->exploitation anneal (gamma at t=0)
    gamma_step: float = 0.15   # increase per buffer flush
    gamma_max: float = 0.95
    # Beyond-paper exploration policy (benchmarks/beyond_paper.py):
    #   "mixed" — the paper's Eq. 4 (gamma-mixed normalised Q + uniform)
    #   "ucb"   — per-agent UCB1 over incoming edges; deterministic argmax
    #             of q_mean + c*sqrt(ln(e+1)/(n+1)), typically converging in
    #             far fewer episodes than the annealed mixed policy.
    policy: str = "mixed"
    ucb_c: float = 1.5


class RLState(NamedTuple):
    q: jax.Array            # (N, N)
    counts: jax.Array       # (N, N) per-action pick counts (UCB)
    buf_actions: jax.Array  # (N, M) int32
    buf_rewards: jax.Array  # (N, M) global rewards (Eq. 3)
    buf_local: jax.Array    # (N, M) local rewards (for Eq. 5)
    r_net_prev: jax.Array   # ()
    t: jax.Array            # () number of buffer flushes so far


class GraphResult(NamedTuple):
    in_edge: jax.Array        # (N,) transmitter chosen by each receiver (Eq. 7)
    q: jax.Array              # (N, N) final Q-table
    ep_mean_local: jax.Array  # (E,) mean local reward per episode
    ep_mean_pfail: jax.Array  # (E,) mean P_D of chosen links per episode
    state: Optional[RLState] = None  # full final state (warm-start seed)


def _gamma(t, cfg: RLConfig):
    return jnp.minimum(cfg.gamma0 + cfg.gamma_step * t.astype(jnp.float32),
                       cfg.gamma_max)


def _row_lookup(mat, actions):
    """mat[i, actions[i]] for every agent i — an axis-1 gather whose rows
    stay on their shard (unlike a fancy-index gather, which the partitioner
    may lower to a cross-shard collective-permute)."""
    return jnp.take_along_axis(mat, actions[:, None], axis=1)[:, 0]


def _mask_self(mat, fill):
    """Self-links masked via a broadcast `where` (row-local; the scatter
    form `at[diag].set` partitions poorly over a sharded agent axis)."""
    n = mat.shape[-1]
    eye = jnp.eye(n, dtype=bool)
    return jnp.where(eye, fill, mat)


def policy_probs(q, gamma, u):
    """Eq. 4 with shifted normalisation; self-links masked.

    q: (N, N), u: (N, N) uniform noise."""
    qs = _mask_self(q, jnp.inf)
    qmin = jnp.min(qs, axis=1, keepdims=True)
    q_shift = _mask_self(q - qmin + 1e-6, 0.0)
    q_norm = q_shift / jnp.sum(q_shift, axis=1, keepdims=True)
    mixed = _mask_self(gamma * q_norm + (1.0 - gamma) * u, 0.0)
    return mixed / jnp.sum(mixed, axis=1, keepdims=True)


def ucb_actions(q, counts, episode, c):
    """UCB1 over incoming edges (beyond-paper variant): value estimate is
    the running mean reward per action; unexplored actions are infinite."""
    mean = q / jnp.maximum(counts, 1.0)
    bonus = c * jnp.sqrt(jnp.log(episode.astype(jnp.float32) + 2.0)
                         / jnp.maximum(counts, 1e-9))
    score = jnp.where(counts > 0, mean + bonus, jnp.inf)
    score = _mask_self(score, -jnp.inf)
    return jnp.argmax(score, axis=1)


def _q_update(q, buf_actions, buf_rewards):
    """Eq. 6: Q_i(a) += mean of buffered global rewards with action a."""
    n = q.shape[1]  # number of actions
    onehot = jax.nn.one_hot(buf_actions, n, dtype=jnp.float32)   # (N,M,A)
    sums = jnp.einsum("nma,nm->na", onehot, buf_rewards)
    counts = jnp.sum(onehot, axis=1)                             # (N,A)
    means = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), 0.0)
    return q + means


def init_rl_state(n: int, cfg: RLConfig = RLConfig()) -> RLState:
    """Cold-start agent state (paper: small equal Q values, empty buffers)."""
    m = cfg.buffer_size
    return RLState(
        # strong-typed f32 (a python-float fill would give a weak-typed
        # array, whose aval differs from the scan's strong-typed output
        # state — costing warm-start calls a pointless retrace)
        q=jnp.full((n, n), cfg.q_init, jnp.float32),
        counts=jnp.zeros((n, n)),
        buf_actions=jnp.zeros((n, m), jnp.int32),
        buf_rewards=jnp.zeros((n, m)),
        buf_local=jnp.zeros((n, m)),
        r_net_prev=jnp.zeros(()),
        t=jnp.zeros((), jnp.int32),
    )


def discover_graph(key, local_r, p_fail, cfg: RLConfig = RLConfig(),
                   init_state: Optional[RLState] = None,
                   n_episodes: Optional[int] = None,
                   rules: Optional[sh.ShardingRules] = None) -> GraphResult:
    """Run Algorithm 1.

    local_r: (N, N) precomputed r_ij (Eq. 2; stationary in the paper's
    setting since lambda and P_D are fixed during discovery).
    p_fail: (N, N) P_D for diagnostics.

    ``init_state`` warm-starts from a previous epoch's final
    :class:`RLState` (``GraphResult.state``) — the online orchestrator uses
    this so short re-discovery bursts inherit the learned Q-tables instead
    of re-exploring from scratch.  ``n_episodes`` overrides
    ``cfg.n_episodes`` for such bursts; the whole burst stays one
    device-resident ``lax.scan``.

    ``rules`` shards the agent axis over the mesh (see module docstring);
    a warm-start state from a sharded run is already correctly placed and
    rides straight back in (re-placement is a no-op ``device_put``).
    """
    n = local_r.shape[0]
    n_ep = cfg.n_episodes if n_episodes is None else n_episodes
    with obs.span("discover", episodes=int(n_ep), agents=int(n),
                  warm=init_state is not None, policy=cfg.policy):
        state = init_state if init_state is not None else init_rl_state(n, cfg)
        # Place every agent-major operand on the CLIENTS mesh axis (scalars
        # in the state — r_net_prev, t — map to replicated); rules=None is
        # the identity.  Placement happens outside the jit below so the
        # traced program only ever sees correctly-placed operands.
        local_r, p_fail, state = sh.shard_clients(
            (jnp.asarray(local_r), jnp.asarray(p_fail), state), rules)
        return _discover_impl(key, local_r, p_fail, state, cfg, n_ep, rules)


# The module-level jit (cfg/n_ep/rules static) is load-bearing for the
# online orchestrator, not a micro-optimisation: a bare `lax.scan` outside
# jit re-traces its body every call, and the eager dispatch cache keys on
# the fresh jaxpr — so every warm re-discovery burst was re-COMPILING the
# episode scan (~0.6 s on CPU) despite identical shapes.  Under a proper
# jit the cache keys on (function, avals, statics) and steady-state bursts
# are cache hits; tests/test_obs.py pins this with the compile counter.
@functools.partial(jax.jit, static_argnums=(4, 5, 6))
@jax.named_scope("discover")
def _discover_impl(key, local_r, p_fail, state, cfg, n_ep, rules):
    n = local_r.shape[0]
    m = cfg.buffer_size
    use_ucb = cfg.policy == "ucb"

    def episode(state: RLState, inp):
        e, key = inp
        state = sh.constrain_clients(state, rules)
        ku, ks = jax.random.split(key)
        gamma = _gamma(state.t, cfg)
        if use_ucb:
            actions = ucb_actions(state.q, state.counts, e, cfg.ucb_c)
        else:
            u = sh.constrain_clients(jax.random.uniform(ku, (n, n)), rules)
            probs = policy_probs(state.q, gamma, u)
            actions = jax.random.categorical(ks, jnp.log(probs + 1e-12),
                                             axis=1)
        actions = sh.constrain_clients(actions, rules)
        r_loc = _row_lookup(local_r, actions)                    # (N,)
        # Eq. 3's episode-mean reward: the first of the two cross-agent
        # scalars — a psum-style all-reduce on a mesh.
        mean_r = sh.client_mean(r_loc, rules)
        r_glob = rw.global_rewards(r_loc, gamma, state.r_net_prev, mean_r)
        hot = jax.nn.one_hot(actions, n, dtype=state.counts.dtype)
        counts = state.counts + hot
        slot = e % m
        buf_a = state.buf_actions.at[:, slot].set(actions)
        buf_r = state.buf_rewards.at[:, slot].set(r_glob)
        buf_l = state.buf_local.at[:, slot].set(r_loc)

        if use_ucb:
            # UCB maintains running reward sums directly (no buffer flush)
            q = state.q + hot * r_glob[:, None]
            state = RLState(q, counts, buf_a, buf_r, buf_l,
                            state.r_net_prev, state.t)
        else:
            def flush(_):
                # Eq. 5: per-agent r_hat is shard-local, the network mean
                # is the second collective.
                r_hat = rw.frequent_local_reward(buf_a, buf_l, n)
                r_net = sh.client_mean(r_hat, rules)
                q = _q_update(state.q, buf_a, buf_r)
                return RLState(q, counts, buf_a, buf_r, buf_l, r_net,
                               state.t + 1)

            def keep(_):
                return RLState(state.q, counts, buf_a, buf_r, buf_l,
                               state.r_net_prev, state.t)

            state = jax.lax.cond(slot == m - 1, flush, keep, None)
        diag = (mean_r, sh.client_mean(_row_lookup(p_fail, actions), rules))
        return sh.constrain_clients(state, rules), diag

    keys = jax.random.split(key, n_ep)
    state, (ep_r, ep_p) = jax.lax.scan(
        episode, state, (jnp.arange(n_ep), keys))

    # Eq. 7: final links = argmax accumulated reward (self masked).
    # UCB: argmax of the running MEAN (sums are count-biased); actions never
    # tried have no estimate and are masked out.
    if use_ucb:
        qf = state.q / jnp.maximum(state.counts, 1.0)
        qf = jnp.where(state.counts == 0, -jnp.inf, qf)
    else:
        qf = state.q
    qf = _mask_self(qf, -jnp.inf)
    in_edge = jnp.argmax(qf, axis=1)
    return GraphResult(in_edge, state.q, ep_r, ep_p, state)


def uniform_graph(key, n: int) -> jax.Array:
    """Baseline: each receiver picks a transmitter uniformly at random."""
    offs = jax.random.randint(key, (n,), 1, n)
    return (jnp.arange(n) + offs) % n

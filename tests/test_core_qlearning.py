"""Q-learning graph discovery (paper Eqs. 4, 6, 7 + Algorithm 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import sharding as sh
from repro.core import qlearning as QL
from repro.launch.mesh import make_client_mesh


def test_policy_probs_simplex_and_no_self():
    n = 6
    q = jax.random.normal(jax.random.PRNGKey(0), (n, n))
    u = jax.random.uniform(jax.random.PRNGKey(1), (n, n))
    p = QL.policy_probs(q, gamma=0.7, u=u)
    np.testing.assert_allclose(np.asarray(jnp.sum(p, 1)), 1.0, rtol=1e-5)
    assert np.all(np.asarray(jnp.diag(p)) == 0.0)
    assert np.all(np.asarray(p) >= 0.0)


def test_policy_gamma_one_proportional_to_q():
    """At gamma=1 (pure exploitation) probs ~ shifted-normalised Q."""
    q = jnp.asarray([[0.0, 1.0, 3.0], [2.0, 0.0, 2.0], [5.0, 1.0, 0.0]])
    u = jnp.zeros((3, 3))
    p = QL.policy_probs(q, gamma=1.0, u=u)
    # row 0: shifted q = [_, 0, 2] (+eps) -> p ~ [0, eps, 2+eps]
    assert float(p[0, 2]) > 0.9
    assert float(p[2, 0]) > 0.8


def test_q_update_eq6_mean_per_action():
    q = jnp.zeros((2, 3))
    buf_a = jnp.asarray([[1, 1, 2], [0, 2, 0]])
    buf_r = jnp.asarray([[2.0, 4.0, 10.0], [1.0, 5.0, 3.0]])
    q2 = QL._q_update(q, buf_a, buf_r)
    np.testing.assert_allclose(np.asarray(q2[0]), [0.0, 3.0, 10.0])
    np.testing.assert_allclose(np.asarray(q2[1]), [2.0, 0.0, 5.0])


def test_discover_graph_finds_high_reward_links():
    """Synthetic bandit: one transmitter clearly best per receiver ->
    the learned graph should pick it for most receivers."""
    n = 8
    key = jax.random.PRNGKey(2)
    best = (jnp.arange(n) + 3) % n
    local_r = jnp.full((n, n), 0.1)
    local_r = local_r.at[jnp.arange(n), best].set(5.0)
    local_r = local_r.at[jnp.arange(n), jnp.arange(n)].set(-1e9)
    res = QL.discover_graph(key, local_r, jnp.zeros((n, n)),
                            QL.RLConfig(n_episodes=400, buffer_size=40))
    hits = int(jnp.sum(res.in_edge == best))
    assert hits >= n - 1, (np.asarray(res.in_edge), np.asarray(best))


def test_discover_graph_no_self_links():
    n = 5
    local_r = jax.random.normal(jax.random.PRNGKey(3), (n, n))
    res = QL.discover_graph(jax.random.PRNGKey(4), local_r, jnp.zeros((n, n)))
    assert np.all(np.asarray(res.in_edge) != np.arange(n))


def test_mean_reward_improves_over_training():
    """Exploration anneals toward exploitation: late-episode mean local
    reward should exceed early-episode mean."""
    n = 10
    key = jax.random.PRNGKey(5)
    local_r = jax.random.uniform(key, (n, n)) * 4.0
    local_r = local_r.at[jnp.arange(n), jnp.arange(n)].set(-1e9)
    res = QL.discover_graph(jax.random.PRNGKey(6), local_r, jnp.zeros((n, n)),
                            QL.RLConfig(n_episodes=600, buffer_size=90))
    early = float(jnp.mean(res.ep_mean_local[:90]))
    late = float(jnp.mean(res.ep_mean_local[-90:]))
    assert late > early


def _world(n=8, seed=0):
    key = jax.random.PRNGKey(seed)
    local_r = jax.random.uniform(jax.random.fold_in(key, 1), (n, n)) * 4.0
    local_r = local_r.at[jnp.arange(n), jnp.arange(n)].set(-1e9)
    p_fail = jax.random.uniform(jax.random.fold_in(key, 2), (n, n)) * 0.3
    return key, local_r, p_fail


def _mesh1_rules():
    return sh.ShardingRules.default(make_client_mesh(1))


def _assert_trees_equal(a, b, what):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


@pytest.mark.parametrize("policy", ["mixed", "ucb"])
def test_sharded_mesh1_bit_identical(policy):
    """discover_graph under a 1-device mesh is bit-for-bit the unsharded
    program for both exploration policies — the acceptance bar for passing
    ``rules`` unconditionally (mirrors the mesh=4 subprocess suite)."""
    key, local_r, p_fail = _world()
    cfg = QL.RLConfig(n_episodes=120, buffer_size=30, policy=policy)
    base = QL.discover_graph(key, local_r, p_fail, cfg)
    shrd = QL.discover_graph(key, local_r, p_fail, cfg, rules=_mesh1_rules())
    _assert_trees_equal(base._replace(state=None),
                        shrd._replace(state=None), policy)
    _assert_trees_equal(base.state, shrd.state, policy)


@pytest.mark.parametrize("policy", ["mixed", "ucb"])
def test_sharded_warm_start_mesh1_bit_identical(policy):
    """A sharded burst resumed from a *mesh-placed* ``GraphResult.state``
    is bit-identical to the unsharded warm-start path: placement survives
    the segment boundary (the online orchestrator's re-discovery pattern)
    without perturbing a single bit of Algorithm 1."""
    key, local_r, p_fail = _world(seed=3)
    rules = _mesh1_rules()
    cfg = QL.RLConfig(n_episodes=90, buffer_size=30, policy=policy)
    cold_base = QL.discover_graph(key, local_r, p_fail, cfg)
    cold_shrd = QL.discover_graph(key, local_r, p_fail, cfg, rules=rules)
    k2 = jax.random.fold_in(key, 1)
    warm_base = QL.discover_graph(k2, local_r, p_fail, cfg,
                                  init_state=cold_base.state, n_episodes=45)
    warm_shrd = QL.discover_graph(k2, local_r, p_fail, cfg,
                                  init_state=cold_shrd.state, n_episodes=45,
                                  rules=rules)
    assert warm_shrd.ep_mean_local.shape == (45,)
    _assert_trees_equal(warm_base.state, warm_shrd.state, policy)
    _assert_trees_equal(warm_base.in_edge, warm_shrd.in_edge, policy)
    # cross-over: an unsharded warm start consuming a mesh-placed state is
    # also exact (placement is a property of the buffers, not the math)
    warm_x = QL.discover_graph(k2, local_r, p_fail, cfg,
                               init_state=cold_shrd.state, n_episodes=45)
    _assert_trees_equal(warm_base.state, warm_x.state, policy)


def test_uniform_graph_no_self():
    g = QL.uniform_graph(jax.random.PRNGKey(7), 12)
    assert np.all(np.asarray(g) != np.arange(12))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), gamma=st.floats(0.0, 1.0))
def test_property_policy_valid_for_any_q(seed, gamma):
    n = 5
    q = jax.random.normal(jax.random.PRNGKey(seed), (n, n)) * 10
    u = jax.random.uniform(jax.random.PRNGKey(seed + 1), (n, n))
    p = QL.policy_probs(q, gamma=gamma, u=u)
    assert bool(jnp.all(jnp.isfinite(p)))
    np.testing.assert_allclose(np.asarray(jnp.sum(p, 1)), 1.0, rtol=1e-4)

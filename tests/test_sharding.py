"""Sharding rules: divisibility fallback, axis dedupe, scalar marker."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding as sh
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh():
    # all CPU devices in a (1, n) data/model mesh
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def _abstract_mesh(sizes, names):
    return jax.sharding.AbstractMesh(sizes, names)


def test_basic_spec(mesh):
    rules = sh.ShardingRules.default(mesh)
    spec = rules.spec((sh.D_MODEL, sh.D_FF))
    assert spec == P(("data",), "model")


def test_divisibility_fallback():
    # use a fake 16-wide model axis via an abstract mesh (no devices needed
    # beyond 1: AbstractMesh carries only shape/axis metadata)
    amesh = _abstract_mesh((16, 16), ("data", "model"))
    rules = sh.ShardingRules.default(amesh)
    spec = rules.spec((sh.D_MODEL, sh.D_FF), dims=(32, 49))
    assert spec[1] is None  # d_ff=49 not divisible by 16 -> replicated
    spec = rules.spec((sh.D_MODEL, sh.D_FF), dims=(32, 64))
    assert spec[1] == "model"


def test_axis_dedupe_moe_fallback():
    """EXPERTS and D_FF both map to "model": the second use is dropped."""
    amesh = _abstract_mesh((16, 16), ("data", "model"))
    rules = sh.ShardingRules.default(amesh)
    spec = rules.spec((sh.EXPERTS, sh.D_MODEL, sh.D_FF), dims=(64, 32, 32))
    assert spec == P("model", ("data",), None)
    # experts NOT divisible (qwen2-moe's 60) -> within-expert TP instead
    spec2 = rules.spec((sh.EXPERTS, sh.D_MODEL, sh.D_FF), dims=(60, 32, 32))
    assert spec2 == P(None, ("data",), "model")


def test_scalar_marker(mesh):
    rules = sh.ShardingRules.default(mesh)
    assert rules.spec(sh.SCALAR) == P()


def test_stack_axis_never_sharded(mesh):
    rules = sh.ShardingRules.default(mesh)
    spec = rules.spec((sh.STACK, sh.D_MODEL, sh.D_FF))
    assert spec[0] is None


def test_batch_spec(mesh):
    rules = sh.ShardingRules.default(mesh)
    assert rules.spec((sh.BATCH, None)) == P(("data",), None)


def test_clients_axis_non_divisible_replicates():
    """A client count that does not divide the data axis degrades to
    replication instead of failing at lower time (graceful N)."""
    amesh = _abstract_mesh((4,), ("data",))
    rules = sh.ShardingRules.default(amesh)
    assert rules.spec((sh.CLIENTS, None), dims=(6, 7)) == P(None, None)
    assert rules.spec((sh.CLIENTS, None), dims=(8, 7)) == P(("data",), None)


def test_sharding_rules_hashable_for_jit_static():
    """ShardingRules rides through jit as a static argument — it must hash
    (the default frozen-dataclass hash would choke on the rules dict)."""
    amesh = _abstract_mesh((4,), ("data",))
    rules = sh.ShardingRules.default(amesh)
    assert hash(rules) == hash(sh.ShardingRules.default(amesh))
    assert rules == sh.ShardingRules.default(amesh)
    assert len({rules, sh.ShardingRules.default(amesh)}) == 1


def test_client_axes_helpers(mesh):
    rules = sh.ShardingRules.default(mesh)
    assert sh.client_axes(3) == (sh.CLIENTS, None, None)
    assert sh.client_axes(0) == ()
    # rules=None is the identity for both helpers
    x = np.ones((4, 3))
    assert sh.shard_clients(x, None) is x
    assert sh.constrain_clients(x, None) is x
    y = sh.shard_clients(jax.numpy.ones((4, 3)), rules)
    assert y.shape == (4, 3)


def test_multi_pod_rules():
    devs = np.array(jax.devices())
    if devs.size < 1:
        pytest.skip("no devices")
    mesh = make_mesh((1, 1, devs.size), ("pod", "data", "model"))
    rules = sh.ShardingRules.default(mesh)
    assert rules.spec((sh.BATCH, None)) == P(("pod", "data"), None)
    assert rules.data_axes() == ("pod", "data")


@pytest.mark.parametrize("case", ["rank_mismatch", "explicit_axes"])
def test_constrain_raises_on_impossible_placement(case):
    """A placement the mesh cannot honour raises instead of returning the
    array unconstrained: a spec of the wrong rank, and a mesh whose axes
    are Explicit (``jax.make_mesh``'s default), which in-jit constraints
    cannot name."""
    if case == "rank_mismatch":
        rules = sh.ShardingRules.default(make_mesh((1,), ("data",)))
        x, logical = jax.numpy.ones((4,)), (sh.CLIENTS, None)
    else:
        rules = sh.ShardingRules.default(jax.make_mesh((1,), ("data",)))
        x, logical = jax.numpy.ones((4, 2)), (sh.CLIENTS, None)
    with pytest.raises(ValueError):
        jax.jit(lambda a: sh.constrain(a, rules, logical))(x)


def test_client_map_is_shard_local_identity(mesh):
    """``client_map`` runs a row-local fn per shard; on this host's mesh the
    result equals the unmapped fn bit for bit, and ``rules=None`` hands
    the fn back untouched."""
    rules = sh.ShardingRules.default(mesh)

    def fn(x, tree):
        return x * 2.0 + tree["b"][:, :1], jax.numpy.sum(x, axis=1)

    x = jax.numpy.arange(12.0).reshape(4, 3)
    tree = {"b": jax.numpy.ones((4, 2))}
    assert sh.client_map(fn, None) is fn
    got = jax.jit(sh.client_map(fn, rules))(x, tree)
    want = fn(x, tree)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

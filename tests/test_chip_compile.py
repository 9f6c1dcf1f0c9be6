"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with jax, compiles for a
chip that is described and not attached, and raises what Mosaic would raise
on the chip (block tiling, layouts, VMEM).  Interpret-mode tests cannot see
those faults.  This is the only test file that describes the chip; the
topology is built inside a fixture, so importing this module loads nothing.

Shapes are the paper's setting (ROADMAP D1): 30 clients of 99 FMNIST-shaped
samples, 32 PCA features and 3 clusters; the gate scores each client's
whole stack (R = cap) and 3 reserves of 40 samples per receiver.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import sharding as sh
from repro.core import exchange, pipeline
from repro.fl import trainer
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import autoencoder as ae

N_CLIENTS, CAP, N_PCA, K = 30, 99, 32, 3
PIXELS, RESERVE = 28 * 28, 40


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_dispatch(monkeypatch):
    """Route ``ops`` to the compiled kernels, as on a TPU backend: this
    process's backend is the CPU, so ``ops`` would pick the oracle.  A
    compile for a described chip cannot be read back from the persistent
    cache, so the cache is off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "_use_pallas", lambda override: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_kmeans_assign_vmapped_compiles(one_chip, chip_dispatch):
    """The clustering program's form: ``kmeans_batched`` vmaps the
    assignment over the (N, cap, d) client stack."""
    text = _compile_text(jax.vmap(ops.kmeans_assign),
                         _spec(one_chip, (N_CLIENTS, CAP, N_PCA)),
                         _spec(one_chip, (N_CLIENTS, K, N_PCA)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", [
    pytest.param((N_CLIENTS, CAP, PIXELS), id="base-whole-cap"),
    pytest.param((N_CLIENTS, K, RESERVE, PIXELS), id="reserves"),
    pytest.param((N_CLIENTS, 1000, PIXELS), id="base-large-cap"),
])
def test_recon_gate_compiles(one_chip, chip_dispatch, shape):
    """Both gate call sites: the base score over each receiver's whole
    stack and the (receiver, cluster) reserve scores; the large cap checks
    that the sample axis is tiled within VMEM."""
    text = _compile_text(ops.recon_gate_score,
                         _spec(one_chip, shape), _spec(one_chip, shape),
                         _spec(one_chip, shape[:-1]))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["kmeans_assign", "recon_gate"])
def test_compiled_kernel_carries_its_name(one_chip, chip_dispatch, kernel):
    """Each kernel's custom-call is named after it (``pallas_call``'s
    ``name``), so a device trace tells the two apart."""
    if kernel == "kmeans_assign":
        text = _compile_text(jax.vmap(ops.kmeans_assign),
                             _spec(one_chip, (N_CLIENTS, CAP, N_PCA)),
                             _spec(one_chip, (N_CLIENTS, K, N_PCA)))
    else:
        shape = (N_CLIENTS, CAP, PIXELS)
        text = _compile_text(ops.recon_gate_score, _spec(one_chip, shape),
                             _spec(one_chip, shape),
                             _spec(one_chip, shape[:-1]))
    calls = re.findall(r"%([\w.-]+) = [^\n]* custom-call\([^\n]*"
                       r"tpu_custom_call", text)
    assert calls and all(re.fullmatch(kernel + r"(\.\d+)?", c)
                         for c in calls), calls


def test_clustering_program_contains_kernel(one_chip, chip_dispatch):
    """The whole jitted clustering program (federated PCA + vmapped
    K-means++) compiles with the assignment kernel inside it."""
    cluster = pipeline._cluster_impl.__wrapped__
    lowered = jax.jit(cluster, static_argnums=(3, 4, 5, 6)).lower(
        _spec(one_chip, (2,), jnp.uint32),
        _spec(one_chip, (N_CLIENTS, CAP, 28, 28, 1)),
        _spec(one_chip, (N_CLIENTS,), jnp.int32),
        N_PCA, K, 25, None)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("cfg", [
    pytest.param(ae.AEConfig(), id="fmnist"),
    pytest.param(ae.AEConfig(height=32, width=32, channels=3), id="cifar"),
])
def test_fl_round_compiles_to_convolutions(one_chip, chip_dispatch, cfg):
    """One FL round (``tau_a`` = 10 local Adam steps of batch 32 and the
    FedAvg mean) lowers the AE's layers to 3x3-window convolutions and
    holds under 1 GiB of temporaries.

    The patch-gather layers these replaced stacked the 9 shifted views of
    every conv input; XLA lowered their einsums to convolutions too, but
    with a 9-tap window over the stacked axis.  Temporaries of this
    compile: 8,306,755,584 B (fmnist) and 11,024,579,584 B (cifar) with the
    patch-gather; 275,093,504 B and 619,716,096 B with XLA convolutions."""
    fl = trainer.FLConfig(tau_a=10, batch_size=32, local_opt="adam")
    params = jax.eval_shape(lambda k: ae.init_ae(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    stacked = jax.tree.map(
        lambda a: _spec(one_chip, (N_CLIENTS,) + a.shape), params)
    carry = trainer.FLCarry(
        stacked, jax.tree.map(lambda a: _spec(one_chip, a.shape), params),
        stacked, stacked, _spec(one_chip, ()))
    compiled = jax.jit(trainer._round_body, static_argnums=(0, 1, 7)).lower(
        fl, cfg, carry,
        _spec(one_chip, (N_CLIENTS, CAP, cfg.height, cfg.width,
                         cfg.channels)),
        _spec(one_chip, (N_CLIENTS,), jnp.int32),
        _spec(one_chip, (N_CLIENTS,)),
        _spec(one_chip, (fl.tau_a, 2), jnp.uint32), None).compile()
    assert re.search(r"convolution\([^\n]*window=\{size=3x3",
                     compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_gate_program_sharded_over_four_chips(topo, chip_dispatch):
    """Under CLIENTS sharding the gate's kernel runs shard-locally: Mosaic
    kernels cannot be partitioned automatically, so the program maps them
    with ``sharding.client_map``.  32 clients over the described 2x2 host;
    no client data is gathered across chips."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rules = sh.ShardingRules.default(
        make_mesh((4,), ("data",), devices=topo.devices))
    n, cfg = 32, ae.AEConfig()

    def spec(shape, dtype=jnp.float32):
        lead = P("data", *(None,) * (len(shape) - 1))
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(rules.mesh, lead))

    params = jax.eval_shape(jax.vmap(lambda k: ae.init_ae(k, cfg)),
                            jax.ShapeDtypeStruct((n, 2), jnp.uint32))
    params = jax.tree.map(lambda a: spec(a.shape, a.dtype), params)
    gate = jax.jit(exchange._gate_scores.__wrapped__,
                   static_argnums=(9, 10, 11))
    text = gate.lower(
        params, spec((n, CAP, 28, 28, 1)), spec((n, CAP)),
        spec((n, K, RESERVE, 28, 28, 1)), spec((n, K, RESERVE)),
        spec((n, K), bool), spec((n,)), spec((n, n)), spec((n,), jnp.int32),
        cfg, True, rules).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text


def test_fl_round_sharded_over_four_chips(topo, chip_dispatch):
    """Under CLIENTS sharding each chip convolves only its own clients: the
    round's vmapped gradient runs under ``sharding.client_map``, and the
    only collectives are FedAvg's all-reduces.  Left to the partitioner,
    the vmapped convolutions all-gather every client's minibatch."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rules = sh.ShardingRules.default(
        make_mesh((4,), ("data",), devices=topo.devices))
    n, cfg = 32, ae.AEConfig()
    fl = trainer.FLConfig(tau_a=10, batch_size=32, local_opt="adam")

    def spec(shape, dtype=jnp.float32, lead=True):
        axes = P("data", *(None,) * (len(shape) - 1)) if lead else P()
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(rules.mesh, axes))

    params = jax.eval_shape(lambda k: ae.init_ae(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    stacked = jax.tree.map(lambda a: spec((n,) + a.shape), params)
    carry = trainer.FLCarry(
        stacked, jax.tree.map(lambda a: spec(a.shape, lead=False), params),
        stacked, stacked, spec((), lead=False))
    text = jax.jit(trainer._round_body, static_argnums=(0, 1, 7)).lower(
        fl, cfg, carry, spec((n, CAP, 28, 28, 1)), spec((n,), jnp.int32),
        spec((n,)), spec((fl.tau_a, 2), jnp.uint32, lead=False),
        rules).compile().as_text()
    assert "all-reduce" in text
    assert "all-gather" not in text

"""``correct`` comes out true for a sound run and false for the control and
for every fault that a cell can have.

The harness runs as ``bench/run.py`` runs it, minus its look for a chip,
at a size a CPU test can hold; the limits are those of ``fmnist-r100``.
Each fault is planted under the timed path (the system's own functions,
patched for the test), the control puts the bfloat16 reference in the
system's place.
"""
import copy
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, spec
from bench import run as bench_run

LIMITS_OF = "fmnist-r100"
SEED = 2 ** 33 + 5


def tiny(adam_lr=1e-2, iters_per_segment=5):
    """A cell of the fmnist configuration's kind at a CPU test's size.  The
    larger default step lets the first round move the tiny model as far as
    the first round of a cell moves its own."""
    cfg = copy.deepcopy(spec.load_config("ae-fmnist-n30"))
    cfg["world"].update(n_clients=10, n_train_per_class=12,
                        n_eval_per_class=2)
    cfg["ae"].update(height=16, width=16, widths=[4, 8], latent_dim=8)
    cfg["fl"].update(batch_size=4, tau_a=5, eval_every=5, adam_lr=adam_lr)
    cfg["pipeline"].update(n_pca=4, kmeans_iters=3)
    cfg["rl"].update(n_episodes=10, buffer_size=5)
    cfg["burst_episodes"] = 5
    cfg["exchange"].update(reserve_per_cluster=2)
    traffic = dict(spec.load_traffic("online-fading-r100"), n_segments=2,
                   iters_per_segment=iters_per_segment)
    return bench_run.make_cell(jax, "tiny", cfg, traffic, SEED)


@pytest.fixture(scope="module")
def limits():
    lim = spec.load_limits(LIMITS_OF)
    assert lim["change_worst_leaf"] is not None, lim
    return lim


def judge(cell, limits, runner=None):
    win = bench_run.measure(jax, cell, 0.0, runner=runner)
    numbers = bench_run.check_run(jax, cell, win["runs"][0])
    ok, table = check.judge(numbers, limits)
    return ok, numbers


@pytest.fixture
def fresh():
    """Every plant recompiles: the system's programs are cached by jit."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_sound_run_is_correct(limits, fresh):
    ok, numbers = judge(tiny(), limits)
    assert ok, numbers
    assert numbers["moved"] == 0 and numbers["offline"] == 0


def _unchanged(monkeypatch):
    from repro.fl import trainer

    def body(cfg, ae_cfg, carry, *args, **kw):
        return carry
    monkeypatch.setattr(trainer, "_round_body", body)
    monkeypatch.setattr(trainer, "_round_fn", jax.jit(
        body, static_argnums=(0, 1, 7)))


def _half_batch(monkeypatch):
    from repro.models import autoencoder as ae

    real = ae.recon_loss

    def half(params, x, cfg):
        if x.shape[0] == 4:            # a local minibatch, not the eval set
            x = x[:2]
        return real(params, x, cfg)
    monkeypatch.setattr(ae, "recon_loss", half)


def _no_fedavg(monkeypatch):
    from repro.fl import trainer

    monkeypatch.setattr(trainer, "_masked_mean",
                        lambda tree, mask: jax.tree.map(lambda p: p[0], tree))


@pytest.mark.parametrize("plant", [_unchanged, _half_batch, _no_fedavg],
                         ids=["unchanged", "half_batch", "no_fedavg"])
def test_planted_fault_is_not_correct(plant, limits, fresh, monkeypatch):
    plant(monkeypatch)
    ok, numbers = judge(tiny(), limits)
    assert not ok, numbers


def test_altered_answer_is_not_correct(limits, fresh):
    """The first eval reports the loss of the model before the round."""
    from repro.dynamics import run_orchestrator
    from repro.models import autoencoder as ae

    def runner(key, cd, labels, ae_cfg, orch, scenario, eval_x):
        res = run_orchestrator(key, cd, labels, ae_cfg, orch, scenario,
                               eval_x)
        init = ae.init_ae(jax.random.split(key, 3)[2], ae_cfg)
        loss = np.asarray(res.eval_loss).copy()
        loss[0] = float(ae.recon_loss(init, eval_x, ae_cfg))
        return res._replace(eval_loss=loss)

    ok, numbers = judge(tiny(), limits, runner)
    assert not ok, numbers


def test_control_is_not_correct(limits, fresh):
    """The reference in bfloat16 put in the system's place, at the
    configuration's own step size."""
    cell = tiny(adam_lr=1e-3, iters_per_segment=10)
    ref_mod = spec.load_reference(cell.cfg["reference"])
    total = cell.traffic["n_segments"] * cell.traffic["iters_per_segment"]
    n, segs = cell.cfg["world"]["n_clients"], cell.traffic["n_segments"]

    def runner(key, cd, labels, ae_cfg, orch, scenario, eval_x):
        r = ref_mod.run(key, cd.data, cd.sizes, eval_x, cell.cfg["ae"],
                        cell.cfg["fl"], total, dtype=jnp.bfloat16)
        seg = types.SimpleNamespace(moved=0, n_available=n)
        return types.SimpleNamespace(
            global_params=r["final"], carry=r["final"], client_data=cd,
            eval_loss=np.asarray(r["eval_loss"], np.float32),
            trace=types.SimpleNamespace(segments=[seg] * segs))

    ok, numbers = judge(cell, limits, runner)
    assert not ok, numbers

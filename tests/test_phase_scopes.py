"""Phase scopes reach the device programs: a tiny federation on the scan
engine records the arguments of the fused chunk and of segment 0's jitted
programs, lowers each, and finds every phase scope it runs as a whole
component of some op's ``op_name`` metadata (what a TPU trace reports as
``tf_op``).  One scope covers both engines because both call the same
functions."""
import re

import jax
import pytest

from repro.core import exchange, pipeline, qlearning
from repro.dynamics import orchestrator
from repro.fl import trainer

PHASES = ("fl", "cluster", "discover", "exchange")
KERNELS = ("kmeans_assign", "recon_gate")

# program -> (module it is called through in segment 0, scopes it runs)
PROGRAMS = {
    "_chunk_fn": (orchestrator, PHASES + KERNELS),
    "_cluster_impl": (pipeline, ("cluster", "kmeans_assign")),
    "_discover_impl": (qlearning, ("discover",)),
    "_pretrain_step": (exchange, ("exchange",)),
    "_exchange_device": (exchange, ("exchange", "recon_gate")),
    "_round_fn": (trainer, ("fl",)),
    "_eval_loss_fn": (trainer, ("fl",)),
}


def _spec(a):
    if isinstance(a, jax.Array):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    return a


@pytest.fixture(scope="module")
def lowered():
    """HLO text with metadata of each program, lowered from the shapes of
    its first call outside a trace."""
    from repro.core.exchange import ExchangeConfig
    from repro.core.pipeline import PipelineConfig
    from repro.core.qlearning import RLConfig
    from repro.data import partition_by_classes
    from repro.data.synthetic import fmnist_like_split
    from repro.dynamics import OrchestratorConfig, run_orchestrator
    from repro.fl import FLConfig
    from repro.models.autoencoder import AEConfig

    ds, ev = fmnist_like_split(jax.random.PRNGKey(0), n_train_per_class=12,
                               n_eval_per_class=4)
    xs, ys, _ = partition_by_classes(0, ds.images, ds.labels, n_clients=4,
                                     classes_per_client=3)
    cfg = OrchestratorConfig(
        n_segments=2, iters_per_segment=10, mode="online",
        rediscover_every=1, burst_episodes=20,
        pipeline=PipelineConfig(
            rl=RLConfig(n_episodes=20, buffer_size=10),
            exchange=ExchangeConfig(apply_channel_failure=True,
                                    overflow="drop",
                                    reserve_selector="device")),
        fl=FLConfig(tau_a=10, eval_every=10, batch_size=8),
        segment_impl="scan")

    calls, originals = {}, {}
    mp = pytest.MonkeyPatch()
    for name, (mod, _) in PROGRAMS.items():
        fn = originals[name] = getattr(mod, name)

        def spy(*args, _fn=fn, _name=name, **kw):
            leaves = jax.tree.leaves((args, kw))
            if _name not in calls and not any(
                    isinstance(x, jax.core.Tracer) for x in leaves):
                calls[_name] = jax.tree.map(_spec, (args, kw))
            return _fn(*args, **kw)
        mp.setattr(mod, name, spy)
    try:
        run_orchestrator(jax.random.PRNGKey(3), xs, ys,
                         AEConfig(28, 28, 1, widths=(4, 8), latent_dim=8),
                         cfg, "fading", ev.images)
    finally:
        mp.undo()
    out = {}
    for name, (args, kw) in calls.items():
        out[name] = originals[name].lower(*args, **kw).as_text(
            dialect="hlo", debug_info=True)
    return out


def _has_scope(text: str, scope: str) -> bool:
    component = re.compile(r"(?:^|/)(?:[\w-]+\()*" + re.escape(scope)
                           + r"\)*(?=/|$)")
    return any(component.search(p)
               for p in re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_program_carries_its_phase_scopes(lowered, program):
    assert program in lowered, f"{program} was never called"
    text = lowered[program]
    for scope in PROGRAMS[program][1]:
        assert _has_scope(text, scope), f"{program}: no {scope!r} scope"


def test_scope_names_no_other_program(lowered):
    """A segment-0 program carries only its own phase: FL's rounds name no
    clustering, discovery or exchange op, and the other way round."""
    for program, (_, scopes) in PROGRAMS.items():
        if program == "_chunk_fn":
            continue
        for phase in set(PHASES) - set(scopes):
            assert not _has_scope(lowered[program], phase), \
                f"{program} names {phase!r}"

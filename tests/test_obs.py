"""Observability plane: tracer semantics, manifest round-trip through
tools/trace_report, and the orchestrator's deferred-metrics perf contracts
(one host transfer per run; steady-state segments compile nothing)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from tools import trace_report as tr


@pytest.fixture(autouse=True)
def _tracer_off_after():
    """Every test leaves the module-level tracer disabled."""
    yield
    if obs.enabled():
        obs.disable()


def test_span_disabled_is_noop():
    assert not obs.enabled()
    with obs.span("phantom", k=1):
        x = 41 + 1
    assert x == 42
    assert obs.events() == []

    @obs.span.wrap("phantom-fn")
    def f(a):
        return a * 2

    assert f(21) == 42
    assert obs.events() == []


def test_span_nesting_close_order_and_attrs():
    obs.enable()
    with obs.span("outer", segment=0):
        with obs.span("inner", kind="child"):
            pass
    with obs.span("sibling"):
        pass
    rec = obs.disable()
    evs = rec["events"]
    # children close before parents: inner, outer, sibling
    assert [e.name for e in evs] == ["inner", "outer", "sibling"]
    assert [e.depth for e in evs] == [1, 0, 0]
    assert evs[1].attrs == {"segment": 0}
    assert evs[0].attrs == {"kind": "child"}
    assert evs[0].dur <= evs[1].dur          # nested window is contained
    assert evs[0].t0 >= evs[1].t0
    assert rec["totals"]["wall"] == pytest.approx(
        evs[1].dur + evs[2].dur)             # top-level spans only


@pytest.mark.parametrize("tracing", [False, True],
                         ids=["tracer-off", "tracer-on"])
def test_span_lands_in_profiler_trace(tmp_path, tracing):
    """A span enters a ``jax.profiler`` annotation under its bare name
    (attrs left out), on the host plane and nested as the spans are,
    whether or not the obs tracer records it."""
    if tracing:
        obs.enable()
    before = [e.name for e in obs.events()]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("outer-phase", segment=3):
            with obs.span("inner-phase"):
                jnp.arange(4.0).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(pb))
    host = {e.name: (e.start_ns, e.end_ns)
            for p in data.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events}
    outer, inner = host["outer-phase"], host["inner-phase"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    assert [e.name for e in obs.events()] == before + (
        ["inner-phase", "outer-phase"] if tracing else [])


def test_counters_attribute_compiles_and_transfers():
    obs.enable()

    @jax.jit
    def f(x):
        return x * 2.0 + 1.0

    x = jnp.arange(8, dtype=jnp.float32)
    with obs.span("cold"):
        f(x).block_until_ready()
    with obs.span("warm"):
        f(x).block_until_ready()
    with obs.span("fetch"):
        host = jax.device_get(f(x))
    rec = obs.disable()
    by = {e.name: e for e in rec["events"]}
    assert by["cold"].compiles >= 1          # fresh jit actually compiled
    assert by["warm"].compiles == 0          # cache hit: no compile event
    assert by["fetch"].transfers == 1
    assert by["fetch"].bytes_fetched >= x.nbytes
    assert by["cold"].transfers == 0
    assert np.asarray(host).shape == (8,)


def test_manifest_round_trip_sums_to_run_total(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    obs.enable(manifest=path, meta={"test": "round-trip"})
    with obs.span("a"):
        with obs.span("b"):
            pass
        with obs.span("b"):
            pass
    with obs.span("c"):
        pass
    rec = obs.disable()

    man = obs.read_manifest(path)
    assert man["run"]["schema"] == "obs-manifest/v1"
    assert man["run"]["meta"] == {"test": "round-trip"}
    assert man["run"]["jax_version"] == jax.__version__
    assert len(man["spans"]) == 4
    assert set(man) == {"run", "spans", "end"}
    assert not any("parent" in s for s in man["spans"])
    assert man["end"] is not None

    # tree reconstruction: b,b close under a; c is top level
    parents = tr.assign_parents(man["spans"])
    names = [s["name"] for s in man["spans"]]
    assert names == ["b", "b", "a", "c"]
    assert parents == [2, 2, None, None]

    # self time telescopes: summed over every span it equals the summed
    # top-level wall, which is what obs.disable() reported as the total
    self_t = tr.self_times(man["spans"], parents)
    top = sum(s["dur"] for s in man["spans"] if s["depth"] == 0)
    assert sum(self_t) == pytest.approx(top, rel=1e-9)
    assert rec["totals"]["wall"] == pytest.approx(top, rel=1e-9)
    # ... and the manifest's end-line wall bounds the span envelope
    assert tr.run_wall(man) >= top * (1 - 1e-6)

    # the rendered report aggregates the same spans
    table = {r["phase"]: r for r in tr.phase_table(man["spans"])}
    assert table["b"]["count"] == 2
    assert table["a"]["total"] == pytest.approx(
        next(s["dur"] for s in man["spans"] if s["name"] == "a"))
    text = tr.report(path)
    for phase in ("a", "b", "c"):
        assert phase in text
    assert "run wall" in text


def test_read_manifest_rejects_non_manifest(tmp_path):
    p = tmp_path / "not_a_manifest.jsonl"
    p.write_text('{"type": "span", "name": "x"}\n')
    with pytest.raises(ValueError, match="no run header"):
        obs.read_manifest(str(p))


@pytest.mark.slow
def test_orchestrator_obs_contracts(tmp_path):
    """The deferred-metrics contracts, pinned by counters instead of prose:

    * exactly ONE ``jax.device_get`` per orchestrator run, inside the
      ``metrics-materialize`` span;
    * with a fixed exchange cap (``overflow="drop"`` — static shapes),
      steady-state segments hit every jit cache: the AE pretrain step, the
      exchange gate, and the FL round fn compile once, so segments >= 2
      record ZERO compile events (segment 1 may retrace the RL scan once —
      the warm-started burst's episode count differs from discovery's).
    """
    from repro.core.exchange import ExchangeConfig
    from repro.core.pipeline import PipelineConfig
    from repro.core.qlearning import RLConfig
    from repro.data import partition_by_classes
    from repro.data.synthetic import fmnist_like_split
    from repro.dynamics import OrchestratorConfig, run_orchestrator
    from repro.fl import FLConfig
    from repro.models.autoencoder import AEConfig

    ds, ev = fmnist_like_split(jax.random.PRNGKey(0), n_train_per_class=40,
                               n_eval_per_class=10)
    xs, ys, _ = partition_by_classes(0, ds.images, ds.labels, n_clients=6,
                                     classes_per_client=3)
    ae_cfg = AEConfig(28, 28, 1, widths=(4, 8), latent_dim=8)
    cfg = OrchestratorConfig(
        n_segments=4, iters_per_segment=10, mode="online",
        rediscover_every=1, burst_episodes=60,
        pipeline=PipelineConfig(
            rl=RLConfig(n_episodes=120, buffer_size=30),
            exchange=ExchangeConfig(apply_channel_failure=True,
                                    overflow="drop")),
        fl=FLConfig(tau_a=10, eval_every=10, batch_size=16))

    obs.enable(manifest=str(tmp_path / "orch.jsonl"))
    run_orchestrator(jax.random.PRNGKey(21), xs, ys, ae_cfg, cfg,
                     "fading", ev.images)
    rec = obs.disable()
    evs = rec["events"]

    # -- one host transfer per run, and it is the metrics materialisation
    assert rec["totals"]["transfers"] == 1
    mat = [e for e in evs if e.name == "metrics-materialize"]
    assert len(mat) == 1 and mat[0].transfers == 1

    # -- steady-state segments are compile-free
    segs = {e.attrs["segment"]: e for e in evs if e.name == "segment"}
    assert sorted(segs) == [0, 1, 2, 3]
    for s in (2, 3):
        assert segs[s].compiles == 0, (
            f"segment {s} retraced: {segs[s].compiles} compile events")

    # -- the AE pretrain step jits once: later pretrains are cache hits
    pre = [e for e in evs if e.name == "pretrain"]
    assert len(pre) >= 2                     # initial pipeline + re-exchanges
    assert all(e.compiles == 0 for e in pre[1:])

    # -- the FL round fn jits once: every later fl span is a cache hit
    fls = [e for e in evs if e.name == "fl"]
    assert len(fls) == 4
    assert all(e.compiles == 0 for e in fls[1:])

    # the manifest agrees with the in-memory totals
    man = obs.read_manifest(str(tmp_path / "orch.jsonl"))
    assert man["end"]["transfers"] == 1
    assert man["end"]["compiles"] == rec["totals"]["compiles"]


@pytest.mark.slow
def test_scan_chunk_compile_contract(tmp_path):
    """The fused engine's compile contract: one ``_chunk_fn`` compile per
    chunk *length* (statics fixed within a run), then cache hits.  With
    ``n_segments=5`` and ``checkpoint_every=2`` the post-0 segments chunk
    as [1], [2, 3], [4]: the len-1 chunk compiles, the len-2 chunk is a
    new shape and compiles again, and the final len-1 chunk is a cache
    hit.  The ONE-transfer-per-run contract holds under the scan too."""
    from repro.core.exchange import ExchangeConfig
    from repro.core.pipeline import PipelineConfig
    from repro.core.qlearning import RLConfig
    from repro.data import partition_by_classes
    from repro.data.synthetic import fmnist_like_split
    from repro.dynamics import OrchestratorConfig, run_orchestrator
    from repro.fl import FLConfig
    from repro.models.autoencoder import AEConfig

    ds, ev = fmnist_like_split(jax.random.PRNGKey(0), n_train_per_class=40,
                               n_eval_per_class=10)
    xs, ys, _ = partition_by_classes(0, ds.images, ds.labels, n_clients=6,
                                     classes_per_client=3)
    ae_cfg = AEConfig(28, 28, 1, widths=(4, 8), latent_dim=8)
    cfg = OrchestratorConfig(
        n_segments=5, iters_per_segment=10, mode="online",
        rediscover_every=1, burst_episodes=60,
        pipeline=PipelineConfig(
            rl=RLConfig(n_episodes=120, buffer_size=30),
            exchange=ExchangeConfig(apply_channel_failure=True,
                                    overflow="drop",
                                    reserve_selector="device")),
        fl=FLConfig(tau_a=10, eval_every=10, batch_size=16),
        segment_impl="scan",
        checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=2)

    obs.enable(manifest=str(tmp_path / "scan.jsonl"))
    run_orchestrator(jax.random.PRNGKey(21), xs, ys, ae_cfg, cfg,
                     "fading", ev.images)
    rec = obs.disable()
    evs = rec["events"]

    chunks = [e for e in evs if e.name == "scan-chunk"]
    assert [(e.attrs["start"], e.attrs["n_segments"])
            for e in chunks] == [(1, 1), (2, 2), (4, 1)]
    assert chunks[0].compiles > 0           # first len-1 chunk program
    assert chunks[1].compiles > 0           # len-2 chunk: new xs shapes
    assert chunks[2].compiles == 0, (       # len-1 again: cache hit
        f"final chunk retraced: {chunks[2].compiles} compile events")

    # the deferred-metrics contract survives fusion: ONE transfer per run
    assert rec["totals"]["transfers"] == 1
    mat = [e for e in evs if e.name == "metrics-materialize"]
    assert len(mat) == 1 and mat[0].transfers == 1

"""Runtime observability plane: spans, JAX counters, manifests, profiling.

Zero-dependency instrumentation for the repo's hot paths.  The library code
(orchestrator, pipeline, exchange, FL, RL, clustering) is pre-instrumented
with :func:`span` phase labels that cost one flag check when observability
is off; turning it on records every phase's wall time plus the jit
compilations and ``jax.device_get`` transfers it performed:

    from repro import obs

    obs.enable(manifest="runs/obs/myrun.jsonl", meta={"scenario": "fading"})
    run_orchestrator(...)
    summary = obs.disable()          # totals + closes the manifest
    # per-phase table: python -m tools.trace_report runs/obs/myrun.jsonl

Environment switches (for drivers that cannot call :func:`enable`):

  * ``REPRO_OBS=1``            — trace in memory (``enable_from_env()``)
  * ``REPRO_OBS=path.jsonl``   — trace and stream a manifest to the path
  * ``REPRO_OBS_MEM=1``        — additionally snapshot live device arrays
    at every span exit (O(live arrays) — diagnosis runs only)
  * ``REPRO_PROFILE=dir``      — capture TensorBoard traces around profiled
    regions (see :mod:`repro.obs.profile`)

Submodules: ``tracer`` (spans), ``counters`` (compile/transfer counts),
``manifest`` (JSONL writer/reader), ``profile`` (``jax.profiler`` bridge).
"""
from __future__ import annotations

import os
from typing import Optional

from repro.obs import counters as _counters
from repro.obs import tracer as _tracer
from repro.obs.manifest import ManifestWriter, read_manifest  # noqa: F401
from repro.obs.profile import maybe_profile, profile_dir  # noqa: F401
from repro.obs.tracer import (SpanEvent, drain, enabled, events,  # noqa: F401
                              phase_totals, span)

__all__ = ["span", "SpanEvent", "enable", "disable", "enabled",
           "enable_from_env", "events", "drain", "phase_totals",
           "counters", "ManifestWriter", "read_manifest",
           "maybe_profile", "profile_dir"]

_writer: Optional[ManifestWriter] = None


def enable(manifest: Optional[str] = None, meta: Optional[dict] = None,
           rules=None) -> None:
    """Start tracing.  ``manifest`` streams events to a JSONL file as they
    close; ``meta`` (any JSON-serialisable dict) and ``rules`` (a
    ``ShardingRules``/mesh, for the mesh shape) land in its header.
    Re-enabling restarts the trace (and closes any previous manifest)."""
    global _writer
    if _tracer.enabled():
        disable()
    if manifest is not None:
        _writer = ManifestWriter(manifest, meta=meta, rules=rules)
    _tracer.start(
        snapshot_memory=bool(os.environ.get("REPRO_OBS_MEM")),
        on_close=_writer.on_span if _writer is not None else None)


def disable() -> dict:
    """Stop tracing; returns ``{"events": [...], "totals": {...}}`` and
    finalises the manifest (totals line) if one was being written."""
    global _writer
    evs = _tracer.stop()
    if _writer is not None:
        _writer.close()
        _writer = None
    totals = {
        "wall": sum(e.dur for e in evs if e.depth == 0),
        "compiles": sum(e.compiles for e in evs if e.depth == 0),
        "transfers": sum(e.transfers for e in evs if e.depth == 0),
        "bytes_fetched": sum(e.bytes_fetched for e in evs if e.depth == 0),
    }
    return {"events": evs, "totals": totals}


def enable_from_env() -> bool:
    """Enable tracing iff ``REPRO_OBS`` is set (see module docstring);
    returns whether tracing is now on.  Idempotent for long-lived drivers:
    an already-running trace is left alone."""
    val = os.environ.get("REPRO_OBS", "")
    if not val:
        return False
    if _tracer.enabled():
        return True
    enable(manifest=val if val not in ("1", "true", "yes") else None)
    return True


def counters() -> dict:
    """Process-wide counter snapshot (zeros until first ``enable``)."""
    c, t, b = _counters.snapshot()
    return {"compiles": c, "transfers": t, "bytes_fetched": b}

"""Structured JSONL run manifests: one event per line, machine-diffable.

A manifest is the durable form of one traced run, landing under ``runs/``
(the bench harness uses ``runs/obs/``).  Line protocol
(schema ``obs-manifest/v1``):

  * ``{"type": "run", ...}``   — header: schema, wall-clock timestamp, JAX
    version/backend/device count, mesh shape, and caller-supplied ``meta``
    (bench config, scenario/mode, ...).
  * ``{"type": "span", ...}``  — one per closed span, streamed as the run
    progresses (a crashed run keeps every span closed before the crash);
    fields as in :class:`repro.obs.tracer.SpanEvent.to_dict`.
  * ``{"type": "end", ...}``   — totals: wall seconds, compiles, transfers,
    bytes fetched.

``tools/trace_report.py`` renders a per-phase breakdown table from a
manifest; ``read_manifest`` here is the parsing half it builds on.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

import jax

SCHEMA = "obs-manifest/v1"

__all__ = ["SCHEMA", "ManifestWriter", "read_manifest"]


def _mesh_desc(rules) -> Optional[dict]:
    """Mesh shape from a ShardingRules-like object, if one was supplied."""
    mesh = getattr(rules, "mesh", rules)
    shape = getattr(mesh, "shape", None)
    if not shape:
        return None
    return {str(k): int(v) for k, v in dict(shape).items()}


class ManifestWriter:
    """Streams one run's events to a JSONL file; close writes the totals."""

    def __init__(self, path: str, meta: Optional[dict] = None, rules=None):
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self.path = path
        self._t0 = time.perf_counter()
        self._compiles = 0
        self._transfers = 0
        self._bytes = 0
        self._f = open(path, "w")
        self._emit({
            "type": "run",
            "schema": SCHEMA,
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "mesh": _mesh_desc(rules) if rules is not None else None,
            "meta": meta or {},
        })

    def _emit(self, obj: Dict[str, Any]) -> None:
        self._f.write(json.dumps(obj) + "\n")
        self._f.flush()

    def on_span(self, ev) -> None:
        """Tracer close hook: append one span line and fold the totals.
        Only top-level spans fold in — a parent's counters already include
        its children, so counting every depth would double-count."""
        if ev.depth == 0:
            self._compiles += ev.compiles
            self._transfers += ev.transfers
            self._bytes += ev.bytes_fetched
        self._emit(ev.to_dict())

    def close(self) -> None:
        if self._f.closed:
            return
        self._emit({
            "type": "end",
            "wall": time.perf_counter() - self._t0,
            "compiles": self._compiles,
            "transfers": self._transfers,
            "bytes_fetched": self._bytes,
        })
        self._f.close()


def read_manifest(path: str) -> Dict[str, Any]:
    """Parse a manifest: ``{"run": header, "spans": [...],
    "end": totals-or-None}``.  Raises on a missing/invalid header so callers
    fail loudly on a file that is not a manifest."""
    run = None
    spans: List[dict] = []
    end = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            t = obj.get("type")
            if t == "run":
                if obj.get("schema") != SCHEMA:
                    raise ValueError(
                        f"{path}: unsupported manifest schema "
                        f"{obj.get('schema')!r} (expected {SCHEMA})")
                run = obj
            elif t == "span":
                spans.append(obj)
            elif t == "end":
                end = obj
    if run is None:
        raise ValueError(f"{path}: no run header — not an obs manifest")
    return {"run": run, "spans": spans, "end": end}

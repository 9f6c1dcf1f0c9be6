"""Reduce a profiler trace of the measured window to the numbers the
per-layer metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  On a TPU each chip is a plane ``/device:TPU:<i>`` whose line
``XLA Ops`` holds one event per executed HLO operation (its name is the
HLO text, ``%fusion.72 = u32[30]{...} fusion(...)``) and whose line
``XLA Modules`` holds one event per executed program
(``jit__chunk_fn(<hash>)``).  The host plane ``/host:CPU`` holds the
benchmark's ``TraceAnnotation`` around each federation run and the
runtime's own host events.  All share one clock.

The reduction works on plain ``(start_ns, end_ns, name)`` tuples, so it
can be checked on a synthesised trace (``bench/tests/test_trace.py``):

- the window is the span from the first run annotation's start to the last
  one's end;
- busy time is the union of a chip's op intervals inside the window, idle
  the rest; with several chips both are averaged over the chips;
- a module's time is the sum of its events inside the window;
- Mosaic (Pallas) time is the union of the ops whose HLO is a
  ``custom-call``;
- the time per op leaves out control flow (``while``, ``conditional``,
  ``call``), whose events span the ops of their bodies.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_OP_NAME = re.compile(r"^%?([^\s=]+)")
_MODULE_HASH = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Chip:
    ops: list        # (start_ns, end_ns, hlo text)
    modules: list    # (start_ns, end_ns, module name)


@dataclasses.dataclass
class Raw:
    chips: list      # [Chip]
    runs: list       # (start_ns, end_ns) of each run annotation
    host: list       # (start_ns, end_ns, name) of every other host event


def union(intervals) -> list:
    """Merge (start, end, ...) intervals into sorted disjoint (start, end)."""
    out = []
    for s, e, *_ in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return float(sum(e - s for s, e, *_ in intervals))


def gaps(busy, lo, hi) -> list:
    """The idle (start, end) intervals of ``[lo, hi]`` around ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def is_custom_call(hlo: str) -> bool:
    """A Mosaic kernel runs as an HLO ``custom-call``."""
    return " custom-call(" in hlo or "tpu_custom_call" in hlo


def is_container(hlo: str) -> bool:
    """Control flow whose event spans the ops of its body."""
    return any(f" {k}(" in hlo for k in ("while", "conditional", "call"))


def op_name(hlo: str) -> str:
    m = _OP_NAME.match(hlo)
    return m.group(1) if m else hlo[:40]


def module_name(name: str) -> str:
    """``jit__chunk_fn(1234)`` -> ``jit__chunk_fn``."""
    return _MODULE_HASH.sub("", name)


@dataclasses.dataclass
class Reduced:
    window_ns: float
    n_runs: int
    busy_ns: float            # mean over chips
    custom_ns: float          # mean over chips
    module_total: dict        # module name -> ns, summed over chips / n
    op_total: dict            # "module:op" -> ns, summed over chips / n
    idle: list                # (label, ns), longest first

    def module_ns(self, pred) -> float:
        return float(sum(v for k, v in self.module_total.items()
                         if pred(k)))


def _label_gap(s, e, modules, mod_starts, host) -> str:
    i = bisect.bisect_right(mod_starts, s) - 1
    before = module_name(modules[i][2]) if i >= 0 else "start"
    j = bisect.bisect_left(mod_starts, e)
    after = module_name(modules[j][2]) if j < len(modules) else "end"
    cover = [h for h in host if h[0] <= s and h[1] >= e]
    what = min(cover, key=lambda h: h[1] - h[0])[2] if cover else "host"
    return f"{what}: {before} -> {after}"


def reduce(raw: Raw, top: int = 10) -> Reduced:
    if not raw.runs:
        raise ValueError("the trace holds no run annotation")
    if not raw.chips:
        raise ValueError("the trace holds no TPU plane")
    lo = min(s for s, _ in raw.runs)
    hi = max(e for _, e in raw.runs)
    n = len(raw.chips)
    busy = custom = 0.0
    mod_total, op_total, idle = {}, {}, []
    for chip in raw.chips:
        ops = clip(chip.ops, lo, hi)
        merged = union(ops)
        busy += length(merged)
        custom += length(union(o for o in ops if is_custom_call(o[2])))
        modules = sorted(clip(chip.modules, lo, hi))
        starts = [m[0] for m in modules]
        for s, e, name in modules:
            k = module_name(name)
            mod_total[k] = mod_total.get(k, 0.0) + (e - s) / n
        for s, e, hlo in ops:
            if is_container(hlo):
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = module_name(modules[i][2]) if i >= 0 else "?"
            k = f"{mod}:{op_name(hlo)}"
            op_total[k] = op_total.get(k, 0.0) + (e - s) / n
        longest = sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])
        idle.extend((_label_gap(s, e, modules, starts, raw.host), e - s)
                    for s, e in longest[:top])
    idle.sort(key=lambda g: -g[1])
    return Reduced(window_ns=hi - lo, n_runs=len(raw.runs),
                   busy_ns=busy / n, custom_ns=custom / n,
                   module_total=mod_total, op_total=op_total,
                   idle=idle[:top])


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The result line's ``breakdown``: device ops by total time and the
    longest idle gaps, in seconds."""
    ops = sorted(red.op_total.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in red.idle[:top]]}


def load(trace_dir, annotation: str) -> Raw:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {files}")
    data = jax.profiler.ProfileData.from_file(str(files[0]))
    chips, runs, host = [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            chip = Chip([], [])
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dest = chip.ops if line.name == OPS_LINE else \
                        chip.modules
                    dest.extend((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events)
            chips.append(chip)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name.split("#")[0] == annotation:
                        runs.append(iv[:2])
                    else:
                        host.append(iv)
    return Raw(chips, runs, host)

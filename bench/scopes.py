"""Per-phase device time and segment 0's idle time, from the same profiler
trace of the window that ``bench/trace.py`` reduces.

The program names its phases in two places:

- a ``jax.named_scope`` inside the device functions that segment 0 and the
  scan chunk share (``fl``, ``cluster``, ``discover``, ``exchange``, and
  ``kmeans_assign`` / ``recon_gate`` around each kernel's dispatch).  XLA
  keeps the scope path in every op's metadata; a TPU trace stores it as
  the ``tf_op`` stat of the op's event metadata
  (``jit(_chunk_fn)/while/body/.../fl/...:``), keyed with the op's
  ``program_id``, which is also the number in its module's event name
  (``jit__chunk_fn(<program_id>)``);
- a host ``TraceAnnotation`` per ``repro.obs`` span (``pipeline``,
  ``segment``, ``fl``, ...), on the device's clock.

``jax.profiler.ProfileData`` gives an event's own stats but not those of
its metadata, so :func:`op_paths` reads the event metadata from the
``.xplane.pb`` with a small reader of the protobuf wire format (the XSpace
schema of ``tsl/profiler/protobuf/xplane.proto``).  Everything else comes
from ``bench.trace.load``.

The reduction, on plain tuples like ``bench/trace.py``'s:

- a scope's time is the union, per chip, of the intervals of the window's
  ops whose path holds the scope as a whole component (``fl``, or wrapped
  by a transform: ``vmap(kmeans_assign)``), control flow left out; a
  kernel's time is the same union over the scope's ``custom-call`` ops;
- segment 0 is the union of the host ``pipeline`` and ``segment``
  annotations (under the scan engine both open in segment 0 only); its
  idle time is the window's device idle time inside it, its programs the
  ``XLA Modules`` events that start inside it;
- with several chips every number is the mean over the chips.

The readers in ``bench/metrics/`` get the reduction from :func:`reading`,
which returns None where the trace holds no scope or span to read.

    python3 bench/scopes.py <trace dir>     # per-scope table as JSON
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import pathlib
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

PHASES = ("fl", "cluster", "discover", "exchange")
KERNELS = ("kmeans_assign", "recon_gate")
SEG0_SPANS = ("pipeline", "segment")
RUN_ANNOTATION = "federation_run"
TRACE_DIR = pathlib.Path(__file__).resolve().parent / "_cache" / "trace"
_PROGRAM_ID = re.compile(r"\((-?\d+)\)$")


def _scope_re(scope: str):
    return re.compile(r"(?:^|/)(?:[\w-]+\()*" + re.escape(scope)
                      + r"\)*(?=[/:]|$)")


_SCOPES = {s: _scope_re(s) for s in PHASES + KERNELS}


# -- protobuf wire format: just enough of XSpace to read event metadata --

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message; a length-delimited value is a
    memoryview, a fixed-width one raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            v, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat_value(buf, stat_names):
    """(stat metadata id, value) of one XStat; a ``ref_value`` is the name
    of the stat metadata it points at."""
    mid, value = None, None
    for f, v in _fields(buf):
        if f == 1:
            mid = v
        elif f in (3, 4):                     # uint64, int64
            value = v
        elif f == 5:                          # str
            value = bytes(v).decode("utf-8", "replace")
        elif f == 7:                          # ref
            value = stat_names.get(v)
    return mid, value


def _plane_paths(buf) -> dict:
    name, event_md, stat_names = "", [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif f == 4:
            event_md.append(v)
        elif f == 5:
            key, md = _map_entry(v)
            for g, w in _fields(md or b""):
                if g == 2:
                    stat_names[key] = bytes(w).decode("utf-8", "replace")
    if not name.startswith(trace.DEVICE_PREFIX):
        return {}
    by_name = {v: k for k, v in stat_names.items()}
    want = {by_name.get("tf_op"), by_name.get("program_id")} - {None}
    out = {}
    for entry in event_md:
        _, md = _map_entry(entry)
        op, stats = None, {}
        for f, v in _fields(md or b""):
            if f == 2:
                op = bytes(v).decode("utf-8", "replace")
            elif f == 5:
                mid, value = _stat_value(v, stat_names)
                if mid in want:
                    stats[stat_names[mid]] = value
        path = stats.get("tf_op")
        pid = stats.get("program_id")
        if op is not None and path and pid is not None:
            out[(str(int(pid) % 2**64), op)] = str(path)
    return out


def op_paths(xplane_file) -> dict:
    """``(program id, op text)`` -> scope path of every device op in the
    trace's event metadata."""
    buf = memoryview(pathlib.Path(xplane_file).read_bytes())
    out = {}
    for f, v in _fields(buf):
        if f == 1:
            out.update(_plane_paths(v))
    return out


# -- the reduction ------------------------------------------------------

@dataclasses.dataclass
class Scoped:
    window_ns: float
    n_runs: int
    scope_ns: dict           # scope -> ns of its ops, union per chip
    kernel_ns: dict          # kernel scope -> ns of its custom-calls
    scoped_module_ns: dict   # module -> ns of its ops under any scope
    seg0_idle_ns: float | None
    seg0_programs: float | None

    def per_run_ms(self, ns):
        if not ns or self.n_runs <= 0:
            return None
        return ns / 1e6 / self.n_runs


def _program_id(module_event_name: str):
    m = _PROGRAM_ID.search(module_event_name)
    return str(int(m.group(1)) % 2**64) if m else None


def _inside(t, windows, starts) -> bool:
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < windows[i][1]


def _overlap(intervals, windows) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, j = 0.0, 0
    for s, e in intervals:
        while j < len(windows) and windows[j][1] <= s:
            j += 1
        k = j
        while k < len(windows) and windows[k][0] < e:
            total += min(e, windows[k][1]) - max(s, windows[k][0])
            k += 1
    return total


def reduce(raw: trace.Raw, paths: dict) -> Scoped:
    """Per-scope device time over the window of ``raw``; ``paths`` maps
    ``(program id, op text)`` to the op's scope path."""
    if not raw.runs or not raw.chips:
        raise ValueError("the trace holds no run annotation or no chip")
    lo = min(s for s, _ in raw.runs)
    hi = max(e for _, e in raw.runs)
    n = len(raw.chips)
    names = {}
    for path in set(paths.values()):
        names[path] = frozenset(s for s, r in _SCOPES.items()
                                if r.search(path))
    seg0 = trace.union(trace.clip(
        [h for h in raw.host if h[2].split("#")[0] in SEG0_SPANS], lo, hi))
    seg0_starts = [w[0] for w in seg0]
    scope_ns = dict.fromkeys(PHASES + KERNELS, 0.0)
    kernel_ns = dict.fromkeys(KERNELS, 0.0)
    module_ns, idle, programs = {}, 0.0, 0
    for chip in raw.chips:
        modules = sorted(chip.modules)
        starts = [m[0] for m in modules]
        by_scope = {s: [] for s in scope_ns}
        by_kernel = {s: [] for s in kernel_ns}
        by_module = {}
        for s, e, hlo in trace.clip(chip.ops, lo, hi):
            if trace.is_container(hlo):
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= modules[i][1]:
                continue
            mod = modules[i][2]
            found = names.get(paths.get((_program_id(mod), hlo)), ())
            if not found:
                continue
            by_module.setdefault(trace.module_name(mod), []).append((s, e))
            for scope in found:
                by_scope[scope].append((s, e))
                if scope in by_kernel and trace.is_custom_call(hlo):
                    by_kernel[scope].append((s, e))
        for d, parts in ((scope_ns, by_scope), (kernel_ns, by_kernel),
                         (module_ns, by_module)):
            for k, ivs in parts.items():
                d[k] = d.get(k, 0.0) + trace.length(trace.union(ivs)) / n
        busy = trace.union(trace.clip(chip.ops, lo, hi))
        idle += _overlap(trace.gaps(busy, lo, hi), seg0) / n
        programs += sum(1 for m in trace.clip(modules, lo, hi)
                        if _inside(m[0], seg0, seg0_starts)) / n
    return Scoped(window_ns=hi - lo, n_runs=len(raw.runs),
                  scope_ns=scope_ns, kernel_ns=kernel_ns,
                  scoped_module_ns=module_ns,
                  seg0_idle_ns=idle if seg0 else None,
                  seg0_programs=programs if seg0 else None)


def load(trace_dir, annotation: str = RUN_ANNOTATION) -> Scoped:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {files}")
    return reduce(trace.load(trace_dir, annotation), op_paths(files[0]))


def _newest_trace_dir():
    """The harness writes each traced window under ``TRACE_DIR/<cell>``
    and hands its readers only the reduction, so the window's own trace is
    the newest one there."""
    files = list(TRACE_DIR.rglob("*.xplane.pb")) if TRACE_DIR.is_dir() \
        else []
    if not files:
        return None
    return max(files, key=lambda p: p.stat().st_mtime).parent


def reading(ctx):
    """The window's :class:`Scoped`, computed once per ``ctx``; None where
    no trace of this window can be found or read.  A trace whose window
    or run count differs from ``ctx["trace"]``'s is another run's."""
    if "scoped" not in ctx:
        scoped = None
        red = ctx.get("trace")
        try:
            where = _newest_trace_dir()
            if red is not None and where is not None:
                scoped = load(where)
                if (scoped.window_ns != red.window_ns
                        or scoped.n_runs != red.n_runs):
                    scoped = None
        except Exception as e:              # a reader never fails a run
            print(f"bench.scopes: no scope reading: {e!r}", file=sys.stderr)
            scoped = None
        ctx["scoped"] = scoped
    return ctx["scoped"]


def scope_ms(ctx, scope: str):
    """Device time per run, in ms, of the ops under ``scope``."""
    s = reading(ctx)
    return None if s is None else s.per_run_ms(s.scope_ns.get(scope))


def kernel_ms(ctx, scope: str):
    """Device time per run, in ms, of the Mosaic custom-calls under
    ``scope``: the kernel alone, without its wrapper's pads."""
    s = reading(ctx)
    return None if s is None else s.per_run_ms(s.kernel_ns.get(scope))


def seg0_idle_ms(ctx):
    """Device idle time per run, in ms, inside segment 0's spans."""
    s = reading(ctx)
    if s is None or s.seg0_idle_ns is None:
        return None
    return s.seg0_idle_ns / 1e6 / s.n_runs


def seg0_programs(ctx):
    """Device programs launched per run inside segment 0's spans."""
    s = reading(ctx)
    if s is None or s.seg0_programs is None:
        return None
    return s.seg0_programs / s.n_runs


def summary(s: Scoped, red: trace.Reduced) -> dict:
    """Every reading per run, and each module's share under a scope."""
    cov = {m: ns / red.module_total[m]
           for m, ns in s.scoped_module_ns.items()
           if red.module_total.get(m)}
    return {"n_runs": s.n_runs,
            "scope_ms": {k: s.per_run_ms(v) for k, v in s.scope_ns.items()},
            "kernel_ms": {k: s.per_run_ms(v)
                          for k, v in s.kernel_ns.items()},
            "module_ms": {k: v / 1e6 / red.n_runs
                          for k, v in red.module_total.items()},
            "scoped_share": cov,
            "custom_call_ms": red.custom_ns / 1e6 / red.n_runs,
            "seg0_idle_ms": s.per_run_ms(s.seg0_idle_ns),
            "seg0_programs": (None if s.seg0_programs is None
                              else s.seg0_programs / s.n_runs)}


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    red = trace.reduce(trace.load(args[0], RUN_ANNOTATION))
    print(json.dumps(summary(load(args[0]), red), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

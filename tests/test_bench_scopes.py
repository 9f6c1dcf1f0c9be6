"""The benchmark's scope reduction (``bench/scopes.py``) on a synthesised
trace: per-scope device time from op scope paths, the kernels' custom-calls,
segment 0's idle time and programs under the program's span annotations,
the readers' None where a trace has none of these, and the XSpace reader
on a hand-encoded protobuf."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import scopes, spec, trace  # noqa: E402

CHUNK = "jit__chunk_fn(123)"
ROUND = "jit__round_body(77)"
FUSION = "%fusion.72 = f32[30]{0} fusion(f32[30,30]{1,0} %p), kind=kLoop"
CONV = "%convolution.4 = f32[8,14,14,32]{3,2,1,0} convolution(%a, %b)"
PAD = "%pad.9 = f32[8,128]{1,0} pad(f32[8,100]{1,0} %x, f32[] %z)"
KMEANS = ("%kmeans_assign.1 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} "
          "%p0), custom_call_target=\"tpu_custom_call\"")
GATE = ("%recon_gate.1 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} "
        "%p0), custom_call_target=\"tpu_custom_call\"")
EIGH = ("%custom-call.3 = f32[64]{0} custom-call(f32[64,64]{1,0} %a), "
        "custom_call_target=\"EighTpu\"")
LOOP = "%while.373 = (s32[], f32[30]{0}) while(%t), condition=%c, body=%b"

BODY = "jit(_chunk_fn)/while/body"
PATHS = {
    ("77", CONV): "jit(_round_body)/fl/while/body/conv_general_dilated:",
    ("77", FUSION): "jit(_round_body)/fl/transpose(jvp(recon_loss))/mul:",
    ("123", CONV): f"{BODY}/closed_call/jit(_round_body)/fl/dot_general:",
    ("123", FUSION): f"{BODY}/cond/branch_1_fun/jit(_discover_impl)/"
                     "discover/while/body/add:",
    ("123", EIGH): f"{BODY}/cond/branch_1_fun/jit(_cluster_impl)/cluster/"
                   "jit(eigh)/eigh:",
    ("123", PAD): f"{BODY}/cond/branch_1_fun/jit(_cluster_impl)/cluster/"
                  "vmap(kmeans_assign)/pad:",
    ("123", KMEANS): f"{BODY}/cond/branch_1_fun/jit(_cluster_impl)/cluster/"
                     "vmap(kmeans_assign)/jit(kmeans_assign_pallas)/"
                     "kmeans_assign/pallas_call:",
    ("123", GATE): f"{BODY}/cond/branch_1_fun/exchange/jit(_gate_scores)/"
                   "recon_gate/pallas_call:",
    ("123", LOOP): f"{BODY}/closed_call/jit(_round_body)/fl/while:",
}


def synthetic(spans=True):
    """Two runs, [100, 300] and [400, 1000] ns.  Segment 0's spans cover
    [100, 250] of the first run, idle at 160..200 and 220..250; the second
    run's chunk holds a re-discovery (eigh, the K-means kernel and its pad,
    an RL step, the gate kernel) and FL."""
    chip = trace.Chip(
        ops=[(50, 90, CONV),                       # before the window
             (100, 140, CONV), (130, 160, FUSION),  # segment 0 FL
             (200, 220, CONV),
             (400, 450, EIGH), (450, 460, PAD), (460, 480, KMEANS),
             (480, 500, FUSION), (520, 530, GATE),
             (600, 700, CONV), (590, 720, LOOP),    # container
             (700, 760, FUSION)],                   # 123:FUSION = discover
        modules=[(100, 160, ROUND), (200, 220, ROUND), (400, 800, CHUNK)])
    host = [(0, 2000, "main")]
    if spans:
        host += [(100, 150, "pipeline"), (150, 250, "segment"),
                 (120, 130, "fl"), (400, 1000, "scan-chunk")]
    return trace.Raw(chips=[chip], runs=[(100, 300), (400, 1000)],
                     host=host)


def test_scope_union_matches_whole_components():
    s = scopes.reduce(synthetic(), PATHS)
    assert s.n_runs == 2 and s.window_ns == 900
    # fl: 100..160 in the round program, 200..220, 600..700 in the chunk
    # (the while container left out)
    assert s.scope_ns["fl"] == 60 + 20 + 100
    assert s.scope_ns["cluster"] == 50 + 10 + 20          # eigh, pad, kernel
    assert s.scope_ns["discover"] == 20 + 60
    assert s.scope_ns["exchange"] == 10
    assert s.scope_ns["kmeans_assign"] == 10 + 20          # wrapper + kernel
    assert s.scope_ns["recon_gate"] == 10
    # kernels: only the custom-calls under the scope; the eigh custom-call
    # is under no kernel scope
    assert s.kernel_ns == {"kmeans_assign": 20, "recon_gate": 10}
    assert s.scoped_module_ns == {"jit__round_body": 80.0,
                                  "jit__chunk_fn": 50 + 10 + 20 + 20 + 10
                                  + 100 + 60}
    ctx = {"trace": trace.reduce(synthetic()), "scoped": s}
    assert spec.load_metric("fl_device_ms").read(ctx) == \
        pytest.approx(180 / 1e6 / 2)
    assert spec.load_metric("kmeans_assign_device_ms").read(ctx) == \
        pytest.approx(20 / 1e6 / 2)
    assert spec.load_metric("recon_gate_device_ms").read(ctx) == \
        pytest.approx(10 / 1e6 / 2)
    assert spec.load_metric("cluster_device_ms").read(ctx) == \
        pytest.approx(80 / 1e6 / 2)


@pytest.mark.parametrize("path, scope, hit", [
    ("jit(f)/fl/while/body/add:", "fl", True),
    ("jit(f)/fl:", "fl", True),
    ("jit(f)/transpose(jvp(fl))/mul:", "fl", True),
    ("jit(f)/flow/add:", "fl", False),
    ("jit(f)/reflect/add:", "fl", False),
    ("jit(f)/vmap(kmeans_assign)/pad:", "kmeans_assign", True),
    ("jit(f)/vmap(jit(kmeans_assign_pallas))/pallas_call:",
     "kmeans_assign", False),
    ("jit(recon_gate_pallas)/pallas_call:", "recon_gate", False),
])
def test_scope_is_a_whole_path_component(path, scope, hit):
    assert bool(scopes._SCOPES[scope].search(path)) is hit


def test_seg0_idle_and_programs_inside_spans():
    s = scopes.reduce(synthetic(), PATHS)
    # segment 0 = [100, 250]; busy 100..160 and 200..220 -> idle 40 + 30
    assert s.seg0_idle_ns == 40 + 30
    assert s.seg0_programs == 2                 # both round programs
    ctx = {"trace": trace.reduce(synthetic()), "scoped": s}
    assert spec.load_metric("seg0_idle_ms").read(ctx) == \
        pytest.approx(70 / 1e6 / 2)
    assert spec.load_metric("seg0_programs").read(ctx) == 1.0


def test_several_chips_average():
    raw = synthetic()
    raw.chips.append(trace.Chip(ops=[(100, 300, CONV)],
                                modules=[(100, 300, ROUND)]))
    s = scopes.reduce(raw, PATHS)
    assert s.scope_ns["fl"] == (180 + 200) / 2
    assert s.seg0_idle_ns == (70 + 0) / 2
    assert s.seg0_programs == (2 + 1) / 2


NEW = ("fl_device_ms", "cluster_device_ms", "discover_device_ms",
       "exchange_device_ms", "kmeans_assign_device_ms",
       "recon_gate_device_ms", "seg0_idle_ms", "seg0_programs")
OLD = ("device_idle_share", "chunk_device_ms", "seg0_device_ms",
       "pallas_busy_share")


def test_no_scope_or_span_reads_none():
    """A trace of a program without scopes or span annotations (the
    parent of this change) gives every new reader nothing to read."""
    raw = synthetic(spans=False)
    ctx = {"trace": trace.reduce(raw), "scoped": scopes.reduce(raw, {})}
    for name in NEW:
        assert spec.load_metric(name).read(ctx) is None, name


def test_no_trace_file_reads_none(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    ctx = {"trace": trace.reduce(synthetic())}
    for name in NEW:
        assert spec.load_metric(name).read(ctx) is None, name


def test_another_windows_trace_reads_none(tmp_path, monkeypatch):
    """The newest trace on disk is used only when it holds the same window
    as the reduction the harness hands over."""
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    (tmp_path / "cell").mkdir()
    (tmp_path / "cell" / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(scopes, "load",
                        lambda d: scopes.reduce(synthetic(spans=False), {}))
    other = synthetic()
    other.runs = [(100, 300)]
    assert scopes.reading({"trace": trace.reduce(other)}) is None
    same = scopes.reading({"trace": trace.reduce(synthetic())})
    assert same is not None and same.window_ns == 900


def test_existing_readings_unchanged_by_span_annotations():
    """The spans add host events only: every existing reading is the same
    with and without them, and an idle gap under a span now names it."""
    with_spans = trace.reduce(synthetic())
    without = trace.reduce(synthetic(spans=False))
    for name in OLD:
        a = spec.load_metric(name).read({"trace": with_spans})
        b = spec.load_metric(name).read({"trace": without})
        assert a == b, name
    assert with_spans.module_total == without.module_total
    assert with_spans.op_total == without.op_total
    assert with_spans.custom_ns == without.custom_ns
    labels = dict((ns, lab) for lab, ns in with_spans.idle)
    assert labels[40].startswith("segment: ")           # 160..200
    assert labels[240].startswith("scan-chunk: ")       # 760..1000
    old = dict((ns, lab) for lab, ns in without.idle)
    assert old[40].startswith("main: ")


# -- the XSpace reader, on a protobuf encoded by hand --

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len(field, payload):
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field, n):
    return _varint(field << 3) + _varint(n % 2**64)


def _str(field, s):
    return _len(field, s.encode())


def _xspace(planes):
    return b"".join(_len(1, p) for p in planes)


def _plane(name, stat_names, events):
    """events: [(op text, [(stat id, kind, value)])]"""
    body = _int(1, 7) + _str(2, name)
    body += _len(3, _str(2, "XLA Ops") + _len(4, _int(1, 1)))
    for sid, sname in stat_names.items():
        body += _len(5, _int(1, sid) + _len(2, _int(1, sid)
                                           + _str(2, sname)))
    for mid, (op, stats) in enumerate(events, start=1):
        md = _int(1, mid) + _str(2, op) + _str(4, op[:8])
        for sid, kind, value in stats:
            st = _int(1, sid)
            st += {"str": lambda v: _str(5, v), "int": lambda v: _int(4, v),
                   "uint": lambda v: _int(3, v),
                   "ref": lambda v: _int(7, v)}[kind](value)
            md += _len(5, st)
        body += _len(4, _int(1, mid) + _len(2, md))
    return body


def test_op_paths_reads_event_metadata(tmp_path):
    names = {1: "tf_op", 2: "program_id", 3: "hlo_category",
             4: "jit(g)/cluster/eigh:"}
    device = _plane("/device:TPU:0", names, [
        (FUSION, [(3, "str", "loop fusion"), (2, "uint", 123),
                  (1, "str", "jit(g)/fl/add:")]),
        (CONV, [(2, "int", -5), (1, "ref", 4)]),      # ref and signed id
        (PAD, [(2, "uint", 123)]),                    # no path: left out
    ])
    host = _plane("/host:CPU", names, [
        ("federation_run", [(1, "str", "jit(h)/fl/x:"), (2, "uint", 9)])])
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_xspace([host, device]))
    assert scopes.op_paths(f) == {
        ("123", FUSION): "jit(g)/fl/add:",
        (str(2**64 - 5), CONV): "jit(g)/cluster/eigh:"}

"""Scope attribution: the XSpace wire reader against ``ProfileData`` and
the ``tf_op`` stats of the recorded v5e trace, and the reduction, gap
naming and readings on a hand-built trace."""

import gzip
import os

import pytest
from jax.profiler import ProfileData

from benchmark import scopes, xplane

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA) as f:
        return f.read()


def test_wire_reader_matches_profile_data(recorded):
    planes = {p.name: p for p in scopes.read_xspace(recorded)}
    profile = ProfileData.from_serialized_xspace(recorded)
    n = 0
    for p in profile.planes:
        mine = dict(planes[p.name].lines)
        for line in p.lines:
            got = [(planes[p.name].names[m], s, e - s) for s, e, m in mine[line.name]]
            want = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            assert got == want
            n += len(want)
    assert n > 4000


def test_tf_op_of_the_recorded_ops(recorded):
    tpu = next(p for p in scopes.read_xspace(recorded) if p.name == "/device:TPU:0")
    op = {xplane.op_name(tpu.names[m]): tpu.tf_ops[m] for m in tpu.names}
    assert op["compare_reduce_fusion.1"].startswith(
        "jit(_run_chunk_while)/while/cond/reduce_or")
    assert op["fusion.65"].startswith(
        "jit(_run_chunk_while)/while/body/jit(propagate_uniform)")
    # Recorded before the program had scopes: all of it is unscoped, and
    # the reduction agrees with xplane.py on busy time and gaps.
    r = scopes.reduce_planes(scopes.read_xspace(recorded))
    old = xplane.reduce_profile(ProfileData.from_serialized_xspace(recorded))
    assert r.busy_s() == old.busy_s
    assert set(r.stage_s()) == {scopes.UNSCOPED}
    assert r.gaps() == [(name, ns) for name, ns in old.gaps]


@pytest.mark.parametrize("tf_op, label", [
    ("jit(f)/while/body/gossip.gather/bucket3/jit(propagate_uniform)/"
     "jit(gather_or_frontier)/gossip.gather/while/body/closed_call/"
     "or_reduce/reduce:", "gossip.gather/bucket3/or_reduce"),
    ("jit(f)/while/body/gossip.gather/fetch/gather", "gossip.gather/fetch"),
    ("jit(f)/while/cond/gossip.quiescence/reduce_or:", "gossip.quiescence"),
    ("jit(f)/gossip.loop/while/body/gossip.update/and", "gossip.update"),
    ("jit(f)/gossip.loop/while:", "gossip.loop"),
    ("jit(f)/while", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
])
def test_scope_of(tf_op, label):
    assert scopes.scope_of(tf_op) == label


# --- a hand-built XSpace ---------------------------------------------------

def _varint(v):
    out = bytearray()
    while True:
        b, v = v & 0x7F, v >> 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _f(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name, events, metadata, tf_ops=None):
    """events: (metadata id, start us, duration us) on one line."""
    line = _f(2, "XLA Ops" if name.startswith("/device") else "python")
    line += _f(3, 0) + b"".join(
        _f(4, _f(1, m) + _f(2, s * 10**6) + _f(3, d * 10**6)) for m, s, d in events)
    out = _f(2, name) + _f(3, line)
    for mid, ev_name in metadata.items():
        meta = _f(1, mid) + _f(2, ev_name)
        if tf_ops and mid in tf_ops:
            meta += _f(5, _f(1, 9) + _f(5, tf_ops[mid]))
        out += _f(4, _f(1, mid) + _f(2, meta))
    if tf_ops:
        out += _f(5, _f(1, 9) + _f(2, _f(1, 9) + _f(2, "tf_op")))
    return _f(1, out)


def _hand_trace():
    """Window [0, 100) us. The chip runs a while [10, 90) holding a gather
    fetch [10, 40), its or_reduce [40, 60), the update [60, 70) and the
    quiescence test [70, 75); idle [0, 10), of which [2, 10) in prepare,
    and [90, 100) in d2h. The while's own time, [75, 90), is unscoped."""
    ops = {1: "%while.1 = while()", 2: "%fusion.1 = fusion()",
           3: "%select_reduce_fusion.1 = fusion()", 4: "%fusion.2 = fusion()",
           5: "%compare_reduce_fusion.1 = fusion()"}
    tf = {1: "jit(f)/while",
          2: "jit(f)/while/body/gossip.gather/bucket0/gossip.gather/fetch/gather",
          3: "jit(f)/while/body/gossip.gather/bucket0/or_reduce/reduce",
          4: "jit(f)/while/body/gossip.update/and",
          5: "jit(f)/while/cond/gossip.quiescence/reduce_or"}
    chip = [(1, 10, 80), (2, 10, 30), (3, 40, 20), (4, 60, 10), (5, 70, 5)]
    host = {1: "bench.window", 2: "bench.call", 3: "gossip.prepare",
            4: "gossip.d2h", 5: "other"}
    spans = [(1, 0, 100), (2, 0, 100), (3, 2, 10), (4, 88, 12), (5, 0, 100)]
    return (_plane("/device:TPU:0", chip, ops, tf)
            + _plane("/host:CPU", spans, host))


def test_hand_built_trace_by_scope():
    r = scopes.reduce_planes(scopes.read_xspace(_hand_trace()))
    assert r.window_ns == 100_000 and r.busy_s() == pytest.approx(80e-6)
    assert r.stage_s() == pytest.approx({
        "gossip.gather": 50e-6, scopes.UNSCOPED: 15e-6,
        "gossip.update": 10e-6, "gossip.quiescence": 5e-6})
    assert r.label_s()["gossip.gather/bucket0/fetch"] == pytest.approx(30e-6)
    top = r.top_ops(2)
    assert [name for name, _ in top] == [
        "gossip.gather/bucket0/fetch fusion.1",
        "gossip.gather/bucket0/or_reduce select_reduce_fusion.1"]
    assert [s for _, s in top] == pytest.approx([30e-6, 20e-6])
    # Gaps take the innermost bench.* or gossip.* span around their
    # midpoint: not bench.call. Engine idle is the idle time inside one.
    assert r.gaps() == [("gossip.prepare", 10_000), ("gossip.d2h", 10_000)]
    assert r.engine_idle_share() == pytest.approx(18.0)


def test_rooflines_on_the_hand_built_trace():
    r = scopes.reduce_planes(scopes.read_xspace(_hand_trace()))
    peak = 1.0  # GB/s: 1 byte a nanosecond
    # nnz 100, W = 1 word: 800 bytes a tick over 50 us under gossip.gather.
    assert scopes.gather_roofline(r, 100, 32, 1, peak) == pytest.approx(
        100 * 800 / 50e-6 / 1e9)
    # N 10: 120 bytes a tick over 15 us of update + quiescence; the delay
    # line is missing from this trace, so the reading refuses.
    with pytest.raises(ValueError, match="gossip.delay_line"):
        scopes.update_roofline(r, 10, 32, 1, peak)

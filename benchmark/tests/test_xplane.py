"""Trace reduction: exact on a hand-built trace, consistent on a small
trace recorded on a v5e (``data/small_trace.xplane.pb.gz``: two
``run_sync_sim`` calls, 20K-node ER, 4,096 shares, under the harness's
``bench.window`` / ``bench.call`` annotations)."""

import gzip
import os

import numpy as np
import pytest
from jax.profiler import ProfileData

from benchmark import xplane

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.xplane.pb.gz")


def _ev(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_us * 10**6} "
            f"duration_ps: {dur_us * 10**6} }}")


def _plane(pid, name, line, events, names):
    meta = " ".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items()
    )
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 name: "{line}" '
            f'timestamp_ns: 0 {" ".join(events)} }} {meta} }}')


def _hand_trace():
    """Window [0, 100) us. Chip 0: while [10, 50) holding fusion [20, 30)
    and all-gather [30, 35); fusion [60, 70). Chip 1: fusion [0, 20) and a
    stray op past the window. Host: two calls, [5, 55) and [58, 100)."""
    ops = {1: "%while.3 = (s32[]) while(x)", 2: "%fusion.7 = u32[4] fusion(y)",
           3: "%all-gather.2 = u32[8] all-gather(z)"}
    chip0 = [_ev(1, 10, 40), _ev(2, 20, 10), _ev(3, 30, 5), _ev(2, 60, 10)]
    chip1 = [_ev(2, 0, 20), _ev(2, 150, 10)]
    host = {1: "bench.window", 2: "bench.call", 3: "unrelated"}
    spans = [_ev(1, 0, 100), _ev(2, 5, 50), _ev(2, 58, 42), _ev(3, 0, 100)]
    return "\n".join([
        _plane(1, "/device:TPU:0", "XLA Ops", chip0, ops),
        _plane(2, "/device:TPU:1", "XLA Ops", chip1, ops),
        _plane(3, "/host:CPU", "python", spans, host),
    ])


def test_hand_built_trace():
    s = xplane.reduce_profile(ProfileData.from_text_proto(_hand_trace()))
    assert s.window_ns == 100_000
    busy = [c.busy_ns for c in s.chips]
    assert busy == [50_000, 20_000]
    assert s.busy_s == pytest.approx(35e-6)
    c0 = s.chips[0]
    assert c0.op_self_ns == {"while.3": 25_000, "fusion.7": 20_000,
                             "all-gather.2": 5_000}
    assert c0.collective_ns == 5_000 and s.chips[1].collective_ns == 0
    # Idle on both chips: [70, 100) inside call 2, and [50, 60), whose
    # midpoint falls between the calls; [0, 10) is covered by chip 1.
    assert s.gaps == [("bench.call", 30_000), ("bench.window", 10_000)]
    assert s.top_ops(2) == [["fusion.7", 20e-6], ["while.3", 12.5e-6]]


def test_nothing_to_read_without_a_window_or_a_chip():
    text = _hand_trace().replace("bench.window", "other")
    assert xplane.reduce_profile(ProfileData.from_text_proto(text)) is None
    text = _hand_trace().replace("/device:TPU:", "/device:GPU:")
    assert xplane.reduce_profile(ProfileData.from_text_proto(text)) is None


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace():
    with gzip.open(DATA) as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    s = xplane.reduce_profile(profile)
    assert s is not None and len(s.chips) == 1
    # Brute force: mark every op's clipped interval on a 1 us grid.
    plane = profile.find_plane_with_name("/device:TPU:0")
    window = [(e.start_ns, e.start_ns + e.duration_ns)
              for p in profile.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events if e.name == "bench.window"]
    lo, hi = window[0]
    grid = np.zeros(int((hi - lo) // 1000) + 2, dtype=bool)
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            a, b = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
            if b > a:
                grid[int((a - lo) // 1000):int(-(-(b - lo) // 1000))] = True
    assert s.chips[0].busy_ns == pytest.approx(grid.sum() * 1000, rel=0.02)
    assert 0 < s.busy_s < s.window_s
    idle = sum(ns for _, ns in s.gaps)
    assert idle + s.chips[0].busy_ns == pytest.approx(s.window_ns, rel=1e-9)
    assert {name for name, _ in s.gaps} <= {"bench.window", "bench.call"}
    self_total = sum(s.chips[0].op_self_ns.values())
    assert self_total == pytest.approx(s.chips[0].busy_ns, rel=0.01)
    assert s.top_ops(1)[0][1] > 0

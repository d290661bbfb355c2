"""Reduce a profiler trace (``.xplane.pb``) to what the readers need.

Read with ``jax.profiler.ProfileData`` and nothing else. The window is the
harness's own ``bench.window`` host annotation; every device interval is
clipped to it. Per TPU plane (``/device:TPU:<i>``), on its ``XLA Ops``
line:

- busy: the union of the op intervals (a ``while`` op encloses its body's
  ops, so the union counts each instant once);
- self time per op: its interval less the ops nested inside it;
- collective time: the union of the ops whose HLO opcode starts with one
  of ``COLLECTIVES``.

Idle gaps are the parts of the window no op covers, each named by the
innermost ``bench.*`` host annotation around its midpoint: what the
harness was doing while the device waited.
"""

from __future__ import annotations

import dataclasses
import re

COLLECTIVES = (
    "all-gather", "all-reduce", "all-to-all", "collective-permute",
    "reduce-scatter",
)
WINDOW = "bench.window"
_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Chip:
    busy_ns: float
    collective_ns: float
    op_self_ns: dict


@dataclasses.dataclass
class Summary:
    window_ns: float
    chips: list          # one Chip per TPU plane
    gaps: list           # (annotation name, ns), longest first

    @property
    def busy_s(self) -> float:
        return sum(c.busy_ns for c in self.chips) / len(self.chips) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def top_ops(self, k: int = 10) -> list:
        total: dict = {}
        for c in self.chips:
            for name, ns in c.op_self_ns.items():
                total[name] = total.get(name, 0.0) + ns
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / len(self.chips) / 1e9] for name, ns in top]


def op_name(event_name: str) -> str:
    """``%fusion.62 = u32[...] fusion(...)`` -> ``fusion.62``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def opcode(name: str) -> str:
    return _SUFFIX.sub("", name)


def union(intervals) -> list:
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def self_times(events) -> dict:
    """Op name -> its time less that of the ops nested inside it."""
    out: dict = {}
    stack: list = []  # [end, name, duration, nested]

    def close(frame):
        out[frame[1]] = out.get(frame[1], 0.0) + frame[2] - frame[3]

    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0.0])
    while stack:
        close(stack.pop())
    return out


def _annotations(profile) -> list:
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def _device_events(plane, lo: float, hi: float) -> list:
    events = []
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            s, e = max(ev.start_ns, lo), min(ev.start_ns + ev.duration_ns, hi)
            if e > s:
                events.append((s, e, op_name(ev.name)))
    return events


def reduce_profile(profile) -> Summary | None:
    """None when the trace holds no TPU op inside the window."""
    spans = _annotations(profile)
    windows = [(s, e) for s, e, name in spans if name == WINDOW]
    if not windows:
        return None
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    chips, busy_all = [], []
    for plane in sorted(profile.planes, key=lambda p: p.name):
        if not plane.name.startswith("/device:TPU:"):
            continue
        events = _device_events(plane, lo, hi)
        if not events:
            continue
        busy = union((s, e) for s, e, _ in events)
        coll = union(
            (s, e) for s, e, n in events if opcode(n).startswith(COLLECTIVES)
        )
        chips.append(Chip(
            busy_ns=sum(e - s for s, e in busy),
            collective_ns=sum(e - s for s, e in coll),
            op_self_ns=self_times(events),
        ))
        busy_all.extend(busy)
    if not chips:
        return None
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW]
    gaps, t = [], lo
    # Idle on every chip at once: the gaps of the union over chips.
    for s, e in union(busy_all) + [[hi, hi]]:
        if s > t:
            mid = (s + t) / 2
            around = [(ae - as_, n) for as_, ae, n in inner if as_ <= mid < ae]
            gaps.append((min(around)[1] if around else WINDOW, s - t))
        t = max(t, e)
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_ns=hi - lo, chips=chips, gaps=gaps)


def reduce_file(path: str) -> Summary | None:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))

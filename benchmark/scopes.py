"""Put a profiler trace's device time down to the program's ``gossip.*``
scopes, and its idle gaps down to the host spans around them.

The engines name every stage of a tick with ``jax.named_scope``
(``gossip.gather`` and its sub-scopes ``fetch``, ``or_reduce``,
``bucket<i>``, ``scatter_back``; ``gossip.update``, ``gossip.delay_line``,
``gossip.quiescence``, ``gossip.loop``, ``gossip.chunk_io``,
``gossip.exchange``, ``gossip.telemetry``), and ``telemetry.span`` writes each host phase of the
chunk loop to the trace as ``gossip.<phase>`` (``prepare``, ``dispatch``,
``d2h``, ``finish``). A device op's scope is in its HLO ``op_name``, which
the trace keeps as the ``tf_op`` stat of the op's event metadata.
``jax.profiler.ProfileData`` does not show event metadata stats, so this
module reads the ``.xplane.pb`` (an ``XSpace`` protobuf) with a small wire
reader of its own, and imports neither TensorFlow nor the program.

The reduction follows ``xplane.py``: the window is the ``bench.window``
host span, every device op is clipped to it, and an op's time is its self
time (its interval less the ops nested inside it), so a ``while`` op counts
only the loop's own time. Ops whose ``tf_op`` holds no ``gossip.*`` scope
(ops XLA makes with no metadata, and a program without scopes) are
``UNSCOPED``. Idle gaps are named by the innermost ``bench.*``
or ``gossip.*`` host span around their midpoint.

Run as a tool: ``python3 benchmark/scopes.py --workload <cell> --seed <n>
--seconds <s>`` sets a cell up as the harness does, runs its window under
the profiler, checks every call against the reference, and prints one JSON
line: the scopes' shares of busy time, the top ops under their scopes, the
named idle gaps, each call's time, and ``gather_roofline``,
``update_roofline`` and ``engine_idle_share`` (PERF.md §3).
"""

from __future__ import annotations

import dataclasses
import gzip
import os
import re
import struct
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

from benchmark import xplane  # noqa: E402

UNSCOPED = "UNSCOPED"
SPAN_PREFIXES = ("bench.", "gossip.")
_SUBSCOPE = re.compile(r"(fetch|or_reduce|scatter_back|bucket\d+)$")
UPDATE_STAGES = ("gossip.update", "gossip.delay_line", "gossip.quiescence")


# --- protobuf wire format -------------------------------------------------

def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint or fixed64/fixed32 field, a memoryview for a length-delimited
    one (a string, bytes or a nested message)."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value = struct.unpack_from("<q", buf, i)[0]
            i += 8
        elif kind == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif kind == 5:
            value = struct.unpack_from("<i", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


# --- XSpace: planes, lines, events, op metadata ---------------------------
# Field numbers from tsl/profiler/protobuf/xplane.proto: XSpace.planes 1;
# XPlane name 2, lines 3, event_metadata 4 (map), stat_metadata 5 (map);
# XLine timestamp_ns 3, name 2, events 4; XEvent metadata_id 1, offset_ps
# 2, duration_ps 3; XEventMetadata id 1, name 2, display_name 4, stats 5;
# XStat metadata_id 1, str_value 5, ref_value 7; XStatMetadata id 1, name 2.

@dataclasses.dataclass
class Plane:
    name: str
    lines: list      # (line name, [(start_ns, end_ns, metadata id)])
    names: dict      # metadata id -> event name
    tf_ops: dict     # metadata id -> tf_op stat ("" where there is none)


def _map_entries(buf):
    entry = dict(fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def _plane(buf) -> Plane:
    name, raw_lines, raw_meta, stat_names = "", [], [], {}
    for f, v in fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            raw_lines.append(v)
        elif f == 4:
            raw_meta.append(v)
        elif f == 5:
            key, meta = _map_entries(v)
            stat_names[key] = bytes(dict(fields(meta)).get(2, b"")).decode()
    tf_op = next((k for k, s in stat_names.items() if s == "tf_op"), None)
    names, tf_ops = {}, {}
    for entry in raw_meta:
        key, meta = _map_entries(entry)
        ev_name, op = "", ""
        for f, v in fields(meta):
            if f == 2:
                ev_name = bytes(v).decode()
            elif f == 5 and tf_op is not None:
                stat = dict(fields(v))
                if stat.get(1) == tf_op:
                    if 5 in stat:
                        op = bytes(stat[5]).decode()
                    elif 7 in stat:
                        op = stat_names.get(stat[7], "")
        names[key], tf_ops[key] = ev_name, op
    lines = []
    for raw in raw_lines:
        line_name, t0, events = "", 0, []
        for f, v in fields(raw):
            if f == 2:
                line_name = bytes(v).decode()
            elif f == 3:
                t0 = _signed(v)
            elif f == 4:
                # Whole nanoseconds, as jax.profiler.ProfileData has them.
                ev = dict(fields(v))
                start = t0 + _signed(ev.get(2, 0)) // 1000
                events.append((start, start + ev.get(3, 0) // 1000, ev.get(1, 0)))
        lines.append((line_name, events))
    return Plane(name, lines, names, tf_ops)


def read_xspace(data: bytes) -> list:
    """The planes of a serialized XSpace (gzip accepted)."""
    if data[:2] == b"\x1f\x8b":
        data = gzip.decompress(data)
    return [_plane(v) for f, v in fields(data) if f == 1]


def scope_of(tf_op: str) -> str:
    """``gossip.loop/while/body/gossip.gather/bucket3/.../or_reduce/reduce``
    -> ``gossip.gather/bucket3/or_reduce``: the innermost ``gossip.*``
    scope and the sub-scopes under its first use; ``UNSCOPED`` where there
    is none. (``gossip.loop`` holds the whole tick loop, so only the loop's
    own ops, which no stage scope names, are booked to it.)"""
    parts = tf_op.split("/")
    stages = [p for p in parts if p.startswith("gossip.")]
    if not stages:
        return UNSCOPED
    subs: list = []
    for p in parts[parts.index(stages[-1]) + 1:]:
        if _SUBSCOPE.match(p) and p not in subs:
            subs.append(p)
    return "/".join([stages[-1]] + subs)


def stage(label: str) -> str:
    """``gossip.gather/bucket3/or_reduce`` -> ``gossip.gather``."""
    return label.split("/", 1)[0]


# --- reduction ------------------------------------------------------------

@dataclasses.dataclass
class Chip:
    busy: list       # [start, end] ns of the union of its ops, in order
    op_ns: dict      # (scope label, op name) -> self time

    @property
    def busy_ns(self) -> float:
        return sum(e - s for s, e in self.busy)

    def stage_ns(self) -> dict:
        out: dict = {}
        for (label, _), ns in self.op_ns.items():
            out[stage(label)] = out.get(stage(label), 0.0) + ns
        return out


@dataclasses.dataclass
class Reduced:
    window: tuple    # (lo, hi) ns of bench.window
    chips: list
    spans: list      # (start, end, name) of bench.* and gossip.* host spans

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def _mean(self, f) -> float:
        return sum(f(c) for c in self.chips) / len(self.chips)

    def busy_s(self) -> float:
        return self._mean(lambda c: c.busy_ns) / 1e9

    def stage_s(self) -> dict:
        """Mean over chips of each stage's self time, in seconds."""
        out: dict = {}
        for c in self.chips:
            for s, ns in c.stage_ns().items():
                out[s] = out.get(s, 0.0) + ns / len(self.chips) / 1e9
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def label_s(self) -> dict:
        out: dict = {}
        for c in self.chips:
            for (label, _), ns in c.op_ns.items():
                out[label] = out.get(label, 0.0) + ns / len(self.chips) / 1e9
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def top_ops(self, k: int = 10) -> list:
        """``["<scope> <op>", seconds]``, longest first."""
        total: dict = {}
        for c in self.chips:
            for key, ns in c.op_ns.items():
                total[key] = total.get(key, 0.0) + ns / len(self.chips)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[f"{label} {op}", ns / 1e9] for (label, op), ns in top]

    def span_at(self, t: float) -> str:
        around = [(e - s, n) for s, e, n in self.spans
                  if s <= t < e and n != xplane.WINDOW]
        return min(around)[1] if around else xplane.WINDOW

    def idle(self, busy) -> list:
        """(start, end) of the window's intervals outside ``busy``."""
        lo, hi = self.window
        out, t = [], lo
        for s, e in list(busy) + [[hi, hi]]:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        return out

    def gaps(self) -> list:
        """(span name, ns) of each interval in which every chip was idle,
        longest first."""
        busy = xplane.union(iv for c in self.chips for iv in c.busy)
        return sorted(((self.span_at((s + e) / 2), e - s)
                       for s, e in self.idle(busy)), key=lambda g: -g[1])

    def engine_idle_share(self) -> float:
        """Share of the window (mean over chips) in which the chip was idle
        while a ``gossip.*`` host span was open, in %."""
        engine = xplane.union(
            (s, e) for s, e, n in self.spans if n.startswith("gossip."))

        def engine_ns(c):
            return sum(max(0, min(e, b) - max(s, a))
                       for s, e in self.idle(c.busy) for a, b in engine)
        return 100.0 * self._mean(engine_ns) / self.window_ns


def reduce_planes(planes: list) -> Reduced | None:
    """None when the trace holds no TPU op inside the window."""
    spans = []
    for p in planes:
        if p.name.startswith("/host:"):
            for _, events in p.lines:
                for s, e, mid in events:
                    if p.names.get(mid, "").startswith(SPAN_PREFIXES):
                        spans.append((s, e, p.names[mid]))
    windows = [(s, e) for s, e, n in spans if n == xplane.WINDOW]
    if not windows:
        return None
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    chips = []
    for p in sorted(planes, key=lambda p: p.name):
        if not p.name.startswith("/device:TPU:"):
            continue
        events = []
        for line_name, evs in p.lines:
            if line_name != "XLA Ops":
                continue
            for s, e, mid in evs:
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    key = (scope_of(p.tf_ops.get(mid, "")),
                           xplane.op_name(p.names.get(mid, "")))
                    events.append((s, e, key))
        if not events:
            continue
        chips.append(Chip(
            busy=xplane.union((s, e) for s, e, _ in events),
            op_ns=xplane.self_times(events),
        ))
    if not chips:
        return None
    return Reduced(window=(lo, hi), chips=chips, spans=spans)


def reduce_file(path: str) -> Reduced | None:
    with open(path, "rb") as f:
        return reduce_planes(read_xspace(f.read()))


# --- the three per-layer readings (PERF.md §3) ----------------------------

def _stage_time(r: Reduced, stages) -> float:
    """Seconds under ``stages``; raises where a stage is missing, so no
    share is ever computed from a partial attribution."""
    got = r.stage_s()
    missing = [s for s in stages if s not in got]
    if missing:
        raise ValueError(f"scope(s) {missing} not on the trace (found {sorted(got)})")
    return sum(got[s] for s in stages)


def gather_roofline(r: Reduced, nnz: int, chunk_shares: int, ticks: int,
                    peak_gbps: float) -> float:
    """``nnz·(4W+4)`` bytes a tick × ticks, over the time under
    ``gossip.gather``, over the HBM peak, in %."""
    from benchmark import bytecount

    moved = int(ticks) * int(nnz) * (4 * bytecount.words(chunk_shares) + 4)
    return 100.0 * moved / _stage_time(r, ("gossip.gather",)) / (peak_gbps * 1e9)


def update_roofline(r: Reduced, n: int, chunk_shares: int, ticks: int,
                    peak_gbps: float) -> float:
    """``3·N·4W`` bytes a tick × ticks, over the time under
    ``gossip.update``, ``gossip.delay_line`` and ``gossip.quiescence``,
    over the HBM peak, in %."""
    from benchmark import bytecount

    moved = int(ticks) * 3 * int(n) * 4 * bytecount.words(chunk_shares)
    return 100.0 * moved / _stage_time(r, UPDATE_STAGES) / (peak_gbps * 1e9)


# --- the tool -------------------------------------------------------------

def _calls(r: Reduced, calls) -> list:
    """Per ``bench.call`` span: [wall s, busy s (chip 0), longest gap s,
    the span the gap sat in]."""
    spans = sorted((s, e) for s, e, n in r.spans if n == "bench.call")
    chip_idle = r.idle(r.chips[0].busy)
    out = []
    for (s, e), c in zip(spans, calls):
        idle = [(max(a, s), min(b, e)) for a, b in chip_idle if b > s and a < e]
        longest = max(idle, key=lambda g: g[1] - g[0], default=(s, s))
        out.append([c.seconds, (e - s - sum(b - a for a, b in idle)) / 1e9,
                    (longest[1] - longest[0]) / 1e9,
                    r.span_at((longest[0] + longest[1]) / 2)])
    return out


def main(argv=None) -> int:
    import argparse
    import glob
    import json
    import shutil
    import tempfile
    import time

    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="copy the trace here")
    args = ap.parse_args(argv)
    # Before JAX is imported, the cache run.py uses.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    from benchmark import bytecount, graphs, harness, peaks, spec
    from p2p_gossip_tpu.models.generation import Schedule
    from p2p_gossip_tpu.models.topology import Graph

    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"FAILED: {cell.name} needs a TPU; JAX found "
              f"{devices[0].platform}", file=sys.stderr)
        return 3
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    indptr, indices, perm = graphs.overlay(cell.config, args.seed)
    n = indptr.shape[0] - 1
    graph = Graph(n=n, indptr=indptr, indices=indices)
    kind = spec.module("kinds", cell.traffic["kind"])
    mix = kind.mix(cell.traffic, n)
    pool = kind.schedules(mix, perm)
    schedules = [Schedule(n, o, t) for o, t in pool]
    path = spec.module("paths", cell.engine["engine"]).Path(
        graph, mix, cell.engine, devices)
    origins, ticks = pool[0]
    path(Schedule(n, origins[: mix.chunk_size], ticks[: mix.chunk_size]))
    setup_s = time.perf_counter() - t_process

    trace_dir = tempfile.mkdtemp(prefix="scopes_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        calls, window_s = harness._window(path, schedules, args.seconds)
        jax.profiler.stop_trace()
        trace = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
        if args.keep:
            shutil.copy(trace, args.keep)
        r = reduce_file(trace)
        old = xplane.reduce_file(trace)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    del path
    _, failed = harness._check(calls, indptr, indices, pool, mix, kind)

    peak = peaks.hbm_peak_gbps(devices[0].device_kind)
    nnz, total_ticks = int(indices.shape[0]), sum(c.ticks for c in calls)
    moved = sum(bytecount.call_bytes(nnz, n, mix.chunk_size, c.ticks) for c in calls)
    busy = r.busy_s()
    stages = r.stage_s()
    out = {
        "cell": cell.name, "seed": args.seed, "correct": failed == 0,
        "calls": len(calls), "setup_s": setup_s,
        "node_updates_per_s": sum(c.node_updates for c in calls) / window_s,
        "window_s": r.window_ns / 1e9, "busy_s": busy,
        "scoped_share": 100.0 * (1 - stages.get(UNSCOPED, 0.0) / busy),
        "stage_share": {s: 100.0 * v / busy for s, v in stages.items()},
        "label_s": r.label_s(),
        "device_ops": r.top_ops(12),
        "idle_gaps": [[name, ns / 1e9] for name, ns in r.gaps()[:12]],
        "per_call": _calls(r, calls),
        "gather_roofline": gather_roofline(r, nnz, mix.chunk_size, total_ticks, peak),
        "update_roofline": update_roofline(r, n, mix.chunk_size, total_ticks, peak),
        "engine_idle_share": r.engine_idle_share(),
        "device_idle_share": 100.0 * (1.0 - old.busy_s / old.window_s),
        "step_roofline": 100.0 * moved / old.busy_s / (peak * 1e9),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

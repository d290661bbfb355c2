"""One benchmark run: set up a cell, measure a window of engine calls,
check every call against the plain reference, print the result line.

Set-up (``setup_s``, from process start to the first timed call): build
and rename the overlay, draw the schedule pool (``kinds/<kind>.py``),
stage the engine path (``paths/<engine>.py``),
one warm-up call of one chunk (its compiles come from the persistent
cache after the first run in a checkout). The window then runs whole
engine calls back to back; the last call counted is the last one started
before ``--seconds`` ran out. ``node_updates_per_s`` is every node-update
the window's calls processed over the wall time from the first call's
start to the last call's end; each call returns host counters, so the
device has finished.

Each phase prints its seconds on stderr as it ends (``phase graph.build
2.801 s``): set-up's ``imports``, ``backend``, ``graph.build``,
``graph.relabel``, ``pool``, ``stage``, ``warmup``, then ``window`` and
``check``.

With ``--trace 1`` the window runs under the profiler, and the result
carries the per-layer metrics the cell's readers take from the trace.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import shutil
import sys
import tempfile
import time

import numpy as np

from benchmark import graphs, reference, spec, xplane

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",  # includes cache loads
)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Call:
    schedule: int        # index into the pool
    seconds: float
    node_updates: int
    ticks: int | None
    counters: dict


@dataclasses.dataclass
class Readings:
    """What a per-layer metric's reader (``metrics/<name>.py``) may read."""

    trace: xplane.Summary | None
    compile_s: float
    calls: list
    n: int
    nnz: int
    chunk_shares: int
    hbm_peak_gbps: float | None


class _CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.events = 0

    def __call__(self, name: str, secs: float, **_) -> None:
        if name in COMPILE_EVENTS:
            self.seconds += secs
            self.events += name == COMPILE_EVENTS[-1]


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _window(path, schedules: list, seconds: float) -> tuple:
    """Whole calls back to back; returns (calls, wall seconds)."""
    calls: list = []
    t_start = time.perf_counter()
    with _annotate("bench.window"):
        while not calls or time.perf_counter() - t_start < seconds:
            i = len(calls) % len(schedules)
            with _annotate("bench.call"):
                c0 = time.perf_counter()
                counters, ticks = path(schedules[i])
                c1 = time.perf_counter()
            calls.append(Call(i, c1 - c0, int(counters["processed"].sum()),
                              ticks, counters))
    return calls, time.perf_counter() - t_start


def _traced_window(path, schedules: list, seconds: float) -> tuple:
    """``_window`` under the profiler; adds the reduced trace (or None)."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        calls, wall = _window(path, schedules, seconds)
        jax.profiler.stop_trace()
        files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        return calls, wall, xplane.reduce_file(files[0]) if files else None
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _check(calls: list, indptr, indices, pool: list, mix, kind) -> tuple:
    """(numbers compared with their limits, calls that failed); the
    reference runs for the pool's schedules that the window called."""
    graphs.check_csr(indptr, indices)
    ref = kind.reference(indptr, indices)
    want = {i: kind.expected(ref, *pool[i], mix)
            for i in sorted({c.schedule for c in calls})}
    failed, mismatches, gap = 0, 0, 0
    for c in calls:
        bad, g = reference.compare(c.counters, want[c.schedule])
        failed += bad > 0
        mismatches += bad
        gap = max(gap, g)
    return {
        "counter_mismatches": {"value": mismatches, "at_most": 0},
        "max_counter_gap": {"value": gap, "at_most": 0},
    }, failed


class _Phases:
    """Each phase's seconds on stderr as it ends, so a run stopped midway
    says where it was."""

    def __init__(self, t_process: float):
        self.last = t_process

    def __call__(self, name: str) -> float:
        now = time.perf_counter()
        _say(f"phase {name} {now - self.last:.3f} s")
        self.last = now
        return now


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_process: float, require_tpu: bool = True) -> dict:
    phase = _Phases(t_process)
    phase("imports")
    import jax
    from jax import monitoring

    devices = jax.devices()
    dev = devices[0]
    phase("backend")
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell.chips):
        raise NoChip(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {dev.platform} device(s)"
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = _CompileClock()
    monitoring.register_event_duration_secs_listener(clock)

    from p2p_gossip_tpu.models.generation import Schedule
    from p2p_gossip_tpu.models.topology import Graph

    with _annotate("bench.setup.graph"):
        indptr, indices = graphs.native_csr(cell.config)
        phase("graph.build")
        n = indptr.shape[0] - 1
        perm = graphs.permutation(n, seed)
        indptr, indices = graphs.relabel(indptr, indices, perm)
        phase("graph.relabel")
        graph = Graph(n=n, indptr=indptr, indices=indices)
    kind = spec.module("kinds", cell.traffic["kind"])
    mix = kind.mix(cell.traffic, n)
    pool = kind.schedules(mix, perm)
    schedules = [Schedule(n, o, t) for o, t in pool]
    phase("pool")
    with _annotate("bench.setup.stage"):
        path = spec.module("paths", cell.engine["engine"]).Path(
            graph, mix, cell.engine, devices)
    phase("stage")
    with _annotate("bench.setup.warmup"):
        # One chunk compiles (or loads) the window's one chunk program.
        origins, ticks = pool[0]
        path(Schedule(n, origins[: mix.chunk_size], ticks[: mix.chunk_size]))
    compile_s, compiles_setup = clock.seconds, clock.events
    setup_s = phase("warmup") - t_process

    if trace:
        calls, window_s, summary = _traced_window(path, schedules, seconds)
    else:
        calls, window_s = _window(path, schedules, seconds)
    window_compiles = clock.events - compiles_setup
    phase("window")
    memory_peak = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices[: cell.chips]
    )
    del path  # frees the program's device state before the reference runs

    check, failed = _check(calls, indptr, indices, pool, mix, kind)
    phase("check")
    correct = failed == 0
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    out = {"correct": correct, "attempted": len(calls), "failed": failed}
    if trace:
        from benchmark import peaks

        readings = Readings(
            trace=summary, compile_s=compile_s, calls=calls, n=n,
            nnz=int(indices.shape[0]), chunk_shares=mix.chunk_size,
            hbm_peak_gbps=(peaks.hbm_peak_gbps(dev.device_kind)
                           if dev.platform == "tpu" else None),
        )
        metrics = {}
        for m in cell.per_layer:
            v = spec.load_reader(m["name"])(readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            out["breakdown"] = {
                "device_ops": summary.top_ops(10),
                "idle_gaps": [[name, ns / 1e9] for name, ns in summary.gaps[:10]],
            }
    else:
        values = {
            "node_updates_per_s": sum(c.node_updates for c in calls) / window_s,
            "setup_s": setup_s,
        }
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
        }
    out["metrics"] = metrics
    out["device"] = device
    out["check"] = check

    seconds_per_call = sorted(c.seconds for c in calls)
    _say(f"cell {cell.name} seed {seed}: {len(calls)} calls in "
         f"{window_s:.3f} s, setup {setup_s:.3f} s (compile {compile_s:.3f} s), "
         f"compiles in window {window_compiles}, call s min/median/max "
         f"{seconds_per_call[0]:.4f}/{float(np.median(seconds_per_call)):.4f}/"
         f"{seconds_per_call[-1]:.4f}")
    for name, c in check.items():
        bound = " ".join(f"{k.replace('_', ' ')} {v}" for k, v in c.items()
                         if k != "value")
        _say(f"check {name} {c['value']} ({bound})")
    return out


def main(args, t_process: float) -> int:
    cell = spec.load_cell(args.workload)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace), t_process)
    except NoChip as e:
        _say(f"FAILED: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0

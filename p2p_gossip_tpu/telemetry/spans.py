"""Host span tracing — nestable monotonic-clock phase timers.

    with telemetry.span("compile", kernel="flood_runner"):
        runner = build(...)

Each closed span emits one ``span`` event: start time relative to the
sink's epoch, duration, nesting depth, and free-form attrs. The clock is
``time.perf_counter`` (monotonic — durations are immune to wall-clock
steps). Nesting is tracked per thread, so spans opened on worker threads
don't corrupt the main thread's depth.

Every span also opens a ``jax.profiler.TraceAnnotation`` named
``gossip.<name>`` (attrs as its arguments), whether or not the sink is on:
under a running profiler trace the span lands on the host plane, on the
same clock as the device ops, so an idle gap on the device can be put
down to the engine phase around it (docs/OBSERVABILITY.md). With no trace
running the annotation records nothing.

When telemetry is off, ``span()`` reads no clock and emits nothing — safe
to leave in place on hot host paths (it still costs a function call and an
inactive annotation, about a microsecond, per use, which is why the engines
only wrap per-CHUNK work, never per-tick work; per-tick visibility is the
metric rings' job).
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax

from p2p_gossip_tpu.telemetry import sink

_tls = threading.local()


def _depth() -> int:
    return getattr(_tls, "depth", 0)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time a phase and emit it as a span event on exit. Nestable;
    exceptions propagate (the span still closes, attr ``error`` set)."""
    with jax.profiler.TraceAnnotation(f"gossip.{name}", **attrs):
        if not sink.enabled():
            yield
            return
        depth = _depth()
        _tls.depth = depth + 1
        start = time.perf_counter()
        try:
            yield
        except BaseException as e:
            attrs = {**attrs, "error": type(e).__name__}
            raise
        finally:
            dur = time.perf_counter() - start
            _tls.depth = depth
            event = {
                "type": "span",
                "name": name,
                "ts": max(start - sink.epoch(), 0.0),
                "dur": dur,
                "depth": depth,
            }
            if attrs:
                event["attrs"] = attrs
            sink.emit(event)


def emit_counter(name: str, value) -> None:
    sink.emit({"type": "counter", "name": name, "value": value})


def emit_jit_cache_counters() -> None:
    """Sample every countable registry entry's jit-cache size (the PR-3
    recompile-sentinel counters) as counter events — the run report's
    jit-cache section. No-op when telemetry is off."""
    if not sink.enabled():
        return
    from p2p_gossip_tpu.staticcheck.registry import countable_entries

    for entry in countable_entries():
        target = entry.jit_target()
        size = getattr(target, "_cache_size", None)
        if callable(size):
            try:
                emit_counter(f"jit_cache.{entry.name}", int(size()))
            except Exception:
                continue

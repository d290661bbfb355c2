"""Every stage of the tick carries a ``gossip.*`` name scope into the
compiled program, and ``telemetry.span`` lands on the profiler's trace.

The scopes are what a device trace attributes time by (each op's
``tf_op`` is its HLO ``op_name``), so these tests read the optimised HLO
the CPU backend compiles at the audit shapes: every instruction of the
chunk loop that carries an ``op_name`` lies under a ``gossip.*`` scope.
Instructions XLA creates itself (copies, loop plumbing) carry none and
are not the program's to name."""

import glob
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from p2p_gossip_tpu import telemetry
from p2p_gossip_tpu.engine import sync
from p2p_gossip_tpu.models.generation import Schedule
from p2p_gossip_tpu.models.topology import barabasi_albert, erdos_renyi
from p2p_gossip_tpu.ops.ell import build_bucket_major, build_degree_buckets

TICK_STAGES = {
    "gossip.gather", "gossip.update", "gossip.delay_line", "gossip.quiescence",
}


def _computations(text: str) -> tuple:
    """(name -> instruction lines, entry name) of optimised HLO text."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        if line.rstrip().endswith("{") and line[:1] not in (" ", "") \
                and not line.startswith("HloModule"):
            head = line.split()
            cur = (head[1] if head[0] == "ENTRY" else head[0]).lstrip("%")
            entry = cur if head[0] == "ENTRY" else entry
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur and line.strip():
            comps[cur].append(line.strip())
    return comps, entry


# Computations an instruction runs: loop bodies and conditions, fusions,
# branches and calls. A reduction's combiner (``to_apply`` of a reduce or
# scatter) is no op of its own.
_CALLED = re.compile(
    r"(?:condition|body|calls)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}"
)


def _loop_ops(text: str) -> list:
    """(instruction line, op_name or None) of everything the entry's
    ``while`` loops run, parameters aside: their bodies and conditions and
    every computation those call, fused ones included."""
    comps, entry = _computations(text)
    todo = [c for line in comps[entry] if " while(" in line
            for c in re.findall(r"(?:condition|body)=%?([\w.\-]+)", line)]
    seen, out = set(), []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            for one, many in _CALLED.findall(line):
                todo += [one] if one else [x.strip().lstrip("%")
                                           for x in many.split(",")]
            if " call(" in line:
                todo += re.findall(r"to_apply=%?([\w.\-]+)", line)
            if " parameter(" in line:
                continue  # a computation's inputs, named by their callers
            m = re.search(r'op_name="([^"]*)"', line)
            out.append((line, m.group(1) if m else None))
    return out


def _stage(op_name: str):
    """The innermost ``gossip.*`` scope: ``gossip.loop`` holds the whole
    tick loop, the stage scopes its ops."""
    stages = [p for p in op_name.split("/") if p.startswith("gossip.")]
    return stages[-1] if stages else None


def _graph(kind: str):
    g = erdos_renyi(48, 0.2, seed=0)
    if kind == "uniform":
        return sync.DeviceGraph.build(g, bucketed=False)
    if kind == "per_edge":
        idx, _ = g.ell()
        delays = (np.arange(idx.size).reshape(idx.shape) % 3 + 1).astype(np.int32)
        return sync.DeviceGraph.build(g, delays, bucketed=False)
    # Several degree buckets on a small power-law graph: bucket-major
    # (the staging's numbering does not depend on min_rows), or in the
    # graph's own numbering on a full-width staging.
    import dataclasses

    b = barabasi_albert(200, 3, seed=0)
    if kind == "node_order":
        return dataclasses.replace(
            sync.DeviceGraph.build(b, bucketed=False),
            buckets=build_degree_buckets(b, None, min_rows=16),
        )
    dg = sync.DeviceGraph.build(b, bucketed=True)
    return dataclasses.replace(
        dg, buckets=build_bucket_major(b, None, min_rows=16)[0]
    )


def _chunk_text(kind: str, coverage: bool = False) -> str:
    dg = _graph(kind)
    if coverage:
        spec = sync._audit_spec_chunk_coverage()
        fn = sync._run_chunk_coverage
    else:
        spec = sync._audit_spec_chunk_while()
        fn = sync._run_chunk_while
    origins = spec.args[1] % dg.n
    args = (dg, origins) + tuple(spec.args[2:])
    return fn.lower(*args, **spec.kwargs).compile().as_text()


@pytest.mark.parametrize("kind", ["uniform", "per_edge", "bucketed"])
def test_every_op_of_the_chunk_loop_is_scoped(kind):
    ops = _loop_ops(_chunk_text(kind))
    named = [(line, op) for line, op in ops if op]
    unscoped = [line[:120] for line, op in named if _stage(op) is None]
    assert not unscoped, unscoped
    assert TICK_STAGES <= {_stage(op) for _, op in named}


def test_every_op_of_the_coverage_loop_is_scoped():
    named = [(line, op) for line, op in _loop_ops(_chunk_text("uniform", True))
             if op]
    assert not [line for line, op in named if _stage(op) is None]
    assert TICK_STAGES <= {_stage(op) for _, op in named}


def _scatter_back_ops(text: str) -> list:
    """(HLO opcode, line) of every instruction under ``scatter_back``."""
    out = []
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        op = re.search(r"=\s*\S+\s+([\w\-]+)\(", line)
        if m and op and "/scatter_back/" in m.group(1):
            out.append((op.group(1), line.strip()))
    return out


@pytest.mark.parametrize("kind", ["bucketed", "node_order"])
def test_scatter_back_is_a_concatenation_on_bucket_major(kind):
    """A bucket-major staging reassembles the buckets' arrivals with no
    scatter under ``gossip.gather/scatter_back`` (the scope still names
    the reassembly); buckets in the graph's own numbering scatter."""
    ops = _scatter_back_ops(_chunk_text(kind))
    assert ops
    scatters = [line for op, line in ops if op.startswith("scatter")]
    if kind == "bucketed":
        assert not scatters, scatters[:2]
    else:
        assert scatters


def test_sharded_scatter_back_is_untouched():
    """The sharded tick keeps its per-shard scatter: padded per-shard
    buckets are not renumbered."""
    from p2p_gossip_tpu.parallel.engine_sharded import (
        ShardedDeviceGraph,
        _audit_mesh,
    )

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices for a 2x2 mesh")
    mesh, _ = _audit_mesh()
    chunk, horizon = 32, 16
    dg = ShardedDeviceGraph.stage(
        barabasi_albert(400, 3, seed=0), mesh, chunk_size=chunk
    )
    assert any(dg.bucket_counts)  # hub-skewed rows keep their buckets
    runner, pass_size = dg.runner(chunk, horizon)
    gen_ticks = np.full(pass_size, horizon, dtype=np.int32)
    gen_ticks[:2] = 0
    args = (
        dg.ell_args, dg.degree, dg.churn_start, dg.churn_end,
        np.zeros(pass_size, dtype=np.int32), gen_ticks,
        np.int32(0), np.int32(0), np.zeros((0,), dtype=np.int32),
    ) + dg.exchange_operands()
    ops = _scatter_back_ops(runner.lower(*args).compile().as_text())
    assert any(op.startswith("scatter") for op, _ in ops)


def test_gather_sub_scopes_name_each_bucket():
    text = _chunk_text("bucketed")
    n_buckets = len(_graph("bucketed").buckets)
    assert n_buckets >= 2
    names = set(re.findall(r'op_name="([^"]*)"', text))
    gather = [n for n in names if _stage(n) == "gossip.gather"]
    for sub in ["fetch", "or_reduce", "scatter_back"] + [
        f"bucket{i}" for i in range(n_buckets)
    ]:
        assert any(f"/{sub}/" in n for n in gather), sub
    # Initialisation and outputs outside the loop are chunk I/O; the
    # loop's own while is the loop.
    assert any(_stage(n) == "gossip.chunk_io" for n in names)
    assert any(n.endswith("gossip.loop/while") for n in names)


@pytest.mark.parametrize("exchange", ["dense", "delta", "hub"])
def test_sharded_collectives_are_the_exchange_scope(exchange):
    from p2p_gossip_tpu.parallel.engine_sharded import _audit_spec_flood_runner

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices for a 2x2 mesh")
    spec = _audit_spec_flood_runner(exchange=exchange)
    text = spec.fn.lower(*spec.args).compile().as_text()
    moves = [line for line in text.splitlines()
             if re.search(r"\s(all-gather|all-to-all)(-start)?\(", line)]
    assert moves
    for line in moves:
        m = re.search(r'op_name="([^"]*)"', line)
        assert m and _stage(m.group(1)) == "gossip.exchange", line[:160]


def _trace_events(tmp_path, run) -> list:
    """(name, stats) of every host-plane event a CPU profiler trace of
    ``run()`` holds."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    profile = ProfileData.from_file(path)
    return [(e.name, dict(e.stats)) for p in profile.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]


def test_span_reaches_the_trace_with_the_sink_off(tmp_path):
    telemetry.reset()
    assert not telemetry.enabled()

    def run():
        with telemetry.span("dispatch", chunk=3, kernel="k"):
            pass

    events = _trace_events(tmp_path, run)
    assert ("gossip.dispatch", {"chunk": 3, "kernel": "k"}) in events
    assert telemetry.event_count() == 0


def test_span_with_the_sink_on_writes_both(tmp_path):
    telemetry.configure(str(tmp_path / "t.jsonl"))
    try:
        def run():
            with telemetry.span("d2h", chunk=1):
                pass

        events = _trace_events(tmp_path / "trace", run)
        spans = [e for e in telemetry.events() if e["type"] == "span"]
    finally:
        telemetry.reset()
    assert ("gossip.d2h", {"chunk": 1}) in events
    assert [s["name"] for s in spans] == ["d2h"]
    assert spans[0]["attrs"] == {"chunk": 1}


def test_run_sync_sim_names_its_host_phases_on_the_trace(tmp_path):
    telemetry.reset()
    g = erdos_renyi(60, 0.1, seed=1)
    sched = Schedule(60, np.arange(40, dtype=np.int32) % 60,
                     np.arange(40, dtype=np.int32) % 5)
    dg = sync.DeviceGraph.build(g)
    sync.run_sync_sim(g, sched, 24, chunk_size=32, device_graph=dg)  # compile

    events = _trace_events(
        tmp_path,
        lambda: sync.run_sync_sim(g, sched, 24, chunk_size=32, device_graph=dg),
    )
    phases = [(n, s.get("chunk")) for n, s in events if n.startswith("gossip.")]
    for phase in ("gossip.prepare", "gossip.dispatch", "gossip.d2h"):
        assert sorted(c for n, c in phases if n == phase) == [0, 1], phase
    assert [n for n, _ in phases].count("gossip.finish") == 1


@pytest.mark.parametrize("exchange", ["dense", "delta"])
def test_sharded_tick_stages_are_scoped(exchange):
    """The sharded runner names its tick's stages as the one-chip loop
    does, so ``benchmark/scopes.py`` books a mesh cell's device time by
    stage, the exchange among them."""
    from p2p_gossip_tpu.parallel.engine_sharded import _audit_spec_flood_runner

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices for a 2x2 mesh")
    spec = _audit_spec_flood_runner(exchange=exchange)
    text = spec.fn.lower(*spec.args).compile().as_text()
    stages = {_stage(op) for _, op in _loop_ops(text) if op}
    assert TICK_STAGES | {"gossip.exchange"} <= stages

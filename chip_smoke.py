"""Chip smoke test: drive the main path once on a TPU, through the entry
points a user calls, and check what comes out.

    python chip_smoke.py            # one chip, every phase below
    python chip_smoke.py --chips 4  # the four-chip sharded phase only

Phases on one chip (BASELINE config 3 sizes, bench.py's headline
workload):

- device:   fail unless JAX's first device is a TPU.
- headline: 100K-node ER p=0.001 flood of 8,192 shares over a 16-tick
            window (`engine.sync.run_sync_sim`): full coverage and the
            reference's conservation laws.
- coverage: `run_flood_coverage` with 4,096 origins, where the Pallas
            coverage kernel is selected; its per-tick coverage equals the
            XLA path's, whose last row equals `bitmask.coverage_per_slot`
            of the final seen state.
- parity:   the native C++ event engine (the plain reference) on the same
            graph with 4 shares; per-node counters equal exactly.
- cli:      `utils.cli.run` with the README's 100K example (plus
            --perNodeStats); the reference-format statistics parse and
            conserve.
- serve:    4 `SimRequest`s through `serve.GossipServer`; each answer is
            bitwise a solo campaign run of the same request.

With ``--chips 4``: a 1M-node BA m=3 graph (BASELINE config 4), 2,048
shares, sharded over a 4-chip ``nodes`` mesh with the dense, delta and
hub exchanges; each run's counters equal the single-chip `run_sync_sim`
bitwise, and each shard's counters come from its own chip.

Each phase prints its wall time and, separately, the compile time inside
it. The last stdout line is the contract line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failed phase exits 1 and prints no such line. Everything runs in
this one process, which holds the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import io
import json
import re
import sys
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    # Headline flood: BASELINE config 3 as bench.py runs it.
    nodes: int = 100_000
    prob: float = 0.001
    seed: int = 0
    shares: int = 8192
    gen_window: int = 16
    horizon: int = 64
    chunk: int = 8192
    # >= 4096 origins keep the bitmask at >= 128 words: the Pallas gate.
    cov_origins: int = 4096
    parity_shares: int = 4
    # The README's 100K CLI example simulates 10 s.
    cli_sim_time: float = 10.0
    serve_nodes: int = 100_000
    # Four-chip phase: BASELINE config 4.
    ba_nodes: int = 1_000_000
    ba_m: int = 3
    ba_shares: int = 2048


FULL = Sizes()
#: CPU rehearsal sizes (tests/test_chip_smoke.py).
TINY = Sizes(
    nodes=2000, prob=0.01, shares=256, chunk=256, cov_origins=64,
    cli_sim_time=0.5, serve_nodes=256, ba_nodes=4000, ba_shares=64,
)


def say(msg: str) -> None:
    print(msg, flush=True)


# --- compile-time accounting ------------------------------------------------

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_compile_s = [0.0]


def _on_duration(name: str, secs: float, **_) -> None:
    if name in _COMPILE_EVENTS:
        _compile_s[0] += secs


# --- phases -------------------------------------------------------------------


@functools.lru_cache(maxsize=2)
def _headline(sz: Sizes):
    """(graph, schedule, DeviceGraph) of the headline workload."""
    import jax

    from bench import headline_workload
    from p2p_gossip_tpu.engine.sync import DeviceGraph

    graph, sched = headline_workload(
        sz.nodes, sz.prob, sz.seed, sz.shares, sz.gen_window
    )
    dg = DeviceGraph.build(graph)
    jax.block_until_ready(dg.ell_idx)
    return graph, sched, dg


def phase_headline(sz: Sizes) -> str:
    from p2p_gossip_tpu.engine.sync import run_sync_sim

    graph, sched, dg = _headline(sz)
    stats = run_sync_sim(
        graph, sched, sz.horizon, chunk_size=sz.chunk, device_graph=dg
    )
    stats.check_conservation()
    processed = stats.totals()["processed"]
    assert processed == sz.shares * graph.n, (
        f"flood did not reach full coverage: {processed} != "
        f"{sz.shares} x {graph.n}"
    )
    return (
        f"N={graph.n} edges={graph.num_edges} shares={sz.shares} "
        f"processed={processed} ticks={stats.extra['ticks_executed']}"
    )


def phase_coverage(sz: Sizes, require_pallas: bool) -> str:
    import jax.numpy as jnp

    from p2p_gossip_tpu.engine.sync import (
        _resolve_block,
        _run_chunk_coverage,
        pallas_coverage_selected,
        run_flood_coverage,
    )
    from p2p_gossip_tpu.models.generation import Schedule
    from p2p_gossip_tpu.ops import bitmask

    graph, _, dg = _headline(sz)
    s = sz.cov_origins
    origins = np.random.default_rng(sz.seed + 1).integers(
        0, graph.n, s
    ).astype(np.int32)
    chunk = bitmask.num_words(s) * bitmask.WORD_BITS
    selected = pallas_coverage_selected(dg, chunk)
    say(f"  coverage kernel: {'Pallas' if selected else 'XLA'} "
        f"(N={dg.n}, W={bitmask.num_words(chunk)} words)")
    if require_pallas:
        assert selected, "the Pallas coverage kernel was not selected"
    _, cov = run_flood_coverage(
        graph, origins, sz.horizon, device_graph=dg, chunk_size=chunk
    )
    # The XLA reference: the same run with the kernel off, and its final
    # seen state reduced by bitmask.coverage_per_slot.
    o, g = Schedule(graph.n, origins, np.zeros(s, np.int32)).padded(
        chunk, sz.horizon
    )
    seen, _, _, cov_xla = _run_chunk_coverage(
        dg, jnp.asarray(o), jnp.asarray(g), None, chunk_size=chunk,
        horizon=sz.horizon, block=_resolve_block(dg, None),
        use_pallas=False, coverage_slots=s,
    )
    cov_xla = np.asarray(cov_xla)[:, :s]
    final = np.asarray(bitmask.coverage_per_slot(seen, chunk))[:s]
    assert np.array_equal(cov, cov_xla), "per-tick coverage differs from XLA"
    assert np.array_equal(cov[-1], final), (
        "final coverage differs from coverage_per_slot of the seen state"
    )
    assert int(final.min()) == graph.n, "flood did not cover the graph"
    return f"origins={s} ticks={cov.shape[0]} final_min={int(final.min())}"


def phase_parity(sz: Sizes) -> str:
    import p2p_gossip_tpu as pg
    from p2p_gossip_tpu.engine.sync import run_sync_sim
    from p2p_gossip_tpu.runtime import native

    graph, sched, dg = _headline(sz)
    k = sz.parity_shares
    small = pg.Schedule(graph.n, sched.origins[:k], sched.gen_ticks[:k])
    ref = native.run_native_sim(graph, small, sz.horizon)
    chip = run_sync_sim(graph, small, sz.horizon, device_graph=dg)
    assert chip.equal_counts(ref), "chip counters differ from the C++ engine"
    return (
        f"shares={k} processed={ref.totals()['processed']} "
        f"events={ref.extra['events_processed']} equal_counts=True"
    )


_NODE_LINE = re.compile(
    r"Node (\d+): Generated (\d+), Received (\d+), Forwarded (\d+), "
    r"Total sent (\d+), Total processed (\d+), Peer count (\d+), "
    r"Socket connections (\d+)"
)


def check_reference_stats(text: str, n: int) -> dict:
    """Parse the PrintStatistics report and check the reference's
    conservation laws per node and in the totals. Returns the totals."""
    rows = np.array(
        [m.groups() for m in _NODE_LINE.finditer(text)], dtype=np.int64
    )
    assert rows.shape == (n, 8), f"{rows.shape[0]} node lines for N={n}"
    assert np.array_equal(rows[:, 0], np.arange(n))
    gen, rec, fwd, sent, proc, _, sock = rows[:, 1:].T
    assert np.array_equal(rec, fwd), "received != forwarded"
    assert np.array_equal(proc, gen + rec), "processed != generated + received"
    assert np.array_equal(sent, (gen + fwd) * sock), "sent != (gen+fwd) x peers"
    totals = {
        key: int(re.search(rf"Total shares {key}: (\d+)", text).group(1))
        for key in ("generated", "received", "forwarded", "sent")
    }
    assert totals == {
        "generated": int(gen.sum()), "received": int(rec.sum()),
        "forwarded": int(fwd.sum()), "sent": int(sent.sum()),
    }, totals
    return totals


def phase_cli(sz: Sizes) -> str:
    from p2p_gossip_tpu.utils import cli

    # The README's example, plus --perNodeStats: above 1,000 nodes the
    # per-node lines the conservation check reads are off by default.
    argv = [
        "--numNodes", str(sz.nodes), "--connectionProb", str(sz.prob),
        "--genModel", "poisson", "--simTime", str(sz.cli_sim_time),
        "--backend", "tpu", "--perNodeStats",
    ]
    say(f"  cli argv: {' '.join(argv)}")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = cli.run(argv)
    assert rc == 0, f"cli.run returned {rc}"
    totals = check_reference_stats(out.getvalue(), sz.nodes)
    assert totals["received"] > 0, "no share travelled"
    return (
        f"simTime={sz.cli_sim_time:g}s generated={totals['generated']} "
        f"received={totals['received']} sent={totals['sent']}"
    )


def phase_serve(sz: Sizes) -> str:
    from p2p_gossip_tpu.serve.server import GossipServer, matches_solo

    n = sz.serve_nodes
    er = {"family": "erdos_renyi", "n": n, "p": 8.0 / n, "seed": 11}
    ws = {"family": "watts_strogatz", "n": n, "k": 8, "beta": 0.1, "seed": 12}
    common = {"shares": 16, "horizon": 32}
    requests = [
        {"request_id": "flood-er", "topology": er, "protocol": "flood",
         "seeds": [0, 1, 2, 3], **common},
        {"request_id": "pushpull-er", "topology": er,
         "protocol": "pushpull", "seeds": [4, 5], **common},
        {"request_id": "pushk-ws", "topology": ws, "protocol": "pushk",
         "seeds": [6], **common},
        {"request_id": "flood-ws-lossy", "topology": ws, "protocol": "flood",
         "seeds": [7, 8], "loss_prob": 0.05, **common},
    ]
    server = GossipServer(slots=8)
    for req in requests:
        server.submit(req)
    batches = server.drain()
    for req in requests:
        rid = req["request_id"]
        assert server.status(rid) == "done", f"{rid}: {server.status(rid)}"
        assert matches_solo(server, rid), f"{rid} differs from its solo run"
    return (
        f"requests={len(requests)} batches={batches} N={n} "
        f"occupancy={server.slot_occupancy():.3f} bitwise=True"
    )


def phase_four_chip(sz: Sizes, devices) -> str:
    """Sharded BA flood per exchange mode vs the single-chip engine on
    ``devices[0]``; ``devices`` holds the four mesh devices."""
    import jax

    import p2p_gossip_tpu as pg
    from p2p_gossip_tpu.engine.sync import run_sync_sim
    from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim
    from p2p_gossip_tpu.parallel.mesh import make_mesh
    from p2p_gossip_tpu.runtime import native

    assert len({d.id for d in devices}) == 4, f"need 4 devices: {devices}"
    graph = native.native_barabasi_albert(sz.ba_nodes, sz.ba_m, seed=sz.seed)
    assert graph is not None, "native library not built (make -C native)"
    rng = np.random.default_rng(sz.seed)
    sched = pg.Schedule(
        graph.n,
        rng.integers(0, graph.n, sz.ba_shares).astype(np.int32),
        rng.integers(0, sz.gen_window, sz.ba_shares).astype(np.int32),
    )
    t0 = time.perf_counter()
    with jax.default_device(devices[0]):
        single = run_sync_sim(
            graph, sched, sz.horizon, chunk_size=sz.ba_shares
        )
    processed = single.totals()["processed"]
    assert processed == sz.ba_shares * graph.n, "single chip: partial coverage"
    say(f"  single chip ({devices[0]}): processed={processed} "
        f"in {time.perf_counter() - t0:.1f}s")
    mesh = make_mesh(4, 1, devices=devices)
    want_ids = sorted(d.id for d in devices)
    for mode in ("dense", "delta", "hub"):
        t0 = time.perf_counter()
        stats = run_sharded_sim(
            graph, sched, sz.horizon, mesh, chunk_size=sz.ba_shares,
            exchange=mode,
        )
        equal = stats.equal_counts(single)
        placement = stats.extra["placement"]
        say(f"  {mode}: equal_counts={equal} placement={placement} "
            f"exchange={stats.extra['exchange'].get('mode')} "
            f"in {time.perf_counter() - t0:.1f}s")
        assert equal, f"{mode}: sharded counters differ from single chip"
        assert sorted(d for d, _ in placement) == want_ids, (
            f"{mode}: shards not one per device: {placement}"
        )
        assert len({row for _, row in placement}) == 4, (
            f"{mode}: two shards hold the same rows: {placement}"
        )
    return (
        f"N={graph.n} edges={graph.num_edges} dmax={graph.max_degree} "
        f"shares={sz.ba_shares} dense/delta/hub bitwise equal"
    )


# --- driver -------------------------------------------------------------------


def run_phases(phases) -> bool:
    """Run (name, fn) phases in order, printing wall and compile time for
    each; True when all passed."""
    ok = True
    for name, fn in phases:
        c0, t0 = _compile_s[0], time.perf_counter()
        try:
            detail, status = fn(), "ok"
        except Exception as e:  # report every phase, then fail the run
            detail, status, ok = f"{type(e).__name__}: {e}", "FAILED", False
        wall = time.perf_counter() - t0
        say(f"phase {name}: {status} wall {wall:.1f}s compile "
            f"{_compile_s[0] - c0:.1f}s | {detail}")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the four-chip sharded phase",
    )
    args = ap.parse_args(argv)

    import jax
    from jax import monitoring

    devices = jax.devices()
    dev = devices[0]
    say(f"devices: {len(devices)} x {dev.platform} ({dev.device_kind})")
    if dev.platform != "tpu":
        say(f"FAILED: JAX found no TPU (platform {dev.platform!r})")
        return 1
    if len(devices) < args.chips:
        say(f"FAILED: --chips {args.chips} but {len(devices)} device(s)")
        return 1

    from p2p_gossip_tpu.utils.platform import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    monitoring.register_event_duration_secs_listener(_on_duration)
    sz = FULL
    if args.chips == 4:
        phases = [("four_chip", lambda: phase_four_chip(sz, devices[:4]))]
    else:
        phases = [
            ("headline", lambda: phase_headline(sz)),
            ("coverage", lambda: phase_coverage(sz, require_pallas=True)),
            ("parity", lambda: phase_parity(sz)),
            ("cli", lambda: phase_cli(sz)),
            ("serve", lambda: phase_serve(sz)),
        ]
    t0 = time.perf_counter()
    ok = run_phases(phases)
    say(f"total: {time.perf_counter() - t0:.1f}s, compile "
        f"{_compile_s[0]:.1f}s")
    if not ok:
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

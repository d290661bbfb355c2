"""Headline row — prints ONE JSON line on stdout naming its device.

Workload (BASELINE.json config 3): 100K-node Erdős–Rényi p=0.001 (mean
degree ~100), 8192 shares with uniformly sampled origins and generation
ticks over a 16-tick window, flooded to full coverage. Metric: node-updates/sec — one node-update is one
node processing one new share (the reference's per-node `processed` counter,
p2pnode.cc:241). The TPU synchronous tick engine is measured after one
warmup pass (compile excluded, as for any steady-state simulation);
``vs_baseline`` is the throughput ratio against the native C++ discrete-event
engine (the NS-3-role baseline, runtime/native.py) on the same graph and
share-generation process, which must deliver ~degree heap messages per
node-update.

The repository's benchmark is ``benchmark/run.py``, run per cell of
``BENCHMARK.json``; this row is the one-line summary. All diagnostics go
to stderr; stdout carries exactly one JSON line.
"""

import json
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def headline_workload(
    n: int, p: float, seed: int, n_shares: int, gen_window: int
):
    """(graph, schedule) of the headline flood: an ER graph from the C++
    builder and ``n_shares`` shares with uniform origins and generation
    ticks in ``[0, gen_window)``, both from ``seed``."""
    import p2p_gossip_tpu as pg
    from p2p_gossip_tpu.runtime import native

    graph = native.native_erdos_renyi(n, p, seed=seed)
    if graph is None:
        raise RuntimeError("native library not built (make -C native)")
    rng = np.random.default_rng(seed)
    sched = pg.Schedule(
        graph.n,
        rng.integers(0, graph.n, n_shares).astype(np.int32),
        rng.integers(0, gen_window, n_shares).astype(np.int32),
    )
    return graph, sched


def main() -> None:
    # A run meant for the chip that finds none exits non-zero; the host
    # CPU runs only when asked for (JAX_PLATFORMS=cpu), on the reduced
    # config under a CPU label.
    from p2p_gossip_tpu.utils.platform import (
        check_backend,
        enable_compile_cache,
    )

    cpu_run = check_backend() != "tpu"
    enable_compile_cache()
    import jax

    import p2p_gossip_tpu as pg
    from p2p_gossip_tpu.engine.sync import DeviceGraph, run_sync_sim
    from p2p_gossip_tpu.runtime import native

    if cpu_run:
        n, p, seed = 20_000, 0.001, 0
        n_shares, gen_window, horizon = 1024, 16, 64
        chunk_size = 1024
    else:
        n, p, seed = 100_000, 0.001, 0
        n_shares, gen_window, horizon = 8192, 16, 64
        # Swept on the real chip (2026-07): 8192-share chunks (W=256 words
        # keeps the row gather on wide 1KB rows) are the throughput peak —
        # ~1.2x over 4096; 16384 regresses. The degree block auto-resolves
        # to the swept TPU optimum (ops/ell.py TUNED_TPU_BLOCK).
        chunk_size = 8192

    log(f"devices: {jax.devices()}")
    t0 = time.perf_counter()
    graph, sched = headline_workload(n, p, seed, n_shares, gen_window)
    log(
        f"graph: N={graph.n} edges={graph.num_edges} dmax={graph.max_degree} "
        f"({time.perf_counter() - t0:.1f}s)"
    )
    dg = DeviceGraph.build(graph)

    t0 = time.perf_counter()
    warm = run_sync_sim(
        graph, sched, horizon, chunk_size=chunk_size, device_graph=dg
    )
    log(f"warmup (incl. compile): {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    stats = run_sync_sim(
        graph, sched, horizon, chunk_size=chunk_size, device_graph=dg
    )
    tpu_wall = time.perf_counter() - t0
    processed = stats.totals()["processed"]
    assert stats.totals() == warm.totals()
    assert processed == n_shares * graph.n, "flood did not reach full coverage"
    tpu_rate = processed / tpu_wall
    log(f"tpu: {processed} node-updates in {tpu_wall:.2f}s = {tpu_rate:.3g}/s")

    # Baseline: native C++ event engine, same graph + generation process,
    # scaled-down share count (per-share cost is linear; measured rate is
    # throughput per node-update either way).
    base_shares = 2
    base_sched = pg.Schedule(
        graph.n,
        sched.origins[:base_shares].copy(),
        sched.gen_ticks[:base_shares].copy(),
    )
    t0 = time.perf_counter()
    base = native.run_native_sim(graph, base_sched, horizon)
    base_wall = time.perf_counter() - t0
    base_processed = base.totals()["processed"]
    base_rate = base_processed / base_wall
    log(
        f"baseline (native-c++): {base_processed} node-updates, "
        f"{base.extra['events_processed']} events in {base_wall:.2f}s = "
        f"{base_rate:.3g}/s"
    )

    device = jax.devices()[0]
    label = "CPU - JAX_PLATFORMS=cpu" if cpu_run else "single chip"
    print(json.dumps({
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
        },
        "metric": (
            f"node-updates/sec ({n // 1000}K-node p={p:g} gossip flood, "
            f"{label})"
        ),
        "value": round(tpu_rate, 1),
        "unit": "node-updates/s",
        "vs_baseline": round(tpu_rate / base_rate, 2),
        "ticks": stats.extra["ticks_executed"],
    }))


if __name__ == "__main__":
    main()

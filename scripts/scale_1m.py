"""Million-node scale demonstration — the BASELINE.json north-star config.

Target (BASELINE.json): "1M-node p=0.001 gossip to 99% share coverage on
v5e-8 < 60 s". This script runs that workload on a SINGLE chip: a 1M-node
Erdős–Rényi p=0.001 graph (~500M undirected links, mean degree ~1000), 4096
shares flooded from random origins at t=0, per-share time-to-99%-coverage
reported — the reference's NS-3 event loop (p2pnetwork.cc:193) processes
~10-100K events/s and would need ~degree × N × shares ≈ 4×10^12 events for
the same experiment.

Usage: python scripts/scale_1m.py [--nodes 1000000] [--shares 4096]
       [--cache /tmp/er1m.npz]

Prints one JSON line on stdout (``metric``/``value``/``unit``, as bench.py
does); diagnostics on
stderr. The graph build is the slow host-side step (~3.5 min native C++ at
1M); pass --cache to reuse it across runs.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

# Self-locate the repo so the script runs from any cwd.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=1_000_000)
    ap.add_argument("--prob", type=float, default=0.001)
    ap.add_argument("--shares", type=int, default=4096)
    ap.add_argument(
        "--chunk", type=int, default=0,
        help="Shares per device pass (0 = auto). Auto sizes the chunk from "
        "the resident-HBM model (engine.sync.flood_resident_hbm_bytes) "
        "against P2P_HBM_BUDGET_GB (default 10 on TPU, unlimited "
        "elsewhere): the full 4096-share pass at 1M nodes models ~12.6 GB "
        "and crashed the 16 GB v5e worker (2026-07-31); 2048-share "
        "passes model ~8.8 GB. Chunks below 4096 shares underfill the "
        "TPU's 128-lane tile (slower gather per byte), so auto halves as "
        "little as possible.",
    )
    ap.add_argument(
        "--block", type=int, default=8,
        help="Degree-block for the gather-OR scan. The per-step gather "
        "intermediate is rows x block x words x 4 B — at N=1M / 4096 "
        "shares the 100K-swept block of 64 wants ~26 GB of HBM, so the "
        "default here stays at 8 (~4 GB).",
    )
    ap.add_argument("--horizon", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--cache", type=str, default="",
        help="npz path to cache the built graph across runs",
    )
    ap.add_argument(
        "--topology", choices=("er", "ba"), default="er",
        help="er = the north-star ER config; ba = BASELINE config 4's "
        "Barabasi-Albert scale-free topology (--baM edges per node)",
    )
    ap.add_argument("--baM", type=int, default=3)
    from p2p_gossip_tpu.utils.platform import add_cpu_arg, apply_cpu_arg

    add_cpu_arg(ap)
    ap.add_argument(
        "--mesh", type=str, default="",
        help="SxN (share-shards x node-shards): run the shard_map sharded "
        "engine over a device mesh instead of the single-device engine — "
        "the BASELINE v5e-8 configuration when 8 chips are attached",
    )
    args = ap.parse_args()
    apply_cpu_arg(args)

    import jax

    import p2p_gossip_tpu as pg
    from p2p_gossip_tpu.engine.sync import (
        DeviceGraph, run_flood_coverage, time_to_coverage,
    )
    from p2p_gossip_tpu.runtime import native

    from p2p_gossip_tpu.utils.platform import (
        check_backend,
        enable_compile_cache,
    )

    check_backend()
    enable_compile_cache()
    devices = jax.devices()

    # Cache handling: reusing a graph built for different flags would
    # attribute the benchmark to the wrong topology (same protection the
    # CLI's --graphFile has). The load/validate/build/save protocol and
    # fingerprint are shared with mesh_rehearsal.py via
    # load_or_build_graph_cache so the two scripts' caches interoperate.
    from p2p_gossip_tpu.models.topology import load_or_build_graph_cache

    def build():
        t0 = time.perf_counter()
        if args.topology == "ba":
            graph = native.native_barabasi_albert(
                args.nodes, m=args.baM, seed=args.seed
            )
            if graph is None:
                graph = pg.barabasi_albert(
                    args.nodes, m=args.baM, seed=args.seed
                )
            log(f"BA graph built: {time.perf_counter()-t0:.1f}s")
        else:
            graph = native.native_erdos_renyi(
                args.nodes, args.prob, seed=args.seed
            )
            if graph is None:
                graph = pg.erdos_renyi(args.nodes, args.prob, seed=args.seed)
            log(f"graph built: {time.perf_counter()-t0:.1f}s")
        return graph

    graph = load_or_build_graph_cache(
        args.cache, topology=args.topology, nodes=args.nodes, prob=args.prob,
        ba_m=args.baM, seed=args.seed, build=build, log=log,
    )
    log(
        f"N={graph.n} edges={graph.num_edges} dmax={graph.max_degree} "
        f"devices={devices}"
    )

    mesh = None
    if args.mesh:
        from p2p_gossip_tpu.parallel.mesh import make_mesh

        shares_shards, node_shards = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(node_shards, shares_shards)
        log(f"mesh: {shares_shards} share-shards x {node_shards} node-shards")
        dg = None
    else:
        t0 = time.perf_counter()
        dg = DeviceGraph.build(graph)
        log(f"device staging: {time.perf_counter()-t0:.1f}s")

    rng = np.random.default_rng(args.seed)
    origins = rng.integers(0, graph.n, args.shares).astype(np.int32)
    # pad: the explicit chunk_size handed to run_flood_coverage (None =
    # the engine's default MIN_CHUNK_SHARES lane pad); chunk: the origin
    # slice per pass. pad may exceed chunk (a 64-share pass padded to the
    # widest W that fits the budget).
    if args.chunk:
        chunk = max(32, min(args.chunk, args.shares))
        pad = chunk
        if mesh is not None:
            log(
                f"mesh: explicit --chunk forwards chunk_size={pad} to the "
                "sharded engine (per-pass resident relief, not just origin "
                "slicing)"
            )
    else:
        # Auto: fit the resident-HBM model into the device budget. Only
        # the single-chip TPU path is budgeted by default — the host has
        # RAM to spare and the mesh path divides rows across chips. None
        # = the default pad already fits (or budgeting is off): stage
        # exactly what the engine always staged.
        from p2p_gossip_tpu.engine.sync import (
            MIN_CHUNK_SHARES, auto_chunk_shares, flood_resident_hbm_bytes,
        )
        from p2p_gossip_tpu.ops.bitmask import num_words

        on_tpu = devices[0].platform == "tpu" and mesh is None
        # Mesh mode ignores the budget entirely (even an exported
        # P2P_HBM_BUDGET_GB): the sharded engine pads every pass to its
        # own chunk default, so a pad computed here would slice origins
        # and log a staged shape that never actually changes — per-chip
        # relief on the mesh comes from the node axis, not share width.
        budget = 0.0 if mesh is not None else float(
            os.environ.get("P2P_HBM_BUDGET_GB", "10" if on_tpu else "0")
        ) * 1e9
        pad = auto_chunk_shares(graph.degree, args.shares, args.block, budget)
        chunk = args.shares if pad is None else min(pad, args.shares)
        if pad is not None:
            default_w = num_words(max(args.shares, MIN_CHUNK_SHARES))
            pad_model = flood_resident_hbm_bytes(
                graph.degree, num_words(pad), args.block
            )
            log(
                f"auto-chunk: default pad models "
                f"{flood_resident_hbm_bytes(graph.degree, default_w, args.block) / 1e9:.1f} GB "
                f"resident > {budget / 1e9:.1f} GB budget; padding to "
                f"{pad} shares ({pad_model / 1e9:.1f} GB)"
                + (f", {chunk} origins per pass" if chunk < args.shares else "")
            )
            if pad_model > budget:
                # auto_chunk_shares floored at min_chunk without meeting
                # the budget (it warns too); say so here in the staging
                # log, or the plan above reads as budget-approved.
                log(
                    f"WARNING auto-chunk budget NOT satisfied: pad {pad} "
                    f"still models {pad_model / 1e9:.1f} GB "
                    f"> {budget / 1e9:.1f} GB (fixed ELL terms dominate); "
                    "proceeding with the least-bad staging."
                )

    def flood_all():
        """Shares are independent: chunked passes, counters additive."""
        processed = 0
        covs = []
        for lo in range(0, args.shares, chunk):
            if mesh is not None:
                from p2p_gossip_tpu.parallel.engine_sharded import (
                    run_sharded_flood_coverage,
                )

                stats, cov = run_sharded_flood_coverage(
                    graph, origins[lo : lo + chunk], args.horizon, mesh,
                    block=args.block,
                    # An explicit --chunk promises resident-footprint
                    # relief on the mesh path too (as mesh_rehearsal.py
                    # does): without forwarding it, each sliced pass is
                    # re-padded to the sharded engine's 4096-share
                    # default — extra passes, no memory relief
                    # (round-4 advisor finding).
                    **({"chunk_size": pad} if args.chunk else {}),
                )
            else:
                stats, cov = run_flood_coverage(
                    graph, origins[lo : lo + chunk], args.horizon,
                    device_graph=dg, block=args.block, chunk_size=pad,
                )
            processed += stats.totals()["processed"]
            covs.append(cov)
        return processed, np.concatenate(covs, axis=1)

    t0 = time.perf_counter()
    flood_all()
    warm_wall = time.perf_counter() - t0
    log(f"warmup (incl. compile): {warm_wall:.1f}s")

    t0 = time.perf_counter()
    processed, cov = flood_all()
    wall = time.perf_counter() - t0

    ttc = time_to_coverage(cov, graph.n, 0.99)
    full = processed == args.shares * graph.n
    log(
        f"flood: {processed} node-updates in {wall:.1f}s, full coverage: "
        f"{full}, ttc99 median {int(np.median(ttc))} / max {int(ttc.max())} "
        f"ticks"
    )
    print(
        json.dumps(
            {
                "metric": f"wall seconds to 99% coverage, {args.shares} "
                f"shares on a {graph.n}-node "
                + (
                    f"BA(m={args.baM}) graph"
                    if args.topology == "ba"
                    else f"p={args.prob:g} graph"
                )
                + (
                    f" ({args.mesh} mesh)" if args.mesh else " (single chip)"
                )
                + (
                    ""
                    if devices[0].platform == "tpu"
                    else f" [{devices[0].platform}]"
                ),
                "value": round(wall, 2),
                "unit": "s",
                "vs_baseline": round(60.0 / wall, 2),
            }
        )
    )


if __name__ == "__main__":
    main()

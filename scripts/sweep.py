"""Campaign sweep runner — one JSON line per grid cell on stdout, plus a
human-readable campaign report on stderr (so piping stdout to a file or
`jq` stays clean). Renders on host CPU with no TPU attached; on-chip runs
just inherit the default device.

    python scripts/sweep.py --sweep examples/sweep_small.json
    python scripts/sweep.py --example            # built-in small spec
    python scripts/sweep.py --sweep spec.json --out campaign.jsonl \
        --batch-size 8 --mesh-shards 4 --compare-sequential

``--compare-sequential`` additionally times the first cell of EACH
protocol's seed ensemble as N sequential solo engine runs and records
the one-jit campaign's end-to-end speedup (the compile-amortization +
batching win the subsystem exists to deliver). Each comparison is also
printed to stdout as its own JSON line (``{"compare_sequential": ...}``)
so a consumer that parses stdout captures it alongside the cell
records.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from p2p_gossip_tpu.utils.platform import check_backend, enable_compile_cache


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _compare_sequential(record: dict) -> dict | None:
    """Time the record's cell as sequential solo engine runs and report
    the campaign's end-to-end advantage, against BOTH baselines:

    - ``sequential_wall_s`` — one solo run per seed with the jit cache
      cleared between runs: the repo's documented status quo ("exactly
      one (topology, seed, config) per process"), each run paying its
      own compile. This is the compile-amortization comparison and the
      headline ``speedup_vs_sequential``.
    - ``warm_loop_wall_s`` — the same loop sharing one compile and one
      staged graph (the best a hand-rolled python loop achieves). The
      campaign's wall INCLUDES its own compile, so this ratio is the
      strictest same-process reading.

    Partnered protocols (pushpull/pull/pushk) compare against the sweep's
    pre-vmap sequential engine (`_run_partnered_cell`, verbatim) and also
    record ``campaign_warm_wall_s`` — a warm re-run of the vmapped cell
    (jit cache hot), the steady-state number a multi-cell sweep actually
    pays — plus its ``speedup_warm_vs_warm_loop``.
    """
    import jax
    import numpy as np

    from p2p_gossip_tpu.batch.sweep import _DEFAULTS, _build_graph, _cell_loss
    from p2p_gossip_tpu.engine.sync import DeviceGraph, run_flood_coverage
    from p2p_gossip_tpu.models.churn import random_churn
    from p2p_gossip_tpu.models.seeds import churn_stream_seed

    # The record's cell dict carries only the reported keys; restore the
    # sweep defaults for the ones it omits (churn knobs, baseSeed).
    cell = {**_DEFAULTS, **record["cell"]}
    cell["baseSeed"] = record["cell"].get("baseSeed", record["seeds"][0])
    graph = _build_graph(cell)
    loss = _cell_loss(cell)
    camp_wall = record["summary"]["wall_s"]

    if cell["protocol"] != "push":
        from p2p_gossip_tpu.batch.campaign import (
            flood_replicas,
            run_protocol_campaign,
        )
        from p2p_gossip_tpu.batch.sweep import _run_partnered_cell

        seeds = np.asarray(record["seeds"], dtype=np.int64)
        replicas = flood_replicas(
            graph, cell["shares"], seeds, cell["horizon"],
            churn_prob=cell["churnProb"],
            mean_down_ticks=cell["churnDowntimeTicks"],
            max_outages=cell["churnOutages"],
        )

        def campaign_once():
            run_protocol_campaign(
                graph, replicas, cell["horizon"], protocol=cell["protocol"],
                fanout=cell["fanout"], loss=loss,
            )

        # Prime the compile unconditionally: an earlier protocol's fresh
        # loop clear_caches()d the jit cache, so "cache hot from
        # run_cell" cannot be assumed.
        campaign_once()
        t0 = time.perf_counter()
        campaign_once()
        camp_warm = time.perf_counter() - t0
        # Warm loop: the pre-vmap sequential engine, one compile shared.
        _run_partnered_cell(cell, graph, seeds[:1], loss)
        t0 = time.perf_counter()
        _run_partnered_cell(cell, graph, seeds, loss)
        seq_warm = time.perf_counter() - t0
        # Fresh (per-run compile), sampled and extrapolated to keep the
        # comparison wall sane — labeled via sequential_sampled.
        sample = min(4, len(seeds))
        t0 = time.perf_counter()
        for s in seeds[:sample]:
            jax.clear_caches()
            _run_partnered_cell(cell, graph, np.asarray([s]), loss)
        seq_fresh = (time.perf_counter() - t0) * (len(seeds) / sample)
        return {
            "sequential_wall_s": round(seq_fresh, 4),
            "sequential_sampled": sample,
            "warm_loop_wall_s": round(seq_warm, 4),
            "campaign_wall_s": camp_wall,
            "campaign_warm_wall_s": round(camp_warm, 4),
            "speedup_vs_sequential": round(seq_fresh / max(camp_wall, 1e-9), 2),
            "speedup_vs_warm_loop": round(seq_warm / max(camp_wall, 1e-9), 2),
            "speedup_warm_vs_warm_loop": round(
                seq_warm / max(camp_warm, 1e-9), 2
            ),
            "replicas": len(record["seeds"]),
        }

    dg = DeviceGraph.build(graph)

    def solo(seed):
        origins = (
            np.random.default_rng(int(seed))
            .integers(0, graph.n, cell["shares"])
            .astype(np.int32)
        )
        churn = (
            random_churn(
                graph.n, cell["horizon"], outage_prob=cell["churnProb"],
                mean_down_ticks=10.0, seed=churn_stream_seed(seed),
            )
            if cell["churnProb"] > 0.0
            else None
        )
        run_flood_coverage(
            graph, origins, cell["horizon"], churn=churn, loss=loss,
            device_graph=dg,
        )

    t0 = time.perf_counter()
    for seed in record["seeds"]:
        jax.clear_caches()  # one-config-per-process semantics
        solo(seed)
    seq_fresh = time.perf_counter() - t0
    solo(record["seeds"][0])  # compile once outside the timed warm loop
    t0 = time.perf_counter()
    for seed in record["seeds"]:
        solo(seed)
    seq_warm = time.perf_counter() - t0

    return {
        "sequential_wall_s": round(seq_fresh, 4),
        "warm_loop_wall_s": round(seq_warm, 4),
        "campaign_wall_s": camp_wall,
        "speedup_vs_sequential": round(seq_fresh / max(camp_wall, 1e-9), 2),
        "speedup_vs_warm_loop": round(seq_warm / max(camp_wall, 1e-9), 2),
        "replicas": len(record["seeds"]),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sweep", type=str, default="", help="sweep spec JSON path")
    ap.add_argument(
        "--example", action="store_true",
        help="run the built-in small example spec (batch.sweep.example_spec)",
    )
    ap.add_argument(
        "--out", type=str, default="",
        help="also append the JSON records to this file (one line each)",
    )
    ap.add_argument(
        "--batch-size", type=int, default=0,
        help="static replica batch size (0 = all replicas in one batch)",
    )
    ap.add_argument(
        "--mesh-shards", type=int, default=0,
        help="shard the replica axis over this many devices (0 = no mesh)",
    )
    ap.add_argument(
        "--compare-sequential", action="store_true",
        help="time the first push cell as sequential solo runs and record "
        "the campaign speedup in its JSON",
    )
    ap.add_argument(
        "--no-report", action="store_true",
        help="suppress the human-readable report (JSON lines only)",
    )
    ap.add_argument(
        "--telemetry", type=str, default="",
        help="stream telemetry (per-cell spans + in-jit metric rings) to "
        "this JSONL file; also honors P2P_TELEMETRY (docs/OBSERVABILITY.md)",
    )
    args = ap.parse_args()

    if args.telemetry:
        from p2p_gossip_tpu import telemetry

        telemetry.configure(args.telemetry, rings=True)

    # Same contract as bench.py: no chip and no JAX_PLATFORMS=cpu means
    # exit non-zero, never a CPU sweep under a chip's name.
    check_backend()
    enable_compile_cache()
    if args.example:
        from p2p_gossip_tpu.batch.sweep import example_spec

        spec = example_spec()
    elif args.sweep:
        with open(args.sweep, encoding="utf-8") as f:
            spec = json.load(f)
    else:
        ap.error("pass --sweep <spec.json> or --example")

    from p2p_gossip_tpu.batch.stats import format_campaign_report
    from p2p_gossip_tpu.batch.sweep import run_sweep

    mesh = None
    if args.mesh_shards:
        from p2p_gossip_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(1, args.mesh_shards)
        log(f"mesh: replica axis over {args.mesh_shards} device(s)")

    out_f = open(args.out, "a", encoding="utf-8") if args.out else None

    def emit(record):
        line = json.dumps(record)
        print(line, flush=True)
        if out_f:
            out_f.write(line + "\n")
            out_f.flush()

    try:
        records = run_sweep(
            spec, batch_size=args.batch_size or None, mesh=mesh, emit=emit
        )
    finally:
        if out_f:
            out_f.close()

    if args.compare_sequential:
        compared: set[str] = set()
        for record in records:
            proto = record["cell"]["protocol"]
            if proto in compared:
                continue
            cmp = _compare_sequential(record)
            if cmp is None:
                continue
            compared.add(proto)
            record["compare_sequential"] = cmp
            log(
                f"compare-sequential [{proto}]: {cmp['replicas']} solo "
                f"runs {cmp['sequential_wall_s']:.2f}s (per-run compile; "
                f"warm loop {cmp['warm_loop_wall_s']:.2f}s) vs campaign "
                f"{cmp['campaign_wall_s']:.2f}s = "
                f"{cmp['speedup_vs_sequential']:.2f}x "
                f"({cmp['speedup_vs_warm_loop']:.2f}x vs warm loop)"
            )
            line = json.dumps(
                {"compare_sequential": {**cmp, "protocol": proto}}
            )
            # stdout too: a consumer of stdout JSON lines sees the
            # comparison beside the cell records.
            print(line, flush=True)
            if args.out:
                with open(args.out, "a", encoding="utf-8") as f:
                    f.write(line + "\n")

    if not args.no_report:
        log(format_campaign_report(records))
    return 0


if __name__ == "__main__":
    sys.exit(main())

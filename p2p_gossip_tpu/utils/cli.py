"""Command-line entry point.

Drop-in counterpart of the reference's `main` (p2pnetwork.cc:289-313): the
same four flags with the same defaults (`--numNodes 10 --connectionProb 0.3
--simTime 60 --Latency 5`), producing the same statistics report — plus a
`--backend` switch selecting the execution engine:

- ``tpu``    — synchronous tick engine on the default JAX device (engine.sync)
- ``event``  — Python discrete-event engine (engine.event)
- ``native`` — C++ discrete-event engine (runtime.native; falls back to
  ``event`` with a warning if the shared library isn't built)

and topology/protocol/latency extensions from the benchmark configs.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from p2p_gossip_tpu.models import topology as topo
from p2p_gossip_tpu.models.generation import poisson_schedule, uniform_renewal_schedule
from p2p_gossip_tpu.models.seeds import (
    churn_stream_seed,
    loss_stream_seed,
    replica_loss_seeds,
)
from p2p_gossip_tpu.models.latency import (
    lognormal_delays,
    serialization_delays,
)
from p2p_gossip_tpu.utils.stats import format_final_statistics


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="p2p_gossip_tpu",
        description="P2P gossip network simulation (TPU-native rebuild of the "
        "NS-3 reference).",
    )
    # Reference flags (p2pnetwork.cc:300-305), same names and defaults.
    p.add_argument("--numNodes", type=int, default=10, help="Number of nodes")
    p.add_argument(
        "--connectionProb", type=float, default=0.3,
        help="Probability of connection between nodes",
    )
    p.add_argument(
        "--simTime", type=float, default=60.0, help="Simulation time in seconds"
    )
    p.add_argument("--Latency", type=float, default=5.0, help="latency in ms")
    # Framework extensions.
    p.add_argument(
        "--backend", choices=("tpu", "sharded", "event", "native"),
        default="tpu",
        help="Execution engine (default: tpu; sharded = multi-chip "
        "shard_map engine over a device mesh)",
    )
    p.add_argument(
        "--meshNodes", type=int, default=0,
        help="Node-axis shards for --backend sharded (default: all devices)",
    )
    p.add_argument(
        "--meshShares", type=int, default=1,
        help="Share-axis shards for --backend sharded",
    )
    p.add_argument(
        "--ringMode", choices=("auto", "replicated", "sharded"),
        default="auto",
        help="History-ring layout for --backend sharded: replicated "
        "(full ring per chip, write-time all_gather) or sharded "
        "(per-chip rows, read-time slice all_gathers — fits rings the "
        "replicated layout can't). auto picks by delay model and size.",
    )
    p.add_argument(
        "--topology",
        choices=("er", "ba", "ring", "ws", "grid", "torus", "complete"),
        default="er",
        help="Topology family (er = reference's random topology; ws = "
        "Watts-Strogatz small-world; grid/torus = 2D lattice)",
    )
    p.add_argument(
        "--refParallelLinks", action="store_true",
        help="Model the reference's parallel-link REGISTER quirk: when a "
        "forced connectivity edge duplicates a sampled one, both endpoints "
        "list each other twice and every broadcast sends the duplicate an "
        "extra copy (dropped by its seen-set on arrival, so dynamics are "
        "unchanged). Reproduces the reference's inflated Total-sent and "
        "Peer-count numbers exactly (p2pnetwork.cc:83,129; p2pnode.cc:186); "
        "off by default because it models a reference bug, not a "
        "capability. er topology with the python graph builder only (the "
        "quirk depends on the builder's sampling stream).",
    )
    p.add_argument(
        "--graphBuilder", choices=("auto", "native", "python"),
        default="python",
        help="Graph construction path for er/ba: the C++ builder "
        "(runtime/native.py) or vectorized numpy. The two are "
        "distribution-identical but use different RNG streams, so a given "
        "--seed yields a different (equally valid) graph per builder — the "
        "python default keeps seeds reproducible on machines without the "
        "native library. Use native (or auto = native when built) for "
        "million-node graphs, where the python builder is impractically "
        "slow.",
    )
    p.add_argument("--baM", type=int, default=3, help="Edges per node for --topology ba")
    p.add_argument("--wsK", type=int, default=4, help="Lattice degree for --topology ws")
    p.add_argument(
        "--wsBeta", type=float, default=0.1,
        help="Rewiring probability for --topology ws",
    )
    p.add_argument(
        "--gridCols", type=int, default=0,
        help="Columns for --topology grid/torus (default: ~sqrt(numNodes))",
    )
    p.add_argument(
        "--protocol", choices=("push", "pushpull", "pull", "pushk"),
        default="push",
        help="Gossip protocol: push flooding (reference), push-pull "
        "anti-entropy, pull-only anti-entropy, or fanout-limited push — "
        "every protocol runs on every backend with identical counters",
    )
    p.add_argument(
        "--fanout", type=int, default=2,
        help="Random neighbor picks per round for --protocol pushk",
    )
    p.add_argument(
        "--genModel", choices=("uniform", "poisson"), default="uniform",
        help="Share generation model (uniform = reference's U(genLo, genHi))",
    )
    p.add_argument("--genLo", type=float, default=2.0)
    p.add_argument("--genHi", type=float, default=5.0)
    p.add_argument("--poissonRate", type=float, default=0.3, help="shares/s/node")
    p.add_argument(
        "--delayModel",
        choices=("constant", "lognormal", "serialization"),
        default="constant",
        help="Per-edge delay model: constant (reference default), "
        "lognormal (heterogeneous links), or serialization (latency + "
        "message size / link bandwidth, the reference's 5 Mbps "
        "point-to-point links)",
    )
    p.add_argument("--delayMeanTicks", type=float, default=2.0)
    p.add_argument("--delaySigma", type=float, default=0.5)
    p.add_argument("--delayMaxTicks", type=int, default=8)
    p.add_argument(
        "--shareBytes", type=int, default=30,
        help="Message size for --delayModel serialization (the reference "
        "share struct is ~30 bytes on the wire)",
    )
    p.add_argument(
        "--bandwidthMbps", type=float, default=5.0,
        help="Link bandwidth for --delayModel serialization "
        "(reference: 5 Mbps, p2pnetwork.cc:113)",
    )
    p.add_argument(
        "--linkQueueing", action="store_true",
        help="FIFO link queueing (the reference's NS-3 DataRate behavior, "
        "p2pnetwork.cc:113 — SURVEY deviation 5): concurrent messages on "
        "one link serialize through a per-link queue sized by "
        "--shareBytes / --bandwidthMbps, ON TOP of the propagation delay "
        "model. Per-message engines only (--backend event|native, "
        "--protocol push); incompatible with --delayModel serialization, "
        "which already charges the closed-form per-message serialization "
        "time (charging both would double it).",
    )
    p.add_argument(
        "--churnProb", type=float, default=0.0,
        help="Node churn: probability each node suffers a random outage "
        "(per outage slot; 0 disables churn). Down nodes lose arriving "
        "shares and skip generations.",
    )
    p.add_argument(
        "--lossProb", type=float, default=0.0,
        help="Per-link message loss probability: each directed link drops "
        "all messages crossing it during an erasure tick with this "
        "probability (0 disables). Deterministic in --seed; identical "
        "counters on every backend.",
    )
    p.add_argument(
        "--churnDowntime", type=float, default=5.0,
        help="Mean outage duration in seconds (geometric, min one tick)",
    )
    p.add_argument(
        "--churnOutages", type=int, default=1,
        help="Maximum outages per node over the run",
    )
    p.add_argument(
        "--connectAtTick", type=int, default=0,
        help="Socket warm-up window: peers connect at this tick "
        "(reference: 5s, p2pnetwork.cc:93-96); shares generated earlier "
        "stay with their origin and charge no sends. 0 = connected at t0",
    )
    p.add_argument(
        "--statsInterval", type=float, default=10.0,
        help="Periodic stats interval in seconds",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--chunkSize", type=int, default=4096,
        help="Shares per device pass (tpu/sharded backends). Values below "
        "4096 shrink every (N, W) device buffer proportionally — the "
        "memory-relief lever for huge N (see "
        "engine.sync.flood_resident_hbm_bytes for the fit arithmetic) at "
        "the price of underfilled 128-lane tiles.",
    )
    p.add_argument(
        "--degreeBlock", type=int, default=0,
        help="Degree-block for the gather-OR scan (tpu/sharded backends; "
        "0 = auto: the swept TPU optimum, conservative default on CPU)",
    )
    p.add_argument(
        "--anim", type=str, default="",
        help="Write a NetAnim-style XML trace to this path",
    )
    p.add_argument(
        "--animMessages", action="store_true",
        help="Embed per-message packet events in the --anim trace "
        "(EnablePacketMetadata analogue; event backend + push protocol "
        "only — the exact per-message path)",
    )
    p.add_argument(
        "--perNodeStats", action="store_true", default=None,
        help="Print per-node lines (default: on for N <= 1000)",
    )
    p.add_argument(
        "--checkpoint", type=str, default="",
        help="Checkpoint file: save progress between share chunks and resume "
        "an interrupted run from it (tpu and sharded backends)",
    )
    p.add_argument(
        "--checkpointEvery", type=int, default=1,
        help="Chunks between checkpoint writes (default 1)",
    )
    p.add_argument(
        "--floodCoverage", type=int, default=0, metavar="S",
        help="Coverage-time experiment instead of the gossip run: flood S "
        "shares from random origins at t=0 and report per-share "
        "time-to-99%%-coverage (tpu and sharded backends)",
    )
    p.add_argument(
        "--replicas", type=int, default=1, metavar="R",
        help="Monte-Carlo campaign: run R seed-ensemble replicas of the "
        "simulation inside one jit (batch/campaign.py) and report "
        "ensemble statistics (ttc percentiles, counter CIs) instead of "
        "one run's numbers. Replica r uses seed (--seed + r), including "
        "its own link-loss stream under --lossProb; every --protocol "
        "rides the vmapped engine (push floods; pushpull/pull/pushk "
        "batch the anti-entropy round loop). --backend tpu only; "
        "composes with --floodCoverage and --checkpoint",
    )
    p.add_argument(
        "--sweep", type=str, default="", metavar="SPEC.json",
        help="Run a campaign sweep from a JSON grid spec (batch/sweep.py: "
        "axes over protocol/p/lossProb/churnProb/fanout x seeds), "
        "emitting one JSON line per cell plus a campaign report. "
        "Ignores the single-run flags; see examples/sweep_small.json",
    )
    p.add_argument(
        "--coverageFraction", type=float, default=0.99,
        help="Coverage fraction reported by --floodCoverage (default 0.99)",
    )
    p.add_argument(
        "--log", type=str, default="",
        help="NS_LOG-style component log spec, e.g. "
        "'Engine.Event=debug:Engine.Sync=info' or '*=info' "
        "(also honors the P2P_LOG environment variable)",
    )
    p.add_argument(
        "--telemetry", type=str, default="", metavar="OUT.jsonl",
        help="Stream telemetry to this JSONL file: host spans "
        "(build/schedule/dispatch/d2h phases) plus in-jit per-tick "
        "metric rings harvested at chunk boundaries "
        "(docs/OBSERVABILITY.md). Also honors P2P_TELEMETRY=<path>. "
        "Off by default — disabled runs compile the exact "
        "uninstrumented kernels. Render with scripts/run_report.py",
    )
    p.add_argument(
        "--heartbeat", type=str, default="", metavar="PATH",
        help="Atomically rewrite this liveness file on every chunk "
        "boundary (telemetry/progress.py): last chunk index, ticks "
        "done, coverage %%, digest head. Works with telemetry off — "
        "watchers read the file's mtime age to tell a long run from a "
        "hang. Also honors P2P_HEARTBEAT=<path>",
    )
    p.add_argument(
        "--graphFile", type=str, default="",
        help="npz graph cache: load the topology from this file if it "
        "exists, else build per --topology and save it — graph builds "
        "dominate startup at million-node scale",
    )
    p.add_argument(
        "--json", action="store_true",
        help="Emit one machine-readable JSON line with config, totals, "
        "and wall time after the reference-format report",
    )
    return p


def _pull_credit_error(g, chunk_size, sched) -> str | None:
    """The pull protocol's uint32-credit precondition as a clean CLI
    error message (None when satisfiable) — every other CLI validation
    prints 'error: ...' and exits 2 rather than leaking a traceback."""
    from p2p_gossip_tpu.models.protocols import (
        PullCreditBoundError,
        _check_pull_credit_bound,
    )

    try:
        _check_pull_credit_bound(g, chunk_size, sched)
    except PullCreditBoundError as e:
        return str(e)
    return None


def _run_flood_coverage_cli(args, g, horizon, delays, churn, loss) -> int:
    """Flood coverage-time experiment (BASELINE.json headline config): S
    shares flooded from random origins at t=0, per-share
    time-to-``coverageFraction`` reported in ticks and seconds. Runs on
    the single-device sync engine or, with --backend sharded, over the
    device mesh (identical coverage values). With --protocol pushpull or
    pushk the same experiment runs under that protocol instead of
    flooding — the direct CLI comparison of the protocols'
    coverage-time/redundancy trade-off."""
    from p2p_gossip_tpu import telemetry
    from p2p_gossip_tpu.engine.sync import run_flood_coverage, time_to_coverage

    tick_dt = args.Latency / 1000.0
    rng = np.random.default_rng(args.seed)
    origins = rng.integers(0, g.n, args.floodCoverage).astype(np.int32)
    t0 = time.perf_counter()
    _sim_span = telemetry.span(
        "simulate", backend=args.backend, protocol=args.protocol,
        experiment="flood_coverage",
    )
    _sim_span.__enter__()
    mesh = None
    if args.backend == "sharded":
        from p2p_gossip_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(args.meshNodes or None, args.meshShares)
        print(
            f"Mesh: {mesh.shape['shares']} share-shards x "
            f"{mesh.shape['nodes']} node-shards"
        )
    if args.protocol in ("pushpull", "pull", "pushk"):
        from p2p_gossip_tpu.models.generation import Schedule

        sched = Schedule(g.n, origins, np.zeros(len(origins), dtype=np.int32))
        if args.protocol == "pull":
            err = _pull_credit_error(g, args.chunkSize, sched)
            if err is not None:
                print(f"error: {err}", file=sys.stderr)
                return 2
        kw = dict(fanout=args.fanout) if args.protocol == "pushk" else {}
        if mesh is not None:
            from p2p_gossip_tpu.parallel.protocols_sharded import (
                run_sharded_partnered_sim,
            )

            stats, coverage = run_sharded_partnered_sim(
                g, sched, horizon, mesh, protocol=args.protocol,
                ell_delays=delays, seed=args.seed,
                chunk_size=args.chunkSize, churn=churn, loss=loss,
                record_coverage=True, ring_mode=args.ringMode, **kw,
            )
        else:
            from p2p_gossip_tpu.models.protocols import (
                run_pushk_sim,
                run_pushpull_sim,
            )

            if args.protocol == "pushk":
                run = run_pushk_sim
            else:
                run = run_pushpull_sim
                kw = dict(mode=args.protocol)
            stats, coverage = run(
                g, sched, horizon, ell_delays=delays, seed=args.seed,
                chunk_size=args.chunkSize, churn=churn, loss=loss,
                record_coverage=True, **kw,
            )
    elif mesh is not None:
        from p2p_gossip_tpu.parallel.engine_sharded import (
            run_sharded_flood_coverage,
        )

        stats, coverage = run_sharded_flood_coverage(
            g, origins, horizon, mesh, ell_delays=delays,
            chunk_size=args.chunkSize, block=args.degreeBlock or None,
            churn=churn, loss=loss, ring_mode=args.ringMode,
        )
    else:
        stats, coverage = run_flood_coverage(
            g, origins, horizon, ell_delays=delays,
            block=args.degreeBlock or None, churn=churn, loss=loss,
        )
    _sim_span.__exit__(None, None, None)
    wall = time.perf_counter() - t0
    telemetry.emit_jit_cache_counters()
    ttc = time_to_coverage(coverage, g.n, args.coverageFraction)
    reached = ttc >= 0
    print(
        f"=== {'Flood' if args.protocol == 'push' else args.protocol} "
        f"Coverage ({args.floodCoverage} shares, target "
        f"{args.coverageFraction:.0%} of {g.n} nodes) ==="
    )
    if reached.any():
        ticks = ttc[reached]
        print(
            f"Shares reaching target: {int(reached.sum())}/{len(ttc)}\n"
            f"Time to {args.coverageFraction:.0%} coverage: "
            f"min {ticks.min()} / median {int(np.median(ticks))} / "
            f"max {ticks.max()} ticks "
            f"({ticks.min() * tick_dt:g}s / {np.median(ticks) * tick_dt:g}s / "
            f"{ticks.max() * tick_dt:g}s)"
        )
    else:
        print(f"Shares reaching target: 0/{len(ttc)} within {horizon} ticks")
    print(
        f"Final coverage: min {coverage[-1].min()} / "
        f"mean {coverage[-1].mean():.1f} / max {coverage[-1].max()} nodes"
    )
    from p2p_gossip_tpu.utils.analysis import (
        format_propagation_report,
        message_redundancy,
        propagation_latency,
    )

    report = propagation_latency(coverage, g.n)
    print(format_propagation_report(report, tick_ms=args.Latency), end="")
    red = message_redundancy(stats)
    spd = red["sends_per_delivery"]  # None when nothing was delivered
    print(
        f"Redundancy: {'n/a' if spd is None else f'{spd:.2f}'} sends per "
        f"delivery ({red['wasted_fraction']:.1%} duplicate or lost)"
    )
    print(
        f"Simulated {horizon} ticks in {wall:.3f}s wall "
        f"({stats.totals()['processed'] / max(wall, 1e-9):.3g} node-updates/s)"
    )
    if args.json:
        import json

        frac = args.coverageFraction
        print(
            json.dumps(
                {
                    "config": {
                        "numNodes": g.n,
                        "edges": int(g.num_edges),
                        "protocol": args.protocol,
                        "backend": args.backend,
                        "shares": int(args.floodCoverage),
                        "coverageFraction": frac,
                        "Latency": args.Latency,
                        "seed": args.seed,
                    },
                    "reached": int(reached.sum()),
                    "ttc_ticks": {
                        "min": int(ttc[reached].min()),
                        "median": float(np.median(ttc[reached])),
                        "max": int(ttc[reached].max()),
                    }
                    if reached.any()
                    else None,
                    "final_coverage": {
                        "min": int(coverage[-1].min()),
                        "mean": float(coverage[-1].mean()),
                        "max": int(coverage[-1].max()),
                    },
                    "sends_per_delivery": spd,
                    "wasted_fraction": red["wasted_fraction"],
                    "wall_s": round(wall, 4),
                }
            )
        )
    return 0


def _run_campaign_cli(args, g, horizon, delays, loss) -> int:
    """--replicas R: a seed-ensemble campaign in one jit. Replica r's
    schedule, churn AND link-loss stream derive from seed (--seed + r)
    with the solo CLI's stream offsets (models/seeds.py), so
    any single replica is bitwise-reproducible as a solo ``--seed
    (--seed + r)`` run. Every protocol batches: push through the flood
    campaign kernels, pushpull/pull/pushk through
    ``run_protocol_campaign``. Reports ensemble statistics — the
    distribution a single-seed run cannot show."""
    import json

    from p2p_gossip_tpu import telemetry
    from p2p_gossip_tpu.batch.campaign import (
        flood_replicas,
        gossip_replicas,
        run_coverage_campaign,
        run_gossip_campaign,
        run_protocol_campaign,
    )
    from p2p_gossip_tpu.batch.stats import ensemble_summary
    from p2p_gossip_tpu.models.protocols import PullCreditBoundError

    seeds = [args.seed + r for r in range(args.replicas)]
    # Per-replica erasure streams: the same loss-stream offset the solo
    # CLI applies to --seed, one per replica seed (models/seeds.py).
    loss_seeds = replica_loss_seeds(seeds) if loss is not None else None
    ckpt_kw = dict(
        checkpoint_path=args.checkpoint or None,
        checkpoint_every=args.checkpointEvery,
    )
    churn_kw = dict(
        churn_prob=args.churnProb,
        mean_down_ticks=max(args.churnDowntime / (args.Latency / 1000.0), 1.0),
        max_outages=args.churnOutages,
    )
    partnered = args.protocol in ("pushpull", "pull", "pushk")
    with telemetry.span("replicas", count=args.replicas):
        if args.floodCoverage:
            replicas = flood_replicas(
                g, args.floodCoverage, seeds, horizon, **churn_kw
            )
        else:
            replicas = gossip_replicas(
                g, args.simTime, args.Latency / 1000.0, seeds, horizon,
                gen_lo=args.genLo, gen_hi=args.genHi, **churn_kw,
            )
    _sim_span = telemetry.span(
        "simulate", backend=args.backend, protocol=args.protocol,
        experiment="campaign",
    )
    _sim_span.__enter__()
    if partnered:
        try:
            result = run_protocol_campaign(
                g, replicas, horizon, protocol=args.protocol,
                fanout=args.fanout, ell_delays=delays, loss=loss,
                loss_seeds=loss_seeds,
                record_coverage=bool(args.floodCoverage), **ckpt_kw,
            )
        except PullCreditBoundError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    elif args.floodCoverage:
        result = run_coverage_campaign(
            g, replicas, horizon, ell_delays=delays, loss=loss,
            loss_seeds=loss_seeds, block=args.degreeBlock or None, **ckpt_kw,
        )
    else:
        result = run_gossip_campaign(
            g, replicas, horizon, ell_delays=delays, loss=loss,
            loss_seeds=loss_seeds, chunk_size=args.chunkSize,
            block=args.degreeBlock or None, **ckpt_kw,
        )
    _sim_span.__exit__(None, None, None)
    telemetry.emit_jit_cache_counters()
    summary = ensemble_summary(result, args.coverageFraction)

    kind = (
        f"{args.floodCoverage} flood shares"
        if args.floodCoverage
        else "gossip schedule"
    )
    print(
        f"=== Campaign: {args.replicas} replicas x {kind}, {g.n} nodes ==="
    )
    ttc = summary.get("ttc")
    if ttc is not None:
        ticks = ttc.get("ticks")
        if ticks:
            tick_ms = args.Latency
            print(
                f"Time to {ttc['fraction']:.0%} coverage: mean "
                f"{ticks['mean']:.1f} / p50 {ticks['p50']:g} / p95 "
                f"{ticks['p95']:g} / p99 {ticks['p99']:g} ticks "
                f"(p99 {ticks['p99'] * tick_ms:g} ms); "
                f"{ttc['reached'] * 100:.1f}% of replica-shares reached"
            )
        else:
            print(
                f"Time to {ttc['fraction']:.0%} coverage: no replica-share "
                f"reached within {horizon} ticks"
            )
    for name in ("processed", "received", "sent"):
        c = summary["counters"][name]
        ci = c["ci95"]
        print(
            f"Total {name} per replica: mean {c['mean']:.1f}"
            + (f" (95% CI {ci[0]:.1f}-{ci[1]:.1f})" if ci else "")
        )
    red = summary["redundancy"]["sends_per_delivery"]
    if red:
        print(
            f"Redundancy: {red['mean']:.2f} sends per delivery "
            f"(p95 {red['p95']:.2f} across replicas)"
        )
    print(
        f"Campaign wall {result.wall_s:.3f}s (one jit, batch "
        f"{result.batch_size}; "
        f"{summary['counters']['processed']['mean'] * args.replicas / max(result.wall_s, 1e-9):.3g} "
        "node-updates/s)"
    )
    if args.json:
        print(
            json.dumps(
                {
                    "config": {
                        "numNodes": g.n,
                        "edges": int(g.num_edges),
                        "protocol": args.protocol,
                        "backend": args.backend,
                        "replicas": args.replicas,
                        "floodCoverage": args.floodCoverage,
                        "lossProb": args.lossProb,
                        "churnProb": args.churnProb,
                        "Latency": args.Latency,
                        "seed": args.seed,
                    },
                    "summary": summary,
                }
            )
        )
    return 0


def run(argv=None) -> int:
    args = build_parser().parse_args(argv)
    tick_dt = args.Latency / 1000.0
    from p2p_gossip_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    from p2p_gossip_tpu.utils import logging as p2plog

    if args.log:
        try:
            p2plog.configure(args.log)
        except ValueError as e:
            print(f"error: --log: {e}", file=sys.stderr)
            return 2
    p2plog.set_time_resolution(tick_dt)
    from p2p_gossip_tpu import telemetry

    if args.telemetry:
        # Explicit flag wins over P2P_TELEMETRY (sink.configure replaces
        # any env-initialized stream).
        try:
            telemetry.configure(args.telemetry, rings=True)
        except OSError as e:
            print(f"error: --telemetry: {e}", file=sys.stderr)
            return 2
    if args.heartbeat:
        # Explicit flag wins over P2P_HEARTBEAT (same precedence rule as
        # --telemetry above).
        telemetry.configure_heartbeat(args.heartbeat)
    horizon = int(round(args.simTime / tick_dt))

    if args.sweep:
        import json
        import os

        if not os.path.exists(args.sweep):
            print(f"error: --sweep {args.sweep} not found", file=sys.stderr)
            return 2
        with open(args.sweep, encoding="utf-8") as f:
            try:
                spec = json.load(f)
            except json.JSONDecodeError as e:
                print(f"error: --sweep {args.sweep}: {e}", file=sys.stderr)
                return 2
        from p2p_gossip_tpu.batch.stats import format_campaign_report
        from p2p_gossip_tpu.batch.sweep import run_sweep

        try:
            records = run_sweep(
                spec, emit=lambda rec: print(json.dumps(rec), flush=True)
            )
        except ValueError as e:
            print(f"error: --sweep: {e}", file=sys.stderr)
            return 2
        print(format_campaign_report(records), end="", file=sys.stderr)
        return 0

    # Fingerprint of every flag that determines the built topology: a cache
    # hit with different parameters is an error, not a silent reuse (same
    # protection the checkpoints get).
    from p2p_gossip_tpu.utils.checkpoint import fingerprint as _fp

    graph_fp = _fp(
        "topology", args.topology, args.numNodes, args.connectionProb,
        args.seed, args.baM, args.wsK, args.wsBeta, args.gridCols,
        args.graphBuilder,
    )
    loaded_graph = None
    if args.graphFile:
        import os

        if os.path.exists(args.graphFile):
            from p2p_gossip_tpu.models.topology import load_graph_cache

            try:
                loaded_graph, cached_fp = load_graph_cache(args.graphFile)
            except ValueError as e:
                print(f"error: --graphFile {e}", file=sys.stderr)
                return 2
            if cached_fp is not None and cached_fp != graph_fp:
                print(
                    f"error: --graphFile {args.graphFile} was built with "
                    "different topology parameters; delete it or match the "
                    "original flags",
                    file=sys.stderr,
                )
                return 2
            if loaded_graph.n != args.numNodes:
                print(
                    f"error: --graphFile holds a {loaded_graph.n}-node graph, "
                    f"--numNodes is {args.numNodes}",
                    file=sys.stderr,
                )
                return 2

    if args.refParallelLinks and (
        args.topology != "er" or loaded_graph is not None
    ):
        print(
            "error: --refParallelLinks needs a freshly built er topology "
            "(the quirk depends on which forced edges duplicate sampled "
            "ones in the builder's own sampling stream)",
            file=sys.stderr,
        )
        return 2
    if args.refParallelLinks and args.graphBuilder == "native":
        print(
            "error: --refParallelLinks requires --graphBuilder python "
            "(the native builder uses a different RNG stream)",
            file=sys.stderr,
        )
        return 2
    if args.refParallelLinks and args.protocol != "push":
        print(
            "error: --refParallelLinks models the reference's broadcast "
            "quirk; it only applies to --protocol push (flood)",
            file=sys.stderr,
        )
        return 2
    if args.refParallelLinks and args.connectAtTick:
        # In the reference, shares generated before makeconnections send
        # zero copies (empty peer list) — but with_parallel_links charges
        # (generated+forwarded)*extra for EVERY broadcast, so the combined
        # flags would overcount Total sent by extra * (warm-up-generated
        # shares on doubled nodes) and break check_conservation's fan math.
        print(
            "error: --refParallelLinks cannot be combined with "
            "--connectAtTick (the quirk's reporting transform charges "
            "extra sends for warm-up broadcasts that the reference never "
            "sends)",
            file=sys.stderr,
        )
        return 2

    use_native_builder = False
    if (
        loaded_graph is None
        and args.graphBuilder != "python"
        and args.topology in ("er", "ba")
        and not args.refParallelLinks
    ):
        from p2p_gossip_tpu.runtime import native as native_rt

        use_native_builder = native_rt.available()
        if args.graphBuilder == "native" and not use_native_builder:
            print(
                "error: --graphBuilder native: the native library is not "
                "built (run `make -C native`)",
                file=sys.stderr,
            )
            return 2
    elif args.graphBuilder == "native" and loaded_graph is None:
        # A warm --graphFile cache needs no builder at all.
        print(
            f"error: --graphBuilder native has no {args.topology} builder "
            "(only er/ba)",
            file=sys.stderr,
        )
        return 2

    parallel_extra = None
    # Explicit enter/exit rather than a with-block: the builder chain
    # below has error-returns that should not re-indent under a context.
    _graph_span = telemetry.span("build_graph", topology=args.topology)
    _graph_span.__enter__()
    if loaded_graph is not None:
        g = loaded_graph
    elif args.topology == "er":
        if use_native_builder:
            g = native_rt.native_erdos_renyi(
                args.numNodes, args.connectionProb, seed=args.seed
            )
        elif args.refParallelLinks:
            g, parallel_extra = topo.erdos_renyi(
                args.numNodes, args.connectionProb, seed=args.seed,
                return_parallel_extra=True,
            )
        else:
            g = topo.erdos_renyi(
                args.numNodes, args.connectionProb, seed=args.seed
            )
    elif args.topology == "ba":
        g = (
            native_rt.native_barabasi_albert(
                args.numNodes, m=args.baM, seed=args.seed
            )
            if use_native_builder
            else topo.barabasi_albert(args.numNodes, m=args.baM, seed=args.seed)
        )
    elif args.topology == "ws":
        g = topo.watts_strogatz(
            args.numNodes, k=args.wsK, beta=args.wsBeta, seed=args.seed
        )
    elif args.topology in ("grid", "torus"):
        if args.gridCols:
            cols = args.gridCols
        else:
            # Most-square factorization: first divisor at or below sqrt(n).
            cols = next(
                c for c in range(int(np.sqrt(args.numNodes)), 0, -1)
                if args.numNodes % c == 0
            )
        rows = -(-args.numNodes // cols)
        if rows * cols != args.numNodes:
            print(
                f"error: --numNodes {args.numNodes} is not rows*cols "
                f"(cols={cols}); pass --gridCols",
                file=sys.stderr,
            )
            return 2
        g = topo.grid_graph(rows, cols, torus=args.topology == "torus")
    elif args.topology == "complete":
        g = topo.complete_graph(args.numNodes)
    else:
        g = topo.ring_graph(args.numNodes)

    if args.graphFile and loaded_graph is None:
        from p2p_gossip_tpu.models.topology import save_graph_cache

        save_graph_cache(args.graphFile, g, fp=graph_fp)
    _graph_span.__exit__(None, None, None)

    with telemetry.span("schedule", model=args.genModel):
        if args.genModel == "uniform":
            sched = uniform_renewal_schedule(
                g.n, args.simTime, tick_dt, args.genLo, args.genHi,
                seed=args.seed,
            )
        else:
            sched = poisson_schedule(
                g.n, args.simTime, tick_dt, args.poissonRate, seed=args.seed
            )

    delays = None
    if args.delayModel == "lognormal":
        delays = lognormal_delays(
            g, args.delayMeanTicks, args.delaySigma, args.delayMaxTicks,
            seed=args.seed,
        )
    elif args.delayModel == "serialization":
        if args.shareBytes < 0 or args.bandwidthMbps <= 0:
            print(
                "error: --shareBytes must be >= 0 and --bandwidthMbps > 0",
                file=sys.stderr,
            )
            return 2
        delays = serialization_delays(
            g, message_bytes=args.shareBytes,
            bandwidth_mbps=args.bandwidthMbps, tick_dt=tick_dt,
        )
        # Surface the quantization: users picking this model should see
        # what the latency+serialization time rounded to in whole ticks.
        print(
            f"serialization delay model: {args.shareBytes} B at "
            f"{args.bandwidthMbps:g} Mbps on {args.Latency:g} ms latency "
            f"-> {int(delays.max())} tick(s)/hop",
            file=sys.stderr,
        )

    fifo = None
    if args.linkQueueing:
        # The queue's state is data-dependent (whoever transmitted last
        # holds the link), which only the per-message engines can track;
        # the tick engines model serialization via the closed form
        # (--delayModel serialization), exact for uncontended traffic.
        if args.backend not in ("event", "native"):
            print(
                "error: --linkQueueing requires --backend event|native "
                "(per-message engines; tick engines model serialization "
                "via --delayModel serialization)",
                file=sys.stderr,
            )
            return 2
        if args.protocol != "push":
            print(
                "error: --linkQueueing supports --protocol push only "
                "(the partnered protocols are round-based digests, not "
                "per-message transmissions)",
                file=sys.stderr,
            )
            return 2
        if args.delayModel == "serialization":
            print(
                "error: --linkQueueing is incompatible with --delayModel "
                "serialization (it would charge the serialization time "
                "twice); use constant or lognormal for the propagation "
                "part",
                file=sys.stderr,
            )
            return 2
        if args.shareBytes < 0 or args.bandwidthMbps <= 0:
            print(
                "error: --shareBytes must be >= 0 and --bandwidthMbps > 0",
                file=sys.stderr,
            )
            return 2
        from p2p_gossip_tpu.models.latency import fifo_link_model

        fifo = fifo_link_model(
            message_bytes=args.shareBytes,
            bandwidth_mbps=args.bandwidthMbps, tick_dt=tick_dt,
        )
        print(
            f"FIFO link queueing: {args.shareBytes} B at "
            f"{args.bandwidthMbps:g} Mbps -> {fifo.ser_micro} micro-ticks "
            "serialization per message per link",
            file=sys.stderr,
        )

    if args.degreeBlock < 0:
        print("error: --degreeBlock must be >= 0", file=sys.stderr)
        return 2
    # Validate mesh flags before any path that builds a mesh (the
    # --floodCoverage branch returns early).
    if args.meshNodes < 0 or args.meshShares < 1:
        print(
            "error: --meshNodes must be >= 0 and --meshShares >= 1",
            file=sys.stderr,
        )
        return 2

    loss = None
    if not 0.0 <= args.lossProb <= 1.0:
        print(
            f"error: --lossProb must be in [0, 1], got {args.lossProb:g}",
            file=sys.stderr,
        )
        return 2
    if args.lossProb > 0.0:
        from p2p_gossip_tpu.models.linkloss import LinkLossModel

        # Offset seed: independent of the topology/schedule/churn streams.
        loss = LinkLossModel(args.lossProb, seed=loss_stream_seed(args.seed))

    churn = None
    if not 0.0 <= args.churnProb <= 1.0:
        print(
            f"error: --churnProb must be in [0, 1], got {args.churnProb:g}",
            file=sys.stderr,
        )
        return 2
    if args.churnProb > 0.0:
        from p2p_gossip_tpu.models.churn import random_churn

        # Offset seed so the churn stream is independent of the topology and
        # schedule streams seeded with args.seed.
        churn = random_churn(
            g.n, horizon,
            outage_prob=args.churnProb,
            mean_down_ticks=max(args.churnDowntime / tick_dt, 1.0),
            max_outages=args.churnOutages,
            seed=churn_stream_seed(args.seed),
        )

    if loaded_graph is not None:
        builder_note = ", graph-builder=cache"
    elif args.topology in ("er", "ba"):
        builder_note = (
            f", graph-builder={'native' if use_native_builder else 'python'}"
        )
    else:
        builder_note = ""
    print(
        f"Starting gossip network simulation: {g.n} nodes, "
        f"{g.num_edges} links, {sched.num_shares} shares scheduled, "
        f"{horizon} ticks ({args.simTime:g}s at {args.Latency:g}ms), "
        f"backend={args.backend}{builder_note}"
    )
    if churn is not None:
        n_outages = int((churn.down_end > churn.down_start).sum())
        print(
            f"Churn enabled: {n_outages} outages scheduled across {g.n} "
            f"nodes (mean downtime {args.churnDowntime:g}s)"
        )
    interval_ticks = int(round(args.statsInterval / tick_dt))
    snapshot_ticks = (
        list(range(interval_ticks, horizon, interval_ticks))
        if interval_ticks > 0
        else []
    )

    if args.protocol == "pushk" and args.fanout < 1:
        # Validated before the --floodCoverage early return: that path
        # runs pushk too.
        print("error: --fanout must be >= 1", file=sys.stderr)
        return 2
    # Validated before the --floodCoverage early return too — these flags
    # must be rejected there, not silently ignored.
    if args.connectAtTick < 0:
        print(
            f"error: --connectAtTick must be >= 0, got {args.connectAtTick}",
            file=sys.stderr,
        )
        return 2
    if args.connectAtTick and (args.protocol != "push" or args.floodCoverage):
        print(
            "error: --connectAtTick supports only --protocol push without "
            "--floodCoverage (the warm-up window is a flood-gossip "
            "reference semantic)",
            file=sys.stderr,
        )
        return 2
    if args.animMessages and not (
        args.anim
        and args.backend == "event"
        and args.protocol == "push"
        and not args.floodCoverage
    ):
        print(
            "error: --animMessages requires --anim with --backend event "
            "and --protocol push (per-message recording lives in the "
            "exact event path)",
            file=sys.stderr,
        )
        return 2

    if args.replicas < 1:
        print(
            f"error: --replicas must be >= 1, got {args.replicas}",
            file=sys.stderr,
        )
        return 2
    if args.replicas > 1:
        # The campaign engine vmaps the single-device engines: the sync
        # flood path for --protocol push, the anti-entropy round scan for
        # pushpull/pull/pushk (batch/campaign.py). Other backends run
        # ensembles via the sweep runner (--sweep).
        if args.backend != "tpu":
            print(
                "error: --replicas requires --backend tpu (the vmapped "
                "campaign engine; use --sweep for other-backend ensembles)",
                file=sys.stderr,
            )
            return 2
        if args.anim:
            print(
                "error: --replicas does not support --anim "
                "(per-replica artifacts are a sweep-runner concern)",
                file=sys.stderr,
            )
            return 2
        if not args.floodCoverage and args.genModel != "uniform":
            print(
                "error: --replicas without --floodCoverage supports "
                "--genModel uniform only",
                file=sys.stderr,
            )
            return 2

    if args.floodCoverage:
        if args.floodCoverage < 0:
            print(
                f"error: --floodCoverage must be positive, got "
                f"{args.floodCoverage}",
                file=sys.stderr,
            )
            return 2
        if args.backend not in ("tpu", "sharded"):
            print(
                "error: --floodCoverage requires --backend tpu|sharded",
                file=sys.stderr,
            )
            return 2
        if not 0.0 < args.coverageFraction <= 1.0:
            print(
                "error: --coverageFraction must be in (0, 1], got "
                f"{args.coverageFraction:g}",
                file=sys.stderr,
            )
            return 2
        if args.replicas > 1:
            return _run_campaign_cli(args, g, horizon, delays, loss)
        return _run_flood_coverage_cli(args, g, horizon, delays, churn, loss)

    if (
        args.protocol in ("pushpull", "pull", "pushk")
        and args.backend == "event"
        and args.delayModel != "constant"
    ):
        print(
            f"error: --protocol {args.protocol} --backend event supports "
            "only --delayModel constant (the numpy oracle is the "
            "one-tick-delay specification)",
            file=sys.stderr,
        )
        return 2
    if args.checkpoint and args.backend not in ("tpu", "sharded"):
        print(
            "error: --checkpoint requires --backend tpu|sharded",
            file=sys.stderr,
        )
        return 2
    if args.checkpointEvery < 1:
        print("error: --checkpointEvery must be >= 1", file=sys.stderr)
        return 2
    if args.protocol == "pull" and args.backend in ("tpu", "sharded"):
        # Only the bitmask engines carry the uint32 credit accumulator;
        # event/native accumulate sent in int64 and have no such bound.
        err = _pull_credit_error(g, args.chunkSize, sched)
        if err is not None:
            print(f"error: {err}", file=sys.stderr)
            return 2

    if args.replicas > 1:
        return _run_campaign_cli(args, g, horizon, delays, loss)

    t0 = time.perf_counter()
    _sim_span = telemetry.span(
        "simulate", backend=args.backend, protocol=args.protocol
    )
    _sim_span.__enter__()
    if args.protocol in ("pushpull", "pull", "pushk") and args.backend == "sharded":
        from p2p_gossip_tpu.parallel.mesh import make_mesh
        from p2p_gossip_tpu.parallel.protocols_sharded import (
            run_sharded_partnered_sim,
        )

        mesh = make_mesh(args.meshNodes or None, args.meshShares)
        print(
            f"Mesh: {mesh.shape['shares']} share-shards x "
            f"{mesh.shape['nodes']} node-shards"
        )
        stats = run_sharded_partnered_sim(
            g, sched, horizon, mesh, protocol=args.protocol,
            fanout=args.fanout, ell_delays=delays, seed=args.seed,
            chunk_size=args.chunkSize, churn=churn, loss=loss,
            checkpoint_path=args.checkpoint or None,
            checkpoint_every=args.checkpointEvery,
            ring_mode=args.ringMode,
        )
    elif args.protocol in ("pushpull", "pull", "pushk") and args.backend == "native":
        from p2p_gossip_tpu.runtime.native import run_native_partnered_sim

        stats = run_native_partnered_sim(
            g, sched, horizon, protocol=args.protocol, fanout=args.fanout,
            ell_delays=delays, seed=args.seed, churn=churn, loss=loss,
        )
    elif args.protocol in ("pushpull", "pull", "pushk") and args.backend == "event":
        from p2p_gossip_tpu.engine.event import run_event_partnered_sim

        stats = run_event_partnered_sim(
            g, sched, horizon, protocol=args.protocol, fanout=args.fanout,
            seed=args.seed, churn=churn, loss=loss,
        )
    elif args.protocol in ("pushpull", "pull"):
        from p2p_gossip_tpu.models.protocols import run_pushpull_sim

        stats, _ = run_pushpull_sim(
            g, sched, horizon, ell_delays=delays, seed=args.seed,
            chunk_size=args.chunkSize, churn=churn, loss=loss,
            checkpoint_path=args.checkpoint or None,
            checkpoint_every=args.checkpointEvery,
            mode=args.protocol,
        )
    elif args.protocol == "pushk":
        from p2p_gossip_tpu.models.protocols import run_pushk_sim

        stats, _ = run_pushk_sim(
            g, sched, horizon, fanout=args.fanout, ell_delays=delays,
            seed=args.seed, chunk_size=args.chunkSize, churn=churn, loss=loss,
            checkpoint_path=args.checkpoint or None,
            checkpoint_every=args.checkpointEvery,
        )
    elif args.backend == "tpu":
        from p2p_gossip_tpu.engine.sync import run_sync_sim

        stats = run_sync_sim(
            g, sched, horizon, ell_delays=delays, chunk_size=args.chunkSize,
            block=args.degreeBlock or None,
            checkpoint_path=args.checkpoint or None,
            checkpoint_every=args.checkpointEvery,
            churn=churn,
            snapshot_ticks=snapshot_ticks,
            loss=loss,
            connect_tick=args.connectAtTick,
        )
    elif args.backend == "sharded":
        from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim
        from p2p_gossip_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(args.meshNodes or None, args.meshShares)
        print(
            f"Mesh: {mesh.shape['shares']} share-shards x "
            f"{mesh.shape['nodes']} node-shards"
        )
        stats = run_sharded_sim(
            g, sched, horizon, mesh, ell_delays=delays,
            chunk_size=args.chunkSize, block=args.degreeBlock or None,
            churn=churn, snapshot_ticks=snapshot_ticks, loss=loss,
            checkpoint_path=args.checkpoint or None,
            checkpoint_every=args.checkpointEvery,
            connect_tick=args.connectAtTick,
            ring_mode=args.ringMode,
        )
    elif args.backend == "native":
        from p2p_gossip_tpu.runtime.native import run_native_sim

        stats = run_native_sim(
            g, sched, horizon, ell_delays=delays, snapshot_ticks=snapshot_ticks,
            churn=churn, loss=loss, connect_tick=args.connectAtTick,
            fifo_links=fifo,
        )
    else:
        from p2p_gossip_tpu.engine.event import run_event_sim

        stats = run_event_sim(
            g, sched, horizon, ell_delays=delays, snapshot_ticks=snapshot_ticks,
            churn=churn, loss=loss, record_messages=args.animMessages,
            connect_tick=args.connectAtTick, fifo_links=fifo,
        )
    _sim_span.__exit__(None, None, None)
    wall = time.perf_counter() - t0
    telemetry.emit_jit_cache_counters()

    if parallel_extra is not None:
        # Pure reporting transform — the duplicate copies never change
        # gossip dynamics (stats.with_parallel_links documents why).
        stats = stats.with_parallel_links(parallel_extra)
        n_dup = int((parallel_extra > 0).sum())
        print(
            f"parallel-link quirk: {int(parallel_extra.sum()) // 2} doubled "
            f"pair(s) across {n_dup} node(s)",
            file=sys.stderr,
        )

    # Periodic reports (PrintPeriodicStats, p2pnetwork.cc:201-204): exact
    # mid-run snapshots (all push backends; push-pull has no snapshot path).
    for snap in stats.extra.get("snapshots", []):
        avg = snap["processed"] // max(g.n, 1)
        print(
            f"=== Periodic Stats at {snap['tick'] * tick_dt:g}s ===\n"
            f"Total shares generated: {snap['generated']}\n"
            f"Average shares per node: {avg}\n"
            f"Total socket connections: {snap['connections']}"
        )
    per_node = args.perNodeStats if args.perNodeStats is not None else g.n <= 1000
    totals = stats.totals()
    print(format_final_statistics(stats, per_node=per_node), end="")
    print(
        f"Simulated {args.simTime:g}s ({horizon} ticks) in {wall:.3f}s wall "
        f"({totals['processed'] / max(wall, 1e-9):.3g} node-updates/s)"
    )
    if args.json:
        import json

        print(
            json.dumps(
                {
                    "config": {
                        "numNodes": g.n,
                        "edges": int(g.num_edges),
                        "topology": args.topology,
                        "protocol": args.protocol,
                        "backend": args.backend,
                        "simTime": args.simTime,
                        "Latency": args.Latency,
                        "seed": args.seed,
                    },
                    "totals": totals,
                    "wall_s": round(wall, 4),
                    "node_updates_per_s": round(
                        totals["processed"] / max(wall, 1e-9), 1
                    ),
                }
            )
        )

    if args.anim:
        from p2p_gossip_tpu.utils.anim import write_animation_xml

        write_animation_xml(
            g, args.anim, tick_dt=tick_dt,
            messages=stats.extra.get("messages"),
        )
        print(f"NetAnim trace written to {args.anim}")
    return 0


def main() -> None:
    sys.exit(run())

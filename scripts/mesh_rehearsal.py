"""Multi-chip 1M-mechanics rehearsal on a virtual CPU mesh (VERDICT r2 #6).

Multi-chip TPU hardware is not attached in this environment, so perf
cannot be measured — but the full BASELINE config-5 *mechanics* can be
proven end-to-end at real scale on an 8-virtual-device CPU mesh: build a
>=100K-node graph, run the sharded flood-coverage engine with lognormal
per-edge delay lines under BOTH history-ring layouts, and check

  - bitwise counter + coverage parity against the single-device engine,
  - per-chip ring bytes scale 1/shards in sharded mode,

so the only untested step to a physical v5e-8 is the hardware itself.

Emits one JSON row per (N, ring_mode) on stdout; diagnostics on stderr.
Usage: python scripts/mesh_rehearsal.py [--nodes 100000] [--prob 0.001]
       [--shares 64] [--devices 8] [--skip-parity]
       [--replicas R]  # campaign rehearsal: R seed replicas of the
       node-sharded graph as ONE compiled program on a factorized
       (replica_shards x node_shards) mesh — per-replica bitwise compare
       vs solo sharded runs, warm/fresh timings vs the sequential
       solo-sharded loop (batch/campaign_sharded.py)
       [--out FILE]    # also append every JSON row to FILE (artifact)
       [--protocol flood|pushpull|pull|pushk]   # partnered legs rehearse
       BASELINE config 5's anti-entropy on the same mesh/ring machinery
       [--exchange dense|delta|hub|ab]  # sharded-ring wire format; "ab"
       runs all three and reports achieved exchange words/tick side by
       side ([--hub-rows H] forces the hub-set size on flat graphs)
       [--partition]  # relabel nodes by the cached BFS-grown partition
       so each shard owns one partition (minimal cross-shard edge cut)
       [--async-k "1,2,4"]  # bounded-staleness async legs (flood only):
       one extra sharded leg per K. K=1 is the synchronous program
       routed through the double-buffer and joins the bitwise cross-leg
       checks; K>=2 trades tick-exactness for overlap by contract, so
       those legs assert fixed-point equality instead (equal counters +
       final coverage row) and report wall_s / wall_per_tick_s next to
       the sync legs — the headline sync-vs-async measurement
"""

import argparse
import json
import os
import sys
import time

# Self-locate the repo so the script runs from any cwd.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _campaign_rehearsal(
    args, graph, delays, devices, emit, aux_base=None
) -> int:
    """--replicas leg: one factorized-mesh campaign vs the sequential
    solo-sharded loop it replaces. Certifies, per replica and per
    exchange wire format, that the campaign counters are BITWISE the
    solo node-sharded run's (same node-shard count, same share pad), and
    times warm/fresh walls for both drivers — the throughput claim the
    factorization makes (per-replica warm wall under the sequential
    loop's) lands in the emitted row as ``speedup_warm_per_replica``."""
    import jax
    import numpy as np

    from p2p_gossip_tpu.batch.campaign import flood_replicas
    from p2p_gossip_tpu.batch.campaign_sharded import (
        run_sharded_campaign,
        run_sharded_protocol_campaign,
    )
    from p2p_gossip_tpu.ops.bitmask import num_words
    from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim
    from p2p_gossip_tpu.parallel.mesh import make_mesh

    r_shards = args.replica_shards
    if args.devices % r_shards:
        raise SystemExit(
            f"--replica-shards {r_shards} must divide --devices "
            f"{args.devices}"
        )
    n_node_shards = args.devices // r_shards
    # Campaign mesh: replicas x nodes over ALL the devices. Solo
    # baseline mesh: nodes-only with the SAME node-shard count — the
    # mesh a sequential seed loop would actually run on, and the mesh
    # the bitwise contract is stated against (campaign_sharded
    # docstring: same node-shard count, same share pad).
    mesh_c = make_mesh(
        n_node_shards, devices=devices[: args.devices], replicas=r_shards
    )
    mesh_s = make_mesh(n_node_shards, 1, devices=devices[:n_node_shards])
    reps = flood_replicas(
        graph, args.shares,
        list(range(args.seed, args.seed + args.replicas)), args.horizon,
    )
    n_delay_values = len(np.unique(delays[graph.ell()[1]]))

    if args.protocol != "flood":
        from p2p_gossip_tpu.parallel.protocols_sharded import (
            run_sharded_partnered_sim,
        )

        sched_kw = {"protocol": args.protocol, "fanout": args.fanout}

    exchanges = (
        ("dense", "delta", "hub") if args.exchange == "ab"
        else (args.exchange,)
    )
    # Campaign and solo meshes shard nodes the same way, so one cached
    # cut plan (keyed by the node-shard count) serves both drivers.
    aux_cache = (
        (aux_base[0], aux_base[1],
         f"floodcut{n_node_shards}_{aux_base[2]}")
        if aux_base else None
    )
    hub_rows = args.hub_rows or None
    for exchange in exchanges:
        if args.protocol == "flood":
            def run_campaign():
                return run_sharded_campaign(
                    graph, reps, args.horizon, mesh_c, ell_delays=delays,
                    block=args.block, exchange=exchange,
                    hub_rows=hub_rows, aux_cache=aux_cache,
                )

            def run_solo(r):
                return run_sharded_sim(
                    graph, reps.replica_schedule(r, args.horizon),
                    args.horizon, mesh_s, ell_delays=delays,
                    block=args.block, exchange=exchange,
                    hub_rows=hub_rows, aux_cache=aux_cache,
                    chunk_size=reps.shares_per_replica,
                )
        else:
            def run_campaign():
                return run_sharded_protocol_campaign(
                    graph, reps, args.horizon, mesh_c, ell_delays=delays,
                    exchange=exchange, hub_rows=hub_rows, **sched_kw,
                )

            def run_solo(r):
                return run_sharded_partnered_sim(
                    graph, reps.replica_schedule(r, args.horizon),
                    args.horizon, mesh_s, ell_delays=delays,
                    seed=int(reps.seeds[r]) & 0xFFFFFFFF,
                    exchange=exchange, hub_rows=hub_rows,
                    chunk_size=reps.shares_per_replica, **sched_kw,
                )

        # Fresh = compile-inclusive (the one-program claim: ONE compile
        # covers every replica); warm = steady-state batch wall.
        t0 = time.perf_counter()
        result = run_campaign()
        fresh_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        result = run_campaign()
        warm_s = time.perf_counter() - t0
        log(f"campaign[{exchange}]: fresh {fresh_s:.1f}s, "
            f"warm {warm_s:.1f}s ({warm_s / args.replicas:.2f}s/replica)")

        # Sequential baseline: compile once on replica 0 (flood_replicas
        # gives every replica identical shapes, so one executable serves
        # the whole loop — the fairest version of the loop the campaign
        # replaces), then time the warm R-replica loop with the bitwise
        # check folded in.
        t0 = time.perf_counter()
        run_solo(0)
        solo_fresh_s = time.perf_counter() - t0
        equal = []
        t0 = time.perf_counter()
        for r in range(args.replicas):
            st = run_solo(r)
            ok = bool(
                np.array_equal(st.received[: graph.n], result.received[r])
                and np.array_equal(st.sent[: graph.n], result.sent[r])
            )
            equal.append(ok)
            log(f"  replica {r}: solo-vs-campaign bitwise "
                f"{'OK' if ok else 'MISMATCH'} (received + sent)")
        solo_loop_s = time.perf_counter() - t0
        assert all(equal), f"campaign diverges from solo loop: {equal}"

        ring = result.extra["ring"]
        row = {
            "rehearsal": (
                "campaign_sharded" if args.protocol == "flood"
                else f"campaign_sharded_{args.protocol}"
            ),
            "platform": jax.devices()[0].platform,
            "nodes": graph.n,
            "topology": args.topology,
            "edges": graph.num_edges,
            "devices": args.devices,
            "replicas": args.replicas,
            "replica_shards": r_shards,
            "node_shards": n_node_shards,
            "local_replicas": result.extra["mesh"]["local_replicas"],
            "shares_per_replica": args.shares,
            "horizon": args.horizon,
            "delay_values": int(n_delay_values),
            "exchange_mode": exchange,
            "ring_mode": ring["mode"],
            "ring_bytes_per_chip": ring["bytes_per_chip"],
            "pad_shares": num_words(args.shares) * 32,
            "bitwise_equal_replicas": int(sum(equal)),
            "campaign_fresh_s": round(fresh_s, 2),
            "campaign_warm_s": round(warm_s, 2),
            "campaign_warm_per_replica_s": round(warm_s / args.replicas, 3),
            "solo_fresh_s": round(solo_fresh_s, 2),
            "solo_loop_warm_s": round(solo_loop_s, 2),
            "solo_warm_per_replica_s": round(solo_loop_s / args.replicas, 3),
            "speedup_warm_per_replica": round(solo_loop_s / warm_s, 2),
        }
        ex = result.extra.get("exchange")
        if ex is not None:
            row["exchange"] = ex
        emit(row)
        log(f"campaign[{exchange}]: {sum(equal)}/{args.replicas} replicas "
            f"bitwise-equal, warm speedup x{row['speedup_warm_per_replica']}"
            f" per replica vs sequential solo loop")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=100_000)
    ap.add_argument("--prob", type=float, default=0.001)
    ap.add_argument(
        "--topology", choices=("er", "ba"), default="er",
        help="er = config 3/5's Erdos-Renyi; ba = config 4's 1M "
        "scale-free, node-sharded over the mesh",
    )
    ap.add_argument("--baM", type=int, default=3)
    ap.add_argument("--shares", type=int, default=64)
    ap.add_argument("--horizon", type=int, default=48)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--block", type=int, default=8)
    ap.add_argument(
        "--delay-max-ticks", type=int, default=4,
        help="lognormal delay cap (distinct delay values L <= cap)",
    )
    ap.add_argument(
        "--protocol", choices=("flood", "pushpull", "pull", "pushk"),
        default="flood",
        help="which engine leg to rehearse: flood (config 3/5's delivery "
        "mechanics) or a partnered protocol (pushpull = BASELINE config "
        "5's anti-entropy leg) — partnered runs check bitwise equality "
        "BETWEEN the two ring layouts (always) and vs the single-device "
        "engine (unless --skip-parity)",
    )
    ap.add_argument("--fanout", type=int, default=3,
                    help="k for --protocol pushk")
    ap.add_argument(
        "--chunkSize", type=int, default=0,
        help="explicit share-pad width (0 = engine default 4096-share "
        "lane pad). On the VIRTUAL mesh all shards live in one host "
        "process, so the default W=128 pad multiplies every ring/frontier "
        "buffer x8 in one RSS — 1M scale-free (dmax 4517, ~40 GB "
        "full-width ELL) OOMs with it and needs e.g. --chunkSize 64",
    )
    ap.add_argument(
        "--exchange", choices=("dense", "delta", "hub", "ab"),
        default="dense",
        help="frontier-exchange wire format for the sharded-ring leg: "
        "dense state-slice all_gathers (default), sparse frontier-delta "
        "buffers (delta), the degree-split hub/tail transport (hub), or "
        "ab = run ALL sharded legs (dense, delta, hub) and report the "
        "achieved exchange words/tick side by side (the wire-format "
        "crossover measurement at rehearsal scale)",
    )
    ap.add_argument(
        "--hub-rows", type=int, default=0,
        help="force the hub-set size for exchange=hub legs (0 = let the "
        "modeled word-count crossover choose; a forced value is for "
        "small graphs whose flat degree profile yields no natural hubs)",
    )
    ap.add_argument(
        "--async-k", type=str, default="",
        help="comma list of bounded-staleness depths (e.g. '1,2,4'): one "
        "extra sharded async leg per K on the --exchange transport(s). "
        "Flood only (the partnered rehearsal's counters are not "
        "delay-invariant at a fixed horizon); K=1 joins the bitwise "
        "cross-leg checks, K>=2 legs assert equal final counters + final "
        "coverage row and report wall_s per leg",
    )
    ap.add_argument(
        "--partition", action="store_true",
        help="relabel node ids by the BFS-grown partition "
        "(models/topology.partition_labels, one partition per mesh "
        "shard) before running — minimizes the cross-shard edge cut the "
        "delta exchange must ship; labels persist in the --cache npz "
        "under the graph's build fingerprint",
    )
    ap.add_argument(
        "--replicas", type=int, default=0,
        help="R > 0 switches to the CAMPAIGN rehearsal: R seed replicas "
        "of the node-sharded graph as ONE compiled program on a "
        "factorized (replica_shards x node_shards) mesh "
        "(batch/campaign_sharded.py) — each replica checked bitwise vs "
        "its solo sharded run, with warm/fresh timings vs the "
        "sequential solo-sharded loop; works with --protocol and "
        "--exchange (ab runs dense, delta, and hub legs)",
    )
    ap.add_argument(
        "--replica-shards", type=int, default=2,
        help="replica-axis device count for --replicas (node shards "
        "take the rest: 8 devices, 2 replica shards -> a (2, 4) mesh); "
        "must divide --devices",
    )
    ap.add_argument(
        "--out", type=str, default="",
        help="also append every emitted JSON row to this file (the "
        "docs/artifacts/ path for committed evidence)",
    )
    ap.add_argument(
        "--skip-parity", action="store_true",
        help="skip the single-device parity run (halves the wall time); "
        "flood runs still check counter conservation, and every run "
        "(flood or partnered) checks the two ring layouts against each "
        "other bitwise",
    )
    ap.add_argument(
        "--cache", type=str, default="",
        help="npz graph cache, interoperable with scale_1m.py --cache "
        "(same fingerprint scheme) — at N=1M the ER build is ~3.5 min, "
        "so the rehearsal reuses the north-star script's graph",
    )
    args = ap.parse_args()

    async_ks = [int(v) for v in args.async_k.split(",") if v.strip()]
    if any(k < 1 for k in async_ks):
        raise SystemExit("--async-k values must be >= 1")
    if async_ks and (args.protocol != "flood" or args.replicas):
        raise SystemExit(
            "--async-k rehearses the flood legs only (partnered/campaign "
            "counters are not delay-invariant at a fixed horizon)"
        )

    # Virtual mesh: this is a mechanics rehearsal, so CPU is the point —
    # pin it before jax loads and fan the host out to N devices.
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    import jax
    import numpy as np

    import p2p_gossip_tpu as pg
    from p2p_gossip_tpu.engine.sync import run_flood_coverage
    from p2p_gossip_tpu.models.latency import lognormal_delays
    from p2p_gossip_tpu.parallel.engine_sharded import (
        run_sharded_flood_coverage,
    )
    from p2p_gossip_tpu.parallel.mesh import make_mesh
    from p2p_gossip_tpu.runtime import native

    devices = jax.devices("cpu")
    assert len(devices) >= args.devices, devices
    mesh = make_mesh(args.devices, 1, devices=devices[: args.devices])

    def emit(row: dict) -> None:
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    # Cache protocol shared with scale_1m.py (same fingerprint, same
    # load/validate/build/save semantics), so /tmp/er1m.npz built by
    # either script serves both.
    from p2p_gossip_tpu.models.topology import load_or_build_graph_cache

    def build():
        if args.topology == "ba":
            graph = native.native_barabasi_albert(
                args.nodes, m=args.baM, seed=args.seed
            )
            if graph is None:
                graph = pg.barabasi_albert(args.nodes, m=args.baM,
                                           seed=args.seed)
            return graph
        graph = native.native_erdos_renyi(
            args.nodes, args.prob, seed=args.seed
        )
        if graph is None:
            graph = pg.erdos_renyi(args.nodes, args.prob, seed=args.seed)
        return graph

    t0 = time.perf_counter()
    graph = load_or_build_graph_cache(
        args.cache, topology=args.topology, nodes=args.nodes,
        prob=args.prob, ba_m=args.baM, seed=args.seed, build=build, log=log,
    )
    log(
        f"graph: N={graph.n} edges={graph.num_edges} dmax={graph.max_degree}"
        f" ({time.perf_counter() - t0:.1f}s)"
    )

    edge_cut_pct = None
    aux_base = None
    if args.partition:
        # Partition-centric layout: relabel so each mesh shard owns one
        # BFS-grown partition. Labels are a pure function of the graph,
        # so they persist in the same npz under the build fingerprint
        # and the 1M partitioning pass runs once per graph build.
        from p2p_gossip_tpu.models.topology import (
            edge_cut,
            load_or_compute_graph_aux,
            partition_labels,
            partition_order,
            relabel_graph,
            scale_graph_fingerprint,
        )

        fp = scale_graph_fingerprint(
            args.topology, args.nodes, args.prob, args.baM, args.seed
        )
        t0 = time.perf_counter()
        g_for_labels = graph
        labels = load_or_compute_graph_aux(
            args.cache, f"partition{args.devices}_s{args.seed}", fp,
            lambda: partition_labels(
                g_for_labels, args.devices, seed=args.seed
            ),
            log,
        )
        cut = edge_cut(graph, labels)
        edge_cut_pct = round(100 * cut / max(graph.num_edges, 1), 2)
        graph, _ = relabel_graph(graph, partition_order(labels))
        log(
            f"partition: {args.devices} parts, edge cut {cut}"
            f"/{graph.num_edges} ({edge_cut_pct}%) "
            f"({time.perf_counter() - t0:.1f}s)"
        )
        if args.cache:
            # Persist the delta/hub exchange's per-destination cut plan
            # (exchange.cached_flood_plan) in the same npz under the
            # same build fingerprint as the labels. The key must pin
            # everything beyond the build that shapes the cut: the
            # relabel (parts + seed) here, the node-shard count at the
            # use site (solo and campaign meshes shard differently).
            aux_base = (args.cache, fp, f"part{args.devices}_s{args.seed}")

    delays = lognormal_delays(
        graph, mean_ticks=2.0, sigma=0.6, max_ticks=args.delay_max_ticks,
        seed=args.seed,
    )

    if args.replicas:
        return _campaign_rehearsal(
            args, graph, delays, devices, emit, aux_base
        )

    # Host-fit arithmetic (shared by the auto-shrink preflight below and
    # the emitted rows): the virtual mesh concentrates every shard in ONE
    # process, so pad width drives host RSS. avail is read once at
    # startup; because it moves with unrelated processes, the chosen pad
    # — and therefore the ring-bytes rows — can differ between runs of
    # the same command, which is why each row now records pad_shares +
    # host_avail_gb so artifacts are self-describing (round-4 advisor).
    from p2p_gossip_tpu.ops.bitmask import num_words

    avail = float(os.environ.get("P2P_HOST_BUDGET_GB", "0")) * 1e9
    if not avail:
        avail = 0.7 * os.sysconf("SC_AVPHYS_PAGES") * os.sysconf(
            "SC_PAGE_SIZE"
        )
    fw_ell = graph.n * graph.max_degree * 9
    ring_slots_model = args.delay_max_ticks + 1

    def host_total(pad):
        row = num_words(max(args.shares, pad)) * 4
        rings = args.devices * ring_slots_model * graph.n * row
        return fw_ell + rings + 6 * graph.n * row

    if not args.chunkSize:
        # Host-fit preflight: the virtual mesh concentrates every shard in
        # ONE process, so the default 4096-share pad — deliberately
        # faithful to config 5's real per-chip ring footprint — can
        # exceed host RAM where 8 real chips would each hold 1/8th. The
        # dominant terms: the sharded engine's FULL-WIDTH ELL staging
        # (N x dmax x (4B idx + 4B delay + 1B mask), hub-sensitive: 1M BA
        # at dmax 4517 is ~40 GB and OOM-killed the first attempt,
        # docs/artifacts/mesh_ba_1m.log) plus one history ring per
        # virtual device. Auto-shrink the pad only when the model
        # exceeds available RAM, and say so loudly — a shrunk pad keeps
        # every parity/coverage check but stops modeling the real
        # config-5 ring bytes.
        pad = 4096
        while pad > 32 and host_total(pad) > avail:
            pad //= 2
        if pad < 4096:
            args.chunkSize = pad
            log(
                f"host-fit: default 4096-share pad models "
                f"{host_total(4096) / 1e9:.1f} GB on this host "
                f"(> {avail / 1e9:.1f} GB available); shrinking pad to "
                f"{pad} shares ({host_total(pad) / 1e9:.1f} GB). Parity "
                "checks are unaffected; ring-bytes rows no longer model "
                "the real config-5 footprint."
            )
    # The pad the engine actually stages: a chunkSize below the share
    # count cannot narrow the rows past the shares themselves (the
    # engine pads to whole 32-bit words of max(shares, chunk)) — record
    # that width, not the raw flag, or the row misdescribes its own
    # ring_bytes accounting.
    eff_pad = num_words(max(args.shares, args.chunkSize or 4096)) * 32
    if host_total(eff_pad) > avail:
        # Not a silent floor: the preflight cannot shrink below 32, and
        # an explicit --chunkSize is taken as given — either way the run
        # proceeds, but the operator (and the artifact row, via
        # host_fit_ok below) must see the model was not satisfied.
        log(
            f"WARNING host-fit NOT satisfied: pad {eff_pad} still models "
            f"{host_total(eff_pad) / 1e9:.1f} GB > {avail / 1e9:.1f} GB "
            "available; proceeding (OOM risk is the operator's)."
        )
    n_delay_values = len(np.unique(delays[graph.ell()[1]]))
    rng = np.random.default_rng(args.seed)
    origins = rng.integers(0, graph.n, args.shares).astype(np.int32)

    # One driver per leg, same (stats, coverage) contract, so the ring
    # loop below treats flood and partnered protocols uniformly.
    if args.protocol == "flood":
        def run_single():
            return run_flood_coverage(
                graph, origins, args.horizon, ell_delays=delays,
                block=args.block,
                chunk_size=args.chunkSize or None,
            )

        aux_cache = (
            (aux_base[0], aux_base[1],
             f"floodcut{args.devices}_{aux_base[2]}")
            if aux_base else None
        )

        def run_mesh(ring_mode, exchange="dense", async_k=0):
            return run_sharded_flood_coverage(
                graph, origins, args.horizon, mesh, ell_delays=delays,
                block=args.block, ring_mode=ring_mode, exchange=exchange,
                hub_rows=args.hub_rows or None, aux_cache=aux_cache,
                **({"async_k": async_k} if async_k else {}),
                **({"chunk_size": args.chunkSize} if args.chunkSize else {}),
            )
    else:
        from p2p_gossip_tpu.models.protocols import (
            run_pushk_sim, run_pushpull_sim,
        )
        from p2p_gossip_tpu.parallel.protocols_sharded import (
            run_sharded_partnered_sim,
        )

        sched = pg.Schedule(
            graph.n, origins, np.zeros(args.shares, dtype=np.int32)
        )

        chunk_kw = (
            {"chunk_size": args.chunkSize} if args.chunkSize else {}
        )

        def run_single():
            if args.protocol == "pushk":
                return run_pushk_sim(
                    graph, sched, args.horizon, fanout=args.fanout,
                    ell_delays=delays, seed=args.seed, record_coverage=True,
                    **chunk_kw,
                )
            return run_pushpull_sim(
                graph, sched, args.horizon, ell_delays=delays,
                seed=args.seed, record_coverage=True, mode=args.protocol,
                **chunk_kw,
            )

        def run_mesh(ring_mode, exchange="dense", async_k=0):
            return run_sharded_partnered_sim(
                graph, sched, args.horizon, mesh, protocol=args.protocol,
                fanout=args.fanout, ell_delays=delays, seed=args.seed,
                record_coverage=True, ring_mode=ring_mode,
                exchange=exchange, hub_rows=args.hub_rows or None,
                **chunk_kw,
            )

    cov_single = None
    if not args.skip_parity:
        t0 = time.perf_counter()
        stats_1, cov_single = run_single()
        log(f"single-device run: {time.perf_counter() - t0:.1f}s")

    # Leg plan: the replicated-ring leg always runs (layout baseline);
    # the sharded-ring leg runs dense, delta, or both ("ab" — the
    # rehearsal-scale dense/delta crossover measurement). Every pair of
    # legs is checked bitwise-equal below, so a delta leg is certified
    # against whichever dense legs ran.
    legs = [("replicated", "dense", 0)]
    if args.exchange in ("dense", "ab"):
        legs.append(("sharded", "dense", 0))
    if args.exchange in ("delta", "ab"):
        legs.append(("sharded", "delta", 0))
    if args.exchange in ("hub", "ab"):
        legs.append(("sharded", "hub", 0))
    # Async legs ride the same transport(s) as the sync legs so the
    # sync-vs-async wall comparison is transport-for-transport.
    for k in async_ks:
        if args.exchange in ("dense", "ab"):
            legs.append(("sharded", "async-dense", k))
        if args.exchange in ("delta", "ab"):
            legs.append(("sharded", "async-delta", k))
        if args.exchange in ("hub", "ab"):
            legs.append(("sharded", "async-hub", k))

    mesh_runs = []
    for ring_mode, exchange, async_k in legs:
        t0 = time.perf_counter()
        stats_m, cov_m = run_mesh(ring_mode, exchange, async_k)
        wall = time.perf_counter() - t0
        ring = stats_m.extra["ring"]
        if args.protocol == "flood":
            # Conservation holds whether or not the parity leg ran — at
            # N=1M the single-device comparison is prohibitive on the
            # host, but received==forwarded / sent==(gen+fwd)*degree
            # still certify the sharded counters. (Partnered protocols
            # have different counter laws; their always-on check is the
            # cross-ring-mode bitwise equality below.)
            stats_m.check_conservation()
        leg_name = f"{ring_mode}/{exchange}" + (
            f"/K{async_k}" if async_k else ""
        )
        mesh_runs.append((leg_name, stats_m, cov_m, async_k))
        parity = None
        if cov_single is not None:
            if async_k >= 2:
                # K >= 2 shifts per-tick timing by contract (bounded
                # staleness); the fixed point is what must survive.
                parity = bool(
                    stats_m.equal_counts(stats_1)
                    and np.array_equal(
                        np.asarray(cov_single)[-1], np.asarray(cov_m)[-1]
                    )
                )
                assert parity, (
                    f"async leg diverges from the sync fixed point "
                    f"({leg_name})"
                )
            else:
                parity = bool(
                    np.array_equal(cov_single, cov_m)
                    and stats_m.equal_counts(stats_1)
                )
                assert parity, (
                    f"mesh diverges from single-device ({leg_name})"
                )
        row = {
            # Historical label continuity: committed artifacts (e.g.
            # docs/artifacts/mesh_1m.json) carry "sharded_flood_coverage".
            "rehearsal": (
                "sharded_flood_coverage" if args.protocol == "flood"
                else f"sharded_{args.protocol}"
            ),
            "nodes": graph.n,
            "topology": args.topology,
            "edges": graph.num_edges,
            "devices": args.devices,
            "shares": args.shares,
            "delay_values": int(n_delay_values),
            "ring_mode": ring["mode"],
            "ring_slots": ring["slots"],
            "ring_bytes_per_chip": ring["bytes_per_chip"],
            # Self-description (round-4 advisor): the pad the run really
            # used, what the host had, and whether the fit model held —
            # so two runs of the same command that chose different pads
            # are distinguishable from their rows alone.
            "pad_shares": eff_pad,
            "host_avail_gb": round(avail / 1e9, 1),
            "host_fit_ok": bool(host_total(eff_pad) <= avail),
            "coverage_final_min": int(np.asarray(cov_m)[-1].min()),
            "parity_vs_single_device": parity,
            "wall_s": round(wall, 1),
            "wall_per_tick_s": round(wall / max(args.horizon, 1), 4),
            "exchange_mode": exchange,
            "async_k": async_k,
            "partitioned": bool(args.partition),
            "edge_cut_pct": edge_cut_pct,
        }
        ex = stats_m.extra.get("exchange")
        if ex is not None:
            # The achieved-traffic report (parallel/engine_sharded.
            # _achieved_exchange_report): modeled dense vs achieved
            # delta words/tick, buffer occupancy, overflow counts.
            row["exchange"] = ex
        log(f"{leg_name}: ring {ring['bytes_per_chip']} "
            f"B/chip, wall {wall:.1f}s, parity {parity}"
            + (f", exchange dense={ex.get('modeled_dense_words_per_tick')}"
               f" delta~{ex.get('achieved_delta_words_per_tick', 0):.1f}"
               f" words/tick (occ "
               f"{ex.get('delta_occupancy', 0):.3f})"
               if ex is not None and ex.get("mode") in ("delta", "hub")
               else ""))
        emit(row)

    # Every pair of legs must agree — a check that costs nothing (all
    # already ran) and survives --skip-parity, so even 1M rehearsals
    # certify layout- and wire-format-independence. Sync legs and the
    # K=1 async anchor agree bitwise per tick; K>=2 async legs shift
    # per-tick timing by contract, so they are held to the fixed point
    # instead (equal counters + final coverage row).
    name0, st0, cov0, _ = mesh_runs[0]
    strict = [r for r in mesh_runs[1:] if r[3] <= 1]
    loose = [r for r in mesh_runs[1:] if r[3] >= 2]
    for name_i, st_i, cov_i, _ in strict:
        assert st0.equal_counts(st_i), (
            f"legs disagree on counters: {name0} vs {name_i}"
        )
        assert np.array_equal(cov0, cov_i), (
            f"legs disagree on coverage: {name0} vs {name_i}"
        )
    for name_i, st_i, cov_i, _ in loose:
        assert st0.equal_counts(st_i), (
            f"async leg disagrees on final counters: {name0} vs {name_i}"
        )
        assert np.array_equal(
            np.asarray(cov0)[-1], np.asarray(cov_i)[-1]
        ), f"async leg disagrees on final coverage: {name0} vs {name_i}"
    log("mesh legs bitwise-equal (counters + coverage): "
        + " == ".join(name for name, _, _, k in mesh_runs if k <= 1)
        + ("" if not loose else
           "; async fixed-point-equal: "
           + " == ".join(name for name, _, _, _ in loose)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

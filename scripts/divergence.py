"""Cross-engine divergence bisector — compare digest streams, name the tick.

    python scripts/divergence.py                     # all pairs, tiny config
    python scripts/divergence.py --pair native-sync --n 128 --horizon 32
    python scripts/divergence.py --inject-fault 7    # self-test: must name 7
    python scripts/divergence.py --json              # one JSON line on stdout

Runs the same seeded workload through two engine configurations, collects
their per-tick state digests (telemetry/digest.py), and reports the first
tick where the streams disagree (telemetry/compare.py). Because engines
that agree produce bit-identical digests, a clean run reports zero
divergence across every pair, and any disagreement is located exactly —
no binary search, no second run.

Pairs:

  native-sync      host event engine (runtime/native's reference
                   semantics, digested through the ``on_tick`` hook)
                   vs the compiled ``engine.sync`` tick kernel
  sync-campaign    solo ``engine.sync`` run vs replica 0 of a vmapped
                   flood campaign (``batch.campaign``)
  pushpull-campaign  solo ``models.protocols`` push-pull run vs replica
                   0 of the vmapped protocol campaign
  sync-sharded     solo ``engine.sync`` vs the shard_map flood runner on
                   a 2x2 mesh (skipped when fewer than 4 devices)
  sync-delta       sharded flood runner with the dense state-slice
                   exchange vs the same runner with the sparse
                   frontier-delta exchange (``exchange="delta"``) —
                   delta's OR-monotone merge must be bit-identical, so
                   shard 0's digest streams must agree tick for tick
                   (skipped when fewer than 4 devices)
  sharded-campaign solo node-sharded flood run vs replica 0 of the
                   factorized (replicas x nodes) campaign
                   (``batch.campaign_sharded``) on the same node-shard
                   count (skipped when fewer than 4 devices)
  sync-async       sharded flood runner with cross-shard delays clamped
                   to K=2 host-side (the async contract's reference
                   semantics, ``parallel.async_ticks.clamp_flood_delays``)
                   vs the bounded-staleness async runner
                   (``exchange="async"``, ``async_k=2``) — the K-ahead
                   double-buffered frontier must be bit-identical to
                   the clamped-delay sync run, tick for tick (skipped
                   when fewer than 4 devices)
  sync-hub         sharded flood runner with the dense exchange vs the
                   degree-split hub/tail transport (``exchange="hub"``,
                   ``hub_rows=8`` forced — the tiny ER workload has no
                   natural hub set) — the allreduced hub block plus the
                   sparse tail must OR back to the dense frontier
                   bit-identically (skipped when fewer than 4 devices)

``--inject-fault T`` is the bisector's self-test: after collecting each
pair it flips one bit of the second stream's digest at tick T and
asserts the comparison names exactly T — exit 0 iff every pair locates
the fault, making a blind bisector loudly non-zero. Without injection,
exit 0 iff every pair is divergence-free; a real divergence additionally
dumps a +/- ``--window`` frontier capture around the named tick
(per-node received totals and seen counts from the host engine for
native-sync; both streams' digest windows otherwise).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

PAIRS = (
    "native-sync",
    "sync-campaign",
    "pushpull-campaign",
    "sync-sharded",
    "sync-delta",
    "sharded-campaign",
    "sync-async",
    "sync-hub",
)


def _setup_backend() -> None:
    from p2p_gossip_tpu.utils.platform import (
        enable_compile_cache,
        use_host_devices,
    )

    use_host_devices(8)
    enable_compile_cache()


def _capture_events(run) -> list:
    """Run ``run()`` with the telemetry sink pointed at a throwaway file
    and hand back the captured event list."""
    from p2p_gossip_tpu import telemetry

    fd, path = tempfile.mkstemp(suffix=".jsonl", prefix="divergence_")
    os.close(fd)
    telemetry.configure(path, rings=True)
    try:
        run()
    finally:
        telemetry.close()
    events = list(telemetry.events())
    telemetry.reset()
    try:
        os.unlink(path)
    except OSError:
        pass
    return events


def _workload(args):
    """The shared seeded workload: an ER graph and a staggered flood
    schedule (three generation waves exercise the delay line)."""
    from p2p_gossip_tpu.models.generation import Schedule
    from p2p_gossip_tpu.models.topology import erdos_renyi

    graph = erdos_renyi(args.n, args.p, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    origins = rng.integers(0, args.n, args.shares).astype(np.int32)
    gen = (np.arange(args.shares, dtype=np.int32) % 3) * 2
    return graph, Schedule(graph.n, origins, gen)


def pair_native_sync(args):
    from p2p_gossip_tpu.engine.sync import run_sync_sim
    from p2p_gossip_tpu.telemetry import compare

    graph, sched = _workload(args)
    cap = compare.capture_event_digests(graph, sched, args.horizon)
    events = _capture_events(
        lambda: run_sync_sim(graph, sched, args.horizon, chunk_size=args.chunk)
    )
    sync = compare.select_stream(
        compare.digest_streams(events), kernel="engine.sync"
    )
    return cap.digests, sync


def pair_sync_campaign(args):
    from p2p_gossip_tpu.batch.campaign import (
        flood_replicas,
        run_coverage_campaign,
    )
    from p2p_gossip_tpu.engine.sync import run_sync_sim
    from p2p_gossip_tpu.telemetry import compare

    graph, _ = _workload(args)
    reps = flood_replicas(
        graph, args.shares, [args.seed, args.seed + 1], args.horizon
    )
    solo_events = _capture_events(
        lambda: run_sync_sim(
            graph, reps.replica_schedule(0, args.horizon), args.horizon,
            chunk_size=args.chunk,
        )
    )
    camp_events = _capture_events(
        lambda: run_coverage_campaign(graph, reps, args.horizon)
    )
    solo = compare.select_stream(
        compare.digest_streams(solo_events), kernel="engine.sync"
    )
    camp = compare.select_stream(
        compare.digest_streams(camp_events), kernel="batch.campaign",
        replica=0,
    )
    return solo, camp


def pair_pushpull_campaign(args):
    from p2p_gossip_tpu.batch.campaign import (
        flood_replicas,
        run_protocol_campaign,
    )
    from p2p_gossip_tpu.models.generation import Schedule
    from p2p_gossip_tpu.models.protocols import run_pushpull_sim
    from p2p_gossip_tpu.telemetry import compare

    graph, _ = _workload(args)
    reps = flood_replicas(
        graph, args.shares, [args.seed, args.seed + 1], args.horizon
    )
    # The campaign's solo reference: flood-style origins from the replica
    # seed, all generated at t=0 (batch/campaign.py's replica contract).
    origins = (
        np.random.default_rng(args.seed)
        .integers(0, graph.n, args.shares)
        .astype(np.int32)
    )
    sched = Schedule(graph.n, origins, np.zeros(args.shares, dtype=np.int32))
    solo_events = _capture_events(
        lambda: run_pushpull_sim(
            graph, sched, args.horizon, seed=args.seed,
            churn=reps.replica_churn(0), record_coverage=True,
        )
    )
    camp_events = _capture_events(
        lambda: run_protocol_campaign(
            graph, reps, args.horizon, protocol="pushpull"
        )
    )
    solo = compare.select_stream(
        compare.digest_streams(solo_events), kernel="models.protocols"
    )
    camp = compare.select_stream(
        compare.digest_streams(camp_events), kernel="run_protocol_campaign",
        replica=0,
    )
    return solo, camp


def pair_sync_sharded(args):
    import jax

    if len(jax.devices()) < 4:
        return None
    from p2p_gossip_tpu.engine.sync import run_sync_sim
    from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim
    from p2p_gossip_tpu.parallel.mesh import make_mesh
    from p2p_gossip_tpu.telemetry import compare

    graph, sched = _workload(args)
    mesh = make_mesh(2, 2)
    solo_events = _capture_events(
        lambda: run_sync_sim(graph, sched, args.horizon, chunk_size=args.chunk)
    )
    sharded_events = _capture_events(
        lambda: run_sharded_sim(
            graph, sched, args.horizon, mesh, chunk_size=args.chunk
        )
    )
    solo = compare.select_stream(
        compare.digest_streams(solo_events), kernel="engine.sync"
    )
    # Shard 0 owns the pass's first chunk_size share slots — with the
    # whole schedule in one chunk that is the solo stream's share set.
    sharded = compare.select_stream(
        compare.digest_streams(sharded_events), kernel="engine_sharded",
        shard=0,
    )
    return solo, sharded


def pair_sync_delta(args):
    import jax

    if len(jax.devices()) < 4:
        return None
    from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim
    from p2p_gossip_tpu.parallel.mesh import make_mesh
    from p2p_gossip_tpu.telemetry import compare

    graph, sched = _workload(args)
    mesh = make_mesh(2, 2)
    dense_events = _capture_events(
        lambda: run_sharded_sim(
            graph, sched, args.horizon, mesh, chunk_size=args.chunk,
            ring_mode="sharded",
        )
    )
    delta_events = _capture_events(
        lambda: run_sharded_sim(
            graph, sched, args.horizon, mesh, chunk_size=args.chunk,
            exchange="delta",
        )
    )
    dense = compare.select_stream(
        compare.digest_streams(dense_events), kernel="engine_sharded",
        shard=0,
    )
    delta = compare.select_stream(
        compare.digest_streams(delta_events), kernel="engine_sharded",
        shard=0,
    )
    return dense, delta


def pair_sharded_campaign(args):
    import jax

    if len(jax.devices()) < 4:
        return None
    from p2p_gossip_tpu.batch.campaign import flood_replicas
    from p2p_gossip_tpu.batch.campaign_sharded import run_sharded_campaign
    from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim
    from p2p_gossip_tpu.parallel.mesh import make_mesh
    from p2p_gossip_tpu.telemetry import compare

    graph, _ = _workload(args)
    reps = flood_replicas(
        graph, args.shares, [args.seed, args.seed + 1], args.horizon
    )
    devices = jax.devices()
    # Factorized (2 replicas x 2 nodes) mesh vs a solo nodes-only mesh
    # with the SAME node-shard count — the campaign's bitwise contract.
    mesh_c = make_mesh(2, devices=devices[:4], replicas=2)
    mesh_s = make_mesh(2, 1, devices=devices[:2])
    solo_events = _capture_events(
        lambda: run_sharded_sim(
            graph, reps.replica_schedule(0, args.horizon), args.horizon,
            mesh_s, chunk_size=args.shares,
        )
    )
    camp_events = _capture_events(
        lambda: run_sharded_campaign(graph, reps, args.horizon, mesh_c)
    )
    solo = compare.select_stream(
        compare.digest_streams(solo_events), kernel="engine_sharded",
        shard=0,
    )
    camp = compare.select_stream(
        compare.digest_streams(camp_events), kernel="run_sharded_campaign",
        replica=0,
    )
    return solo, camp


def pair_sync_async(args):
    import jax

    if len(jax.devices()) < 4:
        return None
    from p2p_gossip_tpu.models.latency import lognormal_delays
    from p2p_gossip_tpu.parallel import async_ticks
    from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim
    from p2p_gossip_tpu.parallel.mesh import make_mesh
    from p2p_gossip_tpu.telemetry import compare

    graph, sched = _workload(args)
    mesh = make_mesh(2, 2)
    delays = lognormal_delays(
        graph, mean_ticks=2.0, sigma=0.5, max_ticks=4, seed=args.seed
    )
    k = 2
    # The async contract: async(K) == sync with cross-shard delays
    # clamped to max(d, K) host-side.  Stream a runs the plain sharded
    # runner on the pre-clamped delay line; stream b runs async K=2 on
    # the original delays.  Per-tick digests must be identical, so
    # --inject-fault bisects this pair like any other.
    ref_delays = async_ticks.clamp_flood_delays(
        graph, 2, k, ell_delays=delays
    )
    sync_events = _capture_events(
        lambda: run_sharded_sim(
            graph, sched, args.horizon, mesh, chunk_size=args.chunk,
            ring_mode="sharded", ell_delays=ref_delays,
        )
    )
    async_events = _capture_events(
        lambda: run_sharded_sim(
            graph, sched, args.horizon, mesh, chunk_size=args.chunk,
            exchange="async", async_k=k, ell_delays=delays,
        )
    )
    sync = compare.select_stream(
        compare.digest_streams(sync_events), kernel="engine_sharded",
        shard=0,
    )
    async_ = compare.select_stream(
        compare.digest_streams(async_events), kernel="engine_sharded",
        shard=0,
    )
    return sync, async_


def pair_sync_hub(args):
    import jax

    if len(jax.devices()) < 4:
        return None
    from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim
    from p2p_gossip_tpu.parallel.mesh import make_mesh
    from p2p_gossip_tpu.telemetry import compare

    graph, sched = _workload(args)
    mesh = make_mesh(2, 2)
    dense_events = _capture_events(
        lambda: run_sharded_sim(
            graph, sched, args.horizon, mesh, chunk_size=args.chunk,
            ring_mode="sharded",
        )
    )
    # hub_rows=8 forces a non-empty hub set: the tiny ER workload is
    # too flat for the modeled crossover to pick h > 0 on its own, and
    # an empty hub would degenerate to the delta pair.
    hub_events = _capture_events(
        lambda: run_sharded_sim(
            graph, sched, args.horizon, mesh, chunk_size=args.chunk,
            exchange="hub", hub_rows=8,
        )
    )
    dense = compare.select_stream(
        compare.digest_streams(dense_events), kernel="engine_sharded",
        shard=0,
    )
    hub = compare.select_stream(
        compare.digest_streams(hub_events), kernel="engine_sharded",
        shard=0,
    )
    return dense, hub


_PAIR_FNS = {
    "native-sync": pair_native_sync,
    "sync-campaign": pair_sync_campaign,
    "pushpull-campaign": pair_pushpull_campaign,
    "sync-sharded": pair_sync_sharded,
    "sync-delta": pair_sync_delta,
    "sharded-campaign": pair_sharded_campaign,
    "sync-async": pair_sync_async,
    "sync-hub": pair_sync_hub,
}


def _frontier_window(args, tick: int) -> dict:
    """Host frontier capture around a divergent tick (native-sync)."""
    from p2p_gossip_tpu.telemetry import compare

    graph, sched = _workload(args)
    lo = max(tick - args.window, 0)
    hi = min(tick + args.window, args.horizon - 1)
    cap = compare.capture_event_digests(
        graph, sched, args.horizon, window=(lo, hi)
    )
    return {
        str(t): {
            "received_total": int(cap.received[t].sum()),
            "seen_total": int(cap.seen_counts[t].sum()),
            "top_received": [
                [int(i), int(cap.received[t][i])]
                for i in np.argsort(cap.received[t])[-5:][::-1]
            ],
        }
        for t in sorted(cap.received)
    }


def run_pair(name: str, args) -> dict:
    from p2p_gossip_tpu.telemetry import compare

    built = _PAIR_FNS[name](args)
    if built is None:
        return {"pair": name, "skipped": "needs >= 4 devices"}
    a, b = built
    report: dict = {"pair": name}
    if args.inject_fault is not None:
        t = args.inject_fault
        try:
            faulty = compare.inject_fault(b, t, bit=args.fault_bit)
        except ValueError as e:
            return {**report, "fault_located": False, "error": str(e)}
        div = compare.first_divergence(a, faulty)
        report["fault_tick"] = t
        report["located_tick"] = div.tick
        report["fault_located"] = div.tick == t
        report["compared"] = div.compared
        return report
    div = compare.first_divergence(a, b)
    report.update(div.as_dict())
    if div.diverged:
        lo = max(div.tick - args.window, 0)
        hi = div.tick + args.window
        report["digest_window"] = {
            "a": {str(t): a[t] for t in sorted(a) if lo <= t <= hi},
            "b": {str(t): b[t] for t in sorted(b) if lo <= t <= hi},
        }
        if name == "native-sync":
            report["frontier"] = _frontier_window(args, div.tick)
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pair", choices=PAIRS, action="append",
                    help="pair(s) to compare (default: all)")
    ap.add_argument("--n", type=int, default=96, help="nodes")
    ap.add_argument("--p", type=float, default=0.08, help="ER edge prob")
    ap.add_argument("--shares", type=int, default=4)
    ap.add_argument("--horizon", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=32,
                    help="solo/sharded share-chunk size")
    ap.add_argument("--inject-fault", type=int, default=None, metavar="T",
                    help="self-test: flip one digest bit at tick T in each "
                    "pair's second stream; exit 0 iff the bisector names T")
    ap.add_argument("--fault-bit", type=int, default=0)
    ap.add_argument("--window", type=int, default=2,
                    help="frontier-capture radius around a divergent tick")
    ap.add_argument("--json", action="store_true",
                    help="one JSON line on stdout")
    ap.add_argument("--with-cost", nargs="?", const="engine.sync",
                    default=None, metavar="SUBSTR",
                    help="after the pairs, also run the compiled-cost "
                    "ledger (scripts/cost_report.py) restricted to "
                    "SUBSTR (default engine.sync) and print its JSON "
                    "line")
    args = ap.parse_args()

    _setup_backend()
    pairs = args.pair or list(PAIRS)
    reports = [run_pair(name, args) for name in pairs]

    if args.inject_fault is not None:
        ok = all(
            r.get("fault_located", True) for r in reports
        ) and any("fault_located" in r for r in reports)
    else:
        ok = not any(r.get("diverged") for r in reports)

    out = {"ok": ok, "mode": (
        "inject-fault" if args.inject_fault is not None else "compare"
    ), "pairs": reports}
    if args.json:
        print(json.dumps(out))
    else:
        for r in reports:
            if "skipped" in r:
                print(f"{r['pair']}: SKIPPED ({r['skipped']})")
            elif "error" in r:
                print(f"{r['pair']}: FAULT INJECTION FAILED — {r['error']}")
            elif "fault_located" in r:
                print(
                    f"{r['pair']}: injected fault at tick "
                    f"{r.get('fault_tick')} -> located "
                    f"{r.get('located_tick')} "
                    f"({'OK' if r['fault_located'] else 'MISSED'}, "
                    f"{r.get('compared', 0)} ticks compared)"
                )
            elif r.get("diverged"):
                print(
                    f"{r['pair']}: DIVERGED at tick {r['tick']} "
                    f"(a={r['a_value']:#010x} b={r['b_value']:#010x}, "
                    f"{r['matched_head']} ticks agreed first)"
                )
            else:
                print(
                    f"{r['pair']}: clean — {r['compared']} common ticks, "
                    "zero divergence"
                )
        print(f"divergence: {'OK' if ok else 'FAIL'}")

    if args.with_cost:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from cost_report import run_cost_report

        cost = run_cost_report(only=args.with_cost)
        print(json.dumps(cost))
        ok = ok and cost["ok"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Headline benchmark — prints ONE JSON line on stdout.

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

All diagnostics go to stderr; stdout carries exactly one JSON line.
"""

import json
import os
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


#: Published peak HBM bandwidth per chip, GB/s, keyed by JAX's
#: ``device_kind``. Source: Google Cloud documentation, "TPU v5e"
#: (16 GB of HBM at 819 GB/s per chip). A kind missing here is an error,
#: never a default.
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}


def hbm_peak_gbps(device_kind: str) -> float:
    """The chip's published HBM peak; raises for a kind not in the table."""
    try:
        return HBM_PEAK_GBPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published HBM peak for device kind {device_kind!r}; add it "
            "to bench.HBM_PEAK_GBPS with its source"
        ) from None


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
    from p2p_gossip_tpu import telemetry
    from p2p_gossip_tpu.engine.sync import DeviceGraph, run_sync_sim
    from p2p_gossip_tpu.runtime import native

    # Host-span telemetry for every bench run: phase timings ride the
    # JSON row (and stream to P2P_TELEMETRY when set). Device metric
    # rings stay OFF regardless — they change the compiled program, and
    # the headline number must measure the uninstrumented kernels
    # (docs/OBSERVABILITY.md). Ring-instrumented runs are the CLI's /
    # battery telemetry stage's job.
    telemetry.configure(
        os.environ.get("P2P_TELEMETRY") or None, rings=False,
    )

    smoke = os.environ.get("P2P_BENCH_SMOKE") == "1"
    if smoke:
        # Tiny shapes for harness tests of the output contract (one parsed
        # JSON line) — not a performance number.
        n, p, seed = 2_000, 0.01, 0
        n_shares, gen_window, horizon = 256, 16, 64
        chunk_size = 256
    elif cpu_run:
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
    with telemetry.span("build_graph", n=n):
        graph, sched = headline_workload(n, p, seed, n_shares, gen_window)
    log(
        f"graph: N={graph.n} edges={graph.num_edges} dmax={graph.max_degree} "
        f"({time.perf_counter() - t0:.1f}s)"
    )

    with telemetry.span("stage"):
        dg = DeviceGraph.build(graph)
        jax.block_until_ready(dg.ell_idx)

    t0 = time.perf_counter()
    with telemetry.span("warmup_compile"):
        warm = run_sync_sim(
            graph, sched, horizon, chunk_size=chunk_size, device_graph=dg
        )
    log(f"warmup (incl. compile): {time.perf_counter() - t0:.1f}s")

    t0 = time.perf_counter()
    with telemetry.span("execute"):
        stats = run_sync_sim(
            graph, sched, horizon, chunk_size=chunk_size, device_graph=dg
        )
    tpu_wall = time.perf_counter() - t0
    processed = stats.totals()["processed"]
    assert stats.totals() == warm.totals()
    assert processed == n_shares * graph.n, "flood did not reach full coverage"
    tpu_rate = processed / tpu_wall
    log(f"tpu: {processed} node-updates in {tpu_wall:.2f}s = {tpu_rate:.3g}/s")

    # Roofline framing: modeled HBM bytes per tick (gather + elementwise
    # passes, DeviceGraph.hbm_bytes_per_tick) over the measured wall,
    # against the chip's published peak HBM bandwidth (HBM_PEAK_GBPS) —
    # "fast" judged against the hardware ceiling, not only against the
    # C++ baseline. Host and smoke runs have no peak to compare with.
    from p2p_gossip_tpu.ops import bitmask

    ticks = stats.extra["ticks_executed"]
    bytes_tick = dg.hbm_bytes_per_tick(bitmask.num_words(chunk_size))
    achieved_gbps = bytes_tick * ticks / tpu_wall / 1e9
    device = jax.devices()[0]
    peak_gbps = (
        None if cpu_run or smoke else hbm_peak_gbps(device.device_kind)
    )
    log(
        f"roofline: {ticks} ticks x {bytes_tick / 1e9:.2f} GB modeled/tick "
        f"= {achieved_gbps:.0f} GB/s achieved"
        + (
            ""
            if peak_gbps is None
            else f" ({100 * achieved_gbps / peak_gbps:.0f}% of "
            f"{peak_gbps:.0f} GB/s peak)"
        )
    )

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
    with telemetry.span("baseline"):
        base = native.run_native_sim(graph, base_sched, horizon)
    base_wall = time.perf_counter() - t0
    base_processed = base.totals()["processed"]
    base_rate = base_processed / base_wall
    log(
        f"baseline (native-c++): {base_processed} node-updates, "
        f"{base.extra['events_processed']} events in {base_wall:.2f}s = "
        f"{base_rate:.3g}/s"
    )

    # Campaign throughput (batch/campaign.py): R seed-ensemble replicas
    # of a flood in ONE jit vs sequential solo runs. Two baselines, both
    # honest: `sequential` clears the jit cache per run (the repo's
    # one-config-per-process status quo — the compile-amortization
    # comparison; sampled and extrapolated to keep the bench wall sane),
    # `warm_loop` shares one compile and one staged graph. Platform is
    # labeled like the headline metric (CPU numbers are CPU numbers).
    from p2p_gossip_tpu.batch.campaign import (
        flood_replicas,
        run_coverage_campaign,
    )
    from p2p_gossip_tpu.engine.sync import run_flood_coverage

    if smoke:
        camp_r, camp_n, camp_p, camp_s, camp_h = 4, 256, 0.05, 2, 32
        fresh_sample = 2
    else:
        camp_r, camp_n, camp_p, camp_s, camp_h = 32, 1024, 0.01, 4, 64
        fresh_sample = 4
    camp_graph = pg.erdos_renyi(camp_n, camp_p, seed=seed)
    camp_reps = flood_replicas(camp_graph, camp_s, list(range(camp_r)), camp_h)
    t0 = time.perf_counter()
    with telemetry.span("campaign", replicas=camp_r):
        camp = run_coverage_campaign(camp_graph, camp_reps, camp_h)
    camp_wall = time.perf_counter() - t0  # includes the one compile
    camp_processed = int((camp.generated + camp.received).sum())
    camp_rate = camp_processed / camp_wall

    from p2p_gossip_tpu.engine.sync import DeviceGraph as _DG

    camp_dg = _DG.build(camp_graph)

    def _solo(s):
        origins = np.random.default_rng(s).integers(
            0, camp_graph.n, camp_s
        ).astype(np.int32)
        run_flood_coverage(camp_graph, origins, camp_h, device_graph=camp_dg)

    t0 = time.perf_counter()
    for s in range(fresh_sample):
        jax.clear_caches()  # one-config-per-process semantics
        _solo(s)
    seq_fresh_est = (time.perf_counter() - t0) * (camp_r / fresh_sample)
    _solo(0)  # compile once outside the timed warm loop
    t0 = time.perf_counter()
    for s in range(camp_r):
        _solo(s)
    seq_warm = time.perf_counter() - t0
    camp_label = (
        "CPU - JAX_PLATFORMS=cpu" if cpu_run else "single chip"
    ) + (", SMOKE" if smoke else "")
    log(
        f"campaign: R={camp_r} x N={camp_n} flood in {camp_wall:.2f}s = "
        f"{camp_rate:.3g} node-updates/s; sequential {seq_fresh_est:.1f}s "
        f"(per-run compile, est from {fresh_sample}) / warm loop "
        f"{seq_warm:.2f}s -> {seq_fresh_est / camp_wall:.1f}x / "
        f"{seq_warm / camp_wall:.1f}x ({camp_label})"
    )

    # Protocol campaign (batch/campaign.py run_protocol_campaign): the
    # random-partner trio batches too. Baseline is the sweep's former
    # sequential-per-seed engine — one solo run_pushpull_sim per seed
    # sharing a warm compile (its best case) — timed in full; the
    # campaign is reported both cold (incl. its one compile) and warm
    # (the steady-state a multi-cell sweep pays). Same honest platform
    # label as the flood campaign.
    from p2p_gossip_tpu.batch.campaign import run_protocol_campaign
    from p2p_gossip_tpu.models.protocols import run_pushpull_sim

    t0 = time.perf_counter()
    with telemetry.span("protocol_campaign", replicas=camp_r):
        pcamp = run_protocol_campaign(
            camp_graph, camp_reps, camp_h, protocol="pushpull"
        )
    pcamp_wall = time.perf_counter() - t0  # includes the one compile
    t0 = time.perf_counter()
    run_protocol_campaign(camp_graph, camp_reps, camp_h, protocol="pushpull")
    pcamp_warm = time.perf_counter() - t0
    pcamp_processed = int((pcamp.generated + pcamp.received).sum())

    def _solo_pp(s):
        origins = np.random.default_rng(s).integers(
            0, camp_graph.n, camp_s
        ).astype(np.int32)
        sched = pg.Schedule(
            camp_graph.n, origins, np.zeros(camp_s, dtype=np.int32)
        )
        run_pushpull_sim(
            camp_graph, sched, camp_h, seed=int(s), record_coverage=True
        )

    _solo_pp(0)  # compile outside the timed warm loop
    t0 = time.perf_counter()
    for s in range(camp_r):
        _solo_pp(s)
    pp_seq_warm = time.perf_counter() - t0
    log(
        f"protocol campaign: R={camp_r} x N={camp_n} pushpull in "
        f"{pcamp_wall:.2f}s cold / {pcamp_warm:.2f}s warm; sequential "
        f"warm loop {pp_seq_warm:.2f}s -> "
        f"{pp_seq_warm / pcamp_wall:.1f}x cold / "
        f"{pp_seq_warm / pcamp_warm:.1f}x warm ({camp_label})"
    )

    # Static-analysis audit status rides every bench record so the
    # driver sees per round whether the compiled surfaces passed the
    # invariant gate (scripts/staticcheck.py). The audit itself is
    # platform-independent — it runs on host CPU in a subprocess (this
    # process holds the chip); the battery's dedicated staticcheck
    # stage covers the on-chip --compile leg. Smoke runs take the
    # lint-only fast path (no jax import) to keep harness tests quick.
    import subprocess

    # One process per chip: this one holds it, so EVERY child below runs
    # with this env, pinned to the host CPU.
    sc_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    sc_args = [sys.executable, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts",
        "staticcheck.py"), "--json"]
    if smoke:
        sc_args.append("--lint-only")
    try:
        sc = subprocess.run(
            sc_args, capture_output=True, text=True, timeout=600,
            env=sc_env,
        )
        staticcheck_ok = sc.returncode == 0
        if not staticcheck_ok:
            log(f"staticcheck: FAIL (rc={sc.returncode}) "
                f"{sc.stdout[-400:]}")
    except Exception as e:  # timeout or spawn failure: unknown, not ok
        log(f"staticcheck: did not complete ({type(e).__name__})")
        staticcheck_ok = None

    # Compiled-cost ledger for the bench kernel family (scripts/
    # cost_report.py): flops / bytes / compile time per engine.sync
    # entry, host-CPU subprocess for the same reason as the audit
    # above. ``platform`` labels the figures — a CPU ledger never
    # masquerades as chip numbers. None when the ledger could not
    # be taken; skipped entirely on smoke runs (compiling four kernels
    # dwarfs the smoke workload).
    cost = None
    if not smoke:
        cr_args = [sys.executable, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts",
            "cost_report.py"), "--json", "--only", "engine.sync"]
        try:
            cr = subprocess.run(
                cr_args, capture_output=True, text=True, timeout=600,
                env=sc_env,
            )
            if cr.returncode == 0:
                cost = json.loads(cr.stdout.strip().splitlines()[-1])
            else:
                log(f"cost report: FAIL (rc={cr.returncode}) "
                    f"{cr.stdout[-400:]}")
        except Exception as e:
            log(f"cost report: did not complete ({type(e).__name__})")

    # Frontier-exchange crossover (scripts/cost_report.py
    # --exchange-only): dense vs sparse-delta exchange words/tick and
    # steady-state delta-buffer occupancy on the two benchmark topology
    # families, from the sharded flood runner's on-device counters.
    # Host-CPU subprocess like the audit above, platform-labeled like
    # the cost ledger (the ``platform`` field inside says "cpu" —
    # chip-scale numbers are the battery's exchange stage /
    # mesh_rehearsal --exchange legs). None on smoke or when the
    # measurement could not run.
    exchange = None
    if not smoke:
        ex_args = [sys.executable, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts",
            "cost_report.py"), "--exchange-only", "--families",
            "erdos_renyi,barabasi_albert"]
        try:
            exr = subprocess.run(
                ex_args, capture_output=True, text=True, timeout=600,
                env=sc_env,
            )
            if exr.returncode == 0:
                exchange = json.loads(exr.stdout.strip().splitlines()[-1])
            else:
                log(f"exchange report: FAIL (rc={exr.returncode}) "
                    f"{exr.stdout[-400:]}")
        except Exception as e:
            log(f"exchange report: did not complete ({type(e).__name__})")

    # Degree-split hub/tail transport summary, distilled from the same
    # exchange report (cost_report runs a hub leg per family): hub-set
    # size, modeled vs achieved hub+tail words/tick, and which wire
    # format won at bench scale. None whenever the exchange report is.
    exchange_hub = None
    if exchange and exchange.get("families"):
        hub_rows = [
            {
                "family": fam.get("family"),
                "hub_count": (fam.get("hub") or {}).get("hub_count"),
                "crossover_h": (fam.get("hub") or {}).get("crossover_h"),
                "modeled_hub_words_per_tick": (
                    (fam.get("hub") or {}).get("modeled_hub_words_per_tick")
                ),
                "achieved_words_per_tick": (
                    (fam.get("hub") or {})
                    .get("achieved_delta_words_per_tick")
                ),
                "delta_over_hub": fam.get("delta_over_hub"),
                "winner": fam.get("winner"),
            }
            for fam in exchange["families"] if fam.get("hub")
        ]
        if hub_rows:
            exchange_hub = {
                "platform": exchange.get("platform"), "families": hub_rows,
            }

    # Campaigns x shards (batch/campaign_sharded.py): R replicas of the
    # node-sharded flood as ONE compiled program on a factorized
    # (replicas, nodes) mesh. The bench process can't re-fan its own
    # backend out to 8 virtual devices after init, so the measurement
    # rides a CPU subprocess of scripts/mesh_rehearsal.py's --replicas
    # leg — which also bitwise-checks every replica against its solo
    # node-sharded run before timing. The row is platform-labeled inside
    # ("platform": "cpu"); chip-scale numbers are the battery's
    # campaign_sharded stage. None on smoke or when the leg could not
    # run.
    campaign_sharded = None
    if not smoke:
        cs_args = [sys.executable, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts",
            "mesh_rehearsal.py"), "--nodes", "4000", "--prob", "0.003",
            "--shares", "32", "--horizon", "32", "--devices", "8",
            "--replicas", "4", "--replica-shards", "2"]
        try:
            csr = subprocess.run(
                cs_args, capture_output=True, text=True, timeout=600,
                env=sc_env,
            )
            if csr.returncode == 0:
                campaign_sharded = json.loads(
                    csr.stdout.strip().splitlines()[-1]
                )
                log(
                    "campaign-sharded leg: "
                    f"{campaign_sharded['bitwise_equal_replicas']}/"
                    f"{campaign_sharded['replicas']} replicas bitwise, "
                    f"warm x{campaign_sharded['speedup_warm_per_replica']}"
                    " vs sequential solo loop (cpu subprocess)"
                )
            else:
                log(f"campaign-sharded leg: FAIL (rc={csr.returncode}) "
                    f"{csr.stderr[-400:]}")
        except Exception as e:
            log(f"campaign-sharded leg: did not complete "
                f"({type(e).__name__})")

    # Bounded-staleness async ticks (parallel/async_ticks.py): sync vs
    # async K in {1,2} flood legs from the rehearsal script on the same
    # 8-virtual-device CPU subprocess pattern as the campaign leg above.
    # The rehearsal asserts K=1 bitwise-equal and K=2 fixed-point-equal
    # before timing, so every row here is parity-certified; one compact
    # entry per leg carries wall_s/tick and the modeled overlap
    # fraction. Platform-labeled "cpu"; chip-scale numbers are the
    # battery's async_ticks stage. None on smoke or when the leg could
    # not run.
    async_ticks = None
    if not smoke:
        at_args = [sys.executable, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts",
            "mesh_rehearsal.py"), "--nodes", "4000", "--prob", "0.003",
            "--shares", "32", "--horizon", "32", "--devices", "8",
            "--async-k", "1,2"]
        try:
            atr = subprocess.run(
                at_args, capture_output=True, text=True, timeout=600,
                env=sc_env,
            )
            if atr.returncode == 0:
                legs = []
                for line in atr.stdout.strip().splitlines():
                    try:
                        leg = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    legs.append({
                        "ring_mode": leg.get("ring_mode"),
                        "exchange_mode": leg.get("exchange_mode"),
                        "async_k": leg.get("async_k"),
                        "wall_s": leg.get("wall_s"),
                        "wall_per_tick_s": leg.get("wall_per_tick_s"),
                        "modeled_overlap_fraction": (
                            leg.get("exchange") or {}
                        ).get("modeled_overlap_fraction"),
                    })
                if legs:
                    async_ticks = {"platform": "cpu", "legs": legs}
                    log(
                        "async-ticks leg: "
                        + "; ".join(
                            f"{lg['exchange_mode']}"
                            + (f"/K{lg['async_k']}" if lg["async_k"] else "")
                            + f" {lg['wall_per_tick_s']}s/tick"
                            for lg in legs
                        )
                        + " (cpu subprocess, parity-certified)"
                    )
            else:
                log(f"async-ticks leg: FAIL (rc={atr.returncode}) "
                    f"{atr.stderr[-400:]}")
        except Exception as e:
            log(f"async-ticks leg: did not complete ({type(e).__name__})")

    # Gossip-as-a-service (serve/): a CI-sized mixed request trace
    # through the continuous-batching server on an 8-virtual-device CPU
    # subprocess (scripts/serve_bench.py --smoke), every request
    # bitwise-verified against a solo campaign run before the row is
    # accepted. Platform-labeled inside ("platform": "cpu"); chip-scale
    # serving numbers are the battery's serve stage. None on smoke or
    # when the leg could not run.
    serve = None
    if not smoke:
        sv_args = [sys.executable, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts",
            "serve_bench.py"), "--smoke"]
        try:
            svr = subprocess.run(
                sv_args, capture_output=True, text=True, timeout=600,
                env=sc_env,
            )
            if svr.returncode == 0:
                serve = json.loads(svr.stdout.strip().splitlines()[-1])
                log(
                    "serve leg: "
                    f"{serve['requests']} requests @ "
                    f"{serve['requests_per_s']}/s, p99 "
                    f"{serve['p99_turnaround_s']}s, occupancy "
                    f"{serve['slot_occupancy']}, bitwise_ok="
                    f"{serve['bitwise_ok']} (cpu subprocess)"
                )
            else:
                log(f"serve leg: FAIL (rc={svr.returncode}) "
                    f"{svr.stderr[-400:]}")
        except Exception as e:
            log(f"serve leg: did not complete ({type(e).__name__})")

    row = {
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
        },
        "metric": (
            f"node-updates/sec ({n // 1000}K-node p={p:g} gossip "
            + (
                "flood, CPU - JAX_PLATFORMS=cpu"
                if cpu_run
                else "flood, single chip"
            )
            + (", SMOKE)" if smoke else ")")
        ),
        "value": round(tpu_rate, 1),
        "unit": "node-updates/s",
        "vs_baseline": round(tpu_rate / base_rate, 2),
        "achieved_gbps": round(achieved_gbps, 1),
        "pct_hbm_peak": (
            round(100 * achieved_gbps / peak_gbps, 1)
            if peak_gbps is not None
            # Host run: the TPU peak is meaningless. Smoke run:
            # tiny shapes can't saturate HBM, the % would be
            # ingested as a real roofline figure.
            else None
        ),
        "ticks": ticks,
        # True/False from the host-CPU audit subprocess; None when the
        # audit itself could not run (never silently green).
        "staticcheck_ok": staticcheck_ok,
        # Compiled-cost ledger (flops/bytes/compile-s per engine.sync
        # entry, platform-labeled); None on smoke or when it could not
        # run.
        "cost": cost,
        # Dense-vs-delta exchange words/tick + delta occupancy per
        # benchmark topology family (platform-labeled, see above); None
        # on smoke or when it could not run.
        "exchange": exchange,
        # Hub/tail transport crossover per family (distilled from the
        # exchange report's hub legs); None whenever ``exchange`` is.
        "exchange_hub": exchange_hub,
        # One factorized (replicas, nodes)-mesh campaign row from the
        # rehearsal script's --replicas leg (platform-labeled "cpu",
        # bitwise-checked per replica); None on smoke or when it could
        # not run.
        "campaign_sharded": campaign_sharded,
        # Sync-vs-async flood legs from the rehearsal script (bounded
        # staleness, parallel/async_ticks.py): wall per tick and modeled
        # overlap fraction per leg, every leg parity-certified before
        # timing. None on smoke or when the leg could not run.
        "async_ticks": async_ticks,
        # Continuous-batching serving row (scripts/serve_bench.py
        # --smoke): requests/s, p50/p99 turnaround, slot occupancy and
        # the per-request bitwise-parity verdict for a mixed trace
        # (platform-labeled "cpu"). None on smoke or when the leg could
        # not run.
        "serve": serve,
    }
    row["campaign"] = {
        "metric": (
            f"campaign node-updates/s (R={camp_r} x {camp_n}-node flood, "
            f"one jit, {camp_label})"
        ),
        "value": round(camp_rate, 1),
        "replicas": camp_r,
        "wall_s": round(camp_wall, 4),
        "sequential_wall_s_est": round(seq_fresh_est, 4),
        "warm_loop_wall_s": round(seq_warm, 4),
        "speedup_vs_sequential": round(seq_fresh_est / camp_wall, 2),
        "speedup_vs_warm_loop": round(seq_warm / camp_wall, 2),
    }
    row["protocol_campaign"] = {
        "metric": (
            f"pushpull campaign node-updates/s (R={camp_r} x "
            f"{camp_n}-node, one jit, {camp_label})"
        ),
        "value": round(pcamp_processed / max(pcamp_warm, 1e-9), 1),
        "replicas": camp_r,
        "wall_s": round(pcamp_wall, 4),
        "warm_wall_s": round(pcamp_warm, 4),
        "sequential_warm_loop_s": round(pp_seq_warm, 4),
        "speedup_incl_compile": round(pp_seq_warm / pcamp_wall, 2),
        "speedup_warm_vs_warm_loop": round(pp_seq_warm / pcamp_warm, 2),
    }
    # Span telemetry rides the row so the battery archives phase timings
    # alongside perf: event count plus total span seconds by phase name
    # (spans only — device rings stay off in bench, see the configure
    # call above). ``stream`` names the JSONL file when P2P_TELEMETRY
    # directed one.
    telemetry.emit_jit_cache_counters()
    span_s: dict = {}
    for ev in telemetry.events():
        if ev.get("type") == "span":
            span_s[ev["name"]] = round(
                span_s.get(ev["name"], 0.0) + ev["dur"], 4
            )
    row["telemetry"] = {
        "events": telemetry.event_count(),
        "span_s_by_phase": span_s,
        "stream": telemetry.path(),
    }
    print(json.dumps(row))


if __name__ == "__main__":
    main()

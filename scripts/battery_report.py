"""Render an on-chip battery artifact (JSONL) as markdown tables.

Closes the last gap between "the battery ran" and "the results are
documented": `onchip_battery.py` persists one JSONL record per stage;
this script turns that file into the markdown sections docs/RESULTS.md
wants (headline bench row, protocol trade-off table, kernel A/B table,
coverage-sweep bisection, 1M north-star lines) as paste-ready
documentation.

Usage: python scripts/battery_report.py [docs/artifacts/battery_latest.jsonl]
Markdown on stdout; exits 1 if the artifact records any failed stage so
automation can tell a complete battery from a partial one.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Mirror onchip_battery.py's --art-dir resolution (P2P_BATTERY_DIR wins)
# so a no-arg report reads the same battery_latest.jsonl the battery wrote.
DEFAULT = os.path.join(
    os.environ.get(
        "P2P_BATTERY_DIR",
        os.path.join(REPO, "docs", "artifacts"),
    ),
    "battery_latest.jsonl",
)


def md_table(rows: list[dict], cols: list[str]) -> str:
    out = ["| " + " | ".join(cols) + " |",
           "|" + "---|" * len(cols)]
    for r in rows:
        # None (e.g. bench.py's deliberately-null pct_hbm_peak on CPU
        # runs) renders as the same em-dash as a missing key.
        out.append(
            "| "
            + " | ".join(
                "—" if r.get(c) is None else str(r[c]) for c in cols
            )
            + " |"
        )
    return "\n".join(out)


def main() -> int:
    path = sys.argv[1] if len(sys.argv) > 1 else DEFAULT
    truncated = 0
    try:
        with open(path) as f:
            records = []
            for line in f:
                if not line.strip():
                    continue
                try:
                    records.append(json.loads(line))
                except json.JSONDecodeError:
                    # A battery killed mid-append leaves a partial final
                    # line; the completed stages must still render —
                    # salvaging partial batteries is this script's job.
                    truncated += 1
    except FileNotFoundError:
        print(f"error: no battery artifact at {path}", file=sys.stderr)
        return 2
    if not records:
        print(f"error: {path} has no complete records", file=sys.stderr)
        return 2
    if truncated:
        print(f"warning: skipped {truncated} truncated record(s) in {path}",
              file=sys.stderr)

    by_stage: dict[str, dict] = {}
    for rec in records:
        by_stage[rec["stage"]] = rec  # later run of a stage wins

    print(f"# On-chip battery report — {records[0]['utc']}\n")
    status_rows = [
        {
            "stage": r["stage"], "rc": r["rc"],
            "wall_s": r["wall_s"], "results": len(r["results"]),
        }
        for r in records
    ]
    print(md_table(status_rows, ["stage", "rc", "wall_s", "results"]))
    print()

    bench_rows = []
    for stage in ("bench", "bench_rep2", "bench_rep3"):
        rec = by_stage.get(stage)
        if rec and rec["results"]:
            bench_rows.append({"stage": stage, **rec["results"][-1]})
    if bench_rows:
        print("## Headline bench\n")
        print(md_table(bench_rows, [
            "stage", "metric", "value", "unit", "vs_baseline",
            "achieved_gbps", "pct_hbm_peak", "ticks",
        ]))
        values = sorted(r["value"] for r in bench_rows)
        if len(values) > 1:
            # The variance line the repeat stages exist for: one number
            # per window can't distinguish drift from noise.
            spread = (values[-1] - values[0]) / values[-1] * 100
            print(
                f"\nacross {len(values)} runs: min {values[0]:.4g}, "
                f"median {values[len(values) // 2]:.4g}, "
                f"max {values[-1]:.4g} ({spread:.1f}% spread)"
            )
        print()

    protocols = by_stage.get("protocols")
    if protocols and protocols["results"]:
        payload = protocols["results"][-1]
        cfg = payload.get("config", {})
        print(
            f"## Protocol comparison (N={cfg.get('nodes')}, "
            f"p={cfg.get('prob')}, {cfg.get('shares')} shares)\n"
        )
        print(md_table(payload.get("results", []), [
            "protocol", "reached_fraction", "final_coverage_mean",
            "ttc_median_ticks", "sends_per_delivery", "total_sent",
            "p95_latency_ticks", "wall_s",
        ]))
        print(
            "\nreached_fraction = shares hitting the 99% coverage bar "
            "within the horizon; final_coverage_mean = mean nodes reached "
            "per share at horizon (rumor mongering trades the last-mile "
            "tail for ~fanout sends per delivery, so a 0.0 bar with high "
            "mean coverage is the protocol's designed trade-off, not a "
            "failure)."
        )
        print()

    camp = by_stage.get("campaign")
    if camp and camp["results"]:
        cells = [r for r in camp["results"] if "cell" in r]
        if cells:
            print("## Campaign engine (vmapped seed ensembles)\n")
            print(md_table(
                [
                    {
                        "protocol": c["cell"].get("protocol"),
                        "engine": c.get("engine"),
                        "platform": c.get("platform"),
                        "replicas": len(c.get("seeds", [])),
                        "lossProb": c["cell"].get("lossProb"),
                        "ttc_p50": ((c.get("summary", {}).get("ttc") or {})
                                    .get("ticks") or {}).get("p50"),
                        "wall_s": c.get("wall_s"),
                    }
                    for c in cells
                ],
                ["protocol", "engine", "platform", "replicas", "lossProb",
                 "ttc_p50", "wall_s"],
            ))
            print()
        cmps = [
            r["compare_sequential"]
            for r in camp["results"]
            if isinstance(r.get("compare_sequential"), dict)
        ]
        if cmps:
            print("## Campaign vs sequential-per-seed\n")
            print(md_table(cmps, [
                "protocol", "replicas", "sequential_wall_s",
                "warm_loop_wall_s", "campaign_wall_s",
                "campaign_warm_wall_s", "speedup_vs_sequential",
                "speedup_vs_warm_loop", "speedup_warm_vs_warm_loop",
            ]))
            print()

    kernel_rows = []
    for stage in ("kernel", "sweep250"):
        rec = by_stage.get(stage)
        if rec:
            for row in rec["results"]:
                kernel_rows.append({"stage": stage, **row})
    if kernel_rows:
        ab = [r for r in kernel_rows if "speedup" in r]
        if ab:
            print("## Kernel A/B (Pallas vs XLA; parity asserted "
                  "before timing)\n")
            print(md_table(ab, [
                "stage", "kernel", "rows", "words", "xla_ms", "pallas_ms",
                "speedup", "parity",
            ]))
            print()
        gather = [r for r in kernel_rows if r.get("kernel") == "gather_or_xla"]
        if gather:
            print("## Gather-OR block sweep (XLA path)\n")
            print(md_table(gather, [
                "rows", "block", "ms_per_tick", "gathered_gb",
                "achieved_gbps",
            ]))
            print()
        wsweep = [
            r for r in kernel_rows if r.get("kernel") == "gather_or_xla_wsweep"
        ]
        if wsweep:
            print("## Gather-OR word-width sweep (block 64)\n")
            print(md_table(wsweep, [
                "rows", "words", "ms_per_tick", "gathered_gb",
                "achieved_gbps",
            ]))
            print()
        rcm = [r for r in kernel_rows if r.get("kernel") == "gather_or_xla_rcm"]
        if rcm:
            print("## Gather-OR with RCM-relabeled graph (block 64)\n")
            print(md_table(rcm, [
                "rows", "words", "ms_per_tick", "gathered_gb",
                "achieved_gbps", "note",
            ]))
            print()

    sc = by_stage.get("staticcheck")
    if sc and sc["results"]:
        rep = sc["results"][-1]
        comp = rep.get("compile") or {}
        comp_entries = comp.get("entries", [])
        print("## Static analysis (jaxpr audit + recompile sentinel + "
              "lint, on-chip compile leg)\n")
        print(md_table([{
            "ok": rep.get("ok"),
            "platform": rep.get("platform"),
            "entries_audited": (rep.get("jaxpr") or {}).get(
                "entries_audited"),
            "lint_files": (rep.get("lint") or {}).get("files_scanned"),
            "sweep_cells": (rep.get("recompile") or {}).get("cells"),
            "compiled_clean": (
                f"{sum(1 for r in comp_entries if r.get('ok'))}/"
                f"{len(comp_entries)}" if comp_entries else None
            ),
            "violations": rep.get("violations_total"),
            "wall_s": rep.get("wall_s"),
        }], [
            "ok", "platform", "entries_audited", "lint_files",
            "sweep_cells", "compiled_clean", "violations", "wall_s",
        ]))
        failed_compiles = [r for r in comp_entries if not r.get("ok")]
        if failed_compiles:
            print("\nentries failing on-chip compile:")
            for r in failed_compiles:
                print(f"- `{r['entry']}`: {r.get('error', '?')}")
        print()

    tel = by_stage.get("telemetry")
    if tel and tel["results"]:
        smokes = [
            r for r in tel["results"] if r.get("kind") == "telemetry_smoke"
        ]
        if smokes:
            s = smokes[-1]
            summ = s.get("summary") or {}
            ring_totals = summ.get("ring_totals") or {}
            total_newly = sum(
                agg.get("newly_infected", 0) for agg in ring_totals.values()
            )
            print("## Telemetry (in-jit metric rings + host spans, "
                  "schema-gated)\n")
            print(md_table([{
                "ok": s.get("ok"),
                "events": summ.get("events"),
                "spans": summ.get("spans"),
                "rings": summ.get("rings"),
                "newly_infected_total": total_newly,
                "expected_receives": s.get("expected_receives"),
                "errors": len(s.get("errors") or []),
            }], [
                "ok", "events", "spans", "rings", "newly_infected_total",
                "expected_receives", "errors",
            ]))
            for err in (s.get("errors") or [])[:5]:
                print(f"- {err}")
            print()

    flightrec = by_stage.get("flightrec")
    if flightrec and flightrec["results"]:
        div = next(
            (r for r in reversed(flightrec["results"])
             if r.get("mode") in ("compare", "inject-fault")),
            None,
        )
        cost = next(
            (r for r in reversed(flightrec["results"])
             if "entries_costed" in r),
            None,
        )
        print("## Flight recorder (digest parity + compiled-cost "
              "ledger)\n")
        if div:
            print(md_table([
                {
                    "pair": p.get("pair"),
                    "result": (
                        p.get("skipped") and f"skipped: {p['skipped']}"
                        or ("fault@{} -> {}".format(
                            p.get("fault_tick"), p.get("located_tick"))
                            if "fault_located" in p else
                            ("DIVERGED @ t=" + str(p.get("tick"))
                             if p.get("diverged") else "clean"))
                    ),
                    "ticks_compared": p.get("compared"),
                }
                for p in div.get("pairs", [])
            ], ["pair", "result", "ticks_compared"]))
            print(f"\nbisector {'OK' if div.get('ok') else 'FAIL'} "
                  f"(mode: {div.get('mode')})\n")
        if cost:
            top = sorted(
                (e for e in cost.get("entries", []) if e.get("ok")),
                key=lambda e: -(e.get("flops") or 0),
            )[:8]
            print(f"compiled-cost ledger on {cost.get('platform')} "
                  f"({cost.get('entries_costed')} entries, "
                  f"{cost.get('total_compile_wall_s')}s total "
                  "compile):\n")
            print(md_table([
                {
                    "entry": e["entry"],
                    "flops": e.get("flops"),
                    "bytes_accessed": e.get("bytes_accessed"),
                    "jaxpr_eqns": e.get("jaxpr_eqns"),
                    "compile_s": e.get("compile_wall_s"),
                }
                for e in top
            ], ["entry", "flops", "bytes_accessed", "jaxpr_eqns",
                "compile_s"]))
            print()

    exch = by_stage.get("exchange")
    if exch and exch["results"]:
        legs = [r for r in exch["results"] if "exchange_mode" in r]
        if legs:
            print("## Frontier exchange: dense vs sparse delta "
                  "(host-mesh rehearsal, legs bitwise-checked)\n")
            print(md_table([
                {
                    "leg": f"{r.get('ring_mode')}/{r.get('exchange_mode')}",
                    "nodes": r.get("nodes"),
                    "topology": r.get("topology"),
                    "edge_cut_pct": r.get("edge_cut_pct"),
                    "modeled_dense_words_per_tick": (
                        (r.get("exchange") or {})
                        .get("modeled_dense_words_per_tick")
                    ),
                    "achieved_delta_words_per_tick": (
                        (r.get("exchange") or {})
                        .get("achieved_delta_words_per_tick")
                    ),
                    "delta_occupancy": (
                        (r.get("exchange") or {}).get("delta_occupancy")
                    ),
                    "wall_s": r.get("wall_s"),
                }
                for r in legs
            ], ["leg", "nodes", "topology", "edge_cut_pct",
                "modeled_dense_words_per_tick",
                "achieved_delta_words_per_tick", "delta_occupancy",
                "wall_s"]))
            dense = next((r for r in legs
                          if r.get("exchange_mode") == "dense"
                          and r.get("ring_mode") == "sharded"), None)
            delta = next((r for r in legs
                          if r.get("exchange_mode") == "delta"), None)
            d_ex = (delta or {}).get("exchange") or {}
            if dense is not None and d_ex.get(
                    "achieved_delta_words_per_tick"):
                ratio = (
                    d_ex.get("modeled_dense_words_per_tick", 0)
                    / d_ex["achieved_delta_words_per_tick"]
                )
                print(f"\ndense/delta wire ratio: {ratio:.2f}x "
                      "(achieved delta words/tick vs the dense "
                      "state-slice exchange on the same run)")
            print()

    hub = by_stage.get("exchange_hub")
    if hub and hub["results"]:
        legs = [r for r in hub["results"] if "exchange_mode" in r]
        if legs:
            print("## Degree-split hub/tail transport (host-mesh "
                  "rehearsal, legs bitwise-checked)\n")
            print(md_table([
                {
                    "leg": (
                        f"{r.get('ring_mode')}/{r.get('exchange_mode')}"
                        + (f"/K{r['async_k']}" if r.get("async_k") else "")
                    ),
                    "nodes": r.get("nodes"),
                    "topology": r.get("topology"),
                    "hub_count": (
                        (r.get("exchange") or {}).get("hub_count")
                    ),
                    "modeled_hub_words_per_tick": (
                        (r.get("exchange") or {})
                        .get("modeled_hub_words_per_tick")
                    ),
                    "achieved_words_per_tick": (
                        (r.get("exchange") or {})
                        .get("achieved_delta_words_per_tick")
                    ),
                    "wall_s": r.get("wall_s"),
                }
                for r in legs
            ], ["leg", "nodes", "topology", "hub_count",
                "modeled_hub_words_per_tick", "achieved_words_per_tick",
                "wall_s"]))
            hleg = next(
                (r for r in legs
                 if (r.get("exchange") or {}).get("mode") == "hub"
                 and not r.get("async_k")), None)
            h_ex = (hleg or {}).get("exchange") or {}
            if h_ex.get("achieved_delta_words_per_tick"):
                ratio = (
                    h_ex.get("modeled_dense_words_per_tick", 0)
                    / h_ex["achieved_delta_words_per_tick"]
                )
                print(f"\ndense/hub wire ratio: {ratio:.2f}x "
                      f"(hub_count {h_ex.get('hub_count')}, crossover_h "
                      f"{h_ex.get('crossover_h')}; achieved hub+tail "
                      "words/tick vs the dense state-slice exchange on "
                      "the same run)")
            if hub.get("pending_tpu"):
                print("\n(host-mesh CPU record — pending_tpu: chip "
                      "numbers owed to the four-chip benchmark cells)")
            print()

    csh = by_stage.get("campaign_sharded")
    if csh and csh["results"]:
        legs = [r for r in csh["results"] if "replica_shards" in r]
        if legs:
            print("## Campaigns × shards (factorized (replicas, nodes) "
                  "mesh, per-replica bitwise-checked)\n")
            print(md_table([
                {
                    "leg": f"{r.get('ring_mode')}/{r.get('exchange_mode')}",
                    "platform": r.get("platform"),
                    "nodes": r.get("nodes"),
                    "topology": r.get("topology"),
                    "mesh": (
                        f"{r.get('replica_shards')}x{r.get('node_shards')}"
                    ),
                    "bitwise": (
                        f"{r.get('bitwise_equal_replicas')}/"
                        f"{r.get('replicas')}"
                    ),
                    "campaign_warm_s/replica": r.get(
                        "campaign_warm_per_replica_s"),
                    "solo_warm_s/replica": r.get(
                        "solo_warm_per_replica_s"),
                    "speedup": r.get("speedup_warm_per_replica"),
                    "fresh_s": r.get("campaign_fresh_s"),
                }
                for r in legs
            ], ["leg", "platform", "nodes", "topology", "mesh", "bitwise",
                "campaign_warm_s/replica", "solo_warm_s/replica",
                "speedup", "fresh_s"]))
            if csh.get("pending_tpu"):
                print("\n(host-mesh CPU record — pending_tpu: chip "
                      "numbers owed to the four-chip benchmark cells)")
            print()

    asy = by_stage.get("async_ticks")
    if asy and asy["results"]:
        legs = [r for r in asy["results"] if "exchange_mode" in r]
        if legs:
            print("## Bounded-staleness async ticks (K-ahead frontiers, "
                  "host-mesh rehearsal; K=1 bitwise == sync, K>=2 "
                  "fixed-point-checked)\n")
            print(md_table([
                {
                    "leg": (
                        f"{r.get('ring_mode')}/{r.get('exchange_mode')}"
                        + (f"/K{r['async_k']}" if r.get("async_k") else "")
                    ),
                    "nodes": r.get("nodes"),
                    "topology": r.get("topology"),
                    "wall_s": r.get("wall_s"),
                    "wall_per_tick_s": r.get("wall_per_tick_s"),
                    "modeled_overlap_fraction": (
                        (r.get("exchange") or {})
                        .get("modeled_overlap_fraction")
                    ),
                }
                for r in legs
            ], ["leg", "nodes", "topology", "wall_s", "wall_per_tick_s",
                "modeled_overlap_fraction"]))
            sync = next(
                (r for r in legs
                 if r.get("ring_mode") == "sharded"
                 and not r.get("async_k")), None)
            best = min(
                (r for r in legs if (r.get("async_k") or 0) >= 2
                 and r.get("wall_per_tick_s")),
                key=lambda r: r["wall_per_tick_s"], default=None)
            if (sync and best and sync.get("wall_per_tick_s")
                    and best["wall_per_tick_s"]):
                ratio = sync["wall_per_tick_s"] / best["wall_per_tick_s"]
                print(f"\nsync/async wall-per-tick ratio: {ratio:.2f}x "
                      f"(best async leg K={best.get('async_k')} vs the "
                      "synchronous sharded exchange on the same run)")
            if asy.get("pending_tpu"):
                print("\n(host-mesh CPU record — pending_tpu: chip "
                      "numbers owed to the four-chip benchmark cells)")
            print()

    srv = by_stage.get("serve")
    if srv and srv["results"]:
        rows = [r for r in srv["results"] if r.get("bench") == "serve"]
        if rows:
            print("## Gossip-as-a-service (continuous-batching server, "
                  "every request bitwise-verified vs solo runs)\n")
            print(md_table(rows, [
                "platform", "requests", "signatures", "slots", "mesh",
                "batches", "requests_per_s", "p50_turnaround_s",
                "p99_turnaround_s", "slot_occupancy", "bitwise_ok",
            ]))
            if srv.get("pending_tpu"):
                print("\n(host-mesh CPU record — pending_tpu: chip "
                      "numbers owed to the four-chip benchmark cells)")
            print()

    for stage, title in (
        ("scale1m", "1M north star (ER p=0.001, 64-share staging plan)"),
        ("scale1m_ba", "1M scale-free (BA m=3)"),
        ("scale1m_full", "1M north star, full config (ER, 4096 shares)"),
    ):
        rec = by_stage.get(stage)
        if rec and rec["results"]:
            print(f"## {title}\n")
            print(md_table(rec["results"], [
                "metric", "value", "unit", "vs_baseline",
            ]))
            print()

    # Judge by each stage's LATEST record (matching the rendering above):
    # a failed-then-rerun-succeeded stage is a success, not a partial.
    failed = [s for s, r in by_stage.items() if not r.get("ok")]
    if failed:
        print(f"**Incomplete battery** — failed/aborted: {failed}. "
              f"Stage stderr tails are in `{os.path.basename(path)}`.")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

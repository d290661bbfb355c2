"""Compiled-cost observatory — flops, bytes, compile time per entry point.

    python scripts/cost_report.py                  # human table
    python scripts/cost_report.py --json           # one JSON line on stdout
    python scripts/cost_report.py --only engine.sync
    python scripts/cost_report.py --exchange       # + dense/delta crossover
    P2P_TELEMETRY=run.jsonl python scripts/cost_report.py   # + counter events

Lowers and compiles every staticcheck-registered entry point on the
default device and harvests what XLA already knows but nobody looks at:
``cost_analysis()`` flops and bytes-accessed, ``memory_analysis()``
temp/argument/output footprints, compile wall time, and jaxpr equation
count (via the auditor's ``iter_eqns`` — the same walk the invariant
rules use). The result is the per-kernel cost ledger: a refactor that
doubles an entry's flops or compile time shows up as a diff in this
report before it shows up as a slow campaign.

When the telemetry sink is enabled each figure is also emitted as a
``counter`` event named ``cost.<entry>.<field>``, so a run report
(scripts/run_report.py) carries the cost ledger of the binary that
produced it. Platform is labeled — CPU figures are CPU
figures, not chip numbers.

``--exchange`` adds the frontier-exchange crossover: one sharded flood
run per topology family under ``exchange="delta"``, reporting modeled
dense vs achieved delta words/tick (the runner's on-device counters)
priced by the shared traffic model
`parallel.exchange.modeled_exchange_words_per_tick`, and which path
wins at that scale.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

COST_FIELDS = (
    "flops", "bytes_accessed", "compile_wall_s", "jaxpr_eqns",
    "temp_bytes", "argument_bytes", "output_bytes",
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _setup_backend() -> None:
    from p2p_gossip_tpu.utils.platform import use_host_devices

    use_host_devices(8)


def _cost_dict(compiled) -> dict:
    """Flops/bytes out of ``cost_analysis()`` — tolerates both the
    list-of-dicts and plain-dict shapes across jax versions, and
    backends that return nothing."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    if "flops" in ca:
        out["flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out


def _memory_dict(compiled) -> dict:
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for field, attr in (
        ("temp_bytes", "temp_size_in_bytes"),
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
    ):
        v = getattr(mem, attr, None)
        if v is not None:
            out[field] = int(v)
    return out


def cost_entry(entry) -> dict:
    """Lower + compile one registered entry and harvest its cost row.
    Never raises — a failing entry gets an ``error`` field."""
    import jax

    from p2p_gossip_tpu.staticcheck.jaxpr_audit import iter_eqns

    row: dict = {"entry": entry.name}
    try:
        spec = entry.spec()
        fn = spec.fn if spec.fn is not None else entry.fn
        wrapped = lambda *args, _fn=fn, _kw=spec.kwargs: _fn(*args, **_kw)  # noqa: E731
        closed = jax.make_jaxpr(wrapped)(*spec.args)
        row["jaxpr_eqns"] = sum(1 for _ in iter_eqns(closed.jaxpr))
        t0 = time.monotonic()
        compiled = jax.jit(wrapped).lower(*spec.args).compile()
        row["compile_wall_s"] = round(time.monotonic() - t0, 3)
        row.update(_cost_dict(compiled))
        row.update(_memory_dict(compiled))
        row["ok"] = True
    except Exception as e:
        row["ok"] = False
        row["error"] = f"{type(e).__name__}: {e}"[:500]
    return row


def run_cost_report(only: str | None = None) -> dict:
    """The full ledger: one row per registered entry (filtered by the
    ``only`` substring), counter events when the sink is on."""
    import jax

    from p2p_gossip_tpu import telemetry
    from p2p_gossip_tpu.staticcheck import entrypoints, registry

    entrypoints.load_all()
    entries = [
        e for e in registry.all_entries()
        if only is None or only in e.name
    ]
    rows = []
    for entry in entries:
        row = cost_entry(entry)
        rows.append(row)
        if telemetry.enabled() and row.get("ok"):
            for field in COST_FIELDS:
                if field in row:
                    telemetry.emit_counter(
                        f"cost.{entry.name}.{field}", row[field]
                    )
        log(f"cost: {entry.name}: "
            + (f"flops={row.get('flops', 0):.0f} "
               f"bytes={row.get('bytes_accessed', 0):.0f} "
               f"eqns={row.get('jaxpr_eqns', '?')} "
               f"compile={row.get('compile_wall_s', 0):.2f}s"
               if row.get("ok") else f"ERROR {row.get('error')}"))
    ok = all(r.get("ok") for r in rows) and bool(rows)
    return {
        "ok": ok,
        "platform": jax.devices()[0].platform,
        "entries_costed": len(rows),
        "total_compile_wall_s": round(
            sum(r.get("compile_wall_s", 0.0) for r in rows), 2
        ),
        "entries": rows,
    }


#: Topology families the exchange crossover is priced on — one
#: representative small instance each (CPU-cheap; the large-N numbers
#: come from scripts/mesh_rehearsal.py --exchange).
EXCHANGE_FAMILIES = ("erdos_renyi", "barabasi_albert", "watts_strogatz",
                     "ring")


def _exchange_family_graph(family: str, n: int, seed: int):
    from p2p_gossip_tpu.models import topology

    if family == "erdos_renyi":
        return topology.erdos_renyi(n, 0.08, seed=seed)
    if family == "barabasi_albert":
        return topology.barabasi_albert(n, 2, seed=seed)
    if family == "watts_strogatz":
        return topology.watts_strogatz(n, 4, 0.1, seed=seed)
    if family == "ring":
        return topology.ring_graph(n)
    raise ValueError(f"unknown family {family!r}")


def run_exchange_report(
    n: int = 96, horizon: int = 24, seed: int = 0,
    families: tuple[str, ...] | None = None,
) -> dict:
    """Modeled-vs-achieved exchange words per tick, per topology family.

    Runs the sharded flood runner once per family with the sparse
    frontier-delta exchange, once with the degree-split hub/tail
    transport (``exchange="hub"``, an 8-row hub set forced so the split
    is exercised at this tiny scale — real graphs let the modeled
    crossover in ``hub.crossover_h`` choose), and folds the runner's
    achieved-traffic counters (``stats.extra['exchange']``) against the
    shared model (`parallel.exchange.modeled_exchange_words_per_tick` —
    the same formula the engines price with). ``winner``
    names the cheapest path per family at this scale; the crossovers
    are visible as ``dense_over_delta`` and ``delta_over_hub``
    (achieved-word ratios — > 1 means the sparser path pays for
    itself)."""
    import jax
    import numpy as np

    from p2p_gossip_tpu import telemetry
    from p2p_gossip_tpu.models.generation import Schedule
    from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim
    from p2p_gossip_tpu.parallel.mesh import make_mesh

    n_dev = len(jax.devices())
    if n_dev < 4:
        return {"ok": True, "skipped": f"needs >= 4 devices, have {n_dev}"}
    mesh = make_mesh(4, n_dev // 4)
    rows = []
    for family in families or EXCHANGE_FAMILIES:
        graph = _exchange_family_graph(family, n, seed)
        rng = np.random.default_rng(seed)
        origins = rng.integers(0, graph.n, 8).astype(np.int32)
        gens = (np.arange(8, dtype=np.int32) % 3) * 2
        sched = Schedule(graph.n, origins, gens)
        row: dict = {"family": family, "n": graph.n}
        try:
            stats = run_sharded_sim(
                graph, sched, horizon, mesh, chunk_size=32,
                exchange="delta",
            )
            ex = dict(stats.extra.get("exchange", {}))
            dense = ex.get("modeled_dense_words_per_tick", 0)
            achieved = ex.get("achieved_delta_words_per_tick", 0.0)
            row.update(ex)
            hub_stats = run_sharded_sim(
                graph, sched, horizon, mesh, chunk_size=32,
                exchange="hub", hub_rows=8,
            )
            hub_ex = dict(hub_stats.extra.get("exchange", {}))
            hub_achieved = hub_ex.get("achieved_delta_words_per_tick", 0.0)
            row["hub"] = hub_ex
            costs = {"dense": dense or None, "delta": achieved or None,
                     "hub": hub_achieved or None}
            row["winner"] = min(
                (k for k, v in costs.items() if v),
                key=lambda k: costs[k], default="dense",
            )
            row["dense_over_delta"] = round(
                dense / achieved, 3) if achieved else None
            row["delta_over_hub"] = round(
                achieved / hub_achieved, 3) if hub_achieved else None
            row["ok"] = True
        except Exception as e:  # noqa: BLE001 - ledger must not die
            row["ok"] = False
            row["error"] = f"{type(e).__name__}: {e}"[:500]
        rows.append(row)
        if telemetry.enabled() and row.get("ok"):
            for field in ("modeled_dense_words_per_tick",
                          "modeled_delta_words_per_tick",
                          "achieved_delta_words_per_tick"):
                if row.get(field) is not None:
                    telemetry.emit_counter(
                        f"cost.exchange.{family}.{field}", row[field]
                    )
        log(f"exchange: {family}: "
            + (f"dense={row.get('modeled_dense_words_per_tick')} "
               f"delta~{row.get('achieved_delta_words_per_tick', 0):.1f} "
               f"hub~{(row.get('hub') or {}).get('achieved_delta_words_per_tick', 0):.1f} "
               f"winner={row.get('winner')}"
               if row.get("ok") else f"ERROR {row.get('error')}"))
    return {
        "ok": all(r.get("ok") for r in rows),
        "platform": jax.devices()[0].platform,
        "families": rows,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="one JSON line on stdout instead of the table")
    ap.add_argument("--only", metavar="SUBSTR", default=None,
                    help="restrict to entries whose name contains SUBSTR")
    ap.add_argument("--exchange", action="store_true",
                    help="also price the dense/delta frontier exchange "
                    "per topology family (modeled vs achieved words/tick)")
    ap.add_argument("--exchange-only", action="store_true",
                    help="skip the entry ledger; print just the exchange "
                    "crossover JSON")
    ap.add_argument("--families", default=None, metavar="A,B",
                    help="comma list of topology families for the "
                    "exchange crossover (default: all)")
    args = ap.parse_args()

    _setup_backend()
    fams = tuple(args.families.split(",")) if args.families else None
    if args.exchange_only:
        ex = run_exchange_report(families=fams)
        print(json.dumps(ex))
        return 0 if ex["ok"] else 1
    report = run_cost_report(only=args.only)
    if args.exchange:
        report["exchange"] = run_exchange_report(families=fams)
        report["ok"] = report["ok"] and report["exchange"]["ok"]

    if args.json:
        print(json.dumps(report))
    else:
        print(f"cost report: {report['entries_costed']} entries on "
              f"{report['platform']} "
              f"(total compile {report['total_compile_wall_s']}s)")
        hdr = (f"{'entry':<48} {'flops':>12} {'bytes':>12} "
               f"{'eqns':>6} {'compile_s':>9}")
        print(hdr)
        print("-" * len(hdr))
        for r in report["entries"]:
            if not r.get("ok"):
                print(f"{r['entry']:<48} ERROR: {r.get('error')}")
                continue
            print(f"{r['entry']:<48} "
                  f"{r.get('flops', 0):>12.0f} "
                  f"{r.get('bytes_accessed', 0):>12.0f} "
                  f"{r.get('jaxpr_eqns', 0):>6d} "
                  f"{r.get('compile_wall_s', 0):>9.3f}")
        ex = report.get("exchange")
        if ex is not None:
            if "skipped" in ex:
                print(f"exchange crossover: SKIPPED ({ex['skipped']})")
            else:
                print("exchange crossover (words/tick, "
                      f"{ex['platform']}):")
                for r in ex["families"]:
                    if not r.get("ok"):
                        print(f"  {r['family']:<16} ERROR: "
                              f"{r.get('error')}")
                        continue
                    print(
                        f"  {r['family']:<16} "
                        f"dense={r.get('modeled_dense_words_per_tick')} "
                        f"delta={r.get('achieved_delta_words_per_tick', 0):.1f} "
                        f"(cap={r.get('capacity')}, "
                        f"occ={r.get('delta_occupancy', 0):.3f}) "
                        f"-> {r.get('winner')}"
                    )
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

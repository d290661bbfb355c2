"""Render a telemetry stream into a human run report.

    python scripts/run_report.py RUN.jsonl              # human report
    python scripts/run_report.py RUN.jsonl --json       # summary JSON line
    python scripts/run_report.py RUN.jsonl --validate   # schema gate (rc 1)
    python scripts/run_report.py RUN.jsonl --chrome OUT.json  # Perfetto
    python scripts/run_report.py --capture-smoke        # run a tiny flood
                                                        # with --telemetry,
                                                        # validate + report

Sections: run metadata, the span waterfall (host phases, nested by
depth), total span time by phase, one block per harvested metric ring
(per-tick frontier curve, messages/tick, loss drops), the flight
recorder's digest streams and progress beats, the compiled-cost ledger
(``cost.*`` counters from scripts/cost_report.py), and the jit-cache
counter samples (the PR-3 recompile-sentinel counters). Every section
is optional — a spans-only stream (bench keeps device rings off)
renders just the waterfall; a ring whose metric hits the uint32
saturation sentinel (4294967295) gets a wrap warning instead of a
silently-absurd total. The schema is
`p2p_gossip_tpu/telemetry/schema.py`; ``--chrome`` output opens in
chrome://tracing or https://ui.perfetto.dev (docs/OBSERVABILITY.md).

``--capture-smoke`` is scripts/ci_tier1.sh's entry point: it
runs a small flood-coverage simulation through the real CLI with
``--telemetry``, validates the emitted JSONL against the schema, checks
the ring's tick sums against the run's final counters, round-trips the
Chrome export, and prints one summary JSON line (``telemetry_smoke``).
Exit 0 iff every check passed.
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from p2p_gossip_tpu.telemetry import chrometrace, schema  # noqa: E402

SPARK = "▁▂▃▄▅▆▇█"
U32_MAX = 0xFFFFFFFF  # rings.u32sum saturation sentinel


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def sparkline(series) -> str:
    if not series:
        return ""
    peak = max(series) or 1
    return "".join(SPARK[min(len(SPARK) - 1, v * len(SPARK) // (peak + 1))]
                   for v in series)


def summarize(events) -> dict:
    """Aggregate a stream into the summary dict the JSON mode prints
    (and --capture-smoke embeds)."""
    spans = [e for e in events if e.get("type") == "span"]
    rings = [e for e in events if e.get("type") == "ring"]
    counters = [e for e in events if e.get("type") == "counter"]
    digests = [e for e in events if e.get("type") == "digest"]
    progress = [e for e in events if e.get("type") == "progress"]
    meta = next((e for e in events if e.get("type") == "meta"), None)
    span_s: dict = {}
    for s in spans:
        span_s[s["name"]] = round(span_s.get(s["name"], 0.0) + s["dur"], 4)
    ring_totals: dict = {}
    wrap_warnings: list[str] = []
    for r in rings:
        agg = ring_totals.setdefault(
            r["kernel"], {c: 0 for c in schema.METRIC_COLUMNS} | {"rings": 0}
        )
        agg["rings"] += 1
        for col in schema.METRIC_COLUMNS:
            series = r.get("metrics", {}).get(col, [])
            agg[col] += sum(series)
            # rings.u32sum saturates at the uint32 max instead of
            # wrapping — a tick stuck at the sentinel means the real
            # figure is LARGER and every total containing it is a floor.
            if any(v == U32_MAX for v in series):
                wrap_warnings.append(
                    f"{r['kernel']}.{col}: tick value saturated at "
                    f"2^32-1 (t0={r.get('t0', 0)}) — totals are lower "
                    "bounds"
                )
    digest_streams = sorted({
        (d.get("kernel"), d.get("chunk"), d.get("replica"), d.get("shard"))
        for d in digests
    }, key=str)
    return {
        "events": len(events),
        "spans": len(spans),
        "rings": len(rings),
        "digests": len(digests),
        "digest_streams": len(digest_streams),
        "progress": len(progress),
        "counters": {c["name"]: c["value"] for c in counters},
        "span_s_by_phase": span_s,
        "ring_totals": ring_totals,
        "wrap_warnings": wrap_warnings,
        "run": (meta or {}).get("run", {}),
    }


def render(events, out=sys.stdout) -> None:
    summary = summarize(events)
    w = out.write
    run = summary["run"]
    w("=== Telemetry run report ===\n")
    if run:
        w(f"run: {run.get('utc', '?')}  pid {run.get('pid', '?')}\n")
        if run.get("argv"):
            w(f"argv: {' '.join(run['argv'])}\n")
    spans = sorted(
        (e for e in events if e.get("type") == "span"),
        key=lambda s: s["ts"],
    )
    if spans:
        w("\n--- span waterfall (host phases) ---\n")
        for s in spans:
            attrs = s.get("attrs", {})
            label = ", ".join(f"{k}={v}" for k, v in attrs.items())
            w(
                f"{s['ts']:9.3f}s  {'  ' * s.get('depth', 0)}{s['name']}"
                f"  {s['dur'] * 1e3:9.2f} ms"
                + (f"  ({label})" if label else "")
                + "\n"
            )
        w("\n--- total span time by phase ---\n")
        for name, total in sorted(
            summary["span_s_by_phase"].items(), key=lambda kv: -kv[1]
        ):
            w(f"  {name:24s} {total * 1e3:10.2f} ms\n")
    rings = [e for e in events if e.get("type") == "ring"]
    if rings:
        w("\n--- device metric rings (per-tick, harvested per chunk) ---\n")
        for r in rings:
            prov = ", ".join(
                f"{k}={r[k]}" for k in ("chunk", "replica", "seed", "shard")
                if k in r
            )
            w(f"{r['kernel']}" + (f" [{prov}]" if prov else "")
              + f": {r['ticks']} tick(s) from t={r['t0']}\n")
            m = r.get("metrics", {})
            frontier = m.get("frontier_bits", [])
            if frontier:
                peak_t = max(range(len(frontier)), key=frontier.__getitem__)
                w(f"  frontier/tick: {sparkline(frontier)} "
                  f"(peak {frontier[peak_t]} @ t={r['t0'] + peak_t})\n")
            for col in schema.METRIC_COLUMNS:
                series = m.get(col, [])
                total = sum(series)
                mean = total / max(len(series), 1)
                sat = "  [SATURATED]" if any(
                    v == U32_MAX for v in series
                ) else ""
                w(f"  {col:15s} total {total:>12}  mean/tick {mean:>10.1f}"
                  f"  max {max(series) if series else 0:>10}{sat}\n")
    if summary["wrap_warnings"]:
        w("\n--- WARNING: uint32 metric saturation ---\n")
        for msg in summary["wrap_warnings"]:
            w(f"  {msg}\n")
    digests = [e for e in events if e.get("type") == "digest"]
    if digests:
        w("\n--- flight recorder: per-tick state digests ---\n")
        for d in digests:
            prov = ", ".join(
                f"{k}={d[k]}" for k in ("chunk", "replica", "seed", "shard")
                if k in d
            )
            values = d.get("values", [])
            head = f"{values[0]:08x}" if values else "-"
            tail = f"{values[-1]:08x}" if values else "-"
            w(f"{d['kernel']}" + (f" [{prov}]" if prov else "")
              + f": {d.get('ticks', len(values))} tick(s) from "
              f"t={d.get('t0', 0)}  digest {head}..{tail}\n")
        w("  (compare streams across engines: scripts/divergence.py)\n")
    progress = [e for e in events if e.get("type") == "progress"]
    if progress:
        w("\n--- progress beats (per-chunk liveness) ---\n")
        for p in progress:
            parts = [f"{p.get('elapsed_s', 0.0):8.3f}s",
                     p.get("kernel", "?")]
            if "chunk" in p:
                total = p.get("chunks_total")
                parts.append(f"chunk {p['chunk']}"
                             + (f"/{total}" if total is not None else ""))
            if "ticks_done" in p:
                parts.append(f"{p['ticks_done']} ticks")
            if "coverage_pct" in p:
                parts.append(f"{p['coverage_pct']:.1f}% coverage")
            if "digest_head" in p:
                parts.append(f"digest {p['digest_head']}")
            w("  " + "  ".join(str(x) for x in parts) + "\n")
    counters = [e for e in events if e.get("type") == "counter"]
    cost = [c for c in counters if c["name"].startswith("cost.")]
    other = [c for c in counters if not c["name"].startswith("cost.")]
    if cost:
        w("\n--- compiled-cost ledger (scripts/cost_report.py) ---\n")
        by_entry: dict = {}
        for c in cost:
            entry, _, field = c["name"][len("cost."):].rpartition(".")
            by_entry.setdefault(entry, {})[field] = c["value"]
        for entry, fields in sorted(by_entry.items()):
            w(f"  {entry}\n")
            for field, val in sorted(fields.items()):
                w(f"    {field:16s} {val}\n")
    if other:
        w("\n--- counters (jit-cache sentinel samples) ---\n")
        for c in other:
            w(f"  {c['name']:48s} {c['value']}\n")
    if not (spans or rings or digests or progress or counters):
        w("\n(no span/ring/digest/progress/counter events — empty or "
          "metadata-only stream)\n")


def _capture_smoke(args) -> int:
    """Run a tiny flood through the real CLI with --telemetry and gate
    the whole pipeline: JSONL schema, ring-vs-counter consistency, and
    the Chrome-trace round trip. One summary JSON line on stdout."""
    from p2p_gossip_tpu.utils.cli import run as cli_run

    result: dict = {"kind": "telemetry_smoke", "ok": False}
    with tempfile.TemporaryDirectory(prefix="p2p_tel_smoke_") as tmp:
        stream = os.path.join(tmp, "telemetry.jsonl")
        argv = [
            "--numNodes", str(args.nodes),
            "--connectionProb", "0.05",
            "--simTime", "0.25",
            "--Latency", "5",
            "--floodCoverage", str(args.shares),
            "--seed", "0",
            "--telemetry", stream,
            "--json",
        ]
        result["argv"] = argv
        import contextlib
        import io

        stdout = io.StringIO()
        with contextlib.redirect_stdout(stdout):
            rc = cli_run(argv)
        cli_json = None
        for line in stdout.getvalue().splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    cli_json = json.loads(line)
                except json.JSONDecodeError:
                    pass
        result["cli_rc"] = rc
        errors: list[str] = []
        if rc != 0:
            errors.append(f"CLI exited {rc}")
        if not os.path.exists(stream):
            errors.append("no telemetry stream written")
        else:
            with open(stream, encoding="utf-8") as f:
                lines = f.readlines()
            errors.extend(schema.validate_stream(lines))
            events = chrometrace.load_stream(stream)
            summary = summarize(events)
            result["summary"] = summary
            if not summary["rings"]:
                errors.append("no ring events in the stream")
            if not summary["spans"]:
                errors.append("no span events in the stream")
            # Per-tick metrics must reconcile with the run's counters:
            # summed newly_infected across rings == total received.
            newly = sum(
                agg["newly_infected"]
                for agg in summary["ring_totals"].values()
            )
            if cli_json is not None:
                # The flood-coverage CLI JSON has no received total;
                # derive it from the final coverage curve instead:
                # sum(final coverage) - shares = receives (each origin
                # already held its own share).
                fc = cli_json.get("final_coverage", {})
                expect = None
                if fc and "mean" in fc:
                    expect = int(round(fc["mean"] * args.shares)) - args.shares
                result["newly_infected_total"] = newly
                result["expected_receives"] = expect
                if expect is not None and newly != expect:
                    errors.append(
                        f"ring newly_infected {newly} != expected "
                        f"receives {expect}"
                    )
            # Chrome round trip.
            trace = chrometrace.to_chrome_trace(events)
            back = chrometrace.spans_from_chrome(trace)
            if len(back) != summary["spans"]:
                errors.append(
                    f"chrome round-trip lost spans "
                    f"({len(back)} != {summary['spans']})"
                )
        result["errors"] = errors
        result["ok"] = not errors
    print(json.dumps(result))
    return 0 if result["ok"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("stream", nargs="?", help="telemetry JSONL file")
    ap.add_argument("--json", action="store_true",
                    help="one summary JSON line instead of the report")
    ap.add_argument("--validate", action="store_true",
                    help="schema-validate the stream; exit 1 on any error")
    ap.add_argument("--chrome", metavar="OUT.json", default="",
                    help="also export a Chrome-trace file (Perfetto)")
    ap.add_argument("--capture-smoke", action="store_true",
                    help="run a tiny flood with --telemetry, validate the "
                    "stream end to end (scripts/ci_tier1.sh)")
    ap.add_argument("--nodes", type=int, default=96,
                    help="capture-smoke graph size")
    ap.add_argument("--shares", type=int, default=4,
                    help="capture-smoke flooded shares")
    args = ap.parse_args()

    if args.capture_smoke:
        return _capture_smoke(args)
    if not args.stream:
        ap.error("pass a telemetry JSONL file (or --capture-smoke)")
    if not os.path.exists(args.stream):
        log(f"error: {args.stream} not found")
        return 2

    if args.validate:
        with open(args.stream, encoding="utf-8") as f:
            errors = schema.validate_stream(f)
        if errors:
            for e in errors:
                log(f"schema: {e}")
            print(json.dumps({"ok": False, "errors": errors}))
            return 1
        print(json.dumps({"ok": True, "errors": []}))
        return 0

    events = chrometrace.load_stream(args.stream)
    if args.chrome:
        chrometrace.write_chrome_trace(events, args.chrome)
        log(f"chrome trace written to {args.chrome} "
            "(open in chrome://tracing or ui.perfetto.dev)")
    if args.json:
        print(json.dumps(summarize(events)))
    else:
        render(events)
    return 0


if __name__ == "__main__":
    sys.exit(main())

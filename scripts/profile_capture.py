"""One-shot profiled bench + trace parse (VERDICT round-4 item #4).

Every perf claim in the repo inherits the error bar of the modeled
roofline (`DeviceGraph.hbm_bytes_per_tick`, engine/sync.py): achieved
GB/s figures are modeled-bytes / measured-wall. This script calibrates
that model against the chip's own counters, once, on hardware:

1. runs bench.py with its opt-in profiler capture enabled
   (P2P_BENCH_PROFILE_DIR — the timed pass runs under
   jax.profiler.trace and the JSON row is stamped "profiled");
2. parses the captured XPlane trace with the xprof converter
   (roofline_model + overview tools: per-HLO-op self time, measured
   memory bandwidth, HBM bandwidth);
3. emits the bench row (pass-through) plus a `profile_summary` JSON
   line: total device time, measured HBM bytes (sum over ops of
   hbm_bw x self_time), the bench's modeled bytes, and the calibration
   factor measured/modeled;
4. gzips the xplane.pb into docs/artifacts/ so the profile itself is a
   committed artifact, not just a derived number.

Every parse step is defensive: a trace laid out differently from the
one this parser was written against still yields the bench row, the
committed capture, and a summary row carrying the parse error —
evidence never goes to zero.

Usage:
  python scripts/profile_capture.py            # real chip via bench.py
  python scripts/profile_capture.py --smoke    # CPU smoke (CI contract)
"""

import argparse
import glob
import gzip
import json
import os
import shutil
import subprocess
import sys
import tempfile
from datetime import datetime, timezone

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ART_DIR = os.path.join(REPO, "docs", "artifacts")


def log(msg: str) -> None:
    print(f"[profile] {msg}", file=sys.stderr, flush=True)


def gviz_rows(tool_json: str | bytes) -> tuple[list[dict], dict]:
    """Flatten one gviz DataTable JSON into [{col_id: value}] + props.

    The converter emits either a bare table or a list of tables; the
    first table carries the per-op rows for every tool used here.
    """
    obj = json.loads(
        tool_json if isinstance(tool_json, str) else tool_json.decode()
    )
    tbl = obj[0] if isinstance(obj, list) else obj
    cols = [c["id"] for c in tbl.get("cols", [])]
    rows = []
    for r in tbl.get("rows", []):
        cells = [c.get("v") if isinstance(c, dict) else None for c in r["c"]]
        rows.append(dict(zip(cols, cells)))
    return rows, tbl.get("p", {})


def fnum(x) -> float:
    """gviz cells arrive as float, int, or formatted string — or None."""
    if x is None:
        return 0.0
    try:
        return float(str(x).replace(",", ""))
    except ValueError:
        return 0.0


def _is_op_row(r: dict) -> bool:
    # Per-op rows have rank > 0; aggregate rows (step="Total"/program)
    # and the IDLE pseudo-op must not enter the sums. Shared by the
    # fallback decision in summarize_trace and the aggregation filter in
    # summarize_rows so the two can never judge different rows.
    return fnum(r.get("rank")) > 0 and r.get("operation") != "IDLE"


def _infeed_flag(r: dict) -> bool:
    # gviz cells can arrive bool, string ("True"/"False"), or
    # numeric (1/0) — see fnum's docstring; bool("False") is True,
    # so normalize via str or a string/numeric-typed table would
    # silently re-double the sums.
    return str(r.get("include_infeed_outfeed")).lower() in (
        "true", "1", "1.0"
    )


def dedup_per_flag_copies(op_rows: list[dict], summary: dict) -> list[dict]:
    """Drop the roofline table's second per-flag copy.

    The table arrives TWICE — one full copy per include_infeed_outfeed
    setting (verified on the committed 20260801T085701Z capture: 258
    rows = 129 ops x exactly 2, the two copies differing only in that
    flag). Summing both doubles self-time and bytes; keep the
    infeed-excluded copy (device compute only — infeed is host
    transfer, not engine work). Factored out of
    summarize_trace so the 2x fix is unit-testable without an xprof
    trace (tests/test_scripts.py).
    """
    flags = {_infeed_flag(r) for r in op_rows}
    if len(flags) <= 1:
        if flags == {True}:
            # Only the infeed-INCLUDED copy is present: nothing to drop,
            # but the sums now follow the opposite convention from the
            # kept-copy (infeed-excluded) norm — stamp it so downstream
            # readers aren't left to infer which convention applies
            # (round-5 advisor finding).
            summary["dedup_note"] = "only infeed-included copy present"
        return op_rows
    kept = [r for r in op_rows if not _infeed_flag(r)]
    # A kept copy at/below half is expected (the infeed-included copy
    # may legitimately carry extra infeed/outfeed-only rows); an empty
    # or larger-than-half kept copy means the table layout changed —
    # keep the sums but say so.
    if not kept or len(kept) * 2 > len(op_rows):
        summary["dedup_note"] = (
            f"per-flag split unexpected: kept {len(kept)} of "
            f"{len(op_rows)} rows"
        )
    return kept


def summarize_rows(rows: list[dict], props: dict, summary: dict) -> dict:
    """Aggregate per-op rows (either tool) into the summary dict —
    split from summarize_trace so synthetic gviz rows can exercise the
    aggregation (incl. the per-flag dedup) in CI, where real TPU
    roofline tables never appear.
    """
    op_rows = dedup_per_flag_copies(
        [r for r in rows if _is_op_row(r)], summary
    )
    total_self_us = sum(fnum(r.get("total_self_time")) for r in op_rows)
    hbm_bytes = sum(
        fnum(r.get("hbm_bw")) * fnum(r.get("total_self_time")) * 1e3
        for r in op_rows
    )
    measured_bytes = sum(
        fnum(r.get("measured_memory_bw")) * fnum(r.get("total_self_time"))
        * 1e3
        for r in op_rows
    )
    summary.update(
        op_rows=len(op_rows),
        ops_with_hbm_bw=sum(1 for r in op_rows if fnum(r.get("hbm_bw")) > 0),
        total_self_time_us=round(total_self_us, 1),
        measured_hbm_bytes=round(hbm_bytes),
        measured_mem_bytes=round(measured_bytes),
        peak_hbm_bw_gbps=fnum(props.get("peak_hbm_bw")),
        device_type=props.get("device_type", ""),
        top_ops=[
            {
                "op": r.get("operation"),
                "category": r.get("category"),
                "self_us": fnum(r.get("total_self_time")),
                "hbm_gbps": fnum(r.get("hbm_bw")),
                "bound_by": r.get("bound_by"),
            }
            for r in sorted(
                op_rows,
                key=lambda r: -fnum(r.get("total_self_time")),
            )[:10]
        ],
    )
    return summary


def summarize_trace(pb_path: str) -> dict:
    """Aggregate measured op time + HBM bytes from one xplane.pb.

    Bytes come from the roofline_model tool's per-op rows:
    hbm_bw [GB/s] x total_self_time [us] = bytes x 1e-3. Ops with no
    HBM figure (CPU traces; infeed) contribute zero — the summary
    records how many ops carried a nonzero figure so a reader can tell
    "measured 0 bytes" from "tool had no counters".
    """
    from xprof.convert import raw_to_tool_data as rtd

    summary: dict = {"trace": os.path.basename(pb_path)}
    rows, props = gviz_rows(
        rtd.xspace_to_tool_data([pb_path], "roofline_model", {})[0]
    )
    # The tool emits aggregate rows (step="Total"/program) alongside
    # per-op rows (rank > 0); only per-op rows sum without double count.
    summary["tool"] = "roofline_model"
    if not any(_is_op_row(r) for r in rows):
        # CPU traces leave the roofline
        # table empty; hlo_stats carries the same self-time +
        # hbm_bw/measured_memory_bw columns per HLO op.
        rows, _ = gviz_rows(
            rtd.xspace_to_tool_data([pb_path], "hlo_stats", {})[0]
        )
        for r in rows:  # hlo_stats names the op column differently —
            r.setdefault("operation", r.get("hlo_op_name"))  # alias BEFORE
        summary["tool"] = "hlo_stats"  # the IDLE filter in
        # summarize_rows, or IDLE rows slip through it
    return summarize_rows(rows, props, summary)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CPU smoke shapes (exercises capture+parse only)")
    ap.add_argument("--art-dir", default=ART_DIR,
                    help="where the gzipped capture + summary land")
    ap.add_argument("--keep-trace-mb", type=float, default=64.0,
                    help="skip committing captures gzipping above this")
    args = ap.parse_args()

    stamp = datetime.now(timezone.utc).strftime("%Y%m%dT%H%M%SZ")
    trace_dir = tempfile.mkdtemp(prefix="p2p_profile_")
    env = dict(os.environ)
    env["P2P_BENCH_PROFILE_DIR"] = trace_dir
    if args.smoke:
        env["P2P_BENCH_SMOKE"] = "1"
        # Forced, not setdefault: a smoke run is a host run.
        env["JAX_PLATFORMS"] = "cpu"

    # bench.py owns the device check / JSON contract and holds the chip
    # (this process never touches JAX before it exits); this wrapper
    # only adds the capture env and the parse. Pass stderr
    # through so the battery record keeps bench's own diagnostics.
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, cwd=REPO, capture_output=True, text=True,
    )
    sys.stderr.write(proc.stderr)
    bench_rows = []
    for line in proc.stdout.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            bench_rows.append(json.loads(line))
        except json.JSONDecodeError:
            log(f"non-JSON bench stdout: {line[:120]}")
    for row in bench_rows:
        print(json.dumps(row), flush=True)
    if proc.returncode != 0:
        log(f"bench.py rc={proc.returncode}; no trace to parse")
        shutil.rmtree(trace_dir, ignore_errors=True)
        return proc.returncode

    summary: dict = {"kind": "profile_summary", "utc_stamp": stamp}
    # The bench row's metric names the platform it actually ran on —
    # carry it so the summary (and the battery report) self-describe
    # CPU vs TPU, per the repo's labeling discipline.
    bench_metric = bench_rows[0]["metric"] if bench_rows else ""
    summary["bench_metric"] = bench_metric
    if not args.smoke and "CPU" in bench_metric:
        # JAX_PLATFORMS=cpu turned the profiled pass into bench.py's
        # reduced CPU config. That trace answers nothing about HBM: do
        # NOT commit it as chip evidence, and exit nonzero so the
        # battery records the stage not-ok and --skip-done runs it
        # again instead of latching a CPU number as the calibration
        # (round-5 review finding).
        summary["error"] = (
            "bench ran on the host CPU; no on-chip trace — stage must "
            "run again on the chip"
        )
        log(summary["error"])
        print(json.dumps(summary), flush=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        return 1
    pbs = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not pbs:
        summary["error"] = "no xplane.pb produced under the profile dir"
        print(json.dumps(summary), flush=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        return 1

    pb = pbs[-1]
    raw_mb = os.path.getsize(pb) / 1e6
    os.makedirs(args.art_dir, exist_ok=True)
    if raw_mb <= args.keep_trace_mb:
        gz = os.path.join(args.art_dir, f"profile_{stamp}.xplane.pb.gz")
        with open(pb, "rb") as fin, gzip.open(gz, "wb") as fout:
            shutil.copyfileobj(fin, fout)
        summary["capture"] = os.path.relpath(gz, REPO)
        summary["capture_raw_mb"] = round(raw_mb, 1)
        log(f"capture committed: {gz} ({raw_mb:.1f} MB raw)")
    else:
        summary["capture"] = None
        summary["capture_raw_mb"] = round(raw_mb, 1)
        log(f"capture too large to commit ({raw_mb:.1f} MB); parsed only")

    try:
        summary.update(summarize_trace(pb))
    except Exception as e:  # parse failure must not lose the capture
        summary["error"] = f"{type(e).__name__}: {e}"
        # Summary-row contract: the aggregate fields are PRESENT with
        # explicit zeros when the parser cannot run at all (e.g. an
        # image without xprof), so consumers read "no measured data"
        # from ops_with_hbm_bw/error instead of hitting missing keys —
        # the same shape a CPU trace with no device-plane rows produces.
        summary.setdefault("op_rows", 0)
        summary.setdefault("ops_with_hbm_bw", 0)
        summary.setdefault("total_self_time_us", 0)
        summary.setdefault("measured_hbm_bytes", 0)
        summary.setdefault("measured_mem_bytes", 0)

    # Calibration, two ways: a bandwidth ratio (measured bytes over the
    # trace's busy time vs the bench's modeled-bytes-over-wall), and —
    # when the bench row carries modeled_bytes_total — a clock-free
    # bytes-to-bytes ratio, which is the cleaner figure.
    for row in bench_rows:
        if "achieved_gbps" in row and row.get("profiled"):
            if summary.get("total_self_time_us", 0) > 0:
                meas_gbps = (
                    summary.get("measured_hbm_bytes", 0)
                    / (summary["total_self_time_us"] * 1e-6) / 1e9
                )
                summary["measured_hbm_gbps_over_self_time"] = round(
                    meas_gbps, 1
                )
                summary["modeled_achieved_gbps"] = row["achieved_gbps"]
                if row["achieved_gbps"]:
                    summary["measured_over_modeled"] = round(
                        meas_gbps / row["achieved_gbps"], 3
                    )
                # Bytes-to-bytes, clock-free: the bench row carries the
                # model's total bytes for the timed pass
                # (ticks x hbm_bytes_per_tick), a fixed per-run figure.
                # Comparing it to the trace's measured byte sum isolates
                # model byte-undercounting from device idle time, which
                # the bandwidth ratio above conflates with it (the
                # profiled bench was busy 1.27 s of its 1.53 s wall).
                modeled_bytes = row.get("modeled_bytes_total", 0)
                if modeled_bytes:
                    summary["modeled_bytes_total"] = modeled_bytes
                    summary["measured_over_modeled_bytes"] = round(
                        summary.get("measured_hbm_bytes", 0) / modeled_bytes,
                        3,
                    )
            break

    print(json.dumps(summary), flush=True)
    summary_path = os.path.join(args.art_dir, f"profile_{stamp}_summary.json")
    with open(summary_path, "w") as f:
        json.dump(summary, f, indent=1)
    log(f"summary written: {summary_path}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

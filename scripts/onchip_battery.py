"""One-shot on-chip evidence battery (VERDICT round-2 item #1).

Runs every on-chip measurement script in one call, value-first within
safety bands, persisting each stage's results the moment the stage
completes — a crash in stage k cannot cost stages 1..k-1. It predates
the driver's benchmark and is retired once the benchmark's cells cover
its stages (ROADMAP Design 4).

Stages (value-first within safety bands — see the note after the list):

  bench     — bench.py on the real chip      -> the headline BENCH JSON
  protocols — protocol_compare.py at 100K    -> flood/pushpull/pull/pushk table
              (standard XLA engines, low risk — before any Pallas runs)
  kernel    — kernel_bench.py at 100K rows   -> Pallas-vs-XLA A/B table
  bench_rep2 — bench.py again                -> headline variance estimate:
  bench_rep3 — bench.py again                   three records distinguish
               drift from noise (round-1 5.60e8 vs round-4 4.41e8 was
               undecidable from singles); cheap (~90 s each) and safe.
  campaign  — sweep.py over the acceptance campaign spec (all four
               protocols at R=32 x N=1024, --compare-sequential) -> the
               first HARDWARE record of the vmapped campaign kernels
               (flood + the batched Demers trio) and their measured
               speedup vs sequential-per-seed; standard XLA (vmap of the
               already-validated engines), so it sits in the safe band
               before any 1M or Pallas stage. Today's campaign numbers
               are CPU-only (docs/artifacts/campaign_accept_cpu.jsonl,
               protocol_campaign_accept_cpu.jsonl).
  staticcheck — staticcheck.py --json --compile -> the static-analysis
               gate's on-chip leg: the jaxpr audit + recompile sentinel
               run as on CPU, and every registered entry point is
               additionally lowered + compiled on the real chip (an
               entry that audits clean can still fail Mosaic/XLA on
               hardware shapes). Standard XLA compiles only — no
               execution at scale — so it sits in the safe band after
               campaign and before any 1M stage.
  telemetry — run_report.py --capture-smoke at a modest on-chip shape:
               a flood with the in-jit metric rings ON, its JSONL stream
               schema-validated and the per-tick ring metrics reconciled
               against the run's final counters — the first hardware
               execution of the instrumented kernels (today's telemetry
               evidence is CPU-only, docs/RESULTS.md). Standard XLA, tiny
               extra carry — safe band, right after staticcheck compiled
               the same instrumented entries.
  flightrec — divergence.py --json --with-cost -> the flight recorder's
               hardware leg: every engine pair re-run with per-tick
               state digests ON and the streams bisected (clean chip
               runs must report zero divergence — the first cross-engine
               bitwise-parity evidence on real hardware), plus the
               compiled-cost ledger (XLA cost_analysis flops/bytes +
               compile wall time) for the engine.sync entries on the
               chip's compiler. Tiny sims + standard XLA — safe band,
               right after telemetry validated the same instrumented
               kernels.
  campaign_sharded — mesh_rehearsal.py --replicas 4 at the acceptance
               shape (100K BA, (2 replicas x 4 nodes) split, dense +
               delta legs): the factorized campaigns-x-shards program
               with per-replica bitwise checks and warm/fresh walls vs
               the sequential solo-sharded loop. Host-mesh CPU by
               design (like exchange); records carry pending_tpu until
               a real multi-chip mesh is attached.
  async_ticks — mesh_rehearsal.py --async-k 1,2,4 at the acceptance
               shape (100K BA, 8-way node shard, dense + delta
               transports): the bounded-staleness async read path next
               to its synchronous twins — K=1 bitwise-equal, K>=2
               fixed-point-equal (the parity ladder asserts inside the
               script), warm wall per tick and modeled overlap fraction
               per leg in the rows. Host-mesh CPU by design (like
               exchange); records carry pending_tpu until a real
               multi-chip mesh is attached.
  serve     — serve_bench.py at the acceptance trace (100 mixed
               requests, 2 topologies x 3 protocols x mixed replica
               counts, every request bitwise-verified against a solo
               campaign run): requests/s, p50/p99 turnaround and slot
               occupancy for the continuous-batching server. Runs on
               the 8-virtual-device host slot mesh by design (the slot
               mesh wants >= 4 devices; a one-chip host has one) —
               records carry pending_tpu until a real multi-chip mesh
               is attached, like the other host-mesh stages.
  scale1m   — scale_1m.py --shares 64 --chunk 64 -> the 1M ER on-chip
               line at the minimal resident footprint (pad W=2, ~5.2 GB
               modeled = essentially the bare ELL). The full-config
               attempt lives in scale1m_full, ordered behind every
               proven-safe stage, because its W=128 one-pass shape
               crashed the TPU worker on 2026-07-31 and a crash takes
               down every stage after it.
  scale1m_ba — scale_1m.py --topology ba     -> BASELINE config 4 (1M
               scale-free) JSON line
  sweep250  — kernel_bench.py --rows 250000  -> coverage A/B at 250K
               (already survived on-chip in 2026-07) plus the gather
               block-128 / word-width / RCM rows — real tuning value.
  scale1m_full — scale_1m.py at the full default config (ER 1M, 4096
               shares). Dead last on purpose: this invocation crashed
               the TPU worker on 2026-07-31 (battery_latest.jsonl stage
               scale1m, rc=1, JaxRuntimeError "TPU worker process
               crashed", after graph build + staging succeeded — the
               resident-HBM model puts the one-pass W=128 footprint at
               ~12.6 GB on a 16 GB chip; Pallas is gated off at 1M, so
               it is not implicated). scale_1m.py now auto-chunks
               against P2P_HBM_BUDGET_GB (4096 shares -> 2x 2048-share
               passes, ~8.8 GB modeled), which should make it
               survivable; it still runs after every proven-safe stage.

  (The round-4 sweep500/sweep1m stages — the Pallas coverage kernel at
  500K/1M rows — are deleted: the bake-off measured the kernel LOSING
  above its 100K crossover and production gates it off there
  (ops/pallas_kernels.py), so those rows would characterize a path
  nothing runs, at real worker-crash risk. Round-4 verdict weak item
  #4.)

Each stage runs in its own subprocess with its own wall budget; this
process never touches JAX, so each stage has the chip to itself.

Artifacts: one JSONL record per stage appended to
docs/artifacts/battery_<UTC>.jsonl as each stage finishes (plus a
'battery_latest.jsonl' copy), and a one-line summary JSON on stdout.

Usage:
  python scripts/onchip_battery.py                 # full battery
  python scripts/onchip_battery.py --stages bench,kernel
  python scripts/onchip_battery.py --smoke         # tiny CPU shapes, CI
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
from datetime import datetime, timezone

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO, "scripts")
ART_DIR = os.path.join(REPO, "docs", "artifacts")

STAGE_ORDER = (
    "bench", "protocols", "kernel", "bench_rep2", "bench_rep3",
    "campaign", "staticcheck", "telemetry", "flightrec", "exchange",
    "exchange_hub", "campaign_sharded", "async_ticks", "serve",
    "scale1m", "scale1m_ba", "sweep250", "scale1m_full",
)

# Host-mesh stages: mesh_rehearsal.py pins JAX_PLATFORMS=cpu by design
# (the delta exchange and the factorized campaign mesh need >= 4 devices),
# so their records are CPU mechanics evidence, not chip numbers. Each
# record is stamped ``pending_tpu``: the chip numbers for these paths
# are owed to the benchmark's four-chip cells (ROADMAP Speed 1).
PENDING_TPU_STAGES = (
    "exchange", "exchange_hub", "campaign_sharded", "async_ticks", "serve",
)


def log(msg: str) -> None:
    print(f"[battery] {msg}", file=sys.stderr, flush=True)


def stage_env(extra: dict | None = None) -> dict:
    """Subprocess env for a stage: this process's env plus overrides."""
    return {**os.environ, **(extra or {})}


def stage_specs(args) -> dict:
    """argv + env + budget per stage. Smoke mode swaps in tiny CPU shapes
    so the battery's own machinery is testable without a chip."""
    py = sys.executable
    if args.smoke:
        # All smoke stages pin CPU, so the battery machinery is
        # exercised without a chip.
        cpu = {"JAX_PLATFORMS": "cpu"}
        kb_small = [
            py, os.path.join(SCRIPTS, "kernel_bench.py"),
            "--rows", "2000", "--words", "8", "--iters", "3",
        ]
        def bench_spec():
            return {
                "argv": [py, os.path.join(REPO, "bench.py")],
                "env": {**cpu, "P2P_BENCH_SMOKE": "1"},
                "budget": args.stage_budget or 900,
            }

        return {
            # One spec for the headline bench and its variance repeats —
            # a drifted copy would make the repeats measure a different
            # configuration than the headline.
            **{n: bench_spec() for n in ("bench", "bench_rep2", "bench_rep3")},
            "protocols": {
                "argv": [
                    py, os.path.join(SCRIPTS, "protocol_compare.py"),
                    "--nodes", "400", "--prob", "0.03", "--shares", "8",
                    "--horizon", "32", "--json",
                ],
                "env": cpu,
                "budget": args.stage_budget or 600,
            },
            "kernel": {
                "argv": kb_small,
                "env": cpu,
                "budget": args.stage_budget or 600,
            },
            # Smoke sweeps stay tiny: the point is the battery's
            # per-process isolation machinery, not the row counts.
            "sweep250": {
                "argv": kb_small + ["--skip-gather"],
                "env": cpu,
                "budget": args.stage_budget or 600,
            },
            "campaign": {
                # The built-in example spec (2 protocols x 2 loss rates x
                # 8 seeds at 256 nodes): exercises the vmapped campaign
                # path end to end, including one sequential comparison
                # per protocol, at CPU-smoke scale.
                "argv": [
                    py, os.path.join(SCRIPTS, "sweep.py"),
                    "--example", "--compare-sequential", "--no-report",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
            "staticcheck": {
                # Full gate incl. the --compile leg, on host CPU: the
                # smoke run proves the stage machinery and record shape.
                "argv": [
                    py, os.path.join(SCRIPTS, "staticcheck.py"),
                    "--json", "--compile",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
            "telemetry": {
                # Same pipeline as the ci_tier1 smoke, pinned to CPU.
                "argv": [
                    py, os.path.join(SCRIPTS, "run_report.py"),
                    "--capture-smoke",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
            "flightrec": {
                # Digest parity across engine pairs + the cost ledger for
                # one kernel, at smoke shapes — proves the stage record
                # shape battery_report.py renders.
                "argv": [
                    py, os.path.join(SCRIPTS, "divergence.py"), "--json",
                    "--n", "64", "--shares", "3", "--horizon", "16",
                    "--with-cost", "engine.sync._run_chunk_while",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
            "campaign_sharded": {
                # Factorized (replicas x nodes) campaign at smoke
                # shapes: 4 replicas on a (2, 4) virtual mesh, dense +
                # delta legs, every replica bitwise-checked against its
                # solo sharded run inside the script.
                "argv": [
                    py, os.path.join(SCRIPTS, "mesh_rehearsal.py"),
                    "--nodes", "2000", "--prob", "0.01", "--shares", "16",
                    "--horizon", "24", "--replicas", "4",
                    "--replica-shards", "2", "--exchange", "ab",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
            "async_ticks": {
                # Bounded-staleness async legs at smoke shapes: sync,
                # K=1 (bitwise anchor), and K=2 (fixed-point check)
                # dense legs side by side, parity asserted inside the
                # script before any timing lands in a row.
                "argv": [
                    py, os.path.join(SCRIPTS, "mesh_rehearsal.py"),
                    "--nodes", "2000", "--prob", "0.01", "--shares", "16",
                    "--horizon", "24", "--async-k", "1,2",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
            "exchange": {
                # Dense/delta frontier-exchange A/B at smoke shapes:
                # three legs (replicated, sharded/dense, sharded/delta)
                # must come back bitwise-equal, rows carry achieved
                # exchange words/tick (mesh_rehearsal pins the CPU
                # virtual mesh by design).
                "argv": [
                    py, os.path.join(SCRIPTS, "mesh_rehearsal.py"),
                    "--nodes", "2000", "--prob", "0.01", "--shares", "32",
                    "--horizon", "24", "--chunkSize", "32",
                    "--exchange", "ab", "--partition",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
            "exchange_hub": {
                # Degree-split hub/tail transport at smoke shapes: the
                # hub leg next to dense/delta, all bitwise-equal, with
                # a forced 16-row hub set (the small ER graph is too
                # flat for the modeled crossover to pick one).
                "argv": [
                    py, os.path.join(SCRIPTS, "mesh_rehearsal.py"),
                    "--nodes", "2000", "--prob", "0.01", "--shares", "32",
                    "--horizon", "24", "--chunkSize", "32",
                    "--exchange", "ab", "--hub-rows", "16", "--partition",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
            "serve": {
                # Continuous-batching server smoke: 12 mixed requests
                # drained on the 8-virtual-device slot mesh, every
                # request bitwise-compared against its solo campaign
                # run inside the script.
                "argv": [
                    py, os.path.join(SCRIPTS, "serve_bench.py"),
                    "--smoke",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
            "scale1m": {
                "argv": [
                    py, os.path.join(SCRIPTS, "scale_1m.py"),
                    "--nodes", "2000", "--prob", "0.01", "--shares", "64",
                    "--horizon", "32", "--block", "8",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
            "scale1m_full": {
                "argv": [
                    py, os.path.join(SCRIPTS, "scale_1m.py"),
                    "--nodes", "2000", "--prob", "0.01", "--shares", "128",
                    "--horizon", "32", "--block", "8",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
            "scale1m_ba": {
                "argv": [
                    py, os.path.join(SCRIPTS, "scale_1m.py"),
                    "--topology", "ba", "--nodes", "2000", "--baM", "3",
                    "--shares", "64", "--horizon", "48", "--block", "8",
                ],
                "env": cpu,
                "budget": args.stage_budget or 900,
            },
        }
    kb = [py, os.path.join(SCRIPTS, "kernel_bench.py")]
    sweep_env = {}
    def bench_spec():
        return {
            "argv": [py, os.path.join(REPO, "bench.py")],
            "env": {},
            "budget": args.stage_budget or 1800,
        }

    return {
        # One spec for the headline bench and its variance repeats (same
        # rationale as the smoke block).
        **{n: bench_spec() for n in ("bench", "bench_rep2", "bench_rep3")},
        "protocols": {
            "argv": [
                py, os.path.join(SCRIPTS, "protocol_compare.py"),
                "--nodes", "100000", "--prob", "0.001", "--shares", "64",
                "--horizon", "96", "--json",
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 1800,
        },
        "kernel": {
            # 2700s: the gather section now also runs the word-width
            # sweep and the RCM-relabeled row (5 extra compile+measure
            # cycles plus a host-side RCM + restaging after the block
            # sweep).
            "argv": kb + ["--rows", "100000"],
            "env": sweep_env,
            "budget": args.stage_budget or 2700,
        },
        "sweep250": {
            # No --skip-gather here: the kernel stage (already banked)
            # ran the gather sweep before block 128 was added to
            # kernel_bench, so this stage carries the open question of
            # whether the round-1 block sweep stopped short of the
            # optimum. The gather runs at min(rows, 100K) = the bench
            # shape either way. 2700s: the gather section runs LAST in
            # kernel_bench (this stage once timed out at 1500s before
            # reaching it) and now also includes the word-width sweep
            # and the RCM-relabeled row — 5 extra compile+measure cycles
            # plus a host-side RCM + restaging.
            "argv": kb + ["--rows", "250000"],
            "env": sweep_env,
            "budget": args.stage_budget or 2700,
        },
        "campaign": {
            # The acceptance campaign spec (all four protocols at
            # R=32 x N=1024 with --compare-sequential): first hardware
            # validation of the vmapped campaign kernels and the packed
            # share pad, with the per-protocol sequential speedups as
            # stdout JSON lines. Standard XLA ops only.
            "argv": [
                py, os.path.join(SCRIPTS, "sweep.py"),
                "--sweep", os.path.join(REPO, "examples",
                                        "campaign_accept.json"),
                "--compare-sequential", "--no-report",
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 1800,
        },
        "staticcheck": {
            # On-chip leg of the static-analysis gate: audit + sentinel
            # as on CPU, plus lower+compile of every registered entry on
            # the real chip. Compiles only — nothing executes at scale.
            "argv": [
                py, os.path.join(SCRIPTS, "staticcheck.py"),
                "--json", "--compile",
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 1800,
        },
        "telemetry": {
            # First hardware execution of the ring-instrumented kernels:
            # a 20K-node flood with --telemetry through the real CLI,
            # stream schema-validated and ring metrics reconciled with
            # the final counters. Modest shape — far below the bench
            # config — because the job is validating instrumentation,
            # not measuring throughput.
            "argv": [
                py, os.path.join(SCRIPTS, "run_report.py"),
                "--capture-smoke", "--nodes", "20000", "--shares", "64",
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 1200,
        },
        "flightrec": {
            # The flight recorder's hardware leg: all engine pairs with
            # digests ON, bisected (a clean chip must report zero
            # divergence — cross-engine bitwise parity ON HARDWARE),
            # plus the engine.sync compiled-cost ledger from the chip's
            # compiler. Tiny sims, standard XLA, compiles dominated by
            # the staticcheck stage's — safe band.
            "argv": [
                py, os.path.join(SCRIPTS, "divergence.py"), "--json",
                "--with-cost",
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 1800,
        },
        "exchange": {
            # The dense/delta frontier-exchange crossover at rehearsal
            # scale: BA 100K on the 8-virtual-device host mesh, all
            # legs bitwise-checked, achieved exchange words/tick per
            # wire format in the rows. mesh_rehearsal pins
            # JAX_PLATFORMS=cpu by design (the delta exchange needs
            # >= 4 mesh devices; a one-chip host has one) — the
            # rows are self-describing about that, so this stage is
            # mechanics + crossover evidence, not a chip perf number.
            # No --cache: the native BA build at 100K is seconds.
            "argv": [
                py, os.path.join(SCRIPTS, "mesh_rehearsal.py"),
                "--topology", "ba", "--nodes", "100000", "--baM", "3",
                "--shares", "64", "--horizon", "48", "--exchange", "ab",
                "--partition", "--skip-parity",
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 3600,
        },
        "exchange_hub": {
            # The degree-split hub/tail transport at rehearsal scale:
            # BA 100K (a real scale-free degree profile, so the split
            # threshold comes from the modeled word-count crossover,
            # not a forced count) with dense + delta + hub legs plus
            # async-hub K in {2, 4} composition, all bitwise-checked
            # before any words/tick lands in a row. Host-mesh CPU by
            # design (PENDING_TPU_STAGES note): wire-format crossover
            # evidence, not a chip number; the record stays pending_tpu
            # until a real multi-chip mesh is attached.
            "argv": [
                py, os.path.join(SCRIPTS, "mesh_rehearsal.py"),
                "--topology", "ba", "--nodes", "100000", "--baM", "3",
                "--shares", "64", "--horizon", "48", "--exchange", "hub",
                "--async-k", "2,4", "--partition", "--skip-parity",
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 3600,
        },
        "campaign_sharded": {
            # Campaigns x shards at the acceptance shape: R=4 replicas
            # of the node-sharded 100K BA graph as ONE compiled program
            # on the (2 replicas x 4 nodes) 8-virtual-device host mesh,
            # dense AND delta legs, each replica bitwise-checked against
            # its solo sharded run, warm/fresh walls vs the sequential
            # solo-sharded loop in the rows. mesh_rehearsal pins
            # JAX_PLATFORMS=cpu by design (PENDING_TPU_STAGES note) —
            # this is mechanics + throughput-factorization evidence, not
            # a chip perf number, and the record stays pending_tpu until
            # a real multi-chip mesh is attached.
            "argv": [
                py, os.path.join(SCRIPTS, "mesh_rehearsal.py"),
                "--topology", "ba", "--nodes", "100000", "--baM", "3",
                "--shares", "64", "--horizon", "48", "--replicas", "4",
                "--replica-shards", "2", "--exchange", "ab",
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 3600,
        },
        "async_ticks": {
            # Bounded-staleness async ticks at the acceptance shape:
            # the 100K BA graph node-sharded 8 ways, sync dense + delta
            # legs next to async K in {1, 2, 4} on both transports
            # (mesh_rehearsal --async-k). The script asserts the parity
            # ladder before timing — K=1 bitwise-equal to the sync legs,
            # K>=2 equal at the fixed point — so the wall_per_tick_s and
            # modeled_overlap_fraction in each row are parity-certified.
            # Host-mesh CPU by design (PENDING_TPU_STAGES note): overlap
            # mechanics evidence, not a chip number; the record stays
            # pending_tpu until a real multi-chip mesh is attached.
            "argv": [
                py, os.path.join(SCRIPTS, "mesh_rehearsal.py"),
                "--topology", "ba", "--nodes", "100000", "--baM", "3",
                "--shares", "64", "--horizon", "48", "--exchange", "ab",
                "--async-k", "1,2,4", "--skip-parity",
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 3600,
        },
        "serve": {
            # The serving acceptance trace: 100 mixed requests (2
            # topology fingerprints x 3 protocols x replica counts
            # cycling 1/2/4, plus a lossy-flood signature) through the
            # continuous-batching server, drained on the slot mesh, and
            # every request re-derived by a solo batch/campaign run and
            # compared bitwise before the row is accepted. serve_bench
            # pins the 8-virtual-device host CPU mesh when no platform
            # is requested (PENDING_TPU_STAGES note): serving-mechanics
            # + packing-throughput evidence, not a chip number; the
            # record stays pending_tpu until a real multi-chip mesh is
            # attached.
            "argv": [
                py, os.path.join(SCRIPTS, "serve_bench.py"),
                "--requests", "100",
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 1800,
        },
        "scale1m": {
            # The minimal-footprint rung of the 1M ladder: --chunk 64
            # pins the pad to W=2, so resident memory is essentially the
            # bare staged ELL (~5.2 GB modeled) — the least the 1M graph
            # can occupy at all. Slow per gathered byte (sub-lane W) but
            # the job is 64 origins; what it buys is the first-ever 1M
            # on-chip completion at the lowest possible crash risk. The
            # auto-chunked ~8.8 GB shape is scale1m_full's job, ordered
            # behind every proven-safe stage.
            "argv": [
                py, os.path.join(SCRIPTS, "scale_1m.py"),
                "--shares", "64", "--chunk", "64",
                "--cache", args.cache, "--block", str(args.block),
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 3600,
        },
        "scale1m_full": {
            "argv": [
                py, os.path.join(SCRIPTS, "scale_1m.py"),
                "--cache", args.cache, "--block", str(args.block),
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 3600,
        },
        "scale1m_ba": {
            # BASELINE config 4: 1M-node scale-free. Mean degree ~2m is
            # far below the ER north star's ~1000, but the hub rows give
            # the degree-bucketed gather its worst-case skew. Pinned to
            # the minimal W=2 pad for the same reason as scale1m: the
            # W=128 crash suspect (N x W frontier/scratch buffers) is
            # topology-independent — even with BA's tiny ELL the default
            # pad models ~7.7 GB — and a worker crash here would take
            # down every later stage.
            "argv": [
                py, os.path.join(SCRIPTS, "scale_1m.py"),
                "--topology", "ba", "--baM", "3", "--shares", "64",
                "--chunk", "64",
                "--cache", args.ba_cache, "--block", str(args.block),
            ],
            "env": sweep_env,
            "budget": args.stage_budget or 3600,
        },
    }


def latest_records(art_dir: str) -> dict[str, dict]:
    """Latest record per stage across every battery_*.jsonl artifact —
    the same latest-record-wins rule battery_report.py judges by. Smoke
    records prove the machinery, not the chip: they are ignored, or a
    bare `--smoke` run into the default art dir would let the watcher's
    next --skip-done fire skip every real stage on CPU evidence."""
    import glob

    latest: dict[str, dict] = {}
    for path in glob.glob(os.path.join(art_dir, "battery_*.jsonl")):
        try:
            with open(path) as f:
                lines = f.readlines()
        except OSError:
            continue
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            name = rec.get("stage")
            if not name or name.startswith("_") or rec.get("smoke"):
                continue
            if name not in latest or rec.get("utc", "") >= latest[name].get(
                "utc", ""
            ):
                latest[name] = rec
    return latest


def run_stage(name: str, spec: dict, hb_path: str | None = None) -> dict:
    """Run one stage to completion (or budget/crash) and return its
    record. stdout lines that parse as JSON are the stage's results.
    ``hb_path`` is the stage's heartbeat file (P2P_HEARTBEAT in its
    env): on a budget kill, the last beat rides the timeout record so
    the artifact says WHERE the stage was when it died — chunk index,
    ticks done, coverage — not just that it died."""
    t0 = time.monotonic()
    log(f"stage {name}: {' '.join(spec['argv'])} (budget {spec['budget']}s)")
    try:
        proc = subprocess.run(
            spec["argv"], timeout=spec["budget"], capture_output=True,
            text=True, env=stage_env(spec["env"]), cwd=REPO,
        )
        rc: int | str = proc.returncode
        out, err = proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        rc = "timeout"
        out = (e.stdout or b"").decode(errors="replace") if isinstance(
            e.stdout, bytes) else (e.stdout or "")
        err = (e.stderr or b"").decode(errors="replace") if isinstance(
            e.stderr, bytes) else (e.stderr or "")
    wall = time.monotonic() - t0
    results, raw = [], []
    for line in out.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            results.append(json.loads(line))
        except json.JSONDecodeError:
            raw.append(line)
    rec = {
        "stage": name,
        "argv": spec["argv"],
        "rc": rc,
        "ok": rc == 0,
        "wall_s": round(wall, 1),
        "results": results,
        "stdout_nonjson": raw[-5:],
        "stderr_tail": err[-1500:],
        "utc": datetime.now(timezone.utc).isoformat(timespec="seconds"),
    }
    if rc == "timeout" and hb_path:
        from p2p_gossip_tpu.telemetry import progress

        hb = progress.read_heartbeat(hb_path)
        age = progress.heartbeat_age_s(hb_path)
        if hb is not None:
            rec["heartbeat"] = hb
        if age is not None:
            rec["heartbeat_age_s"] = round(age, 1)
    log(f"stage {name}: rc={rc} wall={wall:.0f}s results={len(results)}")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--stages", default=",".join(STAGE_ORDER),
        help=f"comma list from {STAGE_ORDER}, run in canonical order",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny CPU shapes: tests the battery machinery, not the chip",
    )
    ap.add_argument(
        "--stage-budget", type=int, default=0,
        help="override every stage's wall budget (seconds; 0 = defaults)",
    )
    ap.add_argument("--cache", default="/tmp/er1m.npz",
                    help="graph cache for the scale1m stage")
    ap.add_argument("--ba-cache", default="/tmp/ba1m.npz",
                    help="graph cache for the scale1m_ba stage")
    ap.add_argument("--block", type=int, default=8,
                    help="degree block for the scale1m/scale1m_ba stages")
    ap.add_argument(
        "--skip-done", action="store_true",
        help="skip stages whose latest artifact record is already ok — "
        "a re-run then only runs what was skipped or failed, instead "
        "of repeating succeeded heavy stages",
    )
    ap.add_argument(
        "--art-dir", default=os.environ.get("P2P_BATTERY_DIR", ART_DIR),
        help="artifact directory (default docs/artifacts; real on-chip "
        "runs commit theirs, tests point this at a tmp dir)",
    )
    args = ap.parse_args()

    wanted = [s.strip() for s in args.stages.split(",") if s.strip()]
    unknown = [s for s in wanted if s not in STAGE_ORDER]
    if unknown:
        print(f"error: unknown stages {unknown}; valid: {STAGE_ORDER}",
              file=sys.stderr)
        return 2
    stages = [s for s in STAGE_ORDER if s in wanted]
    specs = stage_specs(args)

    os.makedirs(args.art_dir, exist_ok=True)
    # Every stage streams liveness to one heartbeat file in the artifact
    # dir: the chunk drivers rewrite it per chunk (telemetry/progress.py)
    # and the battery reads it back on budget kills to tell a long stage
    # from a hung one.
    hb_path = os.path.join(args.art_dir, "heartbeat.json")
    for spec in specs.values():
        spec["env"] = {**spec["env"], "P2P_HEARTBEAT": hb_path}
    stamp = datetime.now(timezone.utc).strftime("%Y%m%dT%H%M%SZ")
    art_path = os.path.join(args.art_dir, f"battery_{stamp}.jsonl")
    latest = os.path.join(args.art_dir, "battery_latest.jsonl")

    def persist(rec: dict) -> None:
        # Append + copy-to-latest after EVERY stage: a later worker crash
        # (or a kill of this process) keeps everything already measured.
        with open(art_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
            f.flush()
            os.fsync(f.fileno())
        shutil.copyfile(art_path, latest)

    summary = {"artifact": art_path, "stages": {},
               "skipped_done": [], "smoke": bool(args.smoke)}
    if args.skip_done:
        prior = latest_records(args.art_dir)
        done = {n for n, rec in prior.items() if rec.get("ok")}
        summary["skipped_done"] = [s for s in stages if s in done]
        for s in summary["skipped_done"]:
            # Counts as ok for the exit code: its evidence already
            # exists. Carry that evidence VERBATIM into this run's
            # artifact — persist() copies the artifact over
            # battery_latest.jsonl, so without the carry a re-fire that
            # ran one stage would leave a "latest" file missing the
            # other seven for battery_report.py.
            summary["stages"][s] = {"ok": True, "rc": "skipped-done"}
            persist(prior[s])
        stages = [s for s in stages if s not in done]
        if summary["skipped_done"]:
            log(f"skip-done: {summary['skipped_done']} already ok in "
                f"{args.art_dir}; running {stages or 'nothing'}")
        if not stages:
            print(json.dumps(summary))
            return 0

    for name in stages:
        rec = run_stage(name, specs[name], hb_path=hb_path)
        if args.smoke:
            # Mark so done_stages never counts CPU smoke runs as on-chip
            # evidence (and artifact readers can tell them apart).
            rec["smoke"] = True
        if name in PENDING_TPU_STAGES:
            # Host-mesh CPU evidence: a real multi-chip record is still
            # owed (see PENDING_TPU_STAGES).
            rec["pending_tpu"] = True
        persist(rec)
        summary["stages"][name] = {"ok": rec["ok"], "rc": rec["rc"]}
    print(json.dumps(summary))
    # Nonzero on any failed stage.
    all_ok = all(s["ok"] for s in summary["stages"].values())
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())

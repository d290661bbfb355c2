"""Smoke tests for the measurement scripts — they generate the judge- and
operator-facing artifacts (kernel A/B tables, protocol comparisons), so
they must keep producing parseable output even as the library evolves.
CPU-pinned, tiny shapes; the real numbers come from TPU runs."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_script(script, *args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", script), *args],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=timeout,
    )


def test_kernel_bench_smoke_emits_parseable_rows():
    r = _run_script(
        "kernel_bench.py", "--rows", "1024", "--words", "4", "--iters", "2",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(line) for line in r.stdout.strip().splitlines()]
    kernels = {row["kernel"] for row in rows}
    assert {
        "coverage_per_slot", "tick_update", "tick_update_cov",
        "gather_or_xla", "gather_or_pallas_rejection",
    } <= kernels
    for row in rows:
        if "parity" in row:
            assert row["parity"] == "ok"
        # CPU rows run Pallas in interpret mode — they must be labeled so
        # they are never mistaken for on-chip bake-off numbers.
        assert row["platform"] == "cpu"
        assert row["interpret_mode"] is True


def test_protocol_compare_smoke_json():
    r = _run_script(
        "protocol_compare.py", "--json", "--nodes", "200", "--prob", "0.03",
        "--shares", "4", "--horizon", "32",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads(r.stdout)
    protos = {row["protocol"].split("(")[0] for row in payload["results"]}
    assert {"flood", "pushpull", "pull", "pushk"} <= protos
    # Strict JSON round-trip (the sends_per_delivery None contract).
    json.loads(json.dumps(payload))


def _run_script_cpu_flag(script, *args, timeout=420):
    """Run a script relying on its --cpu flag INSTEAD of the env pin —
    the no-chip exit a bare invocation on a chipless host needs."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = REPO
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", script), "--cpu", *args],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=timeout,
    )


def test_scale_1m_cpu_flag_runs_and_labels_metric():
    """--cpu must run on the host and stamp [cpu] into the JSON metric so
    a host number is never mistaken for an on-chip result."""
    r = _run_script_cpu_flag(
        "scale_1m.py", "--nodes", "500", "--prob", "0.02", "--shares", "8",
        "--horizon", "32", "--chunk", "0",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert "[cpu]" in row["metric"]
    assert row["unit"] == "s"


def test_scale_1m_auto_chunk_budget():
    """A forced P2P_HBM_BUDGET_GB must engage the resident-HBM auto-chunk
    (stderr announces the chosen pad) and still complete with full
    coverage — the path the on-chip 1M ladder depends on."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env["P2P_HBM_BUDGET_GB"] = "0.0012"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "scale_1m.py"),
         "--cpu", "--nodes", "2000", "--prob", "0.01", "--shares", "2048",
         "--horizon", "32", "--block", "8"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=420,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "auto-chunk:" in r.stderr
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert "[cpu]" in row["metric"]
    assert "full coverage: True" in r.stderr


def test_scale_1m_mesh_explicit_chunk_forwards_pad():
    """--chunk in mesh mode must reach the sharded engine as chunk_size
    (per-pass resident relief), not just slice origins into re-padded
    passes (round-4 advisor finding). The forwarding is announced on
    stderr and the run must still reach full coverage."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "scale_1m.py"),
         "--cpu", "--nodes", "600", "--prob", "0.02", "--shares", "64",
         "--horizon", "32", "--block", "8", "--mesh", "1x2",
         "--chunk", "32"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=420,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "forwards chunk_size=32" in r.stderr
    assert "full coverage: True" in r.stderr


def test_mesh_rehearsal_cache_roundtrip(tmp_path):
    """--cache writes the graph with scale_1m.py's fingerprint scheme on
    the first run and loads it on the second (the 1M rehearsal reuses the
    north-star script's /tmp cache); --skip-parity rows still pass the
    conservation check and report ring accounting."""
    cache = str(tmp_path / "mesh.npz")
    args = (
        "mesh_rehearsal.py", "--nodes", "400", "--prob", "0.02",
        "--shares", "4", "--horizon", "24", "--devices", "2",
        "--skip-parity", "--cache", cache,
    )
    r = _run_script(*args)
    assert r.returncode == 0, r.stderr[-2000:]
    assert os.path.exists(cache)
    rows = [json.loads(line) for line in r.stdout.strip().splitlines()]
    assert {row["ring_mode"] for row in rows} == {"replicated", "sharded"}
    for row in rows:
        assert row["coverage_final_min"] == 400
    r2 = _run_script(*args)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "graph loaded from" in r2.stderr
    assert [json.loads(l)["ring_bytes_per_chip"]
            for l in r2.stdout.strip().splitlines()] == [
        row["ring_bytes_per_chip"] for row in rows
    ]


def test_mesh_rehearsal_ba_topology_and_chunk():
    """--topology ba (config 4's scale-free mesh leg) and --chunkSize (the
    virtual-mesh memory-relief pad) must run with parity and label the
    rows; small pads shrink the ring accounting proportionally."""
    r = _run_script(
        "mesh_rehearsal.py", "--nodes", "500", "--topology", "ba",
        "--baM", "3", "--shares", "8", "--horizon", "24",
        "--devices", "2", "--chunkSize", "32",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(line) for line in r.stdout.strip().splitlines()]
    assert {row["topology"] for row in rows} == {"ba"}
    for row in rows:
        assert row["parity_vs_single_device"] is True
        assert row["coverage_final_min"] == 500
    # W=1 (32 shares) vs the default W=128 pad: ring accounting must
    # reflect the small pad, not the 4096-share default.
    repl = next(r2 for r2 in rows if r2["ring_mode"] == "replicated")
    assert repl["ring_bytes_per_chip"] == repl["ring_slots"] * 500 * 1 * 4
    # Rows self-describe the pad/fit context (round-4 advisor finding):
    # an explicit --chunkSize is recorded as the effective pad, alongside
    # what the host had and whether the fit model held.
    for row in rows:
        assert row["pad_shares"] == 32
        assert row["host_avail_gb"] > 0
        assert row["host_fit_ok"] is True

    # A chunkSize BELOW the share count cannot narrow the staged rows
    # past the shares themselves: pad_shares must report the width the
    # engine really stages (whole words of max(shares, chunk)), not the
    # raw flag (round-5 review finding).
    r2 = _run_script(
        "mesh_rehearsal.py", "--nodes", "300", "--topology", "ba",
        "--baM", "2", "--shares", "40", "--horizon", "24",
        "--devices", "2", "--chunkSize", "32", "--skip-parity",
    )
    assert r2.returncode == 0, r2.stderr[-2000:]
    for line in r2.stdout.strip().splitlines():
        row = json.loads(line)
        assert row["pad_shares"] == 64  # num_words(max(40, 32)) * 32
        # W=2 words; the node-sharded ring holds 1/devices of the rows.
        ring_n = 300 if row["ring_mode"] == "replicated" else 150
        assert row["ring_bytes_per_chip"] == row["ring_slots"] * ring_n * 2 * 4


def test_mesh_rehearsal_partnered_protocol():
    """--protocol pushpull rehearses BASELINE config 5's anti-entropy leg:
    both ring layouts, single-device parity, and the cross-layout bitwise
    check all run on the partnered engine too."""
    r = _run_script(
        "mesh_rehearsal.py", "--nodes", "400", "--prob", "0.02",
        "--shares", "4", "--horizon", "32", "--devices", "2",
        "--protocol", "pushpull",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(line) for line in r.stdout.strip().splitlines()]
    assert {row["rehearsal"] for row in rows} == {"sharded_pushpull"}
    assert {row["ring_mode"] for row in rows} == {"replicated", "sharded"}
    for row in rows:
        assert row["parity_vs_single_device"] is True
        assert row["coverage_final_min"] == 400
    assert "mesh legs bitwise-equal" in r.stderr


def test_protocol_compare_cpu_flag():
    r = _run_script_cpu_flag(
        "protocol_compare.py", "--json", "--nodes", "200", "--prob", "0.03",
        "--shares", "4", "--horizon", "32",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    payload = json.loads(r.stdout)
    assert len(payload["results"]) == 4


def test_onchip_battery_smoke(tmp_path):
    """The battery must run a stage subset end-to-end in smoke mode,
    persist one JSONL record per stage as it completes, and print a
    parseable summary — its contract is tested harder than its
    numbers."""
    r = _run_script(
        "onchip_battery.py", "--smoke", "--stages", "bench,scale1m,scale1m_ba",
        "--art-dir", str(tmp_path), timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["stages"] == {
        "bench": {"ok": True, "rc": 0},
        "scale1m": {"ok": True, "rc": 0},
        "scale1m_ba": {"ok": True, "rc": 0},
    }
    with open(summary["artifact"]) as f:
        records = [json.loads(line) for line in f]
    assert [rec["stage"] for rec in records] == [
        "bench", "scale1m", "scale1m_ba",
    ]
    for rec in records:
        assert rec["ok"] and rec["results"], rec["stderr_tail"]
    # The bench stage's JSON line must be the bench.py contract.
    bench_row = records[0]["results"][-1]
    assert {"metric", "value", "unit", "vs_baseline"} <= set(bench_row)


def test_onchip_battery_skip_done(tmp_path):
    """--skip-done must skip stages whose LATEST artifact record is ok
    and still run the rest — chip time is never spent repeating
    captured evidence, and a later
    failed record outranks an earlier success (latest-record-wins,
    battery_report's rule)."""
    base = {
        "argv": [], "rc": 0, "ok": True, "wall_s": 1.0,
        "results": [{"metric": "m", "value": 1, "unit": "u",
                     "vs_baseline": 2}],
        "stdout_nonjson": [], "stderr_tail": "",
    }
    prior = dict(base, stage="bench", utc="2026-01-01T00:00:00+00:00")
    k_ok = dict(base, stage="kernel", utc="2026-01-01T00:00:00+00:00")
    k_bad = dict(base, stage="kernel", ok=False, rc=1,
                 utc="2026-01-02T00:00:00+00:00", results=[])
    (tmp_path / "battery_prior.jsonl").write_text(
        "\n".join(json.dumps(r) for r in (prior, k_ok, k_bad)) + "\n"
    )
    r = _run_script(
        "onchip_battery.py", "--smoke", "--skip-done",
        "--stages", "bench,kernel", "--art-dir", str(tmp_path), timeout=600,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["skipped_done"] == ["bench"]
    assert summary["stages"]["bench"] == {"ok": True, "rc": "skipped-done"}
    assert summary["stages"]["kernel"] == {"ok": True, "rc": 0}
    # The skipped stage's evidence is carried VERBATIM into this run's
    # artifact: battery_latest.jsonl (a copy of it) must stay complete
    # for battery_report.py even when a re-fire runs one stage.
    with open(summary["artifact"]) as f:
        arts = [json.loads(line) for line in f]
    assert arts[0]["stage"] == "bench" and arts[0]["utc"] == prior["utc"]
    assert [a["stage"] for a in arts] == ["bench", "kernel"]

    # The kernel run above succeeded but in SMOKE mode: its record is
    # marked and must NOT count as done — CPU smoke evidence skipping a
    # real stage is exactly the bug done_stages guards against.
    r2 = _run_script(
        "onchip_battery.py", "--smoke", "--skip-done",
        "--stages", "bench,kernel", "--art-dir", str(tmp_path), timeout=600,
    )
    assert r2.returncode == 0, r2.stderr[-2000:]
    s2 = json.loads(r2.stdout.strip().splitlines()[-1])
    assert s2["skipped_done"] == ["bench"]
    assert s2["stages"]["kernel"] == {"ok": True, "rc": 0}

    # A later REAL ok record does mark it done: a re-fire runs nothing.
    k_fixed = dict(base, stage="kernel", utc="2026-01-03T00:00:00+00:00")
    (tmp_path / "battery_fix.jsonl").write_text(json.dumps(k_fixed) + "\n")
    r3 = _run_script(
        "onchip_battery.py", "--smoke", "--skip-done",
        "--stages", "bench,kernel", "--art-dir", str(tmp_path), timeout=120,
    )
    assert r3.returncode == 0, r3.stderr[-2000:]
    s3 = json.loads(r3.stdout.strip().splitlines()[-1])
    assert s3["skipped_done"] == ["bench", "kernel"]


def test_onchip_battery_rejects_unknown_stage():
    r = _run_script("onchip_battery.py", "--stages", "bench,nope")
    assert r.returncode == 2
    assert "unknown stages" in r.stderr


def test_battery_report_renders_and_flags_failures(tmp_path):
    """battery_report.py renders stage tables from a battery artifact and
    exits nonzero when any stage failed (partial-battery detection)."""
    art = tmp_path / "battery_x.jsonl"
    ok_rec = {
        "stage": "bench", "argv": [], "rc": 0, "ok": True, "wall_s": 1.0,
        "results": [{"metric": "m", "value": 1, "unit": "u",
                     "vs_baseline": 2, "achieved_gbps": 3,
                     "pct_hbm_peak": None, "ticks": 4}],
        "stdout_nonjson": [], "stderr_tail": "", "utc": "T",
    }
    art.write_text(json.dumps(ok_rec) + "\n")
    r = _run_script("battery_report.py", str(art))
    assert r.returncode == 0, r.stderr[-500:]
    assert "## Headline bench" in r.stdout and "| m | 1 | u | 2 |" in r.stdout

    bad = dict(ok_rec, stage="scale1m", ok=False, rc="timeout", results=[])
    art.write_text(json.dumps(ok_rec) + "\n" + json.dumps(bad) + "\n")
    r2 = _run_script("battery_report.py", str(art))
    assert r2.returncode == 1
    assert "Incomplete battery" in r2.stdout and "scale1m" in r2.stdout


def test_battery_report_salvages_truncated_artifact(tmp_path):
    """A battery killed mid-append leaves a partial final line; completed
    stages must still render (with a warning), and None values render as
    an em-dash, not the string 'None'."""
    art = tmp_path / "battery_t.jsonl"
    rec = {
        "stage": "bench", "argv": [], "rc": 0, "ok": True, "wall_s": 1.0,
        "results": [{"metric": "m", "value": 1, "unit": "u",
                     "vs_baseline": 2, "achieved_gbps": 3,
                     "pct_hbm_peak": None, "ticks": 4}],
        "stdout_nonjson": [], "stderr_tail": "", "utc": "T",
    }
    art.write_text(json.dumps(rec) + "\n" + '{"stage": "kern')
    r = _run_script("battery_report.py", str(art))
    assert r.returncode == 0, r.stderr[-500:]
    assert "## Headline bench" in r.stdout
    assert "skipped 1 truncated record" in r.stderr
    assert "None" not in r.stdout  # null pct_hbm_peak renders as em-dash


def test_battery_report_latest_stage_record_wins(tmp_path):
    """A stage that failed and was re-run successfully counts as success:
    exit code judges each stage's latest record, like the rendering."""
    art = tmp_path / "battery_r.jsonl"
    fail = {
        "stage": "bench", "argv": [], "rc": "timeout", "ok": False,
        "wall_s": 1.0, "results": [], "stdout_nonjson": [],
        "stderr_tail": "first try", "utc": "T1",
    }
    ok = dict(fail, rc=0, ok=True, utc="T2", results=[
        {"metric": "m", "value": 1, "unit": "u", "vs_baseline": 2},
    ])
    art.write_text(json.dumps(fail) + "\n" + json.dumps(ok) + "\n")
    r = _run_script("battery_report.py", str(art))
    assert r.returncode == 0, r.stdout + r.stderr[-300:]
    assert "Incomplete battery" not in r.stdout


def test_sweep_script_contract(tmp_path):
    """scripts/sweep.py: one JSON line per cell on stdout, report on
    stderr, --out file mirror — the campaign artifact contract, rendered
    with no TPU attached."""
    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps({
        "numNodes": 48, "p": 0.15, "protocol": "push",
        "lossProb": [0.0, 0.2], "replicas": 2, "shares": 2, "horizon": 16,
    }))
    out = tmp_path / "campaign.jsonl"
    r = _run_script(
        "sweep.py", "--sweep", str(spec), "--out", str(out),
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rows = [json.loads(line) for line in r.stdout.strip().splitlines()]
    assert len(rows) == 2
    for row in rows:
        assert row["platform"] == "cpu"  # honest label, no TPU here
        ttc = row["summary"]["ttc"]
        assert ttc["ticks"] is None or "p99" in ttc["ticks"]
    assert "=== Campaign Report ===" in r.stderr
    mirrored = [json.loads(line) for line in out.read_text().splitlines()]
    assert mirrored == rows

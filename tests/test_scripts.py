"""Smoke tests for the user scripts (protocol comparisons, the 1M
scaling run, the mesh rehearsal, sweeps) — they must keep producing
parseable output even as the library evolves. CPU-pinned, tiny shapes;
the real numbers come from TPU runs."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_script(script, *args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", script), *args],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=timeout,
    )


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


@pytest.mark.parametrize("topology", ["er", "ba"])
def test_scale_1m_cpu_flag_runs_and_labels_metric(topology):
    """--cpu must run on the host and stamp [cpu] into the JSON metric so
    a host number is never mistaken for an on-chip result, on the ER
    north star and on BASELINE config 4's BA topology."""
    r = _run_script_cpu_flag(
        "scale_1m.py", "--nodes", "500", "--prob", "0.02", "--shares", "8",
        "--horizon", "32", "--chunk", "0", "--topology", topology,
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

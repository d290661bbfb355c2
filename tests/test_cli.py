"""CLI smoke tests — reference flags, reference output format."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(*args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return subprocess.run(
        [sys.executable, "-m", "p2p_gossip_tpu", *args],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=300,
    )


def test_reference_default_flags_event_backend():
    r = _run_cli(
        "--numNodes", "10", "--connectionProb", "0.3", "--simTime", "20",
        "--Latency", "5", "--backend", "event", "--seed", "1",
    )
    assert r.returncode == 0, r.stderr
    assert "=== P2P Gossip Network Simulation Statistics ===" in r.stdout
    assert "Node 0: Generated" in r.stdout
    assert "Total shares generated:" in r.stdout
    assert "=== Periodic Stats at 10s ===" in r.stdout


def test_tpu_backend_matches_event_backend_totals():
    common = [
        "--numNodes", "30", "--connectionProb", "0.1", "--simTime", "10",
        "--Latency", "10", "--seed", "3",
    ]
    ev = _run_cli(*common, "--backend", "event")
    tp = _run_cli(*common, "--backend", "tpu")
    assert ev.returncode == 0 and tp.returncode == 0, ev.stderr + tp.stderr

    def node_lines(out):
        return sorted(l for l in out.splitlines() if l.startswith("Node "))

    assert node_lines(ev.stdout) == node_lines(tp.stdout)


def test_anim_export(tmp_path):
    out = tmp_path / "anim.xml"
    r = _run_cli(
        "--numNodes", "9", "--simTime", "5", "--backend", "event",
        "--anim", str(out),
    )
    assert r.returncode == 0, r.stderr
    text = out.read_text()
    assert text.startswith('<?xml version="1.0"')
    assert '<node id="8"' in text
    assert "<link fromId=" in text


def test_bad_flag_fails_cleanly():
    r = _run_cli("--backend", "gpu")
    assert r.returncode != 0
    assert "invalid choice" in r.stderr


def test_flood_coverage_flag(capsys):
    from p2p_gossip_tpu.utils.cli import run

    rc = run([
        "--numNodes", "60", "--connectionProb", "0.1", "--simTime", "0.2",
        "--Latency", "5", "--floodCoverage", "8", "--seed", "2",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "Flood Coverage (8 shares" in out
    assert "Shares reaching target: 8/8" in out


def test_flood_coverage_requires_tpu_backend(capsys):
    from p2p_gossip_tpu.utils.cli import run

    rc = run(["--numNodes", "20", "--floodCoverage", "4", "--backend", "event"])
    assert rc == 2


def test_sharded_backend_cli(capsys):
    """--backend sharded over the 8 virtual CPU devices matches the event
    backend's final statistics."""
    from p2p_gossip_tpu.utils.cli import run

    common = [
        "--numNodes", "50", "--connectionProb", "0.1", "--simTime", "5",
        "--Latency", "5", "--seed", "4", "--chunkSize", "64",
    ]
    assert run(common + ["--backend", "sharded", "--meshNodes", "4",
                         "--meshShares", "2"]) == 0
    sharded_out = capsys.readouterr().out
    assert run(common + ["--backend", "event"]) == 0
    event_out = capsys.readouterr().out

    def node_lines(s):
        return [l for l in s.splitlines() if l.startswith(("Node", "Total"))]

    assert node_lines(sharded_out) == node_lines(event_out)


def test_graph_builder_flag(capsys):
    """--graphBuilder selects the construction path: python is the
    reproducible default, native uses the C++ builder when built, and both
    produce valid full-coverage runs; native is rejected for topologies
    without a C++ builder."""
    from p2p_gossip_tpu.runtime import native
    from p2p_gossip_tpu.utils.cli import run

    common = [
        "--numNodes", "30", "--connectionProb", "0.2", "--simTime", "5",
        "--Latency", "5", "--seed", "2", "--backend", "event",
    ]
    assert run(common + ["--graphBuilder", "python"]) == 0
    out = capsys.readouterr().out
    assert "graph-builder=python" in out

    if native.available():
        assert run(common + ["--graphBuilder", "native"]) == 0
        out = capsys.readouterr().out
        assert "graph-builder=native" in out

    # No native builder exists for ring: explicit native must fail cleanly.
    assert run(
        ["--numNodes", "10", "--topology", "ring", "--graphBuilder",
         "native", "--backend", "event"]
    ) == 2
    assert "no ring builder" in capsys.readouterr().err


def test_coverage_experiment_with_partnered_protocols(capsys):
    """--floodCoverage composes with --protocol pushpull/pushk (single
    device and sharded), reporting the protocol's coverage-time and
    redundancy."""
    from p2p_gossip_tpu.utils.cli import run

    common = [
        "--numNodes", "60", "--connectionProb", "0.1", "--simTime", "0.3",
        "--Latency", "5", "--floodCoverage", "4", "--seed", "2",
    ]
    rc = run(common + ["--protocol", "pushk", "--fanout", "3"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "pushk Coverage (4 shares" in out
    assert "Redundancy:" in out

    rc = run(common + ["--protocol", "pushpull", "--backend", "sharded",
                       "--chunkSize", "32"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "pushpull Coverage (4 shares" in out
    assert "Shares reaching target: 4/4" in out


def test_fanout_validated_on_coverage_path(capsys):
    from p2p_gossip_tpu.utils.cli import run

    rc = run([
        "--numNodes", "20", "--floodCoverage", "3", "--protocol", "pushk",
        "--fanout", "0",
    ])
    assert rc == 2
    assert "--fanout" in capsys.readouterr().err


@pytest.mark.parametrize(
    "proto_args",
    [["--protocol", "pushk", "--fanout", "2"],
     ["--protocol", "pull"],
     ["--protocol", "pushpull"]],
    ids=["pushk", "pull", "pushpull"],
)
def test_partnered_protocols_on_every_backend(capsys, proto_args):
    """Each partnered protocol produces identical totals on event, native,
    tpu (CPU-pinned), and sharded backends — the four-engine parity
    contract from the CLI."""
    from p2p_gossip_tpu.utils.cli import run

    common = [
        "--numNodes", "40", "--connectionProb", "0.15", "--simTime", "2",
        "--Latency", "5", "--seed", "6", "--chunkSize", "32",
    ] + proto_args
    outs = {}
    for backend in ("event", "native", "tpu", "sharded"):  # all four
        rc = run(common + ["--backend", backend])
        out = capsys.readouterr().out
        assert rc == 0, backend
        totals = [ln for ln in out.splitlines() if ln.startswith("Total ")]
        assert totals, backend
        outs[backend] = totals
    assert outs["event"] == outs["native"] == outs["tpu"] == outs["sharded"]


def test_partnered_event_backend_rejects_lognormal(capsys):
    from p2p_gossip_tpu.utils.cli import run

    rc = run([
        "--numNodes", "20", "--protocol", "pushpull", "--backend", "event",
        "--delayModel", "lognormal",
    ])
    assert rc == 2


def test_graph_file_cache_and_json(tmp_path, capsys):
    """--graphFile saves the built topology and reloads it on the next run
    (identical counters); --json appends a machine-readable summary."""
    import json

    from p2p_gossip_tpu.utils.cli import run

    gf = str(tmp_path / "g.npz")
    common = [
        "--numNodes", "30", "--connectionProb", "0.2", "--simTime", "5",
        "--Latency", "5", "--seed", "2", "--backend", "event",
        "--graphFile", gf, "--json",
    ]
    assert run(common) == 0
    first = capsys.readouterr().out
    assert run(common) == 0  # second run loads the cache
    second = capsys.readouterr().out
    assert [l for l in first.splitlines() if l.startswith("Total ")] == [
        l for l in second.splitlines() if l.startswith("Total ")
    ]
    payload = json.loads(second.splitlines()[-1])
    assert payload["config"]["numNodes"] == 30
    assert payload["totals"]["received"] == payload["totals"]["forwarded"]

    # Mismatched --numNodes against the cached graph fails cleanly.
    rc = run([
        "--numNodes", "31", "--backend", "event", "--graphFile", gf,
    ])
    assert rc == 2


def test_graph_file_rejects_mismatched_parameters(tmp_path, capsys):
    from p2p_gossip_tpu.utils.cli import run

    gf = str(tmp_path / "g.npz")
    base = ["--numNodes", "30", "--simTime", "1", "--backend", "event",
            "--graphFile", gf]
    assert run(base + ["--topology", "er", "--seed", "2"]) == 0
    capsys.readouterr()
    rc = run(base + ["--topology", "ring", "--seed", "2"])
    assert rc == 2
    assert "different topology parameters" in capsys.readouterr().err
    # Corrupt cache fails cleanly too.
    with open(gf, "wb") as f:
        f.write(b"not a zip")
    rc = run(base + ["--topology", "er", "--seed", "2"])
    assert rc == 2
    assert "not a readable graph cache" in capsys.readouterr().err


def test_json_supported_with_flood_coverage(capsys):
    # --json with --floodCoverage emits the coverage-run JSON summary
    # (see test_flood_coverage_json for the payload contract).
    from p2p_gossip_tpu.utils.cli import run

    rc = run(["--numNodes", "20", "--floodCoverage", "4", "--json"])
    capsys.readouterr()
    assert rc == 0


def test_pull_credit_bound_is_a_clean_cli_error(capsys):
    """The pull protocol's uint32-credit precondition surfaces as the
    CLI's 'error: ...' + exit 2 convention, not a raw traceback."""
    from unittest import mock

    from p2p_gossip_tpu.models.topology import Graph
    from p2p_gossip_tpu.utils import cli

    common = [
        "--numNodes", "20", "--connectionProb", "0.3", "--simTime", "5",
        "--backend", "tpu", "--protocol", "pull", "--seed", "0",
    ]
    with mock.patch.object(
        Graph, "max_degree", property(lambda self: 1 << 20)
    ):
        rc = cli.run(common)
        assert rc == 2
        err = capsys.readouterr().err
        assert "error:" in err and "uint32" in err
        # Same conversion on the --floodCoverage dispatch path.
        rc = cli.run(common + ["--floodCoverage", "4"])
        assert rc == 2
        err = capsys.readouterr().err
        assert "error:" in err and "uint32" in err
        # The bound is a bitmask-engine precondition only; the event
        # backend accumulates sent in int64 and must not be gated.
        rc = cli.run([
            "--numNodes", "20", "--connectionProb", "0.3", "--simTime", "5",
            "--backend", "event", "--protocol", "pull", "--seed", "0",
        ])
        capsys.readouterr()
        assert rc == 0


def test_serialization_delay_model_cli():
    """--delayModel serialization: tpu and event backends agree, and a
    larger --shareBytes visibly slows propagation."""
    common = [
        "--numNodes", "25", "--connectionProb", "0.2", "--simTime", "8",
        "--Latency", "5", "--seed", "6", "--delayModel", "serialization",
        "--shareBytes", "8000",
    ]
    ev = _run_cli(*common, "--backend", "event")
    tp = _run_cli(*common, "--backend", "tpu")
    assert ev.returncode == 0 and tp.returncode == 0, ev.stderr + tp.stderr

    def node_lines(out):
        return sorted(l for l in out.splitlines() if l.startswith("Node "))

    assert node_lines(ev.stdout) == node_lines(tp.stdout)
    bad = _run_cli(*common[:-2], "--shareBytes", "-1", "--backend", "event")
    assert bad.returncode == 2 and "error:" in bad.stderr


def test_anim_messages_flag(tmp_path, capsys):
    """--animMessages embeds per-message <p> events; invalid combos get
    the clean-error convention."""
    from p2p_gossip_tpu.utils.cli import run

    out = tmp_path / "a.xml"
    rc = run([
        "--numNodes", "12", "--connectionProb", "0.3", "--simTime", "5",
        "--Latency", "5", "--backend", "event", "--seed", "2",
        "--anim", str(out), "--animMessages",
    ])
    capsys.readouterr()
    assert rc == 0
    text = out.read_text()
    assert '<p fId="' in text and 'outcome="delivered"' in text

    rc = run([
        "--numNodes", "12", "--backend", "tpu", "--anim", str(out),
        "--animMessages",
    ])
    assert rc == 2
    assert "--animMessages requires" in capsys.readouterr().err


def test_connect_at_tick_cli(capsys):
    """--connectAtTick mirrors the reference's 5s warm-up: identical
    output across backends, fewer sends than the connected-at-t0 run."""
    from p2p_gossip_tpu.utils.cli import run

    common = [
        "--numNodes", "25", "--connectionProb", "0.2", "--simTime", "8",
        "--Latency", "5", "--seed", "9",
    ]
    # Reference geometry: 5 ms ticks, connect at 5 s = tick 1000; use a
    # smaller window so the run stays quick.
    outs = {}
    for backend in ("event", "tpu"):
        rc = run(common + ["--backend", backend, "--connectAtTick", "600"])
        out = capsys.readouterr().out
        assert rc == 0, backend
        outs[backend] = sorted(
            l for l in out.splitlines() if l.startswith("Node ")
        )
    assert outs["event"] == outs["tpu"]

    rc = run(common + ["--backend", "event", "--connectAtTick", "600",
                       "--protocol", "pushpull"])
    assert rc == 2
    assert "--connectAtTick" in capsys.readouterr().err


def test_connect_at_tick_rejected_on_flood_coverage_and_negative(capsys):
    from p2p_gossip_tpu.utils.cli import run

    rc = run([
        "--numNodes", "20", "--floodCoverage", "4", "--connectAtTick", "600",
    ])
    assert rc == 2
    assert "--connectAtTick" in capsys.readouterr().err
    rc = run(["--numNodes", "20", "--connectAtTick", "-5"])
    assert rc == 2
    assert ">= 0" in capsys.readouterr().err
    rc = run([
        "--numNodes", "20", "--floodCoverage", "4", "--animMessages",
        "--anim", "/tmp/x.xml", "--backend", "event",
    ])
    assert rc == 2


def test_ring_mode_cli(capsys):
    """--ringMode selects the sharded engine's history-ring layout; both
    layouts match the event backend's totals."""
    from p2p_gossip_tpu.utils.cli import run

    common = [
        "--numNodes", "40", "--connectionProb", "0.15", "--simTime", "4",
        "--Latency", "5", "--seed", "11", "--chunkSize", "32",
        "--delayModel", "lognormal",
    ]
    assert run(common + ["--backend", "event"]) == 0
    event_out = capsys.readouterr().out

    def totals(s):
        return [l for l in s.splitlines() if l.startswith("Total ")]

    for mode in ("replicated", "sharded"):
        rc = run(common + ["--backend", "sharded", "--meshNodes", "4",
                           "--meshShares", "2", "--ringMode", mode])
        out = capsys.readouterr().out
        assert rc == 0, mode
        assert totals(out) == totals(event_out), mode


def test_flood_coverage_json(capsys):
    """--floodCoverage --json emits one strict-JSON summary line after the
    text report."""
    import json as _json

    from p2p_gossip_tpu.utils.cli import run

    rc = run([
        "--numNodes", "60", "--connectionProb", "0.1", "--simTime", "0.2",
        "--Latency", "5", "--floodCoverage", "8", "--seed", "2", "--json",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    payload = _json.loads(out.strip().splitlines()[-1])
    assert payload["reached"] == 8
    assert payload["ttc_ticks"]["min"] >= 1
    assert payload["final_coverage"]["max"] == 60
    assert payload["sends_per_delivery"] > 1


def test_ref_parallel_links_flag():
    """--refParallelLinks inflates Total sent and Peer count exactly as the
    reference's doubled forced edges would, identically across backends,
    without changing dynamics (Received/Forwarded/Processed)."""
    common = [
        "--numNodes", "14", "--connectionProb", "0.12", "--simTime", "6",
        "--Latency", "5", "--seed", "2",  # seed 2: nodes 7+8 doubled
    ]
    base = _run_cli(*common, "--backend", "event")
    ev = _run_cli(*common, "--backend", "event", "--refParallelLinks")
    tp = _run_cli(*common, "--backend", "tpu", "--refParallelLinks")
    assert base.returncode == 0 and ev.returncode == 0 and tp.returncode == 0

    def node_fields(out):
        rows = {}
        for line in out.splitlines():
            if line.startswith("Node "):
                parts = line.replace(":", ",").split(",")
                rows[int(parts[0].split()[1])] = [
                    int(p.split()[-1]) for p in parts[1:]
                ]
        return rows

    b, e = node_fields(base.stdout), node_fields(ev.stdout)
    assert e == node_fields(tp.stdout)  # backend-identical under the quirk
    assert "parallel-link quirk: 1 doubled pair(s)" in ev.stderr
    changed = {i for i in b if b[i] != e[i]}
    assert changed == {7, 8}
    for i in (7, 8):
        gen, rec, fwd, sent, proc, peers, socks = b[i]
        gen2, rec2, fwd2, sent2, proc2, peers2, socks2 = e[i]
        # Dynamics unchanged; sent charged one extra copy per broadcast;
        # peer count (peers.size()) inflated, socket count (map) not.
        assert (gen2, rec2, fwd2, proc2) == (gen, rec, fwd, proc)
        assert sent2 == sent + (gen + fwd)
        assert peers2 == peers + 1 and socks2 == socks == peers

    # Guard rails: wrong topology / builder / protocol get clean errors.
    bad = _run_cli(
        "--numNodes", "10", "--connectionProb", "0.3", "--simTime", "2",
        "--topology", "ring", "--refParallelLinks", "--backend", "event",
    )
    assert bad.returncode == 2 and "refParallelLinks" in bad.stderr
    bad2 = _run_cli(
        "--numNodes", "10", "--connectionProb", "0.3", "--simTime", "2",
        "--refParallelLinks", "--protocol", "pushpull", "--backend", "event",
    )
    assert bad2.returncode == 2 and "flood" in bad2.stderr
    # --connectAtTick + quirk would overcount warm-up broadcasts that the
    # reference never sends (round-3 advisor finding) — rejected cleanly.
    bad3 = _run_cli(
        "--numNodes", "10", "--connectionProb", "0.3", "--simTime", "2",
        "--refParallelLinks", "--connectAtTick", "100", "--backend", "event",
    )
    assert bad3.returncode == 2 and "--connectAtTick" in bad3.stderr


def test_link_queueing_flag_and_guards():
    """--linkQueueing (FIFO link model, SURVEY deviation 5) runs on the
    per-message backends with identical event/native counters, and every
    invalid combination is a clean CLI error, not a crash."""
    common = [
        "--numNodes", "16", "--connectionProb", "0.2", "--simTime", "10",
        "--Latency", "5", "--seed", "2", "--linkQueueing",
    ]
    ev = _run_cli(*common, "--backend", "event")
    assert ev.returncode == 0, ev.stderr
    assert "FIFO link queueing" in ev.stderr
    nat = _run_cli(*common, "--backend", "native")
    assert nat.returncode == 0, nat.stderr
    # Same seeds, same model -> same per-node statistics block (compare
    # everything but the wall-clock line, which is timing, not counters).
    def stats_lines(out):
        return [
            line for line in out[out.index("Node 0:"):].splitlines()
            if " wall " not in line
        ]

    assert stats_lines(ev.stdout) == stats_lines(nat.stdout)

    r = _run_cli(*common, "--backend", "tpu")
    assert r.returncode == 2 and "requires --backend event|native" in r.stderr
    r = _run_cli(*common, "--backend", "event", "--protocol", "pushpull")
    assert r.returncode == 2 and "--protocol push only" in r.stderr
    r = _run_cli(*common, "--backend", "event",
                 "--delayModel", "serialization")
    assert r.returncode == 2 and "twice" in r.stderr
    r = _run_cli(*common, "--backend", "event", "--bandwidthMbps", "0")
    assert r.returncode == 2 and "--bandwidthMbps > 0" in r.stderr


def test_replicas_campaign_cli_json():
    """--replicas R --floodCoverage S: ensemble report + one JSON line
    with ttc percentiles and counter CIs (batch campaign engine)."""
    import json

    r = _run_cli(
        "--numNodes", "96", "--connectionProb", "0.08", "--simTime", "2",
        "--Latency", "5", "--backend", "tpu", "--floodCoverage", "2",
        "--replicas", "4", "--seed", "2", "--json",
    )
    assert r.returncode == 0, r.stderr
    assert "=== Campaign: 4 replicas x 2 flood shares" in r.stdout
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["config"]["replicas"] == 4
    assert row["summary"]["counters"]["received"]["ci95"] is not None
    assert row["summary"]["ttc"]["fraction"] == 0.99


def test_replicas_campaign_cli_validation():
    r = _run_cli("--numNodes", "16", "--replicas", "0")
    assert r.returncode == 2 and "--replicas" in r.stderr
    r = _run_cli(
        "--numNodes", "16", "--replicas", "2", "--backend", "event",
    )
    assert r.returncode == 2 and "--backend tpu" in r.stderr
    r = _run_cli(
        "--numNodes", "16", "--replicas", "2", "--anim", "/tmp/x.xml",
    )
    assert r.returncode == 2 and "--anim" in r.stderr


def test_replicas_protocol_campaign_cli(tmp_path):
    """--replicas now covers the partnered protocols, and composes with
    --checkpoint: the second invocation resumes from the snapshot and
    reports identical ensemble statistics."""
    import json

    ck = str(tmp_path / "camp.npz")
    common = (
        "--numNodes", "64", "--connectionProb", "0.1", "--simTime", "1",
        "--Latency", "5", "--backend", "tpu", "--floodCoverage", "2",
        "--replicas", "3", "--seed", "4", "--protocol", "pushpull",
        "--lossProb", "0.1", "--json", "--checkpoint", ck,
    )
    r = _run_cli(*common)
    assert r.returncode == 0, r.stderr
    assert "=== Campaign: 3 replicas x 2 flood shares" in r.stdout
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["config"]["protocol"] == "pushpull"
    import os

    assert os.path.exists(ck)  # the snapshot landed
    r2 = _run_cli(*common)  # resumes from it (fingerprint match)
    assert r2.returncode == 0, r2.stderr
    row2 = json.loads(r2.stdout.strip().splitlines()[-1])
    assert row2["summary"]["counters"] == row["summary"]["counters"]
    assert row2["summary"]["ttc"] == row["summary"]["ttc"]


def test_sweep_cli(tmp_path):
    """--sweep spec.json: one JSON line per grid cell on stdout, campaign
    report on stderr."""
    import json

    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps({
        "numNodes": 48, "p": 0.15, "protocol": "push",
        "lossProb": [0.0, 0.2], "replicas": 2, "shares": 2, "horizon": 16,
    }))
    r = _run_cli("--sweep", str(spec))
    assert r.returncode == 0, r.stderr
    rows = [json.loads(line) for line in r.stdout.strip().splitlines()]
    assert len(rows) == 2
    assert {row["cell"]["lossProb"] for row in rows} == {0.0, 0.2}
    assert all(row["engine"] == "vmap" for row in rows)
    assert "=== Campaign Report ===" in r.stderr
    missing = _run_cli("--sweep", str(tmp_path / "nope.json"))
    assert missing.returncode == 2

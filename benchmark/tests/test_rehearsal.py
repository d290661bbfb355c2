"""CPU rehearsal: every cell's traffic through the harness at host size."""

import json
import os
import subprocess
import sys

import pytest
from conftest import SHARDED

from benchmark import spec

BENCH = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]] + [SHARDED]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_line_shape(run_tiny, name, trace):
    out = run_tiny(name, trace=trace)
    assert list(out)[-1] == "check"  # the numbers compared come last
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    json.dumps(out)
    if not trace:
        want = {m["name"] for m in spec.load_cell(name).end_to_end} if name != SHARDED \
            else {"node_updates_per_s", "setup_s"}
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
    else:
        # The CPU has no TPU plane: only host-side readers report.
        assert set(out["metrics"]) <= {"compile_s"}


def test_every_file_is_found_by_name():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert callable(spec.module("paths", cell.engine["engine"]).Path)
        kind = spec.module("kinds", cell.traffic["kind"])
        for fn in ("mix", "schedules", "reference", "expected", "control"):
            assert callable(getattr(kind, fn))
        assert callable(spec.module("topologies", cell.config["topology"]).csr)
        assert cell.per_layer and cell.end_to_end
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))
    for m in BENCH["per_layer"]:
        assert callable(spec.load_reader(m["name"]))


def test_command_refuses_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""

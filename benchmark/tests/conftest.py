"""The benchmark's tests run on the host CPU with four virtual devices
(the sharded path's mesh); the command itself refuses the CPU."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = f"{_flags} --xla_force_host_platform_device_count=4"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import dataclasses  # noqa: E402

import pytest  # noqa: E402

#: Host-sized stand-ins: the same files, scaled down so a CPU test holds them.
TINY_NODES = {"er100k": 5000, "ba1m": 6000}  # >= 4096: degree buckets
TINY_SHARES = 128


def tiny(cell):
    """``cell`` with its overlay and share mix cut to CPU size."""
    config = dict(cell.config, nodes=TINY_NODES[cell.config["name"]])
    traffic = dict(cell.traffic, chunk_size=TINY_SHARES)
    if "shares" in traffic:
        traffic["shares"] = TINY_SHARES
    else:  # Poisson: a few chunks
        traffic["shares_per_node_per_s"] = 3 * TINY_SHARES / config["nodes"]
    return dataclasses.replace(cell, config=config, traffic=traffic)


SHARDED = "ba1m.sharded_flood"
#: The deferred four-chip cell (PERF.md §7): ``ba1m.flood_burst`` driven
#: through the ``sharded`` path, as its ``workloads/<cell>.json`` would say.
SHARDED_ENGINE = {"engine": "sharded", "mesh_node_shards": 4,
                  "exchange": "dense", "ring_mode": "auto"}


def load(name):
    """A cell by name; the deferred four-chip cell built here."""
    from benchmark import spec

    if name != SHARDED:
        return spec.load_cell(name)
    base = spec.load_cell("ba1m.flood_burst")
    return dataclasses.replace(base, name=name, chips=4, engine=SHARDED_ENGINE)


@pytest.fixture
def run_tiny():
    import time

    from benchmark import harness

    def go(name, trace=False, seed=2**33 + 5, seconds=0.5):
        return harness.run(tiny(load(name)), seed, seconds, trace,
                           time.perf_counter(), require_tpu=False)

    return go

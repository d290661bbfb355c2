"""The four-chip cell ``ba1m.sharded_flood`` and its ``mesh`` path at host
size: found by name, correct against the reference, ticks reported and
equal to the one-chip path's, its roofline reader's arithmetic, and a
planted exchange fault read as not correct."""

import dataclasses
import time

import jax
import numpy as np
import pytest
from conftest import TINY_NODES, TINY_SHARES

from benchmark import graphs, harness, spec, xplane
from p2p_gossip_tpu.parallel import engine_sharded

CELL = "ba1m.sharded_flood"


def tiny_mesh():
    """The real cell's files, its overlay and share mix cut to CPU size."""
    cell = spec.load_cell(CELL)
    config = dict(cell.config, nodes=TINY_NODES["ba1m"])
    traffic = dict(cell.traffic, chunk_size=TINY_SHARES, shares=TINY_SHARES)
    return dataclasses.replace(cell, config=config, traffic=traffic)


def run(trace=False, seed=2**33 + 7):
    return harness.run(tiny_mesh(), seed, 0.5, trace, time.perf_counter(),
                       require_tpu=False)


def test_cell_loads_from_its_files():
    cell = spec.load_cell(CELL)
    flood = spec.load_cell("ba1m.flood_burst")
    assert cell.chips == 4 and cell.engine["engine"] == "mesh"
    assert cell.engine["mesh_node_shards"] == 4
    assert cell.engine["exchange"] == "dense"
    assert cell.traffic == flood.traffic
    same = ("topology", "nodes", "m", "graph_seed", "link_delay_ticks")
    assert all(cell.config[k] == flood.config[k] for k in same)
    assert cell.config["mesh_chips"] == 4
    assert {m["name"] for m in cell.end_to_end} == {"node_updates_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle_share", "compile_s", "collective_share",
        "mesh_step_roofline",
    }
    assert callable(spec.module("paths", "mesh").Path)


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_is_correct(trace):
    out = run(trace=trace)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert list(out)[-1] == "check"
    if trace:  # the CPU has no TPU plane: only host-side readers report
        assert set(out["metrics"]) <= {"compile_s"}
    else:
        assert set(out["metrics"]) == {"node_updates_per_s", "setup_s"}


def test_mesh_path_matches_one_chip_path_with_ticks():
    """Each schedule of the pool through ``mesh`` (four node shards) and
    ``sync`` (one device): bitwise the same counters, the same ticks."""
    from p2p_gossip_tpu.models.generation import Schedule
    from p2p_gossip_tpu.models.topology import Graph

    cell = tiny_mesh()
    indptr, indices, perm = graphs.overlay(cell.config, 11)
    n = indptr.shape[0] - 1
    graph = Graph(n=n, indptr=indptr, indices=indices)
    kind = spec.module("kinds", cell.traffic["kind"])
    mix = kind.mix(cell.traffic, n)
    devices = jax.devices()
    mesh = spec.module("paths", "mesh").Path(graph, mix, cell.engine, devices)
    one = spec.module("paths", "sync").Path(graph, mix, {}, devices)
    for origins, ticks in kind.schedules(mix, perm):
        sched = Schedule(n, origins, ticks)
        (got, t_mesh), (want, t_one) = mesh(sched), one(sched)
        assert isinstance(t_mesh, int) and t_mesh == t_one > 0
        for k in want:
            assert np.array_equal(got[k], want[k]), k


def _readings(ticks, busy_ns=(2e9, 1e9, 3e9, 2e9)):
    chips = [xplane.Chip(busy_ns=b, collective_ns=0.1 * b, op_self_ns={})
             for b in busy_ns]
    summary = xplane.Summary(window_ns=4e9, chips=chips, gaps=[])
    calls = [harness.Call(0, 1.0, 1, t, {}) for t in ticks]
    return harness.Readings(trace=summary, compile_s=1.0, calls=calls,
                            n=1000, nnz=6000, chunk_shares=2048,
                            hbm_peak_gbps=819.0)


def test_mesh_step_roofline_arithmetic():
    read = spec.load_reader("mesh_step_roofline")
    r = _readings([26, 26, 25])
    # Per tick: 6000 edges x (4 x 64 + 4) B + 3 x 1000 nodes x 4 x 64 B.
    tick = 6000 * 260 + 3 * 1000 * 256
    busy_all_chips = (2 + 1 + 3 + 2) * 1.0  # s: mean busy 2 s x 4 chips
    want = 100.0 * tick * 77 / busy_all_chips / 819e9
    assert read(r) == pytest.approx(want, rel=1e-12)
    # One chip's share reads four times as high.
    assert spec.load_reader("step_roofline")(r) == pytest.approx(4 * want)
    assert read(_readings([26, None])) is None
    assert read(dataclasses.replace(r, trace=None)) is None
    assert read(dataclasses.replace(r, hbm_peak_gbps=None)) is None


@pytest.fixture
def fresh_programs():
    jax.clear_caches()  # a patched collective must be traced anew
    yield
    jax.clear_caches()


def test_exchange_reduced_to_local_slice(monkeypatch, fresh_programs):
    lax = engine_sharded.lax

    def local_only(x, axis_name, *, axis=0, tiled=False, **_):
        """What all_gather returns with no exchange: only this shard's
        slice, zeros where the other shards' slices belong."""
        size, me = lax.axis_size(axis_name), lax.axis_index(axis_name)
        if not tiled:
            x = jax.numpy.expand_dims(x, axis)
        shape = list(x.shape)
        shape[axis] *= size
        out = jax.numpy.zeros(shape, x.dtype)
        return lax.dynamic_update_slice_in_dim(out, x, me * x.shape[axis], axis)

    monkeypatch.setattr(engine_sharded.lax, "all_gather", local_only)
    out = run()
    assert out["correct"] is False and out["failed"] == out["attempted"]

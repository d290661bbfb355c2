"""The step byte count reads the overlay, never the staged ELL."""

import numpy as np
import pytest
from conftest import load, tiny

from benchmark import bytecount, graphs, spec


def test_hand_count_on_a_tiny_graph():
    # Path 0-1-2 plus edge 1-3: 3 undirected edges, nnz 6, N 4.
    nnz, n, w = 6, 4, bytecount.words(40)  # 40 shares -> 2 words
    assert w == 2
    per_tick = 6 * (4 * 2 + 4) + 3 * 4 * 4 * 2
    assert bytecount.tick_bytes(nnz, n, w) == per_tick == 168
    assert bytecount.call_bytes(nnz, n, 40, ticks=5) == 5 * 168


@pytest.mark.parametrize("name", ["er100k.flood_burst", "ba1m.flood_burst"])
def test_same_count_bucketed_and_unbucketed(name):
    import jax

    from p2p_gossip_tpu.engine.sync import DeviceGraph
    from p2p_gossip_tpu.models.generation import Schedule
    from p2p_gossip_tpu.models.topology import Graph

    cell = tiny(load(name))
    indptr, indices, perm = graphs.overlay(cell.config, 5)
    n = indptr.shape[0] - 1
    graph = Graph(n=n, indptr=indptr, indices=indices)
    kind = spec.module("kinds", "flood")
    mix = kind.mix(cell.traffic, n)
    sched = Schedule(n, *kind.schedules(mix, perm)[0])
    counts, counters = [], []
    for bucketed in (True, False):
        path = spec.module("paths", "sync").Path(graph, mix, {}, jax.devices())
        path.dg = DeviceGraph.build(graph, bucketed=bucketed)
        assert (path.dg.buckets is not None) is bucketed
        got, ticks = path(sched)
        counts.append(bytecount.call_bytes(indices.shape[0], n, mix.chunk_size,
                                           ticks))
        counters.append(got)
    assert counts[0] == counts[1] > 0
    for k in counters[0]:
        np.testing.assert_array_equal(counters[0][k], counters[1][k])

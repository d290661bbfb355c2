"""The control (the reference with one share a tick late) must read as
not correct, and the reference must equal a message-level flood."""

import numpy as np
import pytest
from conftest import load, tiny

from benchmark import control, graphs, reference, spec

FLOOD = spec.module("kinds", "flood")


@pytest.mark.parametrize("name", ["er100k.flood_burst", "ba1m.flood_burst",
                                  "er100k.poisson_stream"])
@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**40])
def test_control_is_not_correct(name, seed):
    out = control.check(tiny(load(name)), seed)
    assert out["correct"] is False
    assert out["counter_mismatches"]["value"] >= 4
    assert out["max_counter_gap"]["value"] >= 1


def _flood_counters(indptr, indices, origins, ticks, horizon):
    """Message-by-message flood, one tick per link, as p2pnode.cc counts:
    a share reaching a node at tick ``horizon`` or later is never seen."""
    n = indptr.shape[0] - 1
    c = {k: np.zeros(n, dtype=np.int64) for k in reference.COUNTERS}
    for o, g in zip(origins, ticks):
        seen = np.zeros(n, dtype=bool)
        seen[o] = True
        c["generated"][o] += 1
        c["sent"][o] += indptr[o + 1] - indptr[o]
        frontier, t = [int(o)], int(g)
        while frontier and t + 1 < horizon:
            t += 1
            nxt = []
            for u in frontier:
                for v in indices[indptr[u]:indptr[u + 1]]:
                    if not seen[v]:
                        seen[v] = True
                        nxt.append(int(v))
                        c["received"][v] += 1
                        c["forwarded"][v] += 1
                        c["sent"][v] += indptr[v + 1] - indptr[v]
            frontier = nxt
    c["processed"] = c["generated"] + c["received"]
    return c


@pytest.mark.parametrize("name", ["er100k.flood_burst", "ba1m.flood_burst"])
@pytest.mark.parametrize("tail", [2, 6, 40])
def test_reference_equals_a_message_level_flood(name, tail):
    """Shares cut at every depth, and shares that reach every node."""
    cell = tiny(load(name))
    cell.config.update(nodes=300, connection_prob=0.03)
    indptr, indices, perm = graphs.overlay(cell.config, 7)
    n = indptr.shape[0] - 1
    traffic = dict(cell.traffic, shares=60, tail_ticks=tail,
                   probe_hops=[h for h in (0, 1, 2) if h < tail])
    mix = FLOOD.mix(traffic, n)
    origins, ticks = FLOOD.schedules(mix, perm)[0]
    ref = FLOOD.reference(indptr, indices)
    want = FLOOD.expected(ref, origins, ticks, mix)
    got = _flood_counters(indptr, indices, origins, ticks, mix.horizon)
    assert reference.compare(got, want) == (0, 0)
    # With a long tail only the probes need a search of their own; with a
    # short one the horizon cuts bulk shares too.
    if tail == 40:
        assert ref.searches == len(mix.probes)
    if tail == 2:
        assert ref.searches > len(mix.probes)


def test_probes_are_cut_where_the_mix_says():
    """A probe ``h`` hops out reaches exactly the ball of radius ``h``."""
    cell = tiny(load("ba1m.flood_burst"))
    indptr, indices, perm = graphs.overlay(cell.config, 11)
    n = indptr.shape[0] - 1
    mix = FLOOD.mix(dict(cell.traffic, shares=4, probes_per_hop=1,
                         probe_hops=[0, 1, 2, 3]), n)
    origins, ticks = FLOOD.schedules(mix, perm)[0]
    ref = FLOOD.reference(indptr, indices)
    for o, t in zip(origins, ticks):
        want = FLOOD.expected(ref, np.array([o]), np.array([t]), mix)
        hops = mix.horizon - 1 - int(t)
        ball = reference.bfs_depths(indptr, indices, int(o), limit=hops) > 0
        np.testing.assert_array_equal(want["received"], ball.astype(np.int64))
    assert ref.searches == 4

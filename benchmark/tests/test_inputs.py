"""The benchmark's inputs and check at host size: the relabelled overlay
equals the global-sort form, ``check_csr`` rejects every defect it must,
the builders give the bytes they always gave, the breadth-first search
gives the depths it always gave, and the check runs the reference only
for the schedules the window called."""

import hashlib

import numpy as np
import pytest
from conftest import tiny

from benchmark import graphs, harness, reference, spec
from p2p_gossip_tpu.runtime import native

FLOOD = spec.module("kinds", "flood")


def _global_sort_relabel(indptr, indices, perm):
    """The form ``graphs.relabel`` replaced: one stable argsort of all
    edges by (new source, new target)."""
    n = indptr.shape[0] - 1
    src = np.repeat(perm, np.diff(indptr))
    dst = perm[indices]
    order = np.argsort(src * n + dst, kind="stable")
    new_indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=new_indptr[1:])
    return new_indptr, dst[order].astype(np.int32)


def _unblocked_bfs(indptr, indices, src, limit=None):
    """The search ``reference.bfs_depths`` replaced: every level expands
    the whole frontier at once."""
    n = indptr.shape[0] - 1
    dist = np.full(n, -1, dtype=np.int64)
    dist[src] = 0
    frontier = np.array([src], dtype=np.int64)
    depth = 0
    while frontier.size and (limit is None or depth < limit):
        depth += 1
        hit = np.zeros(n, dtype=bool)
        for v in frontier:
            hit[indices[indptr[v]:indptr[v + 1]]] = True
        frontier = np.flatnonzero(hit & (dist < 0))
        dist[frontier] = depth
    return dist


def _csr(rows):
    """(indptr, indices) of a list of neighbour lists, kept as given."""
    indptr = np.zeros(len(rows) + 1, dtype=np.int64)
    np.cumsum([len(r) for r in rows], out=indptr[1:])
    flat = [v for r in rows for v in r]
    return indptr, np.asarray(flat, dtype=np.int32)


def _rows(indptr, indices):
    return [list(indices[indptr[v]:indptr[v + 1]]) for v in range(indptr.shape[0] - 1)]


def _sparse_graph():
    """Two components, isolated nodes and leaves: rows of degree 0 and 1."""
    edges = [(0, 3), (3, 7), (3, 8), (8, 9), (2, 5), (5, 6), (6, 2), (11, 12)]
    rows = [[] for _ in range(14)]
    for u, v in edges:
        rows[u].append(v)
        rows[v].append(u)
    return _csr([sorted(r) for r in rows])


OVERLAYS = {
    "er": lambda: native.native_erdos_renyi(3000, 0.004, 5),
    "er_forced": lambda: native.native_erdos_renyi(2000, 0.0003, 1),
    "ba": lambda: native.native_barabasi_albert(5000, 3, 2),
}


def _overlay(name):
    if name == "sparse":
        return _sparse_graph()
    g = OVERLAYS[name]()
    return g.indptr, g.indices


@pytest.mark.parametrize("block", [graphs.BLOCK, 5])
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 9000000001])
@pytest.mark.parametrize("name", ["er", "er_forced", "ba", "sparse"])
def test_relabel_equals_the_global_sort(monkeypatch, name, seed, block):
    monkeypatch.setattr(graphs, "BLOCK", block)
    indptr, indices = _overlay(name)
    perm = graphs.permutation(indptr.shape[0] - 1, seed)
    got = graphs.relabel(indptr, indices, perm)
    want = _global_sort_relabel(indptr, indices, perm)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)


@pytest.mark.parametrize("block", [graphs.BLOCK, 5])
@pytest.mark.parametrize("name", ["er", "er_forced", "ba", "sparse"])
def test_check_csr_accepts_relabelled_overlays(monkeypatch, name, block):
    monkeypatch.setattr(graphs, "BLOCK", block)
    indptr, indices = _overlay(name)
    graphs.check_csr(*graphs.relabel(indptr, indices,
                                     graphs.permutation(indptr.shape[0] - 1, 3)))


def _self_loop(rows):
    rows[4] = sorted(rows[4] + [4])  # its own reverse: still symmetric


def _repeated_edge(rows):
    u = next(x for x, r in enumerate(rows) if r)
    v = rows[u][0]
    rows[u] = sorted(rows[u] + [v])  # both directions twice: symmetric
    rows[v] = sorted(rows[v] + [u])


def _unsorted_row(rows):
    r = rows[next(v for v, r in enumerate(rows) if len(r) >= 2)]
    r[0], r[1] = r[1], r[0]


def _asymmetric_edge(rows):
    u = 4
    v = next(x for x in range(len(rows)) if x != u and x not in rows[u])
    rows[u] = sorted(rows[u] + [v])  # (u, v) without (v, u)


def _asymmetric_swap(rows):
    """(u,v),(w,x) -> (u,x),(w,v) in one direction only: every degree, and
    every in-degree, stays as it was."""
    for u in range(len(rows)):
        for w in range(u + 1, len(rows)):
            for v in rows[u]:
                for x in rows[w]:
                    if (len({u, v, w, x}) == 4 and x not in rows[u]
                            and v not in rows[w]):
                        rows[u] = sorted([y for y in rows[u] if y != v] + [x])
                        rows[w] = sorted([y for y in rows[w] if y != x] + [v])
                        return
    raise AssertionError("no swap found")


@pytest.mark.parametrize("block", [graphs.BLOCK, 5])
@pytest.mark.parametrize("name", ["er", "ba", "sparse"])
@pytest.mark.parametrize("defect, message", [
    (_self_loop, "self-loop"),
    (_repeated_edge, "repeated or unsorted"),
    (_unsorted_row, "repeated or unsorted"),
    (_asymmetric_edge, "not symmetric"),
    (_asymmetric_swap, "not symmetric"),
])
def test_check_csr_rejects_each_defect(monkeypatch, name, block, defect, message):
    monkeypatch.setattr(graphs, "BLOCK", block)
    rows = _rows(*_overlay(name))
    defect(rows)
    with pytest.raises(ValueError, match=message):
        graphs.check_csr(*_csr(rows))


#: sha1 over (indptr, indices) of the native builders' output, recorded
#: from the builders before the benchmark stopped sorting globally.
BUILDS = [
    ("er", 20000, 0.001, 0, 402502, "d00ffcb03f67bd1ac7ee059086b24490eec4d144"),
    ("er", 1000, 0.3, 2, 300106, "361bff1bab68c7b81a22d7026aee9c3e885d885d"),  # some dense rows
    ("er", 300, 0.4, 1, 35848, "fba73a673f5e2c4652f7f99da60a92d241966070"),  # dense rows
    ("er", 1000, 0.0, 0, 1998, "e9676afb4edcdc278ac4a6f56217f6293460ddb5"),  # forced edges
    ("er", 5000, 0.0001, 4, 10354, "f1368dcacb6dcca5887e5a45c2e043cea1894c98"),
    ("er", 500, 0.02, 3, 5110, "85c8536fca9cc098e32ef803cc2b5ea7d2dc92f2"),
    ("er", 100000, 0.001, 0, 10007076, "b1fee15b395c497041f7ef43823600051bd8f24e"),  # er100k
    ("ba", 800, 3, 4, 4736, "84ee7faab76cf7346e8edb637359cc1661f33069"),
]


@pytest.mark.parametrize("kind, n, arg, seed, nnz, sha1", BUILDS)
def test_builders_give_the_recorded_bytes(kind, n, arg, seed, nnz, sha1):
    g = (native.native_erdos_renyi(n, arg, seed) if kind == "er"
         else native.native_barabasi_albert(n, arg, seed))
    h = hashlib.sha1(g.indptr.tobytes())
    h.update(g.indices.tobytes())
    assert (g.indices.shape[0], h.hexdigest()) == (nnz, sha1)


@pytest.mark.parametrize("block", [graphs.BLOCK, 5])
@pytest.mark.parametrize("name", ["er", "er_forced", "ba", "sparse"])
def test_bfs_depths_equal_the_whole_frontier_search(monkeypatch, name, block):
    monkeypatch.setattr(graphs, "BLOCK", block)
    indptr, indices = _overlay(name)
    n = indptr.shape[0] - 1
    for src in np.random.default_rng(1).integers(0, n, 4):
        for limit in (None, 1, 2, 3, 5):
            got = reference.bfs_depths(indptr, indices, int(src), limit)
            assert np.array_equal(got, _unblocked_bfs(indptr, indices, int(src), limit))


def test_check_runs_the_reference_for_called_schedules_only(monkeypatch):
    cell = tiny(spec.load_cell("er100k.flood_burst"))
    indptr, indices, perm = graphs.overlay(cell.config, 5)
    mix = FLOOD.mix(dict(cell.traffic, pool=3), indptr.shape[0] - 1)
    pool = FLOOD.schedules(mix, perm)
    ref = FLOOD.reference(indptr, indices)
    want = [FLOOD.expected(ref, o, t, mix) for o, t in pool]
    bad = {k: v.copy() for k, v in want[1].items()}
    bad["received"][0] += 3
    calls = [harness.Call(0, 0.1, 0, None, want[0]),
             harness.Call(1, 0.1, 0, None, bad),
             harness.Call(0, 0.1, 0, None, want[0])]
    # The numbers every schedule's reference gave, as the check used to.
    mismatches = sum(reference.compare(c.counters, want[c.schedule])[0] for c in calls)
    gap = max(reference.compare(c.counters, want[c.schedule])[1] for c in calls)

    asked = []
    real = FLOOD.expected
    monkeypatch.setattr(FLOOD, "expected", lambda r, o, t, m: asked.append(o) or real(r, o, t, m))
    check, failed = harness._check(calls, indptr, indices, pool, mix, FLOOD)
    assert check["counter_mismatches"]["value"] == mismatches == 1
    assert check["max_counter_gap"]["value"] == gap == 3
    assert failed == 1
    assert [id(o) for o in asked] == [id(pool[0][0]), id(pool[1][0])]  # not pool[2]


def test_each_phase_is_said_as_it_ends(run_tiny, capsys):
    run_tiny("er100k.flood_burst")
    lines = capsys.readouterr().err.splitlines()
    said = [ln.split()[1] for ln in lines if ln.startswith("phase ")]
    assert said == ["imports", "backend", "graph.build", "graph.relabel", "pool",
                    "stage", "warmup", "window", "check"]
    assert lines[-1].startswith("check max_counter_gap")  # the numbers compared last

"""Topology builder tests — structure, connectivity guarantee, distributions."""

import numpy as np
import pytest

from p2p_gossip_tpu.models.topology import (
    Graph,
    barabasi_albert,
    complete_graph,
    erdos_renyi,
    grid_graph,
    ring_graph,
    watts_strogatz,
)


def _connected(g: Graph) -> bool:
    seen = np.zeros(g.n, dtype=bool)
    stack = [0]
    seen[0] = True
    while stack:
        i = stack.pop()
        for j in g.indices[g.indptr[i] : g.indptr[i + 1]]:
            if not seen[j]:
                seen[j] = True
                stack.append(int(j))
    return bool(seen.all())


def test_from_edges_dedup_and_symmetry():
    g = Graph.from_edges(4, np.array([[0, 1], [1, 0], [1, 2], [2, 3], [3, 3]]))
    g.validate()
    assert g.num_edges == 3  # dup (0,1) and self-loop dropped
    assert list(g.degree) == [1, 2, 2, 1]


def test_ell_roundtrip():
    g = erdos_renyi(50, 0.1, seed=1)
    ell_idx, ell_mask = g.ell()
    for i in range(g.n):
        row = sorted(g.indices[g.indptr[i] : g.indptr[i + 1]].tolist())
        got = sorted(ell_idx[i][ell_mask[i]].tolist())
        assert row == got


@pytest.mark.parametrize("n,p", [(2, 0.0), (10, 0.3), (100, 0.05), (500, 0.0)])
def test_er_no_isolated_nodes(n, p):
    g = erdos_renyi(n, p, seed=42)
    g.validate()
    assert (g.degree >= 1).all()


def test_er_p_zero_is_forced_chain():
    # With p=0 only forced edges remain: (0,1) then (i-1,i) — a path graph.
    g = erdos_renyi(6, 0.0, seed=0)
    assert g.num_edges == 5
    assert _connected(g)


def test_er_degree_distribution():
    n, p = 400, 0.05
    g = erdos_renyi(n, p, seed=7)
    mean_deg = g.degree.mean()
    assert abs(mean_deg - (n - 1) * p) < 3.0


def test_er_sparse_path_matches_distribution():
    # Force sparse path by monkeypatching the limit boundary: n just above it
    # would be slow; instead compare small-n statistics of both paths.
    from p2p_gossip_tpu.models import topology as topo

    old = topo._DENSE_ER_LIMIT
    try:
        topo._DENSE_ER_LIMIT = 10  # force sparse sampling
        g_sparse = erdos_renyi(300, 0.05, seed=3)
    finally:
        topo._DENSE_ER_LIMIT = old
    g_dense = erdos_renyi(300, 0.05, seed=3)
    g_sparse.validate()
    assert abs(g_sparse.degree.mean() - g_dense.degree.mean()) < 3.0
    assert _connected(g_sparse)


def test_er_connected_at_default_config():
    # README default: numNodes=10, connectionProb=0.3.
    for seed in range(5):
        g = erdos_renyi(10, 0.3, seed=seed)
        assert _connected(g)


def test_ba_structure():
    g = barabasi_albert(500, m=3, seed=0)
    g.validate()
    assert _connected(g)
    # Scale-free: max degree well above the mean.
    assert g.max_degree > 4 * g.degree.mean()


def test_ring_and_complete():
    r = ring_graph(8)
    assert (r.degree == 2).all()
    c = complete_graph(6)
    assert (c.degree == 5).all()


def test_edges_canonical():
    g = erdos_renyi(60, 0.1, seed=9)
    e = g.edges()
    assert (e[:, 0] < e[:, 1]).all()
    assert e.shape[0] == g.num_edges


def test_ws_beta_zero_is_lattice():
    g = watts_strogatz(20, k=4, beta=0.0, seed=1)
    g.validate()
    assert (g.degree == 4).all()
    assert _connected(g)
    # Every node links to its 1- and 2-hop ring neighbors.
    for i in (0, 7, 19):
        nbrs = set(g.indices[g.indptr[i] : g.indptr[i + 1]].tolist())
        assert nbrs == {(i - 2) % 20, (i - 1) % 20, (i + 1) % 20, (i + 2) % 20}


def test_ws_beta_one_rewires_most_edges():
    n, k = 400, 4
    g0 = watts_strogatz(n, k=k, beta=0.0, seed=2)
    g1 = watts_strogatz(n, k=k, beta=1.0, seed=2)
    g1.validate()
    # Mean degree is conserved up to duplicate-collapse losses.
    assert g1.num_edges > 0.9 * g0.num_edges
    lattice = {tuple(e) for e in g0.edges().tolist()}
    kept = sum(1 for e in g1.edges().tolist() if tuple(e) in lattice)
    assert kept < 0.1 * g0.num_edges


def test_ws_no_isolated_nodes_and_deterministic():
    for seed in range(5):
        g = watts_strogatz(101, k=2, beta=0.5, seed=seed)
        g.validate()  # asserts min degree >= 1
    a = watts_strogatz(64, k=4, beta=0.3, seed=9)
    b = watts_strogatz(64, k=4, beta=0.3, seed=9)
    assert np.array_equal(a.indices, b.indices)


def test_ws_validates_params():
    with pytest.raises(ValueError):
        watts_strogatz(10, k=3)
    with pytest.raises(ValueError):
        watts_strogatz(4, k=4)
    with pytest.raises(ValueError):
        watts_strogatz(10, k=2, beta=1.5)


def test_grid_structure():
    g = grid_graph(3, 4)
    g.validate()
    assert g.n == 12
    # Interior nodes have degree 4, corners 2, edges 3.
    assert g.num_edges == 3 * 3 + 2 * 4  # rows*(cols-1) + (rows-1)*cols
    assert sorted(g.degree.tolist()).count(2) == 4
    assert _connected(g)


def test_torus_is_regular():
    g = grid_graph(4, 5, torus=True)
    g.validate()
    assert (g.degree == 4).all()
    assert _connected(g)
    # 3xC and Rx2 wrap edges must not duplicate existing lattice edges.
    g2 = grid_graph(2, 4, torus=True)
    g2.validate()


def test_grid_validates_params():
    with pytest.raises(ValueError):
        grid_graph(1, 1)


def test_ell_rows_matches_global_ell():
    """Graph.ell_rows (the direct-CSR row-subset ELL used by degree-bucketed
    staging) is bit-identical to slicing the global ELL."""
    g = erdos_renyi(300, 0.05, seed=2)
    full_idx, full_mask = g.ell()
    rows = np.asarray([0, 7, 123, 299, 5])
    cap = int(g.degree[rows].max()) + 4
    sub_idx, sub_mask = g.ell_rows(rows, cap)
    pad = cap - full_idx.shape[1]
    if pad > 0:
        full_idx = np.pad(full_idx, ((0, 0), (0, pad)))
        full_mask = np.pad(full_mask, ((0, 0), (0, pad)))
    assert np.array_equal(sub_idx, full_idx[rows, :cap])
    assert np.array_equal(sub_mask, full_mask[rows, :cap])


def _reference_peer_lists(n: int, p: float, seed: int):
    """Oracle for the reference's parallel-link REGISTER quirk: replay
    CreateRandomTopology (p2pnetwork.cc:62-96) + makeconnections
    (p2pnetwork.cc:98-106) + the REGISTER handler (p2pnode.cc:178-186)
    against the python builder's exact sampling stream, and return every
    node's `peers` vector INCLUDING duplicates.

    The reference's link map is keyed by the ordered pair passed to
    ConnectNodes: sampled rows insert (i, j) with i < j; a forced
    fallback inserts (i, i-1) (reversed!) or (0, 1). makeconnections
    walks the map in key order calling the deduplicated AddPeer
    synchronously (p2pnode.cc:77-82); each REGISTER packet is delivered
    in a later simulator event, and its handler appends without a
    membership check — so both endpoints of a doubled pair list each
    other twice."""
    rng = np.random.default_rng(seed)
    tri = np.triu(rng.random((n, n)) < p, k=1)
    keys = set()
    for i in range(n):
        for j in range(i + 1, n):
            if tri[i, j]:
                keys.add((i, j))
        if not tri[i].any():
            keys.add((0, 1) if i == 0 else (i, i - 1))
    peers = {i: [] for i in range(n)}
    for a, b in sorted(keys):  # sync phase: client-side AddPeer, dedup'd
        if b not in peers[a]:
            peers[a].append(b)
    for a, b in sorted(keys):  # async phase: REGISTER push_back, no dedup
        peers[b].append(a)
    return peers


def test_parallel_link_extra_matches_reference_oracle():
    """parallel_link_extra = (reference peers.size()) - (unique degree),
    for every node, across seeds with and without doubled pairs."""
    from p2p_gossip_tpu.models.topology import parallel_link_extra

    n, p = 14, 0.12
    saw_dup = 0
    for seed in range(60):
        g, extra = erdos_renyi(n, p, seed=seed, return_parallel_extra=True)
        oracle = _reference_peer_lists(n, p, seed)
        want = np.array(
            [len(oracle[i]) - len(set(oracle[i])) for i in range(n)],
            dtype=np.int32,
        )
        assert np.array_equal(extra, want), f"seed {seed}: {extra} != {want}"
        # The deduplicated peer set must be exactly the graph's adjacency.
        for i in range(n):
            assert sorted(set(oracle[i])) == sorted(
                g.indices[g.indptr[i]:g.indptr[i + 1]].tolist()
            ), f"seed {seed} node {i}"
        saw_dup += int(extra.sum() > 0)
    # The scan must actually exercise the quirk, not just the no-dup path.
    assert saw_dup >= 3, f"only {saw_dup} seeds produced a doubled pair"


def test_with_parallel_links_counters():
    """The stats transform charges (generated+forwarded) extra sends per
    duplicated entry and inflates Peer count but not Socket connections."""
    from p2p_gossip_tpu.utils.stats import NodeStats, format_final_statistics

    g = np.array([2, 0, 1], dtype=np.int64)
    r = np.array([1, 3, 2], dtype=np.int64)
    deg = np.array([2, 2, 2], dtype=np.int64)
    stats = NodeStats(
        generated=g, received=r, forwarded=r.copy(),
        sent=(g + r) * deg, processed=g + r, degree=deg,
    )
    stats.check_conservation()
    extra = np.array([1, 0, 1], dtype=np.int64)
    adj = stats.with_parallel_links(extra)
    adj.check_conservation()  # conservation aware of peer_extra
    assert np.array_equal(adj.sent, (g + r) * (deg + extra))
    text = format_final_statistics(adj)
    assert "Peer count 3, Socket connections 2" in text
    # Unadjusted rows keep peer count == socket count.
    assert "Peer count 2, Socket connections 2" in text


def test_parallel_link_extra_sparse_path_invariants():
    """Above _DENSE_ER_LIMIT the builder switches to per-row binomial
    sampling; the quirk vector must still satisfy the structural
    invariants (the dense-path oracle can't run — different RNG): extras
    come in adjacent (i-1, i) pairs that are real edges, and a doubled
    pair requires row i to have no sampled upper edge."""
    from p2p_gossip_tpu.models.topology import _DENSE_ER_LIMIT

    n = _DENSE_ER_LIMIT + 500
    p = 0.0006  # sparse enough that forced edges occur
    total = 0
    for seed in range(6):
        g, extra = erdos_renyi(n, p, seed=seed, return_parallel_extra=True)
        assert extra.shape == (n,) and (extra >= 0).all()
        # Every doubled pair {i-1, i} marks both endpoints; walking the
        # vector, unmatched residues must pair up with a neighbor.
        resid = extra.copy()
        for i in range(1, n):
            m = min(resid[i - 1], resid[i])
            if m:
                # the pair must be an actual edge of the final graph
                assert i in g.indices[g.indptr[i - 1]:g.indptr[i]].tolist() \
                    or i - 1 in g.indices[g.indptr[i]:g.indptr[i + 1]].tolist()
                resid[i - 1] -= m
                resid[i] -= m
                total += int(m)
        assert (resid == 0).all(), f"seed {seed}: unpaired extras {resid}"
    # With these parameters some seeds must exercise the quirk.
    assert total > 0


def test_load_or_build_graph_cache_protocol(tmp_path, capsys):
    """The shared cache protocol for the big-graph scripts
    (scale_1m.py / mesh_rehearsal.py): build+save on first call, load on
    the second, warn on a legacy fingerprint-less cache, clean
    SystemExit(2) on a parameter mismatch. The ER fingerprint must not
    depend on ba_m (it does not affect an ER build)."""
    from p2p_gossip_tpu.models.topology import (
        load_or_build_graph_cache,
        save_graph_cache,
        scale_graph_fingerprint,
    )

    logs = []
    cache = str(tmp_path / "g.npz")
    built = []

    def build():
        built.append(1)
        return erdos_renyi(200, 0.03, seed=5)

    kw = dict(topology="er", nodes=200, prob=0.03, ba_m=3, seed=5,
              build=build, log=logs.append)
    g1 = load_or_build_graph_cache(cache, **kw)
    assert built == [1] and (tmp_path / "g.npz").exists()
    g2 = load_or_build_graph_cache(cache, **kw)
    assert built == [1]  # loaded, not rebuilt
    assert g2.n == g1.n and np.array_equal(g2.indices, g1.indices)
    assert any("graph loaded" in m for m in logs)

    # ba_m is pinned out of ER fingerprints: a different --baM still loads.
    g3 = load_or_build_graph_cache(cache, **{**kw, "ba_m": 9})
    assert built == [1] and g3.n == g1.n

    # Parameter mismatch -> clean exit 2.
    with pytest.raises(SystemExit) as ei:
        load_or_build_graph_cache(cache, **{**kw, "seed": 6})
    assert ei.value.code == 2
    assert any("different topology flags" in m for m in logs)

    # Legacy cache without a fingerprint loads with a warning.
    legacy = str(tmp_path / "legacy.npz")
    save_graph_cache(legacy, g1)  # fp defaults to ""
    logs.clear()
    g4 = load_or_build_graph_cache(legacy, **kw)
    assert g4.n == g1.n
    assert any("predates cache fingerprints" in m for m in logs)

    # Empty cache path: always build, never save.
    built.clear()
    load_or_build_graph_cache("", **kw)
    assert built == [1]

    # BA fingerprints DO depend on ba_m.
    assert scale_graph_fingerprint("ba", 200, 0.03, 3, 5) != \
        scale_graph_fingerprint("ba", 200, 0.03, 4, 5)


def test_rcm_relabel_preserves_graph_and_dynamics():
    """RCM relabeling is a pure renumbering: the graph survives validate,
    degree multisets match, round-tripping the permutation is identity,
    and flood results unrelabel bitwise — the invariants that make the
    gather-locality candidate safe to even consider."""
    pytest.importorskip("scipy")  # rcm_order's optional dependency

    from p2p_gossip_tpu.engine.sync import run_flood_coverage
    from p2p_gossip_tpu.models.topology import (
        erdos_renyi,
        rcm_order,
        relabel_graph,
        watts_strogatz,
    )

    for g in (erdos_renyi(120, 0.05, seed=2), watts_strogatz(100, k=6, beta=0.05, seed=3)):
        order = rcm_order(g)
        assert sorted(order) == list(range(g.n))
        rg, inv = relabel_graph(g, order)
        rg.validate()
        assert np.array_equal(np.sort(rg.degree), np.sort(g.degree))
        # Round trip: inv is itself an order (inv[new]=old in rg's ids),
        # and applying it undoes the relabeling.
        back, _ = relabel_graph(rg, inv)
        assert np.array_equal(back.indptr, g.indptr)
        assert np.array_equal(back.indices, g.indices)
        # Dynamics are label-invariant: flood on the relabeled graph,
        # unrelabeled, equals the original bitwise.
        origins = np.array([5, 77], dtype=np.int32)
        st, cov = run_flood_coverage(g, origins, 40)
        st2, cov2 = run_flood_coverage(rg, inv[origins].astype(np.int32), 40)
        assert np.array_equal(cov, cov2)  # per-tick counts, label-free
        for f in ("received", "sent", "processed"):
            assert np.array_equal(
                getattr(st, f), getattr(st2, f)[inv]
            ), f

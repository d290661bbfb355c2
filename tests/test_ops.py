"""Op-level tests: bitmask primitives and gather-OR frontier propagation."""

import numpy as np
import jax.numpy as jnp
import pytest

from p2p_gossip_tpu.models.latency import constant_delays, lognormal_delays
from p2p_gossip_tpu.models.topology import erdos_renyi, ring_graph
from p2p_gossip_tpu.ops import bitmask
from p2p_gossip_tpu.ops.ell import propagate, propagate_reference


def test_popcount_rows():
    rng = np.random.default_rng(0)
    words = rng.integers(0, 2**32, size=(17, 3), dtype=np.uint64).astype(np.uint32)
    got = np.asarray(bitmask.popcount_rows(jnp.asarray(words)))
    want = np.array(
        [sum(bin(int(w)).count("1") for w in row) for row in words], dtype=np.int32
    )
    np.testing.assert_array_equal(got, want)


def test_slot_scatter_sets_exact_bits():
    rows = jnp.array([0, 2, 2, 4], dtype=jnp.int32)
    slots = jnp.array([0, 31, 32, 5], dtype=jnp.int32)
    active = jnp.array([True, True, True, False])
    out = np.asarray(bitmask.slot_scatter(5, 2, rows, slots, active))
    assert out[0, 0] == 1
    assert out[2, 0] == np.uint32(1 << 31)
    assert out[2, 1] == 1
    assert out[4, 0] == 0 and out[4, 1] == 0


def test_coverage_per_slot():
    seen = np.zeros((6, 2), dtype=np.uint32)
    seen[0, 0] |= 1       # slot 0 at node 0
    seen[3, 0] |= 1       # slot 0 at node 3
    seen[5, 1] |= 1 << 2  # slot 34 at node 5
    cov = np.asarray(bitmask.coverage_per_slot(jnp.asarray(seen), 40))
    assert cov[0] == 2
    assert cov[34] == 1
    assert cov.sum() == 3


def _numpy_propagate(hist, t, ell_idx, ell_delay, ell_mask, ring):
    d, n, w = hist.shape
    out = np.zeros((n, w), dtype=np.uint32)
    for i in range(n):
        for k in range(ell_idx.shape[1]):
            if ell_mask[i, k]:
                slot = (t - ell_delay[i, k]) % ring
                out[i] |= hist[slot, ell_idx[i, k]]
    return out


def test_propagate_matches_numpy_constant_delay():
    g = erdos_renyi(40, 0.15, seed=2)
    ell_idx, ell_mask = g.ell()
    delays = constant_delays(g, 1)
    ring = 2
    rng = np.random.default_rng(1)
    hist = rng.integers(0, 2**32, size=(ring, g.n, 2), dtype=np.uint64).astype(
        np.uint32
    )
    for t in (0, 1, 5):
        want = _numpy_propagate(hist, t, ell_idx, delays, ell_mask, ring)
        got = np.asarray(
            propagate(
                jnp.asarray(hist),
                jnp.int32(t),
                jnp.asarray(ell_idx),
                jnp.asarray(delays),
                jnp.asarray(ell_mask),
                ring_size=ring,
                block=4,
            )
        )
        np.testing.assert_array_equal(got, want)


def test_propagate_matches_numpy_heterogeneous_delay():
    g = ring_graph(16)
    ell_idx, ell_mask = g.ell()
    delays = lognormal_delays(g, mean_ticks=2.0, sigma=0.8, max_ticks=4, seed=5)
    assert delays.min() >= 1 and delays.max() <= 4
    ring = 5
    rng = np.random.default_rng(3)
    hist = rng.integers(0, 2**32, size=(ring, g.n, 1), dtype=np.uint64).astype(
        np.uint32
    )
    for t in (0, 3, 11):
        want = _numpy_propagate(hist, t, ell_idx, delays, ell_mask, ring)
        got = np.asarray(
            propagate(
                jnp.asarray(hist),
                jnp.int32(t),
                jnp.asarray(ell_idx),
                jnp.asarray(delays),
                jnp.asarray(ell_mask),
                ring_size=ring,
                block=3,
            )
        )
        np.testing.assert_array_equal(got, want)


def test_propagate_blocked_equals_reference():
    g = erdos_renyi(64, 0.1, seed=4)
    ell_idx, ell_mask = g.ell()
    delays = constant_delays(g, 1)
    ring = 2
    rng = np.random.default_rng(9)
    hist = jnp.asarray(
        rng.integers(0, 2**32, size=(ring, g.n, 4), dtype=np.uint64).astype(np.uint32)
    )
    a = propagate(
        hist, jnp.int32(7), jnp.asarray(ell_idx), jnp.asarray(delays),
        jnp.asarray(ell_mask), ring_size=ring, block=8,
    )
    b = propagate_reference(
        hist, jnp.int32(7), jnp.asarray(ell_idx), jnp.asarray(delays),
        jnp.asarray(ell_mask), ring_size=ring,
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scatter_or_matches_numpy():
    from p2p_gossip_tpu.ops.segment import scatter_or

    rng = np.random.default_rng(7)
    m, n_rows, w = 257, 40, 3
    dst = rng.integers(0, n_rows, m).astype(np.int32)
    payload = rng.integers(0, 2**32, size=(m, w), dtype=np.uint64).astype(np.uint32)
    mask = rng.random(m) < 0.8
    want = np.zeros((n_rows, w), dtype=np.uint32)
    for i in range(m):
        if mask[i]:
            want[dst[i]] |= payload[i]
    got = np.asarray(
        scatter_or(n_rows, jnp.asarray(dst), jnp.asarray(payload), jnp.asarray(mask))
    )
    np.testing.assert_array_equal(got, want)
    # No mask: every row lands.
    want2 = np.zeros((n_rows, w), dtype=np.uint32)
    for i in range(m):
        want2[dst[i]] |= payload[i]
    got2 = np.asarray(scatter_or(n_rows, jnp.asarray(dst), jnp.asarray(payload)))
    np.testing.assert_array_equal(got2, want2)


def test_delay_symmetry():
    g = erdos_renyi(30, 0.2, seed=8)
    delays = lognormal_delays(g, seed=11)
    ell_idx, ell_mask = g.ell()
    # delay(i->j) == delay(j->i): full-duplex link parity.
    lut = {}
    for i in range(g.n):
        for k in range(ell_idx.shape[1]):
            if ell_mask[i, k]:
                j = int(ell_idx[i, k])
                lut[(i, j)] = int(delays[i, k])
    for (i, j), d in lut.items():
        assert lut[(j, i)] == d


def test_bucketed_propagate_equals_reference():
    from p2p_gossip_tpu.ops.ell import build_degree_buckets, propagate_bucketed

    # Heavy-tailed degrees so multiple buckets actually form.
    from p2p_gossip_tpu.models.topology import barabasi_albert

    g = barabasi_albert(120, m=3, seed=6)
    ell_idx, ell_mask = g.ell()
    delays = lognormal_delays(g, mean_ticks=2.0, sigma=0.8, max_ticks=4, seed=2)
    ring = 5
    rng = np.random.default_rng(11)
    hist = jnp.asarray(
        rng.integers(0, 2**32, size=(ring, g.n, 3), dtype=np.uint64).astype(np.uint32)
    )
    buckets = build_degree_buckets(g, delays, min_rows=8)
    assert len(buckets) > 1
    # The bucket row sets partition range(n).
    all_rows = np.sort(np.concatenate([np.asarray(b[0]) for b in buckets]))
    np.testing.assert_array_equal(all_rows, np.arange(g.n))
    for t in (0, 3, 11):
        got = np.asarray(
            propagate_bucketed(
                hist, jnp.int32(t), buckets, n_out=g.n, ring_size=ring, block=4
            )
        )
        want = np.asarray(
            propagate_reference(
                hist, jnp.int32(t), jnp.asarray(ell_idx), jnp.asarray(delays),
                jnp.asarray(ell_mask), ring_size=ring,
            )
        )
        np.testing.assert_array_equal(got, want)


def test_bucketed_propagate_uniform_delay():
    from p2p_gossip_tpu.ops.ell import build_degree_buckets, propagate_bucketed

    g = erdos_renyi(90, 0.08, seed=8)
    ell_idx, ell_mask = g.ell()
    delays = constant_delays(g, 2)
    ring = 3
    rng = np.random.default_rng(13)
    hist = jnp.asarray(
        rng.integers(0, 2**32, size=(ring, g.n, 2), dtype=np.uint64).astype(np.uint32)
    )
    buckets = build_degree_buckets(g, None, min_rows=8)
    for t in (0, 2, 7):
        got = np.asarray(
            propagate_bucketed(
                hist, jnp.int32(t), buckets, n_out=g.n, ring_size=ring,
                uniform_delay=2, block=4,
            )
        )
        want = np.asarray(
            propagate_reference(
                hist, jnp.int32(t), jnp.asarray(ell_idx), jnp.asarray(delays),
                jnp.asarray(ell_mask), ring_size=ring,
            )
        )
        np.testing.assert_array_equal(got, want)


def test_partner_pick_hash_np_jnp_bitwise_equal():
    """The partner-pick spec (models/partnersel.py) must evaluate
    identically in numpy and jnp — the cross-engine/seeded-parity
    foundation (the C++ leg is covered by the native parity tests)."""
    import numpy as np

    from p2p_gossip_tpu.models.partnersel import pick_index_jnp, pick_index_np

    rng = np.random.default_rng(0)
    nodes = rng.integers(0, 2**31 - 1, 500)
    ticks = rng.integers(0, 100000, 500)
    picks = rng.integers(0, 16, 500)
    degs = rng.integers(0, 5000, 500)  # includes degree 0
    for seed in (0, 1, 0xDEADBEEF, 2**32 - 1):
        want = pick_index_np(nodes, ticks, picks, degs, seed)
        got = np.asarray(pick_index_jnp(nodes, ticks, picks, degs, seed))
        np.testing.assert_array_equal(got, want)
        assert (want >= 0).all() and (want < np.maximum(degs, 1)).all()


def _bucket_graph(kind: str):
    from p2p_gossip_tpu.models.topology import barabasi_albert

    if kind == "ba":
        return barabasi_albert(3000, m=3, seed=6)
    return erdos_renyi(2000, 0.02, seed=8)


@pytest.mark.parametrize("kind", ["ba", "er"])
@pytest.mark.parametrize("delay_model", ["uniform", "per_edge", "loss"])
def test_tight_bucketed_propagate_equals_reference(kind, delay_model):
    """Degree buckets under the count-tracking policy, scanned at the
    chip's block of 64 (so caps above 64 take evened blocks), give the
    full-ELL reference bitwise: uniform and per-edge delays, and under a
    loss model (each bucket's global row ids feed the loss coin)."""
    from p2p_gossip_tpu.models.linkloss import LinkLossModel, drop_mask_np
    from p2p_gossip_tpu.ops.ell import build_degree_buckets, propagate_bucketed

    g = _bucket_graph(kind)
    ell_idx, ell_mask = g.ell()
    if delay_model == "per_edge":
        delays = lognormal_delays(g, mean_ticks=2.0, sigma=0.8, max_ticks=4,
                                  seed=2)
    else:
        delays = constant_delays(g, 2)
    ring = int(delays.max()) + 1
    uniform = None if delay_model == "per_edge" else 2
    loss = LinkLossModel(0.3, seed=5) if delay_model == "loss" else None
    buckets = build_degree_buckets(
        g, delays if uniform is None else None, min_rows=64
    )
    assert len(buckets) > 2
    rng = np.random.default_rng(17)
    hist = jnp.asarray(
        rng.integers(0, 2**32, size=(ring, g.n, 2), dtype=np.uint64)
        .astype(np.uint32)
    )
    for t in (0, 3, 11):
        keep = ell_mask
        if loss is not None:
            dst = np.arange(g.n)[:, None]
            keep = ell_mask & ~drop_mask_np(ell_idx, dst, t, *loss.static_cfg)
        got = propagate_bucketed(
            hist, jnp.int32(t), buckets, n_out=g.n, ring_size=ring,
            uniform_delay=uniform, block=64,
            loss=loss.static_cfg if loss is not None else None,
        )
        want = propagate_reference(
            hist, jnp.int32(t), jnp.asarray(ell_idx), jnp.asarray(delays),
            jnp.asarray(keep), ring_size=ring,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kind", ["ba", "er"])
@pytest.mark.parametrize("delay_model", ["uniform", "per_edge"])
def test_bucket_major_staging_layout(kind, delay_model):
    """A default staging of a graph large enough to bucket numbers nodes
    bucket-major: the buckets' rows are consecutive ranges in order,
    ``inv[order]`` is the identity, the staged degree is the caller's
    permuted, each row's valid ids are sorted and front-packed with the
    padding masked, and the rows hold exactly the graph's edges (with
    their per-edge delays) renumbered."""
    from p2p_gossip_tpu.engine.sync import DeviceGraph
    from p2p_gossip_tpu.models.topology import barabasi_albert

    if kind == "ba":
        g = barabasi_albert(5000, m=3, seed=6)
    else:
        g = erdos_renyi(4500, 0.004, seed=8)
    delays = None
    if delay_model == "per_edge":
        delays = lognormal_delays(g, mean_ticks=2.0, sigma=0.8, max_ticks=4,
                                  seed=2)
    dg = DeviceGraph.build(g, delays)
    assert dg.buckets is not None and len(dg.buckets) > 1
    order, inv = np.asarray(dg.order), np.asarray(dg.inv)
    np.testing.assert_array_equal(inv[order], np.arange(g.n))
    np.testing.assert_array_equal(np.asarray(dg.degree), g.degree[order])
    start, src, dst, dly = 0, [], [], []
    for rows, idx, mask, delay in dg.buckets:
        rows, idx, mask = np.asarray(rows), np.asarray(idx), np.asarray(mask)
        np.testing.assert_array_equal(
            rows, np.arange(start, start + rows.shape[0])
        )
        start += rows.shape[0]
        cnt = mask.sum(axis=1)
        np.testing.assert_array_equal(cnt, g.degree[order[rows]])
        assert (mask == (np.arange(mask.shape[1]) < cnt[:, None])).all()
        assert (idx[~mask] == 0).all()
        assert (np.diff(idx, axis=1)[mask[:, 1:]] > 0).all()
        src.append(np.broadcast_to(order[rows][:, None], mask.shape)[mask])
        dst.append(order[idx[mask]])
        if delays is not None:
            dly.append(np.asarray(delay)[mask])
    assert start == g.n
    src, dst = np.concatenate(src), np.concatenate(dst)
    csr = np.lexsort((dst, src))
    rows_csr, _ = g.csr_rows_pos()
    np.testing.assert_array_equal(src[csr], rows_csr)
    np.testing.assert_array_equal(dst[csr], g.indices)
    if delays is not None:
        ell_idx, ell_mask = g.ell()
        np.testing.assert_array_equal(
            np.concatenate(dly)[csr], delays[ell_mask]
        )


@pytest.mark.parametrize("kind", ["ba", "er"])
@pytest.mark.parametrize("delay_model", ["uniform", "per_edge", "loss"])
def test_bucket_major_propagate_equals_reference(kind, delay_model):
    """Bucket-major buckets, concatenated, over a history ring in the
    staged numbering give the full-ELL reference in the caller's
    numbering, permuted: uniform and per-edge delays, and under a loss
    model whose coins hash the caller's ids of both ends."""
    from p2p_gossip_tpu.models.linkloss import LinkLossModel, drop_mask_np
    from p2p_gossip_tpu.ops.ell import build_bucket_major, propagate_bucketed

    g = _bucket_graph(kind)
    ell_idx, ell_mask = g.ell()
    if delay_model == "per_edge":
        delays = lognormal_delays(g, mean_ticks=2.0, sigma=0.8, max_ticks=4,
                                  seed=2)
    else:
        delays = constant_delays(g, 2)
    ring = int(delays.max()) + 1
    uniform = None if delay_model == "per_edge" else 2
    loss = LinkLossModel(0.3, seed=5) if delay_model == "loss" else None
    buckets, order = build_bucket_major(
        g, delays if uniform is None else None, min_rows=64
    )
    assert len(buckets) > 2
    rng = np.random.default_rng(17)
    hist = rng.integers(0, 2**32, size=(ring, g.n, 2), dtype=np.uint64).astype(
        np.uint32
    )
    for t in (0, 3, 11):
        keep = ell_mask
        if loss is not None:
            dst = np.arange(g.n)[:, None]
            keep = ell_mask & ~drop_mask_np(ell_idx, dst, t, *loss.static_cfg)
        got = propagate_bucketed(
            jnp.asarray(hist[:, order]), jnp.int32(t), buckets, n_out=g.n,
            ring_size=ring, uniform_delay=uniform, block=64,
            loss=loss.static_cfg if loss is not None else None,
            node_ids=jnp.asarray(order, dtype=jnp.int32),
        )
        want = propagate_reference(
            jnp.asarray(hist), jnp.int32(t), jnp.asarray(ell_idx),
            jnp.asarray(delays), jnp.asarray(keep), ring_size=ring,
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want)[order]
        )


@pytest.mark.parametrize("shape", ["ba1m", "er100k"])
def test_gathered_slots_track_the_edges(shape):
    """On overlays of the benchmark configurations' shapes (1M-node BA
    m=3; 100K-node ER p=0.001) the staged buckets, scanned at block 64,
    gather at most 8% more slots than there are edges, in at most 32
    buckets; each bucket's cap is its rows' max degree and its scanned
    axis exceeds the cap by less than its block count."""
    from p2p_gossip_tpu.engine.sync import DeviceGraph
    from p2p_gossip_tpu.models.topology import barabasi_albert
    from p2p_gossip_tpu.ops.ell import scan_block, scanned_width

    if shape == "ba1m":
        g = barabasi_albert(1_000_000, m=3, seed=0)
    else:
        g = erdos_renyi(100_000, 0.001, seed=0)
    dg = DeviceGraph.build(g, bucketed=True)
    nnz = int(g.degree.sum())
    assert len(dg.buckets) <= 32
    order = np.asarray(dg.order)  # rows are bucket-major staged ids
    for rows, idx, _mask, _delay in dg.buckets:
        cap = idx.shape[1]
        assert cap == int(g.degree[order[np.asarray(rows)]].max())
        nblocks = -(-cap // scan_block(cap, 64))
        assert 0 <= scanned_width(cap, 64) - cap < nblocks
    slots = dg.gather_slots(64)
    assert nnz <= slots <= 1.08 * nnz, slots / nnz


@pytest.mark.parametrize("kind", ["ba", "er"])
def test_bucket_shapes_survive_relabelling(kind):
    """Bucket shapes depend only on the degree multiset: a random node
    relabelling stages the same (rows, cap) list, so every relabelled
    run reuses one compiled program."""
    from p2p_gossip_tpu.models.topology import relabel_graph
    from p2p_gossip_tpu.ops.ell import build_degree_buckets

    g = _bucket_graph(kind)
    order = np.random.default_rng(3).permutation(g.n)
    h, _ = relabel_graph(g, order)

    def shapes(graph):
        return [b[1].shape for b in build_degree_buckets(graph, min_rows=64)]

    assert len(shapes(g)) > 2
    assert shapes(g) == shapes(h)


@pytest.mark.parametrize("cap", [1, 3, 8, 64, 65, 104, 145, 4517])
@pytest.mark.parametrize("block", [8, 64])
def test_scan_block_pads_less_than_its_block_count(cap, block):
    from p2p_gossip_tpu.ops.ell import scan_block, scanned_width

    b = scan_block(cap, block)
    nblocks = -(-cap // b)
    assert b <= block and nblocks == -(-cap // block)
    assert 0 <= scanned_width(cap, block) - cap < nblocks

"""Sharded-engine tests on the 8-virtual-CPU-device mesh: the multi-chip
path must be counter-identical to the event oracle and the single-device
sync engine for every mesh shape."""

import numpy as np
import pytest

import jax

import p2p_gossip_tpu as pg
from p2p_gossip_tpu.engine.event import run_event_sim
from p2p_gossip_tpu.engine.sync import run_sync_sim
from p2p_gossip_tpu.models.latency import lognormal_delays
from p2p_gossip_tpu.parallel.mesh import make_mesh, pad_to_multiple
from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim


def _cpu_mesh(n_node_shards, n_share_shards=1):
    return make_mesh(n_node_shards, n_share_shards, devices=jax.devices("cpu"))


def test_mesh_helper_shapes():
    mesh = _cpu_mesh(4, 2)
    assert mesh.shape["nodes"] == 4 and mesh.shape["shares"] == 2
    with pytest.raises(ValueError):
        make_mesh(16, 1, devices=jax.devices("cpu"))


def test_pad_to_multiple():
    x = np.arange(10)
    assert pad_to_multiple(x, 4).shape == (12,)
    assert pad_to_multiple(x, 5).shape == (10,)


@pytest.mark.parametrize("shards", [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_sharded_parity_all_mesh_shapes(shards):
    ns, ss = shards
    g = pg.erdos_renyi(96, 0.06, seed=1)
    sched = pg.uniform_renewal_schedule(96, sim_time=8.0, tick_dt=0.01, seed=1)
    ev = run_event_sim(g, sched, 800)
    sh = run_sharded_sim(g, sched, 800, _cpu_mesh(ns, ss), chunk_size=64)
    assert sh.equal_counts(ev)
    sh.check_conservation()


def test_sharded_parity_with_row_padding():
    # 103 rows over 4 shards: padded rows must stay inert.
    g = pg.erdos_renyi(103, 0.06, seed=2)
    d = lognormal_delays(g, mean_ticks=2.0, sigma=0.5, max_ticks=4, seed=2)
    sched = pg.poisson_schedule(103, sim_time=3.0, tick_dt=0.01, rate=0.3, seed=2)
    ev = run_event_sim(g, sched, 400, ell_delays=d)
    sh = run_sharded_sim(
        g, sched, 400, _cpu_mesh(4, 2), ell_delays=d, chunk_size=32
    )
    assert sh.equal_counts(ev)


def test_sharded_matches_single_device_engine():
    g = pg.barabasi_albert(120, m=2, seed=3)
    sched = pg.uniform_renewal_schedule(120, sim_time=6.0, tick_dt=0.01, seed=3)
    sy = run_sync_sim(g, sched, 600)
    sh = run_sharded_sim(g, sched, 600, _cpu_mesh(2, 2), chunk_size=96)
    assert sh.equal_counts(sy)


def test_sharded_multiple_passes():
    # More shares than one pass holds: host loop accumulates across passes.
    g = pg.erdos_renyi(64, 0.08, seed=4)
    sched = pg.uniform_renewal_schedule(64, sim_time=30.0, tick_dt=0.01, seed=4)
    assert sched.num_shares > 4 * 32
    ev = run_event_sim(g, sched, 3000)
    sh = run_sharded_sim(g, sched, 3000, _cpu_mesh(2, 2), chunk_size=32)
    assert sh.equal_counts(ev)


def test_sharded_snapshots_match_event_engine():
    """Periodic-stats snapshots on the sharded engine are identical to the
    event oracle's (PrintPeriodicStats timing: totals strictly before the
    boundary), including boundaries past quiescence."""
    g = pg.erdos_renyi(64, 0.08, seed=5)
    sched = pg.uniform_renewal_schedule(64, sim_time=6.0, tick_dt=0.01, seed=5)
    boundaries = [100, 250, 400, 5000]
    ev = run_event_sim(g, sched, 600, snapshot_ticks=boundaries)
    sh = run_sharded_sim(
        g, sched, 600, _cpu_mesh(4, 2), chunk_size=64,
        snapshot_ticks=boundaries,
    )
    assert np.array_equal(ev.received, sh.received)
    # 5000 > horizon: dropped by both engines.
    assert len(ev.extra["snapshots"]) == 3
    assert ev.extra["snapshots"] == sh.extra["snapshots"]


@pytest.mark.parametrize("shards", [(4, 2), (2, 4), (8, 1)])
def test_sharded_flood_coverage_matches_sync(shards):
    """Per-tick coverage and counters from the mesh flood runner are
    identical to the single-device sync engine for every mesh shape."""
    from p2p_gossip_tpu.engine.sync import run_flood_coverage
    from p2p_gossip_tpu.parallel.engine_sharded import (
        run_sharded_flood_coverage,
    )

    g = pg.erdos_renyi(60, 0.1, seed=1)
    origins = [0, 5, 30, 59]
    st_s, cov_s = run_flood_coverage(g, origins, 40)
    st_m, cov_m = run_sharded_flood_coverage(
        g, origins, 40, _cpu_mesh(*shards), chunk_size=64
    )
    assert np.array_equal(cov_s, cov_m)
    for f in ("generated", "received", "forwarded", "sent", "processed"):
        assert np.array_equal(getattr(st_s, f), getattr(st_m, f))


def test_sharded_flood_coverage_under_loss():
    from p2p_gossip_tpu.engine.sync import run_flood_coverage
    from p2p_gossip_tpu.models.linkloss import LinkLossModel
    from p2p_gossip_tpu.parallel.engine_sharded import (
        run_sharded_flood_coverage,
    )

    g = pg.erdos_renyi(50, 0.1, seed=3)
    loss = LinkLossModel(0.4, seed=5)
    st_s, cov_s = run_flood_coverage(g, [2, 17], 60, loss=loss)
    st_m, cov_m = run_sharded_flood_coverage(
        g, [2, 17], 60, _cpu_mesh(2, 2), chunk_size=64, loss=loss
    )
    assert np.array_equal(cov_s, cov_m)
    assert np.array_equal(st_s.received, st_m.received)


@pytest.mark.parametrize("shards", [(8, 1), (4, 2), (2, 4)])
@pytest.mark.parametrize("ring_mode", ["replicated", "sharded"])
def test_ring_modes_bitwise_equal_per_edge_delays(ring_mode, shards):
    """Both ring layouts produce identical counters under a spread of
    per-edge delays (the sharded layout reads via per-delay-value
    frontier all_gathers; see engine_sharded module docstring)."""
    ns, ss = shards
    g = pg.erdos_renyi(80, 0.08, seed=7)
    d = lognormal_delays(g, mean_ticks=2.5, sigma=0.8, max_ticks=6, seed=7)
    sched = pg.uniform_renewal_schedule(80, sim_time=6.0, tick_dt=0.01, seed=7)
    ev = run_event_sim(g, sched, 600, ell_delays=d)
    sh = run_sharded_sim(
        g, sched, 600, _cpu_mesh(ns, ss), ell_delays=d, chunk_size=32,
        ring_mode=ring_mode,
    )
    assert sh.equal_counts(ev)
    assert sh.extra["ring"]["mode"] == ring_mode
    if ring_mode == "sharded":
        assert sh.extra["ring"]["delay_splits"] > 1


def test_ring_modes_bitwise_equal_with_loss_and_churn():
    """The loss coin hashes global (src, dst, t) and churn masks arrivals
    post-OR, so neither may depend on the ring layout."""
    g = pg.erdos_renyi(64, 0.1, seed=9)
    d = lognormal_delays(g, mean_ticks=2.0, sigma=0.6, max_ticks=4, seed=9)
    sched = pg.uniform_renewal_schedule(64, sim_time=5.0, tick_dt=0.01, seed=9)
    loss = pg.LinkLossModel(0.25, seed=4)
    churn = pg.random_churn(
        64, 500, outage_prob=0.3, mean_down_ticks=40, seed=5
    )
    ev = run_event_sim(g, sched, 500, ell_delays=d, loss=loss, churn=churn)
    runs = {
        mode: run_sharded_sim(
            g, sched, 500, _cpu_mesh(4, 2), ell_delays=d, chunk_size=32,
            loss=loss, churn=churn, ring_mode=mode,
        )
        for mode in ("replicated", "sharded")
    }
    for mode, sh in runs.items():
        assert sh.equal_counts(ev), f"ring_mode={mode} diverges"


def test_ring_auto_policy_and_memory_accounting():
    """auto -> sharded for uniform delays (same traffic, 1/shards HBM);
    replicated for small per-edge rings; per-chip bytes reported."""
    from p2p_gossip_tpu.parallel.engine_sharded import (
        RING_REPLICATED_MAX_BYTES,
        resolve_ring_mode,
    )

    # Uniform delay: always sharded.
    mode, b = resolve_ring_mode("auto", 1, 2, 1024, 8, 4)
    assert mode == "sharded" and b == 4 * 2 * (1024 // 8) * 4
    # Small per-edge ring: replicated.
    mode, b = resolve_ring_mode("auto", None, 4, 1024, 8, 4)
    assert mode == "replicated" and b == 4 * 4 * 1024 * 4
    # A 1M-node-scale per-edge ring exceeds the ceiling: sharded.
    n, ring, w = 1_000_000, 8, 256
    assert 4 * ring * n * w > RING_REPLICATED_MAX_BYTES
    mode, b = resolve_ring_mode("auto", None, ring, n, 8, w)
    assert mode == "sharded" and b == 4 * ring * (n // 8) * w

    # End-to-end: a uniform-delay run reports the sharded ring.
    g = pg.erdos_renyi(48, 0.12, seed=3)
    sched = pg.uniform_renewal_schedule(48, sim_time=4.0, tick_dt=0.01, seed=3)
    ev = run_event_sim(g, sched, 400)
    sh = run_sharded_sim(g, sched, 400, _cpu_mesh(4, 2), chunk_size=32)
    assert sh.equal_counts(ev)
    assert sh.extra["ring"]["mode"] == "sharded"


def test_split_ell_by_delay_partitions_edges():
    from p2p_gossip_tpu.ops.ell import split_ell_by_delay

    g = pg.erdos_renyi(40, 0.15, seed=11)
    d = lognormal_delays(g, mean_ticks=2.0, sigma=0.7, max_ticks=5, seed=11)
    ell_idx, ell_mask = g.ell()
    splits = split_ell_by_delay(ell_idx, d, ell_mask)
    # Valid (row, neighbor) pairs partition exactly.
    seen_pairs = set()
    for dval, idx_d, msk_d in splits:
        rows, cols = np.nonzero(msk_d)
        for r, c in zip(rows, cols):
            pair = (int(r), int(idx_d[r, c]))
            assert pair not in seen_pairs
            seen_pairs.add(pair)
            # Every packed edge really has this delay in the source ELL.
            src_cols = np.nonzero(
                (ell_idx[r] == idx_d[r, c]) & ell_mask[r]
            )[0]
            assert any(d[r, sc] == dval for sc in src_cols)
    expect = {
        (int(r), int(ell_idx[r, c])) for r, c in zip(*np.nonzero(ell_mask))
    }
    assert seen_pairs == expect


def test_multihost_bootstrap_and_mesh(tmp_path):
    """initialize_multihost + make_multihost_mesh single-process path:
    the distributed bootstrap must leave jax usable, the mesh must carry
    the canonical (shares, nodes) axes, and the sharded engine must run
    on it with oracle-identical counters. Runs in a subprocess because
    jax.distributed.initialize is process-global state."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        # Bootstrap FIRST: jax.distributed.initialize must run before
        # anything touches the XLA backend (importing the package pulls
        # in modules that do) — the same ordering a pod launcher needs.
        from p2p_gossip_tpu.parallel.mesh import (
            NODES_AXIS, SHARES_AXIS, initialize_multihost,
            make_multihost_mesh,
        )

        # Explicit single-process coordinator: the code path a pod
        # launcher runs, shrunk to one process.
        idx, count = initialize_multihost("localhost:19357", 1, 0)
        assert (idx, count) == (0, 1), (idx, count)
        # Second call must be a no-op, not a crash.
        assert initialize_multihost("localhost:19357", 1, 0) == (0, 1)

        import numpy as np
        import p2p_gossip_tpu as pg

        mesh = make_multihost_mesh(n_node_shards=4, n_share_shards=2)
        assert mesh.axis_names == (SHARES_AXIS, NODES_AXIS)
        assert mesh.devices.shape == (2, 4)

        from p2p_gossip_tpu.engine.event import run_event_sim
        from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim

        g = pg.erdos_renyi(60, 0.1, seed=4)
        sched = pg.uniform_renewal_schedule(
            60, sim_time=1.2, tick_dt=0.005, seed=4
        )
        sh = run_sharded_sim(g, sched, 300, mesh, chunk_size=32)
        ev = run_event_sim(g, sched, 300)
        assert sh.equal_counts(ev), "multihost-mesh engine diverged"
        print("MULTIHOST-OK")
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # Extend, don't overwrite: the parent env may carry flags/paths the
    # child needs to import its dependencies.
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    keep = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([repo, *keep])
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=420, env=env,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MULTIHOST-OK" in r.stdout


def test_degree_bucketed_sharded_gather_multi_bucket_parity():
    """bucket_min_rows=1 on a hub-skewed (BA) graph forces the sharded
    engine's multi-bucket gather regime (the default 2048 floor folds
    small test graphs into one bucket); counters must stay bitwise equal
    to the event engine in both ring layouts, with per-edge delays and
    with loss — and the staged bucket layout must actually be multiple
    buckets, or this test is vacuous."""
    from p2p_gossip_tpu.models.linkloss import LinkLossModel

    g = pg.barabasi_albert(220, m=3, seed=5)
    sched = pg.uniform_renewal_schedule(g.n, sim_time=3.0, tick_dt=0.01,
                                        seed=5)
    delays = lognormal_delays(g, mean_ticks=2.0, sigma=0.7, max_ticks=4,
                              seed=5)
    for ring_mode in ("replicated", "sharded"):
        for loss in (None, LinkLossModel(0.2, seed=9)):
            ev = run_event_sim(g, sched, 300, ell_delays=delays, loss=loss)
            sh = run_sharded_sim(
                g, sched, 300, _cpu_mesh(4, 2), ell_delays=delays,
                chunk_size=32, loss=loss, ring_mode=ring_mode,
                bucket_min_rows=1,
            )
            assert sh.equal_counts(ev), (ring_mode, loss)
            counts = sh.extra["ring"]["degree_buckets"]
            assert len(counts) == 4  # one group per distinct delay value
            assert max(counts) > 1, counts  # multi-bucket regime reached
    # Uniform-delay path too (single group, bucketed).
    ev = run_event_sim(g, sched, 300)
    sh = run_sharded_sim(
        g, sched, 300, _cpu_mesh(4, 2), chunk_size=32, bucket_min_rows=1,
    )
    assert sh.equal_counts(ev)
    assert max(sh.extra["ring"]["degree_buckets"]) > 1

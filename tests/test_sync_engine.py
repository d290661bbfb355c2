"""Sync (TPU tick) engine tests — exact parity with the event-driven oracle.

This is the "NS-3 stats parity" axis: same topology + schedule + integer
delays must give identical per-node counters on both engines.
"""

import os

import numpy as np
import pytest

import p2p_gossip_tpu as pg
from p2p_gossip_tpu.engine.event import run_event_sim
from p2p_gossip_tpu.engine.sync import (
    run_flood_coverage,
    run_sync_sim,
    time_to_coverage,
)
from p2p_gossip_tpu.models.generation import single_share_schedule
from p2p_gossip_tpu.models.latency import lognormal_delays
from p2p_gossip_tpu.models.topology import barabasi_albert, ring_graph


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_parity_er_constant_delay(seed):
    g = pg.erdos_renyi(100, 0.05, seed=seed)
    sched = pg.uniform_renewal_schedule(100, sim_time=20.0, tick_dt=0.005, seed=seed)
    horizon = int(20.0 / 0.005)
    ev = run_event_sim(g, sched, horizon)
    sy = run_sync_sim(g, sched, horizon)
    assert sy.equal_counts(ev)
    sy.check_conservation()


def test_parity_heterogeneous_delays():
    g = pg.erdos_renyi(80, 0.06, seed=3)
    d = lognormal_delays(g, mean_ticks=2.0, sigma=0.6, max_ticks=5, seed=1)
    sched = pg.uniform_renewal_schedule(80, sim_time=3.0, tick_dt=0.005, seed=3)
    ev = run_event_sim(g, sched, 700, ell_delays=d)
    sy = run_sync_sim(g, sched, 700, ell_delays=d)
    assert sy.equal_counts(ev)


def test_parity_truncated_horizon():
    # Horizon cuts floods mid-flight: both engines must cut identically.
    g = ring_graph(40)
    sched = pg.uniform_renewal_schedule(40, sim_time=2.0, tick_dt=0.1, seed=4)
    for horizon in (3, 7, 15):
        ev = run_event_sim(g, sched, horizon)
        sy = run_sync_sim(g, sched, horizon)
        assert sy.equal_counts(ev), f"horizon={horizon}"


def test_parity_scale_free_topology():
    g = barabasi_albert(200, m=2, seed=5)
    sched = pg.poisson_schedule(200, sim_time=5.0, tick_dt=0.01, rate=0.1, seed=5)
    horizon = 600
    ev = run_event_sim(g, sched, horizon)
    sy = run_sync_sim(g, sched, horizon)
    assert sy.equal_counts(ev)


def test_parity_multiple_chunks():
    # Chunked execution (shares split across several device passes) must be
    # invisible in the counters.
    g = pg.erdos_renyi(60, 0.08, seed=6)
    sched = pg.uniform_renewal_schedule(60, sim_time=40.0, tick_dt=0.01, seed=6)
    assert sched.num_shares > 128
    ev = run_event_sim(g, sched, 4000)
    sy = run_sync_sim(g, sched, 4000, chunk_size=128)
    assert sy.equal_counts(ev)


def test_flood_coverage_monotone_and_complete():
    g = pg.erdos_renyi(128, 0.05, seed=7)
    stats, cov = run_flood_coverage(g, [0, 17, 63], 64)
    assert cov.shape[1] == 3
    assert (np.diff(cov, axis=0) >= 0).all()
    assert (cov[-1] == g.n).all()
    t99 = time_to_coverage(cov, g.n, 0.99)
    assert (t99 > 0).all()
    stats.check_conservation()


def test_flood_coverage_matches_event_arrivals():
    g = ring_graph(16)
    stats, cov = run_flood_coverage(g, [0], 20)
    ev = run_event_sim(g, single_share_schedule(16), 20, coverage_slots=1)
    arr = ev.extra["arrival_ticks"][0]
    # Coverage at tick t == nodes with arrival tick <= t.
    for t in range(20):
        assert cov[t, 0] == int((arr >= 0).sum() if t >= arr.max() else (arr <= t).sum())


def test_empty_schedule():
    g = ring_graph(8)
    sched = pg.Schedule(8, np.array([], dtype=np.int32), np.array([], dtype=np.int32))
    sy = run_sync_sim(g, sched, 10)
    assert sy.totals()["processed"] == 0


def test_parity_bucketed_device_graph():
    """Bucketed ELL staging gives bitwise-identical counters (both delay
    models), including against the event oracle."""
    from p2p_gossip_tpu.engine.sync import DeviceGraph

    g = barabasi_albert(150, m=2, seed=9)
    sched = pg.uniform_renewal_schedule(150, sim_time=15.0, tick_dt=0.005, seed=4)
    horizon = int(15.0 / 0.005)
    d = lognormal_delays(g, mean_ticks=2.0, sigma=0.6, max_ticks=5, seed=2)
    for delays in (None, d):
        dg_b = DeviceGraph.build(g, delays, bucketed=True)
        dg_p = DeviceGraph.build(g, delays, bucketed=False)
        ev = run_event_sim(g, sched, horizon, ell_delays=delays)
        sb = run_sync_sim(g, sched, horizon, ell_delays=delays, device_graph=dg_b)
        sp = run_sync_sim(g, sched, horizon, ell_delays=delays, device_graph=dg_p)
        assert sb.equal_counts(ev)
        assert sp.equal_counts(ev)


@pytest.mark.parametrize("bucketed", [True, False])
def test_gather_slots_reported(bucketed):
    """run_sync_sim reports the slots its gather fetches a tick: the staged
    buckets' (or the full ELL's) rows times their scanned width, and the
    ratio to the directed edges. Summing two runs' stats keeps them."""
    from p2p_gossip_tpu.engine.sync import DeviceGraph, _resolve_block
    from p2p_gossip_tpu.ops.ell import scanned_width

    g = barabasi_albert(150, m=2, seed=9)
    sched = pg.uniform_renewal_schedule(150, sim_time=2.0, tick_dt=0.01, seed=4)
    dg = DeviceGraph.build(g, bucketed=bucketed)
    st = run_sync_sim(g, sched, 200, device_graph=dg)
    block = _resolve_block(dg, None)
    staged = dg.buckets if bucketed else [(None, dg.ell_idx)]
    want = sum(
        b[1].shape[0] * scanned_width(b[1].shape[1], block) for b in staged
    )
    nnz = int(g.degree.sum())
    assert st.extra["gather_slots"] == want >= nnz
    assert st.extra["gather_slot_overhead"] == want / nnz
    both = st + st
    assert both.extra["gather_slots"] == want
    assert both.extra["ticks_executed"] == 2 * st.extra["ticks_executed"]


def test_snapshot_parity_with_event_engine():
    """Periodic-stats snapshots (PrintPeriodicStats) match the event oracle
    exactly at every boundary, including boundaries past quiescence."""
    g = pg.erdos_renyi(90, 0.06, seed=5)
    sched = pg.uniform_renewal_schedule(90, sim_time=20.0, tick_dt=0.005, seed=5)
    horizon = int(20.0 / 0.005)
    boundaries = [500, 1000, 2000, 3500, horizon]
    ev = run_event_sim(g, sched, horizon, snapshot_ticks=boundaries)
    sy = run_sync_sim(g, sched, horizon, snapshot_ticks=boundaries)
    assert sy.equal_counts(ev)
    assert sy.extra["snapshots"] == ev.extra["snapshots"]
    # Snapshots are cumulative and end at the final totals.
    processed = [s["processed"] for s in sy.extra["snapshots"]]
    assert processed == sorted(processed)
    assert processed[-1] == sy.totals()["processed"]


def test_snapshot_boundary_past_horizon_dropped():
    """A boundary beyond the horizon never fires — on either engine."""
    g = pg.erdos_renyi(40, 0.1, seed=1)
    sched = pg.uniform_renewal_schedule(40, sim_time=1.0, tick_dt=0.005, seed=1)
    ev = run_event_sim(g, sched, 200, snapshot_ticks=[100, 250])
    sy = run_sync_sim(g, sched, 200, snapshot_ticks=[100, 250])
    assert sy.extra["snapshots"] == ev.extra["snapshots"]
    assert len(sy.extra["snapshots"]) == 1


def test_snapshot_parity_multi_chunk_and_resume(tmp_path):
    """Snapshot accumulation is exact across share chunks, and the
    accumulated snapshot counts survive a checkpoint interrupt/resume."""
    g = pg.erdos_renyi(80, 0.08, seed=7)
    sched = pg.uniform_renewal_schedule(80, sim_time=20.0, tick_dt=0.005, seed=7)
    horizon = int(20.0 / 0.005)
    boundaries = [800, 2000, 3200]
    ev = run_event_sim(g, sched, horizon, snapshot_ticks=boundaries)
    # Small explicit chunk => several chunks.
    sy = run_sync_sim(
        g, sched, horizon, chunk_size=256, snapshot_ticks=boundaries
    )
    assert sched.num_shares > 256  # really multi-chunk
    assert sy.equal_counts(ev)
    assert sy.extra["snapshots"] == ev.extra["snapshots"]

    # Interrupt after one chunk, then resume from the checkpoint.
    ckpt = str(tmp_path / "snap.npz")
    part = run_sync_sim(
        g, sched, horizon, chunk_size=256, snapshot_ticks=boundaries,
        checkpoint_path=ckpt, stop_after_chunks=1,
    )
    resumed = run_sync_sim(
        g, sched, horizon, chunk_size=256, snapshot_ticks=boundaries,
        checkpoint_path=ckpt,
    )
    assert resumed.equal_counts(ev)
    assert resumed.extra["snapshots"] == ev.extra["snapshots"]


def test_snapshots_all_past_horizon_empty_list():
    g = pg.erdos_renyi(30, 0.15, seed=2)
    sched = pg.uniform_renewal_schedule(30, sim_time=0.5, tick_dt=0.005, seed=2)
    ev = run_event_sim(g, sched, 100, snapshot_ticks=[500])
    sy = run_sync_sim(g, sched, 100, snapshot_ticks=[500])
    assert sy.extra["snapshots"] == ev.extra["snapshots"] == []


def test_serialization_delay_model_parity_and_math():
    """Serialization delay = round((latency + size*8/bandwidth)/tick_dt)
    (reference: 5 Mbps p2p links, p2pnetwork.cc:113); event and sync
    engines agree on the resulting integer-tick delay lines."""
    import pytest

    from p2p_gossip_tpu.engine.event import run_event_sim
    from p2p_gossip_tpu.models.latency import serialization_delays

    g = pg.erdos_renyi(60, 0.1, seed=4)
    # Reference config: 30-byte shares at 5 Mbps, 5 ms ticks -> 48 us
    # serialization on a 5 ms latency = 5.048 ms, which rounds to the
    # same 1 tick/hop the reference effectively has (NOT quantized up —
    # that would silently double the default per-hop delay).
    d = serialization_delays(
        g, message_bytes=30, bandwidth_mbps=5.0, tick_dt=0.005
    )
    assert int(d.min()) == int(d.max()) == 1
    # A payload filling >1 tick of link time adds proportionally.
    d_big = serialization_delays(
        g, message_bytes=8_000, bandwidth_mbps=5.0, tick_dt=0.005
    )
    # 5 ms latency + 8000 B * 8 / 5e6 = 12.8 ms -> 17.8 ms -> 4 ticks.
    assert int(d_big.max()) == 4
    # Zero-size messages cost latency only.
    d0 = serialization_delays(
        g, message_bytes=0, bandwidth_mbps=5.0, tick_dt=0.005
    )
    assert int(d0.max()) == 1
    with pytest.raises(ValueError):
        serialization_delays(g, bandwidth_mbps=0.0)
    with pytest.raises(ValueError):
        serialization_delays(g, message_bytes=-1)

    sched = pg.uniform_renewal_schedule(60, sim_time=5.0, tick_dt=0.01, seed=4)
    ev = run_event_sim(g, sched, 500, ell_delays=d_big)
    sy = run_sync_sim(g, sched, 500, ell_delays=d_big, chunk_size=32)
    assert sy.equal_counts(ev)


def test_flood_coverage_explicit_small_chunk_bitwise():
    """An explicit chunk_size below MIN_CHUNK_SHARES is honored (W shrinks)
    and changes nothing observable — the HBM-relief path the 1M north star
    uses (scale_1m.py auto-chunk) must be bitwise-identical to the padded
    default, not merely statistically equivalent."""
    g = pg.erdos_renyi(96, 0.06, seed=11)
    origins = [0, 31, 44, 90]
    ref_stats, ref_cov = run_flood_coverage(g, origins, 48)
    small_stats, small_cov = run_flood_coverage(
        g, origins, 48, chunk_size=64
    )
    assert np.array_equal(ref_cov, small_cov)
    for f in ("generated", "received", "forwarded", "sent", "processed"):
        assert np.array_equal(
            getattr(ref_stats, f), getattr(small_stats, f)
        ), f
    small_stats.check_conservation()


def test_resident_hbm_model_and_auto_chunk():
    from p2p_gossip_tpu.engine.sync import (
        auto_chunk_shares,
        flood_resident_hbm_bytes,
    )

    # The north-star shape the model exists for: 1M nodes, mean degree
    # ~1000, block 8. W=128 (the 4096-share pass that crashed the 16 GB
    # v5e worker) must model over 12 GB; W=64 must model under 10 GB.
    degree = np.full(1_000_000, 1000, dtype=np.int64)
    full = flood_resident_hbm_bytes(degree, w=128, block=8)
    half = flood_resident_hbm_bytes(degree, w=64, block=8)
    assert full > 12e9
    assert half < 10e9
    assert half < full  # monotone in W

    # Auto-chunk: None = stage the engine's default pad (budget disabled,
    # or the default already fits); the 10 GB device budget halves the
    # default pad once to 2048 — including for a 64-share request, whose
    # DEFAULT pad is the same MIN_CHUNK_SHARES W=128 that crashed; a
    # budget below the fixed ELL term floors at min_chunk instead of
    # looping forever.
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a satisfied budget must NOT warn
        assert auto_chunk_shares(degree, 4096, 8, 0) is None
        assert auto_chunk_shares(degree, 4096, 8, 100e9) is None
        assert auto_chunk_shares(degree, 4096, 8, 10e9) == 2048
        assert auto_chunk_shares(degree, 64, 8, 10e9) == 2048
    # A budget below the fixed ELL term floors at min_chunk — and must
    # SAY the fit model was not satisfied, or callers log a staging plan
    # that reads as budget-approved (round-4 advisor finding).
    with pytest.warns(RuntimeWarning, match="cannot be met"):
        assert auto_chunk_shares(degree, 4096, 8, 1e9, min_chunk=512) == 512


@pytest.mark.parametrize(
    "seed", range(int(os.environ.get("P2P_FUZZ_SEEDS", "4")))
)
def test_flood_coverage_chunk_pad_fuzz(seed):
    """Randomized pad widths through the explicit-chunk_size path must stay
    bitwise-equal to the default MIN_CHUNK_SHARES pad — the guard for the
    HBM-relief staging scale_1m.py picks on the chip."""
    rng = np.random.default_rng(seed + 900)
    n = int(rng.integers(40, 160))
    g = pg.erdos_renyi(n, 0.08, seed=seed)
    s = int(rng.integers(1, 9))
    origins = rng.integers(0, n, s).astype(np.int32)
    pad = int(rng.choice([32, 64, 96, 128, 256]))
    horizon = int(rng.integers(16, 48))
    ref_stats, ref_cov = run_flood_coverage(g, origins, horizon)
    st, cv = run_flood_coverage(g, origins, horizon, chunk_size=pad)
    assert np.array_equal(ref_cov, cv), f"pad={pad}"
    for f in ("generated", "received", "forwarded", "sent", "processed"):
        assert np.array_equal(getattr(ref_stats, f), getattr(st, f)), f


# --- bucket-major staging: the same answers in the caller's numbering ------

def _bucket_major_graph(kind: str):
    """Graphs large enough to bucket by default (n >= 4096)."""
    if kind == "ba":
        return barabasi_albert(4500, m=3, seed=12)
    return pg.erdos_renyi(4500, 0.003, seed=12)


def _bucket_major_schedule(n: int, shares: int = 24, seed: int = 1):
    rng = np.random.default_rng(seed)
    return pg.Schedule(
        n, rng.integers(0, n, shares).astype(np.int32),
        rng.integers(0, 6, shares).astype(np.int32),
    )


def _staged_pair(g, delays=None):
    """The default (bucket-major) staging and a full-width one."""
    from p2p_gossip_tpu.engine.sync import DeviceGraph

    dg_b = DeviceGraph.build(g, delays)
    assert dg_b.buckets is not None and dg_b.order is not None
    return dg_b, DeviceGraph.build(g, delays, bucketed=False)


@pytest.mark.parametrize("axis", ["ids", 0, -1])
@pytest.mark.parametrize("staging", ["bucket_major", "full_width"])
def test_to_staged_from_staged_round_trip(staging, axis):
    """`DeviceGraph.to_staged` maps caller node ids to the staged rows
    that hold those nodes, and reorders per-node data along either axis
    to match; `from_staged` undoes it exactly. A full-width staging keeps
    the caller's numbering: both are the identity."""
    import jax.numpy as jnp

    g = _bucket_major_graph("ba")
    dg_b, dg_p = _staged_pair(g)
    dg = dg_b if staging == "bucket_major" else dg_p
    rng = np.random.default_rng(5)
    ids = rng.integers(0, g.n, 64).astype(np.int32)
    staged_ids = np.asarray(dg.to_staged(jnp.asarray(ids)))
    if axis == "ids":
        np.testing.assert_array_equal(
            np.asarray(dg.degree)[staged_ids], g.degree[ids]
        )
        if staging == "bucket_major":
            np.testing.assert_array_equal(
                np.asarray(dg.order)[staged_ids], ids
            )
            assert (staged_ids != ids).any()
        else:
            np.testing.assert_array_equal(staged_ids, ids)
        return
    shape = (g.n, 3) if axis == 0 else (3, g.n)
    x = rng.integers(0, 2**31 - 1, shape).astype(np.int32)
    staged = np.asarray(dg.to_staged(jnp.asarray(x), node_axis=axis))
    np.testing.assert_array_equal(
        np.take(staged, staged_ids, axis=axis), np.take(x, ids, axis=axis)
    )
    assert (staged != x).any() == (staging == "bucket_major")
    back = dg.from_staged(jnp.asarray(staged), node_axis=axis)
    np.testing.assert_array_equal(np.asarray(back), x)


@pytest.mark.parametrize("kind", ["ba", "er"])
@pytest.mark.parametrize(
    "case", ["plain", "churn", "loss", "snapshots", "connect", "per_edge"]
)
def test_bucket_major_counters_equal_full_width_and_event(kind, case):
    """`run_sync_sim` on a bucket-major staging gives the per-node counters
    (and snapshots) of a full-width staging and of the event engine,
    bitwise, under churn, link loss, snapshots, the connect window and
    per-edge delays; it reassembles no row by scatter."""
    from p2p_gossip_tpu.models.churn import random_churn
    from p2p_gossip_tpu.models.linkloss import LinkLossModel

    g = _bucket_major_graph(kind)
    sched = _bucket_major_schedule(g.n)
    horizon = 30
    kw, delays = {}, None
    if case == "churn":
        kw["churn"] = random_churn(g.n, horizon, outage_prob=0.2, seed=3)
    elif case == "loss":
        kw["loss"] = LinkLossModel(0.25, seed=7)
    elif case == "snapshots":
        kw["snapshot_ticks"] = [2, 5, 9, horizon]
    elif case == "connect":
        kw["connect_tick"] = 3
    elif case == "per_edge":
        delays = lognormal_delays(g, mean_ticks=2.0, sigma=0.6, max_ticks=4,
                                  seed=2)
    dg_b, dg_p = _staged_pair(g, delays)
    ev = run_event_sim(g, sched, horizon, ell_delays=delays, **kw)
    sb = run_sync_sim(g, sched, horizon, ell_delays=delays, chunk_size=128,
                      device_graph=dg_b, **kw)
    sp = run_sync_sim(g, sched, horizon, ell_delays=delays, chunk_size=128,
                      device_graph=dg_p, **kw)
    assert sb.equal_counts(sp) and sb.equal_counts(ev)
    np.testing.assert_array_equal(sb.degree, g.degree)
    assert sb.extra.get("snapshots") == sp.extra.get("snapshots")
    if case == "snapshots":
        assert sb.extra["snapshots"] == ev.extra["snapshots"]


@pytest.mark.parametrize("kind", ["ba", "er"])
@pytest.mark.parametrize("case", ["plain", "loss_churn"])
def test_bucket_major_flood_coverage_equal(kind, case):
    """`run_flood_coverage`: coverage and counters of the bucket-major
    staging equal the full-width staging's."""
    from p2p_gossip_tpu.models.churn import random_churn
    from p2p_gossip_tpu.models.linkloss import LinkLossModel

    g = _bucket_major_graph(kind)
    kw = {}
    if case == "loss_churn":
        kw = dict(
            loss=LinkLossModel(0.2, seed=5),
            churn=random_churn(g.n, 24, outage_prob=0.2, seed=6),
        )
    dg_b, dg_p = _staged_pair(g)
    origins = [0, 17, 4000, 4499]
    sb, cb = run_flood_coverage(g, origins, 24, device_graph=dg_b, **kw)
    sp, cp = run_flood_coverage(g, origins, 24, device_graph=dg_p, **kw)
    np.testing.assert_array_equal(cb, cp)
    assert sb.equal_counts(sp)


@pytest.mark.parametrize("flavor", ["coverage", "gossip"])
def test_bucket_major_campaign_equal(flavor):
    """The flood campaigns (counters, coverage) on a bucket-major staging
    equal the full-width staging's, replica by replica, with per-replica
    churn and loss seeds."""
    from p2p_gossip_tpu.batch.campaign import (
        ReplicaSet,
        flood_replicas,
        run_coverage_campaign,
        run_gossip_campaign,
    )
    from p2p_gossip_tpu.models.linkloss import LinkLossModel

    g = _bucket_major_graph("ba")
    horizon = 20
    if flavor == "coverage":
        reps = flood_replicas(g, 3, [4, 5, 6], horizon, churn_prob=0.2)
        run = run_coverage_campaign
    else:
        rng = np.random.default_rng(8)
        reps = ReplicaSet(
            n=g.n,
            origins=rng.integers(0, g.n, (3, 40)).astype(np.int32),
            gen_ticks=rng.integers(0, 8, (3, 40)).astype(np.int32),
            seeds=np.array([4, 5, 6], dtype=np.int64),
        )
        run = run_gossip_campaign
    dg_b, dg_p = _staged_pair(g)
    kw = dict(loss=LinkLossModel(0.2, seed=1), loss_seeds=[11, 12, 13],
              chunk_size=128)
    rb = run(g, reps, horizon, device_graph=dg_b, **kw)
    rp = run(g, reps, horizon, device_graph=dg_p, **kw)
    for name in ("received", "sent", "degree"):
        np.testing.assert_array_equal(getattr(rb, name), getattr(rp, name))
    if flavor == "coverage":
        np.testing.assert_array_equal(rb.coverage, rp.coverage)


def test_bucket_major_checkpoint_fingerprint_and_resume(tmp_path):
    """Per-edge delays fingerprint the same on either staging, and a run
    checkpointed on a full-width staging resumes on a bucket-major one to
    the uninterrupted counters."""
    from p2p_gossip_tpu.engine.sync import _canonical_delays

    g = _bucket_major_graph("ba")
    delays = lognormal_delays(g, mean_ticks=2.0, sigma=0.6, max_ticks=4,
                              seed=3)
    dg_b, dg_p = _staged_pair(g, delays)
    np.testing.assert_array_equal(_canonical_delays(dg_b),
                                  _canonical_delays(dg_p))
    sched = _bucket_major_schedule(g.n, shares=300, seed=4)
    whole = run_sync_sim(g, sched, 30, ell_delays=delays, chunk_size=128,
                         device_graph=dg_b)
    ckpt = str(tmp_path / "ckpt.npz")
    run_sync_sim(g, sched, 30, ell_delays=delays, chunk_size=128,
                 device_graph=dg_p, checkpoint_path=ckpt, stop_after_chunks=1)
    resumed = run_sync_sim(g, sched, 30, ell_delays=delays, chunk_size=128,
                           device_graph=dg_b, checkpoint_path=ckpt)
    assert resumed.equal_counts(whole)


@pytest.mark.parametrize("engine", ["sync", "coverage", "campaign"])
def test_bucket_major_digests_equal(engine):
    """Per-tick state digests hash the caller's node ids: the bucket-major
    staging's digest stream equals the full-width staging's."""
    from p2p_gossip_tpu import telemetry
    from p2p_gossip_tpu.batch.campaign import (
        flood_replicas,
        run_coverage_campaign,
    )
    from p2p_gossip_tpu.telemetry import compare

    g = _bucket_major_graph("er")
    sched = _bucket_major_schedule(g.n)
    reps = flood_replicas(g, 2, [3, 4], 16)

    def stream(dg):
        telemetry.reset()
        telemetry.configure(None, rings=True)
        try:
            if engine == "sync":
                run_sync_sim(g, sched, 16, chunk_size=128, device_graph=dg)
            elif engine == "coverage":
                run_flood_coverage(g, [1, 2, 3000], 16, device_graph=dg)
            else:
                run_coverage_campaign(g, reps, 16, device_graph=dg)
            return compare.digest_streams(telemetry.events())
        finally:
            telemetry.reset()

    dg_b, dg_p = _staged_pair(g)
    got, want = stream(dg_b), stream(dg_p)
    assert want and got == want

"""Campaigns x shards: R replicas of a node-sharded graph in ONE program.

batch/campaign.py vmaps replicas of the SINGLE-DEVICE engines — capped at
graphs that fit one chip. parallel/engine_sharded.py shards one run's
graph rows over the whole mesh — one seed at a time. This module
factorizes the mesh into ``(replicas, nodes)`` axes
(``mesh.make_mesh(replicas=...)``) and drives the CAMPAIGN mode of the
sharded runners: the replica axis carries independent seeds (pure data
parallelism — zero cross-replica communication), the node axis carries
the graph shards (the gather-OR frontier exchange rides inside each
replica shard), and jax.vmap over each replica shard's local batch folds
``local_replicas`` seeds per device group into the SAME compiled
while_loop. One jitted program per batch, R bitwise-exact replicas out.

The replica-parallel x data-sharded factorization is the standard
distributed-SpMV trade (replication vs communication — Node-Aware SpMV,
arXiv:1612.08060; sparse allreduce on power-law graphs, arXiv:1312.3020)
applied to the frontier step: adding replica shards costs no extra
exchange traffic per replica, so ensemble statistics come at the
node-sharded run's marginal cost instead of R sequential runs.

Bitwise contract (tests/test_campaign_sharded.py): replica r of
``run_sharded_campaign`` equals the solo ``run_sharded_sim`` with
schedule/seed/churn/loss of replica r on a nodes-only mesh with the same
node-shard count — dense or delta exchange — because the tick bodies are
the SAME code (engine_sharded extracts one replica's tick and either
calls it directly or vmaps it), loss coins hash global node ids with the
replica's own traced seed, and the extra ticks a fast replica executes
past its own quiescence are exact identities (all-zero frontier; the
batch runs to the slowest replica's quiescence, the argument
batch/campaign.py makes for the single-device batch).

Ensemble reductions (`batch/stats.py`) and the `CampaignResult` shape are
shared with batch/campaign.py unchanged; batch-boundary checkpointing
follows the same fingerprint-over-everything contract.

Delta-exchange caveat: under vmap, the per-slot dense-fallback
``lax.cond`` lowers to a select that executes BOTH branches, so the
campaign delta path pays the dense all_gather every tick alongside the
sparse exchange — results stay bitwise-identical (the select keeps the
exact branch value per replica), but the delta path's traffic win on a
campaign mesh is limited to HBM, not ICI, until a batched-cond lowering
lands. The achieved counters in ``result.extra['exchange']`` stay
honest either way.
"""

from __future__ import annotations

import time

import numpy as np

from p2p_gossip_tpu.batch.campaign import (
    CampaignResult,
    ReplicaSet,
    _campaign_generated,
    _iter_batches,
    _resolve_loss,
)
from p2p_gossip_tpu.engine.sync import MIN_CHUNK_SHARES
from p2p_gossip_tpu.models.topology import Graph
from p2p_gossip_tpu.ops import bitmask
from p2p_gossip_tpu.parallel import async_ticks
from p2p_gossip_tpu.parallel.mesh import NODES_AXIS, REPLICAS_AXIS
from p2p_gossip_tpu import telemetry
from p2p_gossip_tpu.telemetry import digest as tel_digest
from p2p_gossip_tpu.telemetry import rings as tel_rings


def _campaign_mesh_dims(mesh) -> tuple[int, int]:
    """(replica_shards, node_shards) of a factorized campaign mesh."""
    if REPLICAS_AXIS not in mesh.shape or NODES_AXIS not in mesh.shape:
        raise ValueError(
            "sharded campaigns need a (replicas, nodes) mesh — build it "
            "with parallel.mesh.make_mesh(replicas=...)"
        )
    return int(mesh.shape[REPLICAS_AXIS]), int(mesh.shape[NODES_AXIS])


def _resolve_campaign_batch(
    replicas: ReplicaSet, batch_size: int | None, replica_shards: int
) -> int:
    """Batch size rounded UP to a multiple of the replica-shard count so
    the (B, ...) operands split evenly over the replica axis; sentinel
    padding absorbs the overhang (same convention as batch/campaign.py's
    device-count rounding)."""
    if batch_size is None:
        batch_size = replicas.num_replicas
    if batch_size < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")
    if batch_size % replica_shards:
        batch_size += replica_shards - batch_size % replica_shards
    return batch_size


def _campaign_chunk(mesh, shares: int, chunk_size: int | None) -> int:
    """The single share-pass width: every replica's whole padded schedule
    rides one pass (the campaign factorization trades the share axis for
    the replica axis). TPU meshes keep the MIN_CHUNK_SHARES lane floor;
    host meshes pack to the word-rounded share count, like
    run_coverage_campaign."""
    on_tpu = any(d.platform == "tpu" for d in mesh.devices.flat)
    if chunk_size is None:
        chunk_size = max(shares, MIN_CHUNK_SHARES) if on_tpu else shares
    if chunk_size < shares:
        raise ValueError(
            f"sharded campaigns run one share pass per replica: chunk_size "
            f"({chunk_size}) must cover shares_per_replica ({shares})"
        )
    return bitmask.num_words(max(1, chunk_size)) * bitmask.WORD_BITS


def _pad_batch_churn(churn, batch: int, n_padded: int):
    """(B, N, K) churn intervals padded to the graph's node rows ((B,
    n_padded, 1) zeros when churn is off — padding rows have start ==
    end, i.e. never down, matching `_padded_churn`)."""
    if churn is None:
        z = np.zeros((batch, n_padded, 1), dtype=np.int32)
        return z, z.copy()
    cs, ce = churn
    pad = n_padded - cs.shape[1]
    if pad:
        cs = np.pad(cs, ((0, 0), (0, pad), (0, 0)))
        ce = np.pad(ce, ((0, 0), (0, pad), (0, 0)))
    return (
        np.ascontiguousarray(cs, dtype=np.int32),
        np.ascontiguousarray(ce, dtype=np.int32),
    )


def _campaign_loss_seeds(loss_cfg, lseed_arr, r_total: int):
    """The campaign runners always thread a TRACED per-replica loss seed
    when a loss model is on (static cfg (threshold, None)): with
    per-replica seeds it is the seed vector, with a shared cell seed it
    is that seed broadcast — the traced coin equals the static-seed coin,
    so both reproduce the matching solo run bitwise."""
    if loss_cfg is None:
        return None, None
    thr, static_seed = loss_cfg
    if lseed_arr is None:
        lseed_arr = np.full(r_total, int(static_seed) & 0xFFFFFFFF,
                            dtype=np.int64)
    return (thr, None), lseed_arr


def _pad_batch_schedule(origins, gen_ticks, chunk: int, horizon: int):
    """(B, S) schedules padded to the pass width with the never-fires
    sentinel."""
    b, s = origins.shape
    pad_o = np.zeros((b, chunk), dtype=np.int32)
    pad_g = np.full((b, chunk), horizon, dtype=np.int32)
    pad_o[:, :s] = origins
    pad_g[:, :s] = gen_ticks
    return pad_o, pad_g


def run_sharded_campaign(
    graph: Graph,
    replicas: ReplicaSet,
    horizon: int,
    mesh,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    loss=None,
    loss_seeds=None,
    batch_size: int | None = None,
    chunk_size: int | None = None,
    block: int | None = None,
    record_coverage: bool = False,
    checkpoint_path: str | None = None,
    checkpoint_every: int = 1,
    stop_after_batches: int | None = None,
    ring_mode: str = "auto",
    bucket_min_rows: int = 2048,
    exchange: str = "dense",
    async_k: int = 2,
    hub_rows: int | None = None,
    aux_cache: tuple | None = None,
) -> CampaignResult:
    """Seed-ensemble flood campaign over a factorized (replicas, nodes)
    mesh: R replicas of the node-sharded flood engine in one jitted
    program per batch (module docstring). Replica r's counters (and
    coverage, with ``record_coverage``) are bitwise those of the solo
    ``run_sharded_sim`` / ``run_sharded_flood_coverage`` with replica r's
    schedule, churn, and loss seed.

    ``loss``/``loss_seeds`` follow batch/campaign.py's `_resolve_loss`
    contract: a shared `LinkLossModel` gives every replica the model's
    own seed; ``loss_seeds`` (one per replica,
    `models.seeds.replica_loss_seeds`) gives independent erasure streams.
    ``exchange`` "dense"/"delta"/"auto"/"hub" resolves like
    run_sharded_sim — the delta capacity (and under "hub" the
    fan-ranked degree split, with ``hub_rows`` pinning the hub size and
    ``aux_cache`` persisting the cut scan) is planned once from the
    shared partition edge cut and reused by every replica — and the
    async spellings ("async"/"async-dense"/"async-delta"/"async-hub"
    with ``async_k`` = K) switch every replica to the bounded-staleness
    read path, exactly as `run_sharded_sim` does (replica r stays
    bitwise its solo async run, i.e. its sync run with cross-shard
    delays clamped to max(d, K)).
    Resolved ring/exchange reports land in ``result.extra``."""
    from p2p_gossip_tpu.parallel.engine_sharded import ShardedDeviceGraph

    replica_shards, n_node_shards = _campaign_mesh_dims(mesh)
    r_total = replicas.num_replicas
    s = replicas.shares_per_replica
    batch_size = _resolve_campaign_batch(replicas, batch_size, replica_shards)
    rb = batch_size // replica_shards
    chunk = _campaign_chunk(mesh, s, chunk_size)

    dg = ShardedDeviceGraph.build(
        graph, mesh, constant_delay=constant_delay, ell_delays=ell_delays,
        ring_mode=ring_mode, exchange=exchange,
        bucket_min_rows=bucket_min_rows, chunk_size=chunk, block=block,
        async_k=async_k, hub_rows=hub_rows, aux_cache=aux_cache,
    )
    n_padded, ring_mode, k_async = dg.n_padded, dg.ring_mode, dg.k_async
    exchange_mode, capacity, hub_n = (
        dg.exchange_mode, dg.capacity, dg.hub_count
    )
    delta_on = exchange_mode in ("delta", "hub")

    loss_cfg, lseed_arr = _resolve_loss(loss, loss_seeds, r_total)
    static_loss, lseed_arr = _campaign_loss_seeds(loss_cfg, lseed_arr, r_total)

    tel = telemetry.rings_enabled()
    runner, _pass = dg.runner(
        chunk, horizon, loss=static_loss,
        record_coverage=record_coverage,
        cov_slots=(s if record_coverage else None),
        telemetry_on=tel,
        replica_axis=REPLICAS_AXIS, local_replicas=rb,
        per_replica_loss=(loss is not None),
    )

    received = np.zeros((r_total, n_padded), dtype=np.int64)
    sent = np.zeros((r_total, n_padded), dtype=np.int64)
    coverage = (
        np.zeros((r_total, horizon, s), dtype=np.int64)
        if record_coverage else None
    )

    checkpointer = None
    if checkpoint_path is not None:
        from p2p_gossip_tpu.utils.checkpoint import (
            ChunkCheckpointer,
            fingerprint,
        )

        fp = fingerprint(
            "campaign_sharded", "flood", graph.n, graph.edges(),
            replicas.origins, replicas.gen_ticks, replicas.seeds, horizon,
            chunk, replica_shards, n_node_shards, batch_size,
            ell_delays if ell_delays is not None else constant_delay,
            ring_mode, exchange_mode, int(record_coverage),
            # Async K >= 2 changes results (bounded staleness on
            # cross-shard folds) — resumes must not mix with sync runs.
            *(["async", k_async] if k_async else []),
            replicas.churn[0] if replicas.churn is not None else None,
            replicas.churn[1] if replicas.churn is not None else None,
            *(["loss", static_loss[0]] if static_loss else []),
            *(["lseeds", lseed_arr] if lseed_arr is not None else []),
        )
        arrays = {"received": received, "sent": sent}
        if record_coverage:
            arrays["coverage"] = coverage
        checkpointer = ChunkCheckpointer(
            checkpoint_path, fp, arrays, checkpoint_every
        )

    from p2p_gossip_tpu.utils.checkpoint import checkpointed_chunks

    snap = np.zeros((0,), dtype=np.int32)
    exch_counters = np.zeros(3, dtype=np.int64)  # used, ovf, fallback
    exch_ticks = 0
    batches = list(_iter_batches(replicas, batch_size, horizon, lseed_arr))
    t0 = time.perf_counter()
    for _bi, batch in checkpointed_chunks(
        batches, checkpointer, stop_after_batches
    ):
        lo, live, origins_b, gen_b, churn_b, _seeds, lseeds_b = batch
        pad_o, pad_g = _pad_batch_schedule(origins_b, gen_b, chunk, horizon)
        live_ticks = pad_g[pad_g < horizon]
        if live_ticks.size == 0:
            continue  # every replica in the batch is sentinel padding
        # Global loop bounds: first and last live gen tick across the
        # batch — replicas with narrower windows run identity ticks at
        # the edges (empty frontier, no gens), bitwise free.
        t_start = np.int32(live_ticks.min())
        last_gen = np.int32(live_ticks.max())
        cs_b, ce_b = _pad_batch_churn(churn_b, batch_size, n_padded)
        args = (dg.ell_args, dg.degree, cs_b, ce_b, pad_o, pad_g,
                t_start, last_gen, snap)
        if loss is not None:
            args = args + (lseeds_b,)
        args = args + dg.exchange_operands()
        with telemetry.span(
            "dispatch",
            kernel="parallel.engine_sharded.flood_runner[campaign]",
            batch=_bi,
        ):
            out = runner(*args)
        r, snt = out[0], out[1]
        cov = out[3] if record_coverage else None
        with telemetry.span("d2h", batch=_bi):
            received[lo:lo + live] = np.asarray(r, dtype=np.int64)[:live]
            sent[lo:lo + live] = np.asarray(snt, dtype=np.int64)[:live]
            if record_coverage:
                coverage[lo:lo + live] = np.asarray(cov)[:live, :, :s]
        if delta_on:
            ec = np.asarray(out[-1], dtype=np.uint64)[:live]  # (live, 8)
            exch_counters[0] += int(
                bitmask.combine_u64(ec[:, 0], ec[:, 1]).sum()
            )
            exch_counters[1] += int(ec[:, 2].sum())
            exch_counters[2] += int(ec[:, 3].sum())
            exch_ticks += int(ec[:, 4].sum())
        digest_head = None
        if tel:
            met_np = np.asarray(out[4])
            dig_np = np.asarray(out[5])
            for i in range(live):
                tel_rings.emit_ring(
                    "batch.campaign_sharded.run_sharded_campaign",
                    met_np[i], t0=int(t_start), replica=lo + i,
                    seed=int(replicas.seeds[lo + i]),
                )
                nz = np.flatnonzero(dig_np[i])
                tel_digest.emit_digest(
                    "batch.campaign_sharded.run_sharded_campaign",
                    dig_np[i],
                    t0=int(t_start),
                    ticks=(int(nz[-1]) + 1 - int(t_start) if nz.size else 0),
                    replica=lo + i, seed=int(replicas.seeds[lo + i]),
                )
            nz = np.flatnonzero(dig_np[0])
            digest_head = int(dig_np[0][nz[-1]]) if nz.size else None
        telemetry.emit_progress(
            "batch.campaign_sharded.run_sharded_campaign",
            chunk=_bi, chunks_total=len(batches), digest_head=digest_head,
        )
    wall = time.perf_counter() - t0

    extra = {
        "ring": dict(dg.ring_extra),
        "mesh": {
            "replica_shards": replica_shards,
            "node_shards": n_node_shards,
            "local_replicas": rb,
        },
    }
    if delta_on:
        from p2p_gossip_tpu.parallel.engine_sharded import (
            _achieved_exchange_report,
        )

        extra["exchange"] = _achieved_exchange_report(
            dg.exchange_extra, exch_counters, exch_ticks, n_node_shards,
            n_padded // n_node_shards, dg.w, capacity, hub_count=hub_n,
        )
    else:
        extra["exchange"] = dict(dg.exchange_extra)

    return CampaignResult(
        n=graph.n,
        seeds=replicas.seeds,
        generated=_campaign_generated(replicas, horizon),
        received=received[:, : graph.n],
        sent=sent[:, : graph.n],
        degree=graph.degree.astype(np.int64),
        horizon=horizon,
        wall_s=wall,
        batch_size=batch_size,
        coverage=coverage,
        extra=extra,
    )


def run_sharded_protocol_campaign(
    graph: Graph,
    replicas: ReplicaSet,
    horizon: int,
    mesh,
    protocol: str = "pushpull",
    fanout: int = 2,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    loss=None,
    loss_seeds=None,
    batch_size: int | None = None,
    chunk_size: int | None = None,
    record_coverage: bool = False,
    checkpoint_path: str | None = None,
    checkpoint_every: int = 1,
    stop_after_batches: int | None = None,
    ring_mode: str = "auto",
    exchange: str = "dense",
    async_k: int = 2,
    hub_rows: int | None = None,
) -> CampaignResult:
    """Seed-ensemble random-partner campaign over the factorized mesh:
    the campaign counterpart of `run_sharded_partnered_sim`, replica
    seeds riding the replica axis as traced partner-pick seeds (the
    counter-based hash takes the seed as data, so one compiled program
    serves every seed). Replica r is bitwise its solo partnered run with
    ``seed=replicas.seeds[r]``, including under the async exchange
    spellings (``exchange``/``async_k``/``hub_rows`` follow
    `run_sharded_partnered_sim`: anti-entropy only, delays clamped
    host-side to max(d, K); "hub" plans the degree split once and every
    replica shares it)."""
    from p2p_gossip_tpu.parallel.engine_sharded import (
        _padded_device_graph,
    )
    from p2p_gossip_tpu.parallel.protocols_sharded import (
        _resolve_partnered_exchange,
        build_partnered_runner,
    )

    if protocol not in ("pushpull", "pull", "pushk"):
        raise ValueError(f"unknown protocol {protocol!r}")
    transport, k_async = async_ticks.parse_exchange(exchange, async_k)
    exchange = transport
    if k_async:
        if protocol == "pushk":
            raise ValueError(
                "async exchange needs an anti-entropy protocol "
                "(pushpull/pull): fanout push exchanges same-round "
                "digests — there is nothing to overlap"
            )
        ring_mode = "sharded"
    replica_shards, n_node_shards = _campaign_mesh_dims(mesh)
    r_total = replicas.num_replicas
    s = replicas.shares_per_replica
    batch_size = _resolve_campaign_batch(replicas, batch_size, replica_shards)
    rb = batch_size // replica_shards
    chunk = _campaign_chunk(mesh, s, chunk_size)
    if protocol == "pull":
        from p2p_gossip_tpu.models.protocols import _check_pull_credit_bound

        for r in range(r_total):
            _check_pull_credit_bound(
                graph, chunk, replicas.replica_schedule(r, horizon)
            )

    ell_idx, ell_delay, degree, ring = _padded_device_graph(
        graph, ell_delays, constant_delay, n_node_shards,
    )
    n_padded = ell_idx.shape[0]
    if k_async:
        # Clamp BEFORE the distinct-delay set / ring sizing, exactly as
        # run_sharded_partnered_sim does.
        stale_values, stale_amounts = async_ticks.protocol_staleness_amounts(
            ell_delay, k_async
        )
        ell_delay = async_ticks.clamp_partner_delays(ell_delay, k_async)
        ring = async_ticks.effective_ring(ring, k_async)
    else:
        stale_values, stale_amounts = (), ()

    # Ring + exchange resolution shared with run_sharded_partnered_sim
    # (including the "hub" degree split — planned once, shared by every
    # replica: the split depends only on the graph, not the seed).
    w = bitmask.num_words(chunk)
    (ring_mode, ring_bytes, delay_values, exchange, capacity, hub_ops,
     aggregate, delta_on, exchange_extra, async_staleness) = (
        _resolve_partnered_exchange(
            exchange, protocol, ring_mode, ell_delay, ring, n_padded,
            n_node_shards, w, degree, k_async, stale_values,
            stale_amounts, hub_rows,
        )
    )
    n_loc = n_padded // n_node_shards

    loss_cfg, lseed_arr = _resolve_loss(loss, loss_seeds, r_total)
    static_loss, lseed_arr = _campaign_loss_seeds(loss_cfg, lseed_arr, r_total)

    tel = telemetry.rings_enabled()
    runner, _pass = build_partnered_runner(
        mesh, protocol, n_padded, ring, chunk, horizon,
        fanout if protocol == "pushk" else 1,
        static_loss, record_coverage,
        ring_mode=ring_mode, delay_values=delay_values, telemetry_on=tel,
        exchange_mode=exchange if delta_on else "dense",
        delta_capacity=capacity,
        hub_count=hub_ops[0] if hub_ops else 0,
        delta_aggregate=aggregate,
        replica_axis=REPLICAS_AXIS, local_replicas=rb,
        per_replica_loss=(loss is not None),
        async_k=k_async, async_staleness=async_staleness,
    )

    received = np.zeros((r_total, n_padded), dtype=np.int64)
    sent = np.zeros((r_total, n_padded), dtype=np.int64)
    coverage = (
        np.zeros((r_total, horizon, s), dtype=np.int64)
        if record_coverage else None
    )

    checkpointer = None
    if checkpoint_path is not None:
        from p2p_gossip_tpu.utils.checkpoint import (
            ChunkCheckpointer,
            fingerprint,
        )

        fp = fingerprint(
            "campaign_sharded", protocol,
            fanout if protocol == "pushk" else 1,
            graph.n, graph.edges(), replicas.origins, replicas.gen_ticks,
            replicas.seeds, horizon, chunk, replica_shards, n_node_shards,
            batch_size,
            ell_delays if ell_delays is not None else constant_delay,
            ring_mode, exchange, int(record_coverage),
            # The fingerprint hashes the USER delay array (pre-clamp),
            # so the async clamp must be marked explicitly.
            *(["async", k_async] if k_async else []),
            replicas.churn[0] if replicas.churn is not None else None,
            replicas.churn[1] if replicas.churn is not None else None,
            *(["loss", static_loss[0]] if static_loss else []),
            *(["lseeds", lseed_arr] if lseed_arr is not None else []),
        )
        arrays = {"received": received, "sent": sent}
        if record_coverage:
            arrays["coverage"] = coverage
        checkpointer = ChunkCheckpointer(
            checkpoint_path, fp, arrays, checkpoint_every
        )

    from p2p_gossip_tpu.utils.checkpoint import checkpointed_chunks

    exch_counters = np.zeros(3, dtype=np.int64)
    exch_ticks = 0
    batches = list(_iter_batches(replicas, batch_size, horizon, lseed_arr))
    t0 = time.perf_counter()
    for _bi, batch in checkpointed_chunks(
        batches, checkpointer, stop_after_batches
    ):
        lo, live, origins_b, gen_b, churn_b, seeds_b, lseeds_b = batch
        pad_o, pad_g = _pad_batch_schedule(origins_b, gen_b, chunk, horizon)
        cs_b, ce_b = _pad_batch_churn(churn_b, batch_size, n_padded)
        args = (ell_idx, ell_delay, degree, cs_b, ce_b, pad_o, pad_g,
                seeds_b)
        if loss is not None:
            args = args + (lseeds_b,)
        if hub_ops:
            args = args + (hub_ops[1], hub_ops[2], hub_ops[3])
        with telemetry.span(
            "dispatch",
            kernel=f"parallel.protocols_sharded.{protocol}_runner[campaign]",
            batch=_bi,
        ):
            out = runner(*args)
        r, s_lo, s_hi = out[0], out[1], out[2]
        cov = out[3] if record_coverage else None
        with telemetry.span("d2h", batch=_bi):
            received[lo:lo + live] = np.asarray(r, dtype=np.int64)[:live]
            sent[lo:lo + live] = bitmask.combine_u64(
                np.asarray(s_lo), np.asarray(s_hi)
            )[:live]
            if record_coverage:
                coverage[lo:lo + live] = np.asarray(cov)[:live, :, :s]
        if delta_on:
            ec = np.asarray(out[-1], dtype=np.uint64)[:live]
            exch_counters[0] += int(
                bitmask.combine_u64(ec[:, 0], ec[:, 1]).sum()
            )
            exch_counters[1] += int(ec[:, 2].sum())
            exch_counters[2] += int(ec[:, 3].sum())
            exch_ticks += int(ec[:, 4].sum())
        digest_head = None
        if tel:
            met_np = np.asarray(out[4])
            dig_np = np.asarray(out[5])
            for i in range(live):
                tel_rings.emit_ring(
                    "batch.campaign_sharded.run_sharded_protocol_campaign",
                    met_np[i], t0=0, ticks=horizon, replica=lo + i,
                    seed=int(replicas.seeds[lo + i]),
                )
                tel_digest.emit_digest(
                    "batch.campaign_sharded.run_sharded_protocol_campaign",
                    dig_np[i], t0=0, ticks=horizon, replica=lo + i,
                    seed=int(replicas.seeds[lo + i]),
                )
            digest_head = int(dig_np[0][-1]) if live else None
        telemetry.emit_progress(
            "batch.campaign_sharded.run_sharded_protocol_campaign",
            chunk=_bi, chunks_total=len(batches), digest_head=digest_head,
        )
    wall = time.perf_counter() - t0

    if delta_on:
        from p2p_gossip_tpu.parallel.engine_sharded import (
            _achieved_exchange_report,
        )

        exchange_extra = _achieved_exchange_report(
            exchange_extra, exch_counters, exch_ticks,
            n_node_shards, n_loc, w, capacity,
            hub_count=hub_ops[0] if hub_ops else 0,
        )
    extra = {
        "ring": {
            "mode": ring_mode,
            "bytes_per_chip": ring_bytes,
            "slots": ring,
            "delay_splits": len(delay_values) if delay_values else 1,
        },
        "mesh": {
            "replica_shards": replica_shards,
            "node_shards": n_node_shards,
            "local_replicas": rb,
        },
        "exchange": exchange_extra,
    }

    return CampaignResult(
        n=graph.n,
        seeds=replicas.seeds,
        generated=_campaign_generated(replicas, horizon),
        received=received[:, : graph.n],
        sent=sent[:, : graph.n],
        degree=graph.degree.astype(np.int64),
        horizon=horizon,
        wall_s=wall,
        batch_size=batch_size,
        coverage=coverage,
        extra=extra,
    )

"""Multi-chip synchronous engine: shard_map over a (shares, nodes) mesh.

Scales the tick engine (engine/sync.py) the way the BASELINE.json headline
config demands (1M nodes over a v5e-8 mesh): graph rows, seen-bitmask, and
counters are sharded along ``nodes``; independent share chunks along
``shares``. Counters `psum` over the shares axis once per pass.

The delay-line history ring has two layouts (``ring_mode``):

- ``"replicated"`` — each chip holds the full (ring, N, W) ring; per tick
  the local newly-frontier is `all_gather`ed over the nodes axis and
  written globally, and the gather-OR reads are purely local. Fastest
  when the ring fits in HBM.
- ``"sharded"`` — each chip holds only ITS rows' history (ring, N/shards,
  W); per-chip ring memory scales down with the mesh. Per-edge delays are
  static host data, so the read side becomes one `all_gather` of the
  (t - d)-slice per distinct delay value d (`ops.ell.split_ell_by_delay`
  plans the per-delay ELLs): for the reference's uniform-latency model
  that is exactly ONE all_gather per tick — the same ICI traffic as
  replicated mode with 1/n_shards the ring HBM — and for an L-valued
  delay distribution it is L all_gathers (traffic xL, the price of
  fitting 1M-node lognormal rings on 16 GB chips).

``"auto"`` picks sharded for uniform delays (strictly better) and
otherwise switches to sharded when the replicated ring would exceed
``RING_REPLICATED_MAX_BYTES`` per chip.

On top of the sharded ring, ``exchange="delta"`` swaps the per-delay
slice all_gathers for the sparse frontier-delta exchange
(`parallel/exchange.py`): one fixed-capacity all_to_all of changed-word
(idx, val) pairs per tick — traffic scales with the frontier delta
instead of N — with a mesh-uniform dense fallback per overflowed ring
slot. Bitwise-identical counters on every path; modeled and achieved
wire words are reported in ``stats.extra['exchange']``.

``exchange="async"`` (bounded-staleness async ticks,
parallel/async_ticks.py) removes the read-side exchange barrier on
either transport: each shard carries a ``landed`` double-buffer — the
completed gather of an older ring slot, issued a full tick before its
first reader — and runs up to K ticks ahead on locally-known bits while
the next gather completes in the background. Results are bitwise
identical, per tick, to the synchronous engine run with cross-shard
edge delays clamped to ``max(d, K)`` (intra-shard edges stay timely);
K=1 is the synchronous program itself. See the async_ticks module
docstring for the exact-semantics contract and the OR-monotonicity
safety argument.

Single-device equivalence is bitwise for BOTH layouts: the tick body ORs
the same edge set in either decomposition, and the tests assert identical
per-node counters against `engine.sync` and `engine.event` across mesh
shapes and ring modes.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p2p_gossip_tpu.engine.sync import (
    apply_tick_updates,
    assemble_snapshots,
    filter_snapshot_boundaries,
)
from p2p_gossip_tpu.models.churn import effective_generated, up_mask_jnp
from p2p_gossip_tpu.models.generation import Schedule
from p2p_gossip_tpu.models.topology import Graph
from p2p_gossip_tpu.ops import bitmask
from p2p_gossip_tpu.ops.ell import (
    DEFAULT_DEGREE_BLOCK,
    fused_slots,
    gather_or_frontier,
    gather_slot_report,
    scan_block,
    scanned_width,
    shard_bucket_csr,
    shard_bucket_ell,
    split_ell_by_delay,
    tuned_degree_block,
)
from p2p_gossip_tpu.parallel import async_ticks
from p2p_gossip_tpu.parallel.mesh import NODES_AXIS, SHARES_AXIS, pad_to_multiple
from p2p_gossip_tpu import telemetry
from p2p_gossip_tpu.telemetry import digest as tel_digest
from p2p_gossip_tpu.telemetry import rings as tel_rings
from p2p_gossip_tpu.utils.stats import NodeStats


def _rss_log(tag: str) -> None:
    """Staging-memory audit line, enabled by P2P_STAGE_RSS=1: current and
    peak process RSS at each staging milestone. Exists because the 1M
    scale-free virtual-mesh rehearsal OOM-killed a 125 GB host twice
    with no visible culprit — one run under this flag localizes which
    staging step owns the peak instead of guessing from models."""
    if os.environ.get("P2P_STAGE_RSS") != "1":
        return
    import resource

    peak_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    try:
        with open("/proc/self/statm") as f:
            cur_gb = (
                int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 1e9
            )
    except (OSError, ValueError, IndexError):
        cur_gb = float("nan")
    print(
        f"[stage-rss] {tag}: cur {cur_gb:.1f} GB, peak {peak_gb:.1f} GB",
        file=sys.stderr, flush=True,
    )


def _padded_device_graph(
    graph: Graph,
    ell_delays: np.ndarray | None,
    constant_delay: int,
    n_node_shards: int,
):
    """The partnered protocols' graph operands: ``(ell_idx, ell_delays,
    degree, ring)``, the (N, dmax) neighbour ids and per-edge delays
    padded so rows divide evenly across node shards (padding rows have
    degree 0: they never send or receive), and the ring, the largest
    delay plus one. Picks always land on valid ELL entries, so no mask
    is staged."""
    ell_idx, _ = graph.ell()
    _rss_log("global ELL materialized")
    if ell_delays is None:
        ell_delays = np.full(ell_idx.shape, constant_delay, dtype=np.int32)
    ring = (int(ell_delays.max()) if ell_delays.size else 1) + 1
    return (
        pad_to_multiple(ell_idx, n_node_shards),
        pad_to_multiple(ell_delays, n_node_shards, fill=1),
        pad_to_multiple(graph.degree.astype(np.int32), n_node_shards),
        ring,
    )


def _padded_churn(churn, n_padded: int, n_node_shards: int):
    """Churn intervals padded with their node rows ((n_padded, 1) zeros —
    vacuously up — when churn is off)."""
    if churn is not None:
        return (
            pad_to_multiple(churn.down_start, n_node_shards),
            pad_to_multiple(churn.down_end, n_node_shards),
        )
    return (
        np.zeros((n_padded, 1), dtype=np.int32),
        np.zeros((n_padded, 1), dtype=np.int32),
    )


#: Per-chip ceiling for the replicated (ring, N, W) history under
#: ring_mode="auto": above this the sharded ring layout is chosen. The
#: v5e has 16 GB HBM; 1 GiB of replicated ring leaves the rest for ELL,
#: seen, and the gather intermediates.
RING_REPLICATED_MAX_BYTES = 1 << 30


def resolve_ring_mode(
    ring_mode: str,
    uniform: int | None,
    ring: int,
    n_padded: int,
    n_node_shards: int,
    w: int,
) -> tuple[str, int]:
    """Resolve "auto" and return (mode, per-chip ring bytes).

    Uniform delays always take the sharded ring (same ICI traffic, 1/shards
    the HBM); per-edge delays stay replicated until the replicated ring
    would exceed RING_REPLICATED_MAX_BYTES per chip (the sharded read side
    costs one all_gather per distinct delay value per tick)."""
    if ring_mode not in ("auto", "replicated", "sharded"):
        raise ValueError(f"unknown ring_mode {ring_mode!r}")
    replicated_bytes = 4 * ring * n_padded * w
    if ring_mode == "auto":
        if uniform is not None or replicated_bytes > RING_REPLICATED_MAX_BYTES:
            ring_mode = "sharded"
        else:
            ring_mode = "replicated"
    bytes_per_chip = (
        replicated_bytes
        if ring_mode == "replicated"
        else 4 * ring * (n_padded // n_node_shards) * w
    )
    return ring_mode, bytes_per_chip


def _delay_model(graph: Graph, ell_delays, constant_delay: int):
    """(uniform delay or None, ring slots): the delay every edge shares,
    read over the CSR entries (``ell_delays`` is ELL-aligned, so the
    global ELL mask is not needed to find the valid ones), and the
    history ring, the largest delay plus one."""
    if ell_delays is None:
        return int(constant_delay), int(constant_delay) + 1
    ell_delays = np.asarray(ell_delays)
    rows, pos = graph.csr_rows_pos()
    valid = ell_delays[rows, pos]
    uniform = (
        int(valid[0]) if valid.size and (valid == valid[0]).all() else None
    )
    return uniform, (int(ell_delays.max()) if ell_delays.size else 1) + 1


def _achieved_exchange_report(
    exchange_extra: dict,
    counters,
    ticks: int,
    n_shards: int,
    n_loc: int,
    w: int,
    capacity: int,
    hub_count: int = 0,
) -> dict:
    """Fold the delta runner's achieved-traffic counters into the
    ``stats.extra['exchange']`` report: used entries / overflow writes /
    dense fallbacks summed over passes and share shards, plus the
    achieved per-chip per-tick wire words (fixed all_to_all footprint,
    plus the fixed hub all_gather block under ``exchange="hub"``, +
    amortized dense fallbacks) and the steady-state buffer occupancy —
    used entries over the wire-relevant slot count."""
    k = n_shards
    extra = dict(exchange_extra)
    extra["achieved_used_entries"] = int(counters[0])
    extra["overflow_write_ticks"] = int(counters[1])
    extra["dense_fallback_reads"] = int(counters[2])
    extra["exchange_ticks"] = int(ticks)
    if ticks:
        extra["achieved_delta_words_per_tick"] = (
            (k - 1) * (2 * capacity + hub_count * w)
            + int(counters[2]) * (k - 1) * n_loc * w / ticks
        )
        extra["delta_occupancy"] = int(counters[0]) / (
            ticks * k * max(1, k - 1) * capacity
        )
    return extra


def _stage_ell_args(groups, n_padded: int):
    """The runner's propagation operands — layout-independent since the
    delay-split unification (the ring layout only decides WHERE each
    frontier slice is read from, in the runner's read_slice). Returns
    (ell_args flat tuple, bucket_counts).

    ``groups`` holds one ``(buckets, direct)`` pair per gather group — one
    for the uniform delay, or one per distinct delay value (per-edge
    delays: `split_ell_by_delay`; the replicated path used to stage the
    full-width (idx, delay, mask) triple and run the dense `propagate` —
    at the 1M scale-free shape (dmax 4517) those are ~40 GB of operands
    plus the same again in in-jit blocked transposes, which OOM-killed a
    125 GB host three times). ``buckets`` are the group's per-node-shard
    DEGREE BUCKETS (`shard_bucket_csr` / `shard_bucket_ell`), so a
    group's gather reads ~its own valid entries instead of rows padded to
    the group's global column cap — on hub-skewed graphs (1M BA: dmax
    4517, mean degree 6) the full-cap gather is ~750x masked traffic.
    ``direct(cap)`` gives the group's full-width ``(n_padded, cap)``
    (idx, mask) pair instead. ``ell_args`` is the flat tuple of
    per-bucket (rows, idx, mask) triples in group order;
    ``bucket_counts[g]`` says how many triples group g owns.
    """
    ell_args: list = []
    bucket_counts: list[int] = []
    for buckets, direct in groups:
        bucketed_entries = sum(idx_b.size for _, idx_b, _ in buckets)
        cap = max((idx_b.shape[2] for _, idx_b, _ in buckets), default=1)
        if bucketed_entries > 0.75 * n_padded * cap:
            # Uniform-degree group: bucketing saves <25% of the gather
            # while adding per-bucket dispatch and a scatter per tick —
            # measured a 22% sharded-leg regression on the 1M ER mesh
            # (dmax 1164, mean degree ~1000). Stage the direct
            # full-width pair instead (bucket_counts 0 = the runner
            # consumes it without the bucket machinery); columns still
            # trim to the group's max count. Hub-skewed groups (1M BA:
            # 750x full-cap waste) keep the buckets.
            bucket_counts.append(0)
            ell_args.extend(np.ascontiguousarray(a) for a in direct(cap))
            continue
        bucket_counts.append(len(buckets))
        for rows_b, idx_b, msk_b in buckets:
            ell_args.extend((rows_b, idx_b, msk_b))
    _rss_log("degree buckets staged")
    return tuple(ell_args), tuple(bucket_counts)


def _gather_slots(ell_args, bucket_counts, count) -> int:
    """Neighbour slots one tick's gathers fetch over all node shards (one
    share shard's copy of the graph): ``count(rows, cap)`` of every staged
    direct pair and of every shard's slice of every bucket of
    `_stage_ell_args`, padding rows included."""
    slots = pos = 0
    for n_buckets in bucket_counts:
        if n_buckets == 0:
            rows, cap = ell_args[pos].shape
            slots += count(rows, cap)
            pos += 2
            continue
        for _ in range(n_buckets):
            shards, rows, cap = ell_args[pos + 1].shape
            slots += shards * count(rows, cap)
            pos += 3
    return int(slots)


def _rows_spec(x) -> P:
    """Rows (the leading axis) split over the nodes axis."""
    return P(NODES_AXIS, *([None] * (np.ndim(x) - 1)))


@dataclasses.dataclass(frozen=True)
class ShardedDeviceGraph:
    """Graph, latency model and frontier-exchange plan staged once on a
    nodes mesh: the sharded counterpart of `engine.sync.DeviceGraph`.
    `run_sharded_sim` (``device_graph=``), `run_sharded_flood_coverage`
    and `batch.campaign_sharded.run_sharded_campaign` run from it and
    stage nothing per call.

    ``ell_args`` (per-shard degree buckets, `_stage_ell_args`),
    ``degree``, the no-churn placeholders and the exchange operands
    (``need``, ``hub_ops``) are placed on the mesh by `build`, rows split
    over the nodes axis; `stage` keeps them on the host (shapes for a
    described mesh). ``w`` is the row width in words the ring and
    exchange plans were made for: a run must use a chunk of that width.
    """

    mesh: Mesh
    n: int
    n_padded: int
    w: int
    block: int
    ring: int                   # history-ring slots
    uniform: int | None         # the delay every edge shares, else None
    ring_mode: str              # "replicated" or "sharded"
    k_async: int                # bounded-staleness K, 0 = synchronous
    delay_values: tuple | None  # one gather group per value (per-edge)
    bucket_counts: tuple
    ell_args: tuple
    degree: object
    churn_start: object         # (n_padded, 1) zeros: vacuously up
    churn_end: object
    exchange_mode: str          # "dense", "delta" or "hub"
    need: object | None         # (n_padded, shards) cut membership
    capacity: int
    hub_ops: tuple | None       # (hub_count, hub_local, hub_global)
    aggregate: bool
    ring_extra: dict            # stats.extra["ring"]
    exchange_extra: dict        # stats.extra["exchange"], before a run

    @property
    def n_node_shards(self) -> int:
        return self.mesh.shape[NODES_AXIS]

    @property
    def gather_slots(self) -> int:
        return self.ring_extra["gather_slots"]

    @property
    def hub_count(self) -> int:
        return self.hub_ops[0] if self.hub_ops else 0

    def exchange_operands(self) -> tuple:
        """The runner's trailing operands: the cut membership on the
        delta and hub paths, and the hub rows on the hub path."""
        if self.exchange_mode == "dense":
            return ()
        return (self.need,) + (self.hub_ops[1:] if self.hub_ops else ())

    def runner(self, chunk_size: int, horizon: int, **kw):
        """`build_sharded_runner` for this staging at ``chunk_size``
        shares per pass shard; ``kw`` passes the run's own options
        (``num_snaps``, ``loss``, ``record_coverage``, ``cov_slots``,
        ``connect_tick``, ``telemetry_on``, the campaign options)."""
        if bitmask.num_words(chunk_size) != self.w:
            raise ValueError(
                f"graph staged for {self.w}-word rows, run asks for "
                f"{bitmask.num_words(chunk_size)}"
            )
        return build_sharded_runner(
            self.mesh, self.n_padded, self.ring, chunk_size, horizon,
            self.block, self.uniform,
            ring_mode=self.ring_mode, delay_values=self.delay_values,
            bucket_counts=self.bucket_counts,
            exchange_mode=self.exchange_mode, delta_capacity=self.capacity,
            hub_count=self.hub_count, delta_aggregate=self.aggregate,
            async_k=self.k_async, **kw,
        )

    @staticmethod
    def build(graph: Graph, mesh: Mesh, **kw) -> "ShardedDeviceGraph":
        """`stage` on the host, then every operand onto the mesh, under
        the ``gossip.stage`` span. Takes `stage`'s options."""
        with telemetry.span(
            "stage", nodes=graph.n, node_shards=mesh.shape[NODES_AXIS]
        ):
            dg = ShardedDeviceGraph.stage(graph, mesh, **kw)

            def put(x, spec=None):
                return jax.device_put(
                    x, NamedSharding(mesh, spec or _rows_spec(x))
                )

            hub_ops = dg.hub_ops and (
                dg.hub_ops[0], put(dg.hub_ops[1]),
                put(dg.hub_ops[2], P(None, None)),  # replicated
            )
            dg = dataclasses.replace(
                dg,
                ell_args=tuple(put(a) for a in dg.ell_args),
                degree=put(dg.degree),
                churn_start=put(dg.churn_start),
                churn_end=put(dg.churn_end),
                need=None if dg.need is None else put(dg.need),
                hub_ops=hub_ops,
            )
            _rss_log("sharded graph on the mesh")
        return dg

    @staticmethod
    def stage(
        graph: Graph,
        mesh: Mesh,
        *,
        constant_delay: int = 1,
        ell_delays: np.ndarray | None = None,
        ring_mode: str = "auto",
        exchange: str = "dense",
        bucket_min_rows: int = 2048,
        chunk_size: int = 4096,
        block: int | None = None,
        async_k: int = 2,
        hub_rows: int | None = None,
        aux_cache: tuple | None = None,
    ) -> "ShardedDeviceGraph":
        """Host-side staging, operands as numpy arrays. Options as
        `run_sharded_sim`'s; ``chunk_size`` fixes the row width the ring
        and exchange plans are made for; ``block`` None takes the swept
        TPU optimum, which each gather narrows to its own cap (results
        are bitwise-identical for any block).

        Under a uniform delay (``constant_delay``, or ``ell_delays`` equal
        on every edge) the degree buckets come straight from CSR
        (`ops.ell.shard_bucket_csr`): neither the global ``(N, dmax)`` ELL
        nor a delay array of its shape is built. Per-edge delays
        (`split_ell_by_delay`) and the delta / hub cut plan read the
        padded global ELL."""
        _rss_log("sharded staging enter")
        transport, k_async = async_ticks.parse_exchange(exchange, async_k)
        if k_async or transport in ("delta", "hub"):
            # The delta/hub paths compress the sharded ring's write
            # slices, and async reads prefetch them: a replicated ring
            # has no read-time exchange to compress or prefetch.
            ring_mode = "sharded"
        n_node_shards = mesh.shape[NODES_AXIS]
        n_padded = -(-graph.n // n_node_shards) * n_node_shards
        n_loc = n_padded // n_node_shards
        w = bitmask.num_words(chunk_size)
        if block is None:
            block = tuned_degree_block(mesh.devices.flat)
        uniform, ring = _delay_model(graph, ell_delays, constant_delay)
        ring = async_ticks.effective_ring(ring, k_async)
        ring_mode, ring_bytes = resolve_ring_mode(
            ring_mode, uniform, ring, n_padded, n_node_shards, w
        )
        if transport == "auto":
            transport = (
                "delta"
                if ring_mode == "sharded" and n_node_shards > 1
                else "dense"
            )
        ell_idx = ell_mask = None
        if uniform is None or transport in ("delta", "hub"):
            ell_idx, ell_mask = (
                pad_to_multiple(a, n_node_shards) for a in graph.ell()
            )
            _rss_log("global ELL materialized")
        if uniform is not None:
            delay_values = None

            def direct(cap):
                idx, msk = graph.ell_rows(np.arange(graph.n), cap)
                return (pad_to_multiple(idx, n_node_shards),
                        pad_to_multiple(msk, n_node_shards))

            groups = [(
                shard_bucket_csr(
                    graph, n_node_shards, min_rows=bucket_min_rows
                ),
                direct,
            )]
        else:
            splits = split_ell_by_delay(
                ell_idx,
                pad_to_multiple(np.asarray(ell_delays), n_node_shards, fill=1),
                ell_mask,
            )
            _rss_log("delay splits built")
            delay_values = tuple(d for d, _, _ in splits)
            groups = [
                (
                    shard_bucket_ell(
                        i, m, n_node_shards, min_rows=bucket_min_rows
                    ),
                    lambda cap, i=i, m=m: (i[:, :cap], m[:, :cap]),
                )
                for _, i, m in splits
            ]
        ell_args, bucket_counts = _stage_ell_args(groups, n_padded)
        delay_splits = len(delay_values) if delay_values else 1
        ring_extra = {
            "mode": ring_mode,
            "bytes_per_chip": ring_bytes,
            "slots": ring,
            "delay_splits": delay_splits,
            "degree_buckets": bucket_counts,
            # Slots a tick (`ops.ell.scanned_width`), and those fetched
            # into on-chip tiles (`ops.ell.fused_slots`).
            "gather_slots": _gather_slots(
                ell_args, bucket_counts,
                lambda rows, cap: rows * scanned_width(cap, block),
            ),
            "gather_fused_slots": _gather_slots(
                ell_args, bucket_counts,
                lambda rows, cap: fused_slots(rows, cap, block, w),
            ),
        }
        from p2p_gossip_tpu.parallel import exchange as exch

        need, capacity, hub_ops, aggregate = None, 0, None, False
        if transport in ("delta", "hub"):
            need, need_counts = exch.cached_flood_plan(
                ell_idx, ell_mask, n_node_shards, aux_cache=aux_cache
            )
            max_cut = int(need_counts.max()) if need_counts.size else 0
            hub_report = None
            if transport == "hub":
                hplan = exch.plan_hub_split(
                    need, need_counts, n_node_shards, n_loc, w,
                    delay_splits, hub_rows=hub_rows,
                )
                hub_report = hplan["report"]
                need = hplan["need_tail"]
                capacity = hplan["capacity"]
                if hplan["hub_count"] > 0:
                    hub_ops = (
                        hplan["hub_count"], hplan["hub_local"],
                        hplan["hub_global"],
                    )
            else:
                capacity = exch.delta_capacity(
                    max(max_cut, 1), n_loc, w, delay_splits,
                )
            aggregate = exch.choose_aggregate(n_node_shards, capacity)
            exchange_extra = {
                "mode": transport,
                "capacity": capacity,
                "aggregated": aggregate,
                "max_cut_rows": max_cut,
                "modeled_dense_words_per_tick":
                    exch.modeled_exchange_words_per_tick(
                        "dense" if ring_mode == "sharded" else "replicated",
                        n_shards=n_node_shards, n_loc=n_loc, w=w,
                        delay_splits=delay_splits,
                    ),
                "modeled_delta_words_per_tick": (
                    hub_report["modeled_delta_words_per_tick"]
                    if hub_report is not None
                    else exch.modeled_exchange_words_per_tick(
                        "delta", n_shards=n_node_shards, n_loc=n_loc, w=w,
                        capacity=capacity,
                    )
                ),
            }
            if hub_report is not None:
                exchange_extra.update({
                    "hub_count": hub_report["hub_count"],
                    "hub_rows_forced": hub_report["hub_rows_forced"],
                    "crossover_h": hub_report["crossover_h"],
                    "modeled_hub_words_per_tick":
                        hub_report["modeled_hub_words_per_tick"],
                })
        else:
            mode = "dense" if ring_mode == "sharded" else "replicated"
            exchange_extra = {
                "mode": mode,
                "capacity": 0,
                "modeled_dense_words_per_tick":
                    exch.modeled_exchange_words_per_tick(
                        mode, n_shards=n_node_shards, n_loc=n_loc, w=w,
                        delay_splits=delay_splits,
                    ),
            }
        if k_async:
            exchange_extra.update(async_ticks.modeled_overlap_report(
                transport,
                (uniform,) if uniform is not None else delay_values,
                k_async, n_node_shards, n_loc, w, capacity,
                hub_count=hub_ops[0] if hub_ops else 0,
            ))
        churn_start, churn_end = _padded_churn(None, n_padded, n_node_shards)
        return ShardedDeviceGraph(
            mesh=mesh, n=graph.n, n_padded=n_padded, w=w, block=block,
            ring=ring, uniform=uniform, ring_mode=ring_mode, k_async=k_async,
            delay_values=delay_values, bucket_counts=bucket_counts,
            ell_args=ell_args,
            degree=pad_to_multiple(
                graph.degree.astype(np.int32), n_node_shards
            ),
            churn_start=churn_start, churn_end=churn_end,
            exchange_mode=transport, need=need, capacity=capacity,
            hub_ops=hub_ops, aggregate=aggregate,
            ring_extra=ring_extra, exchange_extra=exchange_extra,
        )


@functools.lru_cache(maxsize=32)
def build_sharded_runner(
    mesh: Mesh,
    n_padded: int,
    ring_size: int,
    chunk_size: int,
    horizon: int,
    block: int = DEFAULT_DEGREE_BLOCK,
    uniform_delay: int | None = None,
    num_snaps: int = 0,
    loss: tuple | None = None,
    record_coverage: bool = False,
    cov_slots: int | None = None,
    ring_mode: str = "replicated",
    delay_values: tuple | None = None,
    connect_tick: int = 0,
    bucket_counts: tuple = (1,),
    telemetry_on: bool = False,
    exchange_mode: str = "dense",
    delta_capacity: int = 0,
    hub_count: int = 0,
    delta_aggregate: bool = False,
    replica_axis: str | None = None,
    local_replicas: int = 1,
    per_replica_loss: bool = False,
    async_k: int = 0,
):
    """Compile the per-pass runner: each shares-shard processes its own
    ``chunk_size`` shares over the row-sharded graph, from the chunk's first
    generation tick to quiescence. Memoized so repeated calls with the same
    mesh/shapes reuse the jitted executable.

    ``replica_axis`` switches the runner to CAMPAIGN mode over a
    factorized ``(replica_axis, nodes)`` mesh (mesh.make_mesh(replicas=…)):
    the first mesh axis carries seed-ensemble replicas instead of share
    shards, and the SAME tick step is ``jax.vmap``ed over each replica
    shard's ``local_replicas`` batch inside ONE shared while_loop (vmap of
    the whole solo loop would trigger JAX's batched-while transform —
    per-element selects on every carried array, the ~4x cost
    batch/campaign.py measured). Per-replica operands grow a leading
    replica dim: origins/gen_ticks (R, chunk) sharded over the replica
    axis, churn intervals (R, n_padded, K), and — with
    ``per_replica_loss`` — one traced uint32 loss seed per replica
    appended after ``snap_ticks`` (the static ``loss`` pair is then
    (threshold, None); the traced seed feeds the same erasure coin, so a
    solo run with that static seed matches bitwise). Outputs stay
    per-replica — no counter psum over the first axis — giving global
    (R, n_padded) counters, (R, horizon, cov_slots) coverage, per-replica
    telemetry/digest rings, and (R, 8) delta counters. The loop runs to
    the SLOWEST replica's quiescence; a replica past its own has an
    all-zero frontier, so every extra tick is an exact identity — replica
    r is bitwise-identical to its solo sharded run. Second return value
    is the per-replica pass width (``chunk_size``).

    The first runner argument is the flat ``ell_args`` tuple staged by
    `_stage_ell_args` for (``uniform_delay``, ``delay_values``,
    ``bucket_counts``); its layout — per-group degree buckets of
    (rows, idx, mask) triples — is part of the compiled signature.
    Outside campaign mode the last output is the tick the loop stopped
    at, so a pass ran ``t - t_start`` ticks.

    ``num_snaps`` > 0 additionally returns (num_snaps, n_loc) received
    counts captured when the tick counter reaches each entry of the
    ``snap_ticks`` input — periodic-stats boundaries, same timing as the
    sync engine (totals over all ticks strictly before the boundary).

    ``record_coverage`` additionally returns per-tick per-slot coverage
    (horizon, cov_slots) for the first ``cov_slots`` of this shard's share
    slots (default: all chunk_size; the flood driver restricts it to the
    live slots so dead padding isn't counted every tick) — node counts
    psum'ed over the nodes axis each tick, rows past quiescence holding
    the final (constant) coverage, exactly like the sync engine's
    coverage runs.

    ``telemetry_on`` (static, part of the memoized signature) carries a
    (horizon, NUM_METRICS) metric ring through the loop — per-tick rows
    psum'ed over the nodes axis only, so each shares-shard's ring covers
    ITS share chunk (the host emits one ring event per shard, matching
    the solo engine's one-event-per-chunk convention) — returned stacked
    per share-shard as one extra trailing output.

    ``exchange_mode`` "delta" (sharded ring only) replaces the per-delay
    slice all_gathers with the sparse frontier-delta exchange
    (parallel/exchange.py): each tick ships at most ``delta_capacity``
    changed-word entries per destination over one all_to_all, readers
    reconstruct slices by scatter + own-slice overlay, and a mesh-uniform
    per-slot overflow flag routes readers to the dense all_gather when a
    shard's delta outgrew the buffer — bitwise-identical results either
    way (OR-monotone merge). Takes one extra trailing operand (the
    (n_loc, n_shards) cut membership from `plan_flood_exchange`) and
    returns one extra trailing output: a per-share-shard (8,) uint32
    counter row [used_entries_lo, used_entries_hi, overflow_write_ticks,
    dense_fallback_reads, exchange_ticks, 0, 0, 0] for achieved-traffic
    accounting (host side: `stats.extra['exchange']`).

    ``async_k`` > 0 (sharded ring only) switches the read side to the
    bounded-staleness async path (module docstring,
    parallel/async_ticks.py): a ``landed`` carry holds one prefetched
    full-canvas slice per distinct offset ``off = max(d, K)``, issued at
    the top of the PREVIOUS tick from pre-write ring state (slot
    ``t - off`` is final and is never this tick's write slot, so the
    value equals a read-time gather — the restructure only moves the
    collective a full tick ahead of its first reader, which is what
    lets XLA overlap it with the whole tick's compute). Reads overlay
    the shard's own timely ``(t - d)`` slice onto the landed canvas, so
    intra-shard edges see delay d and cross-shard edges ``max(d, K)``
    automatically. The quiescence predicate ORs the landed carry in
    (`async_ticks.in_flight`) so termination is agreed at a common fold
    epoch. Works on both transports; requires
    ``ring_size >= max(dmax, K) + 1`` (`async_ticks.effective_ring`)."""
    campaign = replica_axis is not None
    if campaign:
        if local_replicas < 1:
            raise ValueError(
                f"local_replicas must be >= 1, got {local_replicas}"
            )
        # Campaign meshes carry replicas on axis 0, not share shards: the
        # whole chunk rides one share pass per replica.
        n_share_shards = 1
    else:
        n_share_shards = mesh.shape[SHARES_AXIS]
    if per_replica_loss and (not campaign or loss is None):
        raise ValueError(
            "per_replica_loss requires replica_axis and a loss model"
        )
    axis0 = replica_axis if campaign else SHARES_AXIS
    rb = local_replicas if campaign else 1
    n_node_shards = mesh.shape[NODES_AXIS]
    n_loc = n_padded // n_node_shards
    w = bitmask.num_words(chunk_size)
    tel = tel_rings.active(telemetry_on)
    dig = tel_digest.active(telemetry_on)
    if cov_slots is None:
        cov_slots = chunk_size
    cov_w = bitmask.num_words(cov_slots)
    sharded_ring = ring_mode == "sharded"
    hist_rows = n_loc if sharded_ring else n_padded
    # "hub" is the delta transport plus a static index-free hub block;
    # hub_count == 0 (the cost search picked pure delta) compiles the
    # plain delta program — no zero-size hub collectives.
    delta = exchange_mode in ("delta", "hub")
    hub = exchange_mode == "hub" and hub_count > 0
    if delta and not sharded_ring:
        raise ValueError(
            f"exchange_mode={exchange_mode!r} requires ring_mode='sharded'"
        )
    if delta and delta_capacity < 1:
        raise ValueError(f"delta_capacity must be >= 1, got {delta_capacity}")
    if delta:
        from p2p_gossip_tpu.parallel import exchange as exch
    # Static gather-group count (one per distinct delay value): the
    # per-tick dense exchange multiplier in the telemetry traffic row.
    n_groups = (
        1 if uniform_delay is not None
        else (len(delay_values) if delay_values else 1)
    )
    group_delays_s = (
        (uniform_delay,) if uniform_delay is not None else delay_values
    )
    if async_k > 0:
        if not sharded_ring:
            raise ValueError("async exchange requires ring_mode='sharded'")
        offs, off_index, amounts = async_ticks.group_offsets(
            group_delays_s, async_k
        )
        if offs and ring_size < max(offs) + 1:
            raise ValueError(
                f"async_k={async_k} needs ring_size >= {max(offs) + 1} "
                f"(async_ticks.effective_ring), got {ring_size}"
            )
    else:
        offs, off_index, amounts = (), (), ()
    n_offs = len(offs)
    # Dense read-time gather count per tick: one per landed slice plus
    # one per direct-read group (off == 1: K=1 delay-1 edges).
    n_dense_reads = (
        n_offs + sum(1 for i in off_index if i < 0) if async_k > 0
        else n_groups
    )

    def local_coverage(seen):
        return bitmask.coverage_per_slot(seen[:, :cov_w], cov_slots)

    def pass_fn(
        ell_args, degree, churn_start, churn_end,
        origins, gen_ticks, t_start, last_gen, snap_ticks,
        *extra_args,
    ):
        # Local shapes: ell_args arrays (n_loc, cols); churn_* (n_loc, K)
        # downtime intervals ((n_loc, 1) zeros when churn is off — the
        # compare is vacuously up); origins/gen_ticks (chunk_size,);
        # t_start/last_gen scalars (min/max over ALL slices, so loop trip
        # counts agree across devices); snap_ticks (num_snaps,) replicated.
        # Campaign mode prepends a local replica dim rb to churn_*,
        # origins and gen_ticks, and appends the per-replica loss-seed
        # vector (rb,) before the delta operand when per_replica_loss.
        if campaign and per_replica_loss:
            lseeds = extra_args[0]
            delta_args = extra_args[1:]
        else:
            lseeds = None
            delta_args = extra_args
        row_offset = lax.axis_index(NODES_AXIS).astype(jnp.int32) * n_loc
        slots = jnp.arange(chunk_size, dtype=jnp.int32)
        # (Loss-coin dst ids are built per bucket inside arrivals_for:
        # row_offset + the bucket's local rows — global ids, so every
        # mesh shape agrees with the single-device engines.)

        rstate = (
            jnp.zeros((n_loc, w), dtype=jnp.uint32),              # seen (local)
            # History ring: global rows (replicated) or local rows (sharded).
            jnp.zeros((ring_size, hist_rows, w), dtype=jnp.uint32),
            jnp.zeros((n_loc,), dtype=jnp.int32),                 # received
            jnp.zeros((n_loc,), dtype=jnp.int32),                 # sent
            jnp.zeros((num_snaps, n_loc), dtype=jnp.int32),       # snapshots
            jnp.zeros(
                (cov_slots if record_coverage else 0,),
                dtype=jnp.int32,
            ),                                                    # running cov
            jnp.zeros(
                (horizon if record_coverage else 0,
                 cov_slots if record_coverage else 0),
                dtype=jnp.int32,
            ),                                                    # coverage
        )
        if tel:
            rstate = rstate + (tel_rings.init(horizon),)          # metrics
        tel_i = 7
        dig_i = 7 + (1 if tel else 0)
        if dig:
            rstate = rstate + (tel_digest.init(horizon),)         # digests
        ex_i = 7 + (1 if tel else 0) + (1 if dig else 0)
        if delta:
            need = delta_args[0]  # (n_loc, n_shards) cut membership
            if hub:
                # Static hub membership (plan_hub_split): this shard's
                # local hub row ids (leading shard axis sliced to row 0)
                # and the replicated global ids of every shard's block.
                hub_rows_l = delta_args[1][0]
                hub_global = delta_args[2]
            rstate = rstate + (
                # Received-delta rings, slot-aligned with hist: axis 1 is
                # the SOURCE shard post all_to_all. idx -1 = empty.
                jnp.full(
                    (ring_size, n_node_shards, delta_capacity),
                    -1, dtype=jnp.int32,
                ),
                jnp.zeros(
                    (ring_size, n_node_shards, delta_capacity),
                    dtype=jnp.uint32,
                ),
                # Mesh-uniform per-slot overflow flags: readers take the
                # dense all_gather branch for flagged slots.
                jnp.zeros((ring_size,), dtype=jnp.bool_),
                # [used_lo, used_hi, overflow_writes, fallback_reads,
                #  exchange_ticks, 0, 0, 0]
                jnp.zeros((8,), dtype=jnp.uint32),
            )
        if hub:
            # Hub block ring, slot-aligned with hist: every shard's h
            # hub rows at the written tick, all_gathered at write time.
            # Unwritten slots stay zero, so overlaying them is a no-op.
            rstate = rstate + (
                jnp.zeros(
                    (ring_size, n_node_shards * hub_count, w),
                    dtype=jnp.uint32,
                ),
            )
        landed_i = (
            7 + (1 if tel else 0) + (1 if dig else 0)
            + (4 if delta else 0) + (1 if hub else 0)
        )
        if n_offs:
            # Async landed double-buffer: one prefetched full-canvas
            # slice per distinct offset, holding the completed gather of
            # ring slot (t - off) at the top of tick t. Zeros are exact
            # for any t_start: every pass starts from a zeroed ring, so
            # the slots those gathers would have read are all-zero.
            rstate = rstate + (
                jnp.zeros((n_offs, n_padded, w), dtype=jnp.uint32),
            )
        if campaign:
            # One state copy per local replica: the tick step is vmapped
            # over this leading rb axis inside the shared while_loop.
            rstate = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (rb,) + a.shape), rstate
            )
        state = (t_start,) + rstate

        @jax.named_scope("gossip.quiescence")
        def cond(state):
            t, hist = state[0], state[2]
            # Local ring rows are a subset (sharded) or a replica
            # (replicated) of the global frontier state; the mesh-wide
            # OR-reduce makes the predicate uniform either way. In
            # campaign mode the loop runs until the SLOWEST replica on
            # the mesh quiesces (extra ticks are exact identities for
            # the already-quiet replicas, see build docstring). Async
            # runs OR the landed carry in: quiescence is agreed only at
            # a common fold epoch (async_ticks.in_flight).
            in_flight = async_ticks.in_flight(
                hist, state[1 + landed_i] if n_offs else None
            )
            in_flight = lax.psum(
                in_flight.astype(jnp.int32), (axis0, NODES_AXIS)
            ) > 0
            return (t < horizon) & (in_flight | (t <= last_gen))

        @jax.named_scope("gossip.exchange")
        def read_slice(hist, dstate, t, delay):
            """The global (t - delay) frontier: a local ring read when the
            ring is replicated, an all_gather of the local slice when it
            is sharded (the read-time dense frontier exchange, riding
            ICI) — or, on the delta path, a reconstruction from the
            received frontier-delta buffers: scatter the slot's (idx,
            val) entries onto a zero canvas and overlay this shard's own
            slice. Slots whose write overflowed the delta capacity carry
            a mesh-uniform flag and fall back to the dense all_gather —
            both branches are static-shaped."""
            slot = jnp.mod(t - delay, ring_size)
            sl = hist[slot]
            if not sharded_ring:
                return sl
            if not delta:
                return lax.all_gather(sl, NODES_AXIS, axis=0, tiled=True)
            didx_ring, dval_ring, dflag_ring = dstate[:3]

            def dense_read(_):
                return lax.all_gather(sl, NODES_AXIS, axis=0, tiled=True)

            def delta_read(_):
                recon = exch.scatter_deltas(
                    didx_ring[slot], dval_ring[slot], n_loc, w, n_padded
                )
                if hub:
                    # Hub rows never ride the tail buffers (the plan
                    # clears them from the cut): overlay the slot's
                    # gathered hub block — disjoint rows, exact .set.
                    recon = exch.overlay_hub(
                        recon, hub_global, dstate[3][slot]
                    )
                # Own rows never ride the wire (plan_flood_exchange
                # excludes them): overlay the local slice directly.
                return lax.dynamic_update_slice(recon, sl, (row_offset, 0))

            return lax.cond(dflag_ring[slot], dense_read, delta_read,
                            operand=None)

        @jax.named_scope("gossip.exchange")
        def prefetch_landed(hist, dstate, t):
            """The async gathers for tick t+1's reads, issued at the top
            of tick t from PRE-write ring state: slot (t+1-off) was
            written at tick t+1-off <= t-1 (off >= 2) and is never this
            tick's write slot (2 <= off < ring_size), so each slice
            equals the read-time gather read_slice would have done — the
            restructure only moves the collective a full tick ahead of
            its first reader. On the delta transport the slot's
            overflow flag routes to the dense gather exactly like
            read_slice; the scatter canvas leaves own rows zero (they
            never ride the wire) and the dense branch's own rows are
            stale — both fine, the reader overlays its timely local
            slice either way."""
            slices = []
            for off in offs:
                slot_u = jnp.mod(t + 1 - off, ring_size)
                sl = hist[slot_u]
                if not delta:
                    slices.append(
                        lax.all_gather(sl, NODES_AXIS, axis=0, tiled=True)
                    )
                    continue
                didx_ring, dval_ring, dflag_ring = dstate[:3]

                def dense_pre(_, sl=sl):
                    return lax.all_gather(sl, NODES_AXIS, axis=0, tiled=True)

                def delta_pre(_, slot_u=slot_u):
                    recon = exch.scatter_deltas(
                        didx_ring[slot_u], dval_ring[slot_u], n_loc, w,
                        n_padded,
                    )
                    if hub:
                        # Same overlay as delta_read; own hub rows get
                        # their written-slot values, then the reader's
                        # timely own-slice overlay wins (arrivals_for).
                        recon = exch.overlay_hub(
                            recon, hub_global, dstate[3][slot_u]
                        )
                    return recon

                slices.append(lax.cond(
                    dflag_ring[slot_u], dense_pre, delta_pre, operand=None
                ))
            return jnp.stack(slices)

        def arrivals_for(hist, dstate, t, loss_cfg=loss, lseed=None,
                         landed=None):
            # One gather group per delay value (one group total under a
            # uniform delay); read_slice resolves local vs all_gathered
            # per ring layout. Within a group, the degree buckets
            # partition this shard's rows (shard_bucket_ell), so each
            # bucket gathers at its own tight column cap and one
            # mode="drop" scatter reassembles the group's arrivals
            # (padding rows carry id n_loc and fall out). Groups OR
            # together: the delay-split ELLs partition the edge set, so
            # the OR over groups equals the full-ELL gather.
            # ``loss_cfg`` defaults to the compiled loss model; the
            # telemetry row prices loss_dropped by re-gathering with
            # loss_cfg=None (telemetry-on only). Async groups read the
            # prefetched landed canvas (slot t - max(d, K)) with the
            # shard's own timely (t - d) slice overlaid, so intra-shard
            # edges see delay d and cross-shard edges max(d, K).
            group_delays = group_delays_s
            def loss_dst_ids(local_rows):
                # THE global-id convention the loss coin hashes (shared
                # with the single-device engines): shard row offset +
                # local row id. One definition for both gather branches.
                if loss_cfg is None:
                    return None
                return row_offset + local_rows

            acc = jnp.zeros((n_loc, w), dtype=jnp.uint32)
            pos = 0
            for gi, dval in enumerate(group_delays):
                if n_offs and off_index[gi] >= 0:
                    sl = lax.dynamic_update_slice(
                        landed[off_index[gi]],
                        hist[jnp.mod(t - dval, ring_size)],
                        (row_offset, 0),
                    )
                else:
                    sl = read_slice(hist, dstate, t, dval)
                if bucket_counts[gi] == 0:
                    # Direct full-width pair (uniform-degree group —
                    # bucketing would save <25%, see _stage_ell_args):
                    # rows are 0..n_loc-1 in order, no scatter needed.
                    idx_g, msk_g = ell_args[pos: pos + 2]
                    pos += 2
                    acc = acc | gather_or_frontier(
                        sl, t, idx_g, msk_g,
                        block=scan_block(idx_g.shape[1], block),
                        loss=loss_cfg,
                        dst_ids=loss_dst_ids(
                            jnp.arange(n_loc, dtype=jnp.int32)
                        ),
                        loss_seed=lseed,
                    )
                    continue
                cat_rows, cat_parts = [], []
                for _ in range(bucket_counts[gi]):
                    rows_b, idx_b, msk_b = ell_args[pos: pos + 3]
                    pos += 3
                    # Leading shard axis: this device's slice is row 0.
                    rows_b, idx_b, msk_b = rows_b[0], idx_b[0], msk_b[0]
                    part = gather_or_frontier(
                        sl, t, idx_b, msk_b,
                        block=scan_block(idx_b.shape[1], block),
                        loss=loss_cfg,
                        dst_ids=loss_dst_ids(rows_b),
                        loss_seed=lseed,
                    )
                    cat_rows.append(rows_b)
                    cat_parts.append(part)
                with jax.named_scope("gossip.gather"), jax.named_scope(
                    "scatter_back"
                ):
                    grp = (
                        jnp.zeros((n_loc, w), dtype=jnp.uint32)
                        .at[jnp.concatenate(cat_rows)]
                        .set(jnp.concatenate(cat_parts), mode="drop")
                    )
                acc = acc | grp
            return acc

        def tick(rstate, origins_r, gen_ticks_r, churn_start_r, churn_end_r,
                 lseed, t):
            # ONE replica's tick over its node shard — the solo body
            # verbatim, minus the tick counter (carried outside so the
            # campaign vmap shares it). All collectives inside address
            # NODES_AXIS only, so the vmap batches them per replica.
            seen, hist, received, sent, snaps, cov_run, cov_hist = rstate[:7]
            landed = rstate[landed_i] if n_offs else None
            if delta:
                didx_ring, dval_ring, dflag_ring, ectr = rstate[ex_i:ex_i + 4]
                hub_ring = rstate[ex_i + 4] if hub else None
                dstate = (didx_ring, dval_ring, dflag_ring) + (
                    (hub_ring,) if hub else ()
                )
                # Dense fallbacks this tick: one per read slot carrying
                # the (mesh-uniform) overflow flag — per landed offset
                # plus per direct-read group under async, per delay
                # group otherwise.
                read_backs = (
                    offs + tuple(
                        dv for gi, dv in enumerate(group_delays_s)
                        if off_index[gi] < 0
                    )
                    if n_offs else group_delays_s
                )
                fb_t = jnp.zeros((), dtype=jnp.uint32)
                for dv in read_backs:
                    fb_t = fb_t + dflag_ring[
                        jnp.mod(t - dv, ring_size)
                    ].astype(jnp.uint32)
            else:
                dstate = None
            if n_offs:
                # Issue tick t+1's gathers FIRST — no dependency on this
                # tick's compute or writes, so the collective can ride
                # the whole tick in the background.
                landed_next = prefetch_landed(hist, dstate, t)
            if num_snaps:
                snaps = jnp.where(
                    (snap_ticks == t)[:, None], received[None, :], snaps
                )
            with jax.named_scope("gossip.gather"):
                arrivals = arrivals_for(hist, dstate, t, lseed=lseed,
                                        landed=landed)
                if tel:
                    received_in = received
                    arrivals_raw = arrivals  # post-loss, pre-churn wire view
                    arrivals_nl = (
                        arrivals_for(hist, dstate, t, None, landed=landed)
                        if loss is not None else None
                    )
            with jax.named_scope("gossip.update"):
                up = up_mask_jnp(churn_start_r, churn_end_r, t)
                arrivals = jnp.where(up[:, None], arrivals, jnp.uint32(0))
                local_rows = origins_r - row_offset
                # Negative indices wrap under .at[] before mode="drop"
                # applies, so shares owned by other row shards must be
                # masked explicitly.
                in_shard = (local_rows >= 0) & (local_rows < n_loc)
                gen_active = (
                    (gen_ticks_r == t)
                    & in_shard
                    & up[jnp.clip(local_rows, 0, n_loc - 1)]
                )
                gen_bits = bitmask.slot_scatter(
                    n_loc, w, local_rows, slots, gen_active
                )
                gen_cnt = (
                    jnp.zeros((n_loc,), dtype=jnp.int32)
                    .at[local_rows]
                    .add(gen_active.astype(jnp.int32), mode="drop")
                )
                if connect_tick:
                    # Socket warm-up window (engine.sync._tick_body): the
                    # pre-connect generation enters seen only — no frontier,
                    # no sent charge.
                    pre = t < connect_tick
                    live_bits = jnp.where(pre, jnp.uint32(0), gen_bits)
                    live_cnt = jnp.where(pre, 0, gen_cnt)
                    seen, newly_out, received, sent = apply_tick_updates(
                        seen, arrivals, live_bits, live_cnt, received, sent,
                        degree,
                    )
                    seen = seen | jnp.where(pre, gen_bits, jnp.uint32(0))
                else:
                    seen, newly_out, received, sent = apply_tick_updates(
                        seen, arrivals, gen_bits, gen_cnt, received, sent,
                        degree,
                    )
            with jax.named_scope("gossip.delay_line"):
                if sharded_ring:
                    # Local write; the frontier exchange happens at READ time
                    # (read_slice), so per-chip ring HBM is n_loc rows.
                    hist = hist.at[jnp.mod(t, ring_size)].set(newly_out)
                else:
                    # Write-time frontier exchange: local newly -> global rows.
                    with jax.named_scope("gossip.exchange"):
                        newly_full = lax.all_gather(
                            newly_out, NODES_AXIS, axis=0, tiled=True
                        )
                    hist = hist.at[jnp.mod(t, ring_size)].set(newly_full)
            if delta:
                # Write-time sparse exchange: pack this tick's changed
                # words per destination (cut-restricted, self-excluded)
                # and ship ONE all_to_all of fixed-capacity buffers —
                # post-exchange axis 0 is the source shard. A truncated
                # buffer anywhere on the mesh raises the slot's uniform
                # overflow flag so every reader takes the dense branch.
                with jax.named_scope("gossip.exchange"):
                    cidx, cval, ccounts = exch.compress_deltas(
                        newly_out, need, delta_capacity,
                        aggregate=delta_aggregate,
                    )
                    idx_recv = lax.all_to_all(
                        cidx, NODES_AXIS, split_axis=0, concat_axis=0
                    )
                    val_recv = lax.all_to_all(
                        cval, NODES_AXIS, split_axis=0, concat_axis=0
                    )
                    ovf = lax.psum(
                        jnp.any(ccounts > delta_capacity).astype(jnp.int32),
                        NODES_AXIS,
                    ) > 0
                slot_w = jnp.mod(t, ring_size)
                didx_ring = didx_ring.at[slot_w].set(idx_recv)
                dval_ring = dval_ring.at[slot_w].set(val_recv)
                dflag_ring = dflag_ring.at[slot_w].set(ovf)
                if hub:
                    # Index-free hub exchange: every shard's h hub rows
                    # ride one tiled all_gather per tick — w words per
                    # row per peer, no (idx, val) overhead, no overflow
                    # (the block is exactly sized).
                    with jax.named_scope("gossip.exchange"):
                        hub_all = lax.all_gather(
                            newly_out[hub_rows_l], NODES_AXIS, axis=0,
                            tiled=True,
                        )
                    hub_ring = hub_ring.at[slot_w].set(hub_all)
                # Achieved-traffic counters (uniform within the share
                # shard): entries actually shipped mesh-wide this tick,
                # overflow write ticks, dense fallback reads, ticks.
                used_t = lax.psum(
                    jnp.sum(jnp.minimum(ccounts, delta_capacity)),
                    NODES_AXIS,
                ).astype(jnp.uint32)
                lo, hi = bitmask.add_u64(ectr[0], ectr[1], used_t)
                ectr = jnp.stack((
                    lo, hi,
                    ectr[2] + ovf.astype(jnp.uint32),
                    ectr[3] + fb_t,
                    ectr[4] + jnp.uint32(1),
                    ectr[5], ectr[6], ectr[7],
                ))
            if record_coverage:
                # Incremental, like engine.sync: newly_out bits are
                # disjoint across ticks, so the mesh-wide coverage is a
                # running sum of the local frontier's per-slot counts.
                cov_run = cov_run + lax.psum(
                    local_coverage(newly_out), NODES_AXIS
                )
                cov_hist = lax.dynamic_update_slice(
                    cov_hist, cov_run[None], (t, 0)
                )
            out = (seen, hist, received, sent, snaps, cov_run, cov_hist)
            if tel:
                # Per-chip state-slice exchange words received this tick
                # (ICI traffic model, see exchange.py): the NODES psum
                # below turns it into the mesh total for this share
                # chunk, like the other columns.
                if delta:
                    ex_words = (
                        jnp.uint32(
                            (n_node_shards - 1)
                            * (2 * delta_capacity + hub_count * w)
                        )
                        + fb_t * jnp.uint32((n_node_shards - 1) * n_loc * w)
                    )
                elif sharded_ring:
                    ex_words = jnp.uint32(
                        n_dense_reads * (n_node_shards - 1) * n_loc * w
                    )
                else:
                    ex_words = jnp.uint32((n_node_shards - 1) * n_loc * w)
                # Async staleness accounting: each group running
                # off = max(d, K) > d late charges its (off - d) amount
                # on ticks where its remote (cross-shard) view held any
                # pending bit. Same canvas on every shard, so the NODES
                # psum below scales both columns by n_node_shards — the
                # schema documents the columns as summed over node
                # shards, like the rest of the row.
                stale_t = jnp.uint32(0)
                folds_t = jnp.uint32(0)
                if n_offs and any(a > 0 for a in amounts):
                    remote_row = (
                        jnp.arange(n_padded, dtype=jnp.int32) // n_loc
                        != lax.axis_index(NODES_AXIS).astype(jnp.int32)
                    )
                    for gi, amt in enumerate(amounts):
                        if amt <= 0:
                            continue
                        pending = jnp.any(jnp.where(
                            remote_row[:, None],
                            landed[off_index[gi]], jnp.uint32(0),
                        ) != 0).astype(jnp.uint32)
                        stale_t = stale_t + jnp.uint32(amt) * pending
                        folds_t = folds_t + pending
                # Local row, psum'ed over node shards only: this shard's
                # ring describes its own share chunk system-wide.
                met_row = lax.psum(
                    tel_rings.flood_row(
                        arrivals_raw, newly_out, received - received_in,
                        degree, arrivals_lossless=arrivals_nl,
                        exchange_words=ex_words,
                        staleness=stale_t, stale_folds=folds_t,
                    ),
                    NODES_AXIS,
                )
                out = out + (tel_rings.write(rstate[tel_i], t, met_row),)
            if dig:
                # Global node ids make the salts mesh-shape-invariant;
                # the node-pad rows are all-zero and the sparse fold
                # skips them, so this equals the solo digest bit-for-bit.
                dval = tel_digest.tick_digest_sharded(
                    seen, received, sent,
                    node_ids=row_offset + jnp.arange(n_loc, dtype=jnp.int32),
                    axis_name=NODES_AXIS,
                )
                out = out + (tel_digest.write(rstate[dig_i], t, dval),)
            if delta:
                out = out + (didx_ring, dval_ring, dflag_ring, ectr)
            if hub:
                out = out + (hub_ring,)
            if n_offs:
                out = out + (landed_next,)
            return out

        if campaign:
            def body(state):
                t = state[0]
                if per_replica_loss:
                    new = jax.vmap(
                        lambda rs, o, g, cs, ce, ls:
                            tick(rs, o, g, cs, ce, ls, t)
                    )(state[1:], origins, gen_ticks,
                      churn_start, churn_end, lseeds)
                else:
                    new = jax.vmap(
                        lambda rs, o, g, cs, ce:
                            tick(rs, o, g, cs, ce, None, t)
                    )(state[1:], origins, gen_ticks, churn_start, churn_end)
                return (t + 1,) + new
        else:
            def body(state):
                return (state[0] + 1,) + tick(
                    state[1:], origins, gen_ticks, churn_start, churn_end,
                    None, state[0],
                )

        with jax.named_scope("gossip.loop"):
            loop_out = lax.while_loop(cond, body, state)
        t = loop_out[0]
        received, sent, snaps = loop_out[3], loop_out[4], loop_out[5]
        cov_run, cov_hist = loop_out[6], loop_out[7]
        if record_coverage:
            # Rows past quiescence hold the (monotone, now constant) final
            # coverage — same convention as the sync engine.
            ticks = jnp.arange(horizon, dtype=jnp.int32)[:, None]
            if campaign:
                cov_hist = jnp.where(
                    ticks[None] >= t, cov_run[:, None, :], cov_hist
                )
            else:
                cov_hist = jnp.where(ticks >= t, cov_run[None, :], cov_hist)
        if num_snaps:
            # Boundaries at/after quiescence see the (unchanging) final
            # counts — same convention as the sync engine.
            if campaign:
                snaps = jnp.where(
                    (snap_ticks >= t)[None, :, None],
                    received[:, None, :], snaps,
                )
            else:
                snaps = jnp.where(
                    (snap_ticks >= t)[:, None], received[None, :], snaps
                )
        if not campaign:
            # Fold the independent share slices: counters add across
            # SHARES_AXIS. (Campaign mode skips this: each replica's
            # node-shard counters already cover its whole chunk.)
            received = lax.psum(received, SHARES_AXIS)
            sent = lax.psum(sent, SHARES_AXIS)
            snaps = lax.psum(snaps, SHARES_AXIS)
        outs = (received, sent, snaps, cov_hist)
        if tel:
            # Stack per share-shard: each shard's ring is its chunk's
            # telemetry (the host emits one event per shard). Campaign
            # rings already carry the leading replica axis.
            ring_out = loop_out[1 + tel_i]
            outs = outs + ((ring_out if campaign else ring_out[None]),)
        if dig:
            dg_out = loop_out[1 + dig_i]
            outs = outs + ((dg_out if campaign else dg_out[None]),)
        if delta:
            # Achieved-exchange counters, stacked per share-shard like
            # the telemetry ring (uniform across node shards).
            ec_out = loop_out[1 + ex_i + 3]
            outs = outs + ((ec_out if campaign else ec_out[None]),)
        if not campaign:
            # The tick the loop stopped at (mesh-uniform: the quiescence
            # predicate is psum'ed), last: t - t_start ticks ran.
            outs = outs + (t,)
        return outs

    # Per bucket triple: rows (S, R) + idx/mask (S, R, C), all with the
    # shard axis leading — splitting it hands each device its own
    # (1, ...) slice. A 0 count is a direct full-width (idx, mask) pair
    # sharded by rows (see _stage_ell_args).
    ell_specs: tuple = ()
    for bc in bucket_counts:
        if bc == 0:
            ell_specs += (P(NODES_AXIS, None), P(NODES_AXIS, None))
        else:
            ell_specs += (
                P(NODES_AXIS, None), P(NODES_AXIS, None, None),
                P(NODES_AXIS, None, None),
            ) * bc
    if campaign:
        # Per-replica operands: (R, …) over the replica axis; churn also
        # sharded over nodes on axis 1. Outputs keep the replica axis —
        # no share fold, each replica's counters are already complete.
        sched_spec = P(replica_axis, None)
        in_specs = (
            ell_specs,            # ell_args (replicated over replicas)
            P(NODES_AXIS),        # degree
            P(replica_axis, NODES_AXIS, None),  # churn_start (R, n_pad, K)
            P(replica_axis, NODES_AXIS, None),  # churn_end
            sched_spec,           # origins (R, chunk)
            sched_spec,           # gen_ticks (R, chunk)
            P(),                  # t_start
            P(),                  # last_gen
            P(),                  # snap_ticks
        )
        if per_replica_loss:
            in_specs = in_specs + (P(replica_axis),)  # loss seeds (R,)
        if delta:
            in_specs = in_specs + (P(NODES_AXIS, None),)  # cut membership
        if hub:
            in_specs = in_specs + (
                P(NODES_AXIS, None),  # hub_local (k, h) row ids
                P(None, None),        # hub_global (k, h), replicated
            )
        out_specs: tuple = (
            P(replica_axis, NODES_AXIS),        # received (R, n_padded)
            P(replica_axis, NODES_AXIS),        # sent
            P(replica_axis, None, NODES_AXIS),  # snapshots
            P(replica_axis, None, None),        # coverage (R, horizon, slots)
        )
        if tel:
            out_specs = out_specs + (P(replica_axis, None, None),)
        if dig:
            out_specs = out_specs + (P(replica_axis, None),)
        if delta:
            out_specs = out_specs + (P(replica_axis, None),)
    else:
        in_specs = (
            ell_specs,            # ell_args (bucketed, see _stage_ell_args)
            P(NODES_AXIS),        # degree
            P(NODES_AXIS, None),  # churn_start
            P(NODES_AXIS, None),  # churn_end
            P(SHARES_AXIS),       # origins
            P(SHARES_AXIS),       # gen_ticks
            P(),                  # t_start
            P(),                  # last_gen
            P(),                  # snap_ticks
        ) + (
            ((P(NODES_AXIS, None),) if delta else ())  # cut membership
            # hub_local (k, h) row ids + replicated hub_global (k, h).
            + ((P(NODES_AXIS, None), P(None, None)) if hub else ())
        )
        out_specs = (
            P(NODES_AXIS), P(NODES_AXIS), P(None, NODES_AXIS),
            P(None, SHARES_AXIS),
        ) + (
            ((P(SHARES_AXIS, None, None),) if tel else ())
            + ((P(SHARES_AXIS, None),) if dig else ())
            + ((P(SHARES_AXIS, None),) if delta else ())  # exchange ctrs
            + (P(),)  # final tick
        )
    mapped = jax.shard_map(
        pass_fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(mapped), (
        chunk_size if campaign else n_share_shards * chunk_size
    )


# --- staticcheck audit spec (p2p_gossip_tpu/staticcheck/) -----------------

def _audit_mesh():
    """Smallest real mesh the audit can stage on this host: 2x2 when at
    least four devices exist (tests force 8 virtual CPU devices), else
    1x1 — a single TPU chip still traces the full shard_map program."""
    from p2p_gossip_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    shards = 2 if len(devices) >= 4 else 1
    return make_mesh(shards, shards, devices=devices[: shards * shards]), shards


def _audit_campaign_mesh():
    """Smallest factorized (replicas, nodes) mesh the audit can stage:
    (2 replicas x 2 nodes) when four devices exist, else (1 x 1)."""
    from p2p_gossip_tpu.parallel.mesh import make_mesh

    devices = jax.devices()
    if len(devices) >= 4:
        return make_mesh(2, devices=devices[:4], replicas=2)
    return make_mesh(1, devices=devices[:1], replicas=1)


def _audit_spec_flood_runner(
    telemetry_on: bool = False, exchange: str = "dense",
    campaign: bool = False, async_k: int = 0,
):
    """Stage + compile-build the sharded flood runner on tiny shapes and
    hand the auditor the exact mapped callable the production driver
    runs (shard_map + jit), uniform delay, sharded ring; ``exchange``
    "delta" audits the sparse frontier-delta path (both cond branches
    trace, so the dense fallback is covered too). ``campaign`` audits
    the replica-factorized mode (vmapped tick over the replica batch on
    a (replicas, nodes) mesh) — the jit surface
    batch/campaign_sharded.py dispatches. ``async_k`` > 0 audits the
    bounded-staleness landed-carry prefetch path (K-ahead reads on
    either transport, parallel/async_ticks.py)."""
    from p2p_gossip_tpu.models.topology import erdos_renyi
    from p2p_gossip_tpu.staticcheck.registry import AuditSpec
    from p2p_gossip_tpu.telemetry.schema import NUM_METRICS

    if campaign:
        from p2p_gossip_tpu.parallel.mesh import REPLICAS_AXIS

        mesh = _audit_campaign_mesh()
        local_replicas = 2
        r_batch = mesh.shape[REPLICAS_AXIS] * local_replicas
    else:
        mesh, _ = _audit_mesh()
    graph = erdos_renyi(16, 0.3, seed=0)
    chunk, horizon = 32, 16
    dg = ShardedDeviceGraph.stage(
        graph, mesh, chunk_size=chunk,
        exchange=(f"async-{exchange}" if async_k else exchange),
        async_k=async_k or 2,
        # The tiny ER audit graph has no natural hubs — pin h so the
        # hub collectives and overlays actually trace.
        hub_rows=(8 if exchange == "hub" else None),
    )
    runner, pass_size = dg.runner(
        chunk, horizon, telemetry_on=telemetry_on,
        replica_axis=(REPLICAS_AXIS if campaign else None),
        local_replicas=(local_replicas if campaign else 1),
    )
    churn_start, churn_end = dg.churn_start, dg.churn_end
    if campaign:
        origins = np.zeros((r_batch, pass_size), dtype=np.int32)
        gen_ticks = np.full((r_batch, pass_size), horizon, dtype=np.int32)
        gen_ticks[:, :2] = 0
        churn_start = np.zeros((r_batch, dg.n_padded, 1), dtype=np.int32)
        churn_end = churn_start.copy()
    else:
        origins = np.zeros(pass_size, dtype=np.int32)
        gen_ticks = np.full(pass_size, horizon, dtype=np.int32)
        gen_ticks[:2] = 0
    words: tuple = (bitmask.num_words(chunk),)
    if telemetry_on:
        # Stacked per-shard digest rings are (1, horizon) uint32 — the
        # horizon is a declared minor width, like NUM_METRICS.
        words = words + (NUM_METRICS, horizon)
    args = (
        dg.ell_args, dg.degree, churn_start, churn_end, origins, gen_ticks,
        np.int32(0), np.int32(0), np.zeros((0,), dtype=np.int32),
    ) + dg.exchange_operands()
    if dg.exchange_mode in ("delta", "hub"):
        # Delta buffers (capacity minor dim) and the (1, 8) counter row.
        words = words + (dg.capacity, 8)
        if dg.hub_ops:
            words = words + (dg.hub_count,)
    return AuditSpec(
        fn=runner,
        args=args,
        integer_only=True,
        bitmask_words=words,
    )


from p2p_gossip_tpu.staticcheck.registry import register_entry  # noqa: E402

register_entry(
    "parallel.engine_sharded.flood_runner",
    spec=_audit_spec_flood_runner,
)
register_entry(
    "parallel.engine_sharded.flood_runner[telemetry]",
    spec=lambda: _audit_spec_flood_runner(telemetry_on=True),
)
register_entry(
    "parallel.engine_sharded.flood_runner[delta]",
    spec=lambda: _audit_spec_flood_runner(exchange="delta"),
)
register_entry(
    "parallel.engine_sharded.flood_runner[campaign]",
    spec=lambda: _audit_spec_flood_runner(campaign=True),
)
register_entry(
    "parallel.engine_sharded.flood_runner[campaign-delta]",
    spec=lambda: _audit_spec_flood_runner(exchange="delta", campaign=True),
)
register_entry(
    "parallel.engine_sharded.flood_runner[async]",
    spec=lambda: _audit_spec_flood_runner(async_k=2),
)
register_entry(
    "parallel.engine_sharded.flood_runner[async-delta]",
    spec=lambda: _audit_spec_flood_runner(exchange="delta", async_k=2),
)
register_entry(
    "parallel.engine_sharded.flood_runner[hub]",
    spec=lambda: _audit_spec_flood_runner(exchange="hub"),
)
register_entry(
    "parallel.engine_sharded.flood_runner[campaign-hub]",
    spec=lambda: _audit_spec_flood_runner(exchange="hub", campaign=True),
)
register_entry(
    "parallel.engine_sharded.flood_runner[async-hub]",
    spec=lambda: _audit_spec_flood_runner(exchange="hub", async_k=2),
)


def run_sharded_sim(
    graph: Graph,
    schedule: Schedule,
    horizon_ticks: int,
    mesh: Mesh,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    chunk_size: int = 4096,
    block: int | None = None,
    churn=None,
    snapshot_ticks: list[int] | None = None,
    loss=None,
    checkpoint_path: str | None = None,
    checkpoint_every: int = 1,
    stop_after_chunks: int | None = None,
    ring_mode: str = "auto",
    connect_tick: int = 0,
    bucket_min_rows: int = 2048,
    exchange: str = "dense",
    async_k: int = 2,
    hub_rows: int | None = None,
    aux_cache: tuple | None = None,
    device_graph: ShardedDeviceGraph | None = None,
) -> NodeStats:
    """Drop-in counterpart of run_sync_sim/run_event_sim on a device mesh:
    identical per-node counters, any number of shares — including under a
    `models.churn.ChurnModel` (intervals shard with their node rows), a
    `models.linkloss.LinkLossModel` (the counter-based coin hashes global
    node ids, so shard boundaries don't change which messages drop), and
    with ``snapshot_ticks`` periodic-stats boundaries (identical snapshot
    values to the other engines; see run_sync_sim).

    ``chunk_size`` is per share-shard. The 4096 default keeps the bitmask
    minor dimension at the TPU's full 128-lane tile width — narrower chunks
    demote the hot gather to a measured ~15x slower path (see
    engine.sync.MIN_CHUNK_SHARES); tests use small chunks on CPU where only
    chunking semantics matter.

    ``checkpoint_path``/``checkpoint_every``/``stop_after_chunks`` give the
    same pass-boundary checkpoint/resume contract as run_sync_sim: counters
    accumulated so far are written atomically every ``checkpoint_every``
    passes, a restart with identical inputs resumes after the last
    completed pass, and a checkpoint from any different configuration
    (including a different mesh shape) is detected by fingerprint and
    ignored.

    ``ring_mode`` selects the history-ring layout (module docstring):
    "replicated", "sharded", or "auto" (default); counters are bitwise
    identical either way, and the resolved choice is reported in
    ``stats.extra['ring']`` with its per-chip byte cost.

    ``exchange`` selects the cross-shard frontier exchange: "dense"
    (slice all_gathers, the default), "delta" (sparse frontier-delta
    buffers over the cached cut structure — forces the sharded ring,
    bitwise-identical counters), "hub" (the delta transport with a
    static high-fan-out hub block shipped index-free every tick,
    `exchange.plan_hub_split` — also sharded, also bitwise-identical;
    ``hub_rows`` pins the split size, ``aux_cache`` persists the cut
    structure through the graph's npz aux cache), or "auto" (delta
    whenever the ring is sharded across >1 node shards). The resolved
    path, its modeled per-tick traffic, the host-chosen delta packing
    (``aggregated``), and the achieved counters land in
    ``stats.extra['exchange']``.

    ``exchange`` "async" / "async-dense" / "async-delta" switch to the
    bounded-staleness async read path with ``async_k`` = K (module and
    `parallel/async_ticks.py` docstrings): the engine runs up to K
    ticks ahead on locally-known bits over a prefetched landed
    double-buffer, bitwise-equal per tick to the synchronous run with
    cross-shard edge delays clamped to ``max(d, K)``
    (`async_ticks.clamp_flood_delays` builds that reference). "async"
    resolves the transport like "auto"; the ring is forced sharded and
    grows to ``max(dmax, K) + 1`` slots. ``async_k`` is ignored on the
    synchronous modes. Because K >= 2 changes results (by design —
    staleness trades ticks for overlap), the checkpoint fingerprint
    includes it.

    ``device_graph`` is a `ShardedDeviceGraph` built once for ``graph``
    on ``mesh`` (at this ``chunk_size``'s row width): the call then
    stages nothing, and its staging options (``ell_delays``,
    ``constant_delay``, ``block``, ``ring_mode``, ``bucket_min_rows``,
    ``exchange``, ``async_k``, ``hub_rows``, ``aux_cache``) are the
    ones it was built with. Without it the call builds one.
    ``stats.extra["ticks_executed"]`` is the ticks the passes ran,
    summed, as `run_sync_sim` reports them."""
    chunk_size = bitmask.num_words(chunk_size) * bitmask.WORD_BITS
    dg = device_graph or ShardedDeviceGraph.build(
        graph, mesh, constant_delay=constant_delay, ell_delays=ell_delays,
        ring_mode=ring_mode, exchange=exchange,
        bucket_min_rows=bucket_min_rows, chunk_size=chunk_size, block=block,
        async_k=async_k, hub_rows=hub_rows, aux_cache=aux_cache,
    )
    if dg.mesh != mesh or dg.n != graph.n:
        raise ValueError("device_graph was built for another graph or mesh")
    n_padded, n_node_shards = dg.n_padded, dg.n_node_shards
    churn_start, churn_end = (
        (dg.churn_start, dg.churn_end) if churn is None
        else _padded_churn(churn, n_padded, n_node_shards)
    )
    boundaries = filter_snapshot_boundaries(snapshot_ticks, horizon_ticks)
    snap_ticks_arr = np.asarray(boundaries, dtype=np.int32)
    delta_on = dg.exchange_mode in ("delta", "hub")
    tel = telemetry.rings_enabled()
    runner, pass_size = dg.runner(
        chunk_size, horizon_ticks, num_snaps=len(boundaries),
        loss=loss.static_cfg if loss is not None else None,
        connect_tick=connect_tick, telemetry_on=tel,
    )
    by_shares = NamedSharding(mesh, P(SHARES_AXIS))
    n_share_shards = mesh.shape[SHARES_AXIS]
    exch_counters = np.zeros(3, dtype=np.int64)  # used, ovf, fallback
    exch_ticks = 0
    placement = None

    received = np.zeros(n_padded, dtype=np.int64)
    sent = np.zeros(n_padded, dtype=np.int64)
    snap_received = np.zeros((len(boundaries), n_padded), dtype=np.int64)

    checkpointer = None
    if checkpoint_path is not None:
        from p2p_gossip_tpu.utils.checkpoint import (
            ChunkCheckpointer,
            fingerprint,
        )

        # Fingerprint the caller's raw inputs (the staged layout is
        # derived deterministically from them); mesh shape is included so
        # a resume on a different mesh starts fresh — pass boundaries
        # differ, so partial counters would not line up.
        ckpt_fp = fingerprint(
            "sharded_sim", graph.n, graph.edges(), schedule.origins,
            schedule.gen_ticks, horizon_ticks, chunk_size,
            mesh.shape[SHARES_AXIS], mesh.shape[NODES_AXIS],
            ell_delays if ell_delays is not None else constant_delay,
            churn.down_start if churn is not None else None,
            churn.down_end if churn is not None else None,
            np.asarray(loss.static_cfg, dtype=np.int64)
            if loss is not None
            else None,
            *([np.asarray(boundaries, dtype=np.int64)] if boundaries else []),
            # Warm-up window changes the results; appended only when on.
            *(["connect", connect_tick] if connect_tick else []),
            # Async K >= 2 changes results (bounded staleness on
            # cross-shard edges); appended only when on so synchronous
            # fingerprints stay byte-stable across this change.
            *(["async", dg.k_async] if dg.k_async else []),
        )
        checkpointer = ChunkCheckpointer(
            checkpoint_path, ckpt_fp,
            {"received": received, "sent": sent,
             "snap_received": snap_received},
            checkpoint_every,
        )

    from p2p_gossip_tpu.utils.checkpoint import checkpointed_chunks

    ticks_executed = 0
    chunks = schedule.chunk(pass_size)
    for ci, chunk in checkpointed_chunks(chunks, checkpointer, stop_after_chunks):
        live = chunk.gen_ticks < horizon_ticks
        if live.any():
            with telemetry.span("prepare", chunk=ci):
                origins, gen_ticks = (
                    jax.device_put(a, by_shares)
                    for a in chunk.padded(pass_size, horizon_ticks)
                )
                t_start = np.int32(chunk.gen_ticks[live].min())
                last_gen = np.int32(chunk.gen_ticks[live].max())
            with telemetry.span(
                "dispatch", kernel="parallel.engine_sharded.flood_runner",
                chunk=ci,
            ):
                *out, t_end = runner(
                    dg.ell_args, dg.degree, churn_start, churn_end,
                    origins, gen_ticks, t_start, last_gen, snap_ticks_arr,
                    *dg.exchange_operands(),
                )
            r, s, sn = out[0], out[1], out[2]
            if placement is None:
                # (device id, first node row) per counter shard: which
                # chip computed which rows.
                placement = sorted(
                    (sh.device.id, sh.index[0].start or 0)
                    for sh in r.addressable_shards
                )
            if tel:
                met, dstream = out[4], out[5]
            if delta_on:
                ec = np.asarray(out[-1], dtype=np.uint64)  # (shards, 8)
                exch_counters[0] += int(
                    bitmask.combine_u64(ec[:, 0], ec[:, 1]).sum()
                )
                exch_counters[1] += int(ec[:, 2].sum())
                exch_counters[2] += int(ec[:, 3].sum())
                exch_ticks += int(ec[:, 4].sum())
            with telemetry.span("d2h", chunk=ci):
                received += np.asarray(r, dtype=np.int64)
                sent += np.asarray(s, dtype=np.int64)
                ticks_executed += int(t_end) - int(t_start)
                if boundaries:
                    snap_received += np.asarray(sn, dtype=np.int64)
            digest_head = None
            if tel:
                met_np = np.asarray(met)
                dig_np = np.asarray(dstream)
                for k in range(n_share_shards):
                    tel_rings.emit_ring(
                        "parallel.engine_sharded.run_sharded_sim",
                        met_np[k], t0=int(t_start), chunk=ci, shard=k,
                    )
                    # Rows past quiescence were never written (zero);
                    # trim them like emit_ring does.
                    nz = np.flatnonzero(dig_np[k])
                    ticks_k = (
                        int(nz[-1]) + 1 - int(t_start) if nz.size else 0
                    )
                    tel_digest.emit_digest(
                        "parallel.engine_sharded.run_sharded_sim",
                        dig_np[k], t0=int(t_start), ticks=ticks_k,
                        chunk=ci, shard=k,
                    )
                    if k == 0 and nz.size:
                        digest_head = int(dig_np[0][nz[-1]])
            telemetry.emit_progress(
                "parallel.engine_sharded.run_sharded_sim",
                chunk=ci, chunks_total=len(chunks),
                ticks_done=ticks_executed, digest_head=digest_head,
            )

    with telemetry.span("finish"):
        received = received[: graph.n]
        sent = sent[: graph.n]
        generated = effective_generated(schedule, horizon_ticks, churn)
        stats = NodeStats(
            generated=generated,
            received=received,
            forwarded=received.copy(),
            sent=sent,
            processed=generated + received,
            degree=graph.degree.astype(np.int64),
        )
        stats.extra["ticks_executed"] = ticks_executed
        stats.extra["ring"] = dict(dg.ring_extra)
        stats.extra.update(gather_slot_report(
            dg.gather_slots, dg.ring_extra["gather_fused_slots"],
            int(graph.indices.shape[0]),
        ))
        stats.extra["placement"] = placement
        stats.extra["exchange"] = (
            _achieved_exchange_report(
                dg.exchange_extra, exch_counters, exch_ticks,
                n_node_shards, n_padded // n_node_shards, dg.w,
                dg.capacity, hub_count=dg.hub_count,
            )
            if delta_on
            else dict(dg.exchange_extra)
        )
        if snapshot_ticks is not None:
            stats.extra["snapshots"] = assemble_snapshots(
                schedule, churn, boundaries, snap_received[:, : graph.n],
                stats.degree.sum(),
            )
    return stats


def run_sharded_flood_coverage(
    graph: Graph,
    origins,
    horizon_ticks: int,
    mesh: Mesh,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    chunk_size: int = 4096,
    block: int | None = None,
    churn=None,
    loss=None,
    ring_mode: str = "auto",
    bucket_min_rows: int = 2048,
    exchange: str = "dense",
    async_k: int = 2,
    hub_rows: int | None = None,
    aux_cache: tuple | None = None,
):
    """Flood coverage-time experiment on the device mesh — the BASELINE
    north-star metric (time-to-99% coverage at 1M nodes on a v5e-8 mesh)
    with the same contract as `engine.sync.run_flood_coverage`: one share
    per origin at t=0, returns (stats, (horizon, num_origins) per-tick node
    counts). Coverage values are identical to the single-device run for
    every mesh shape (the per-tick count psums over node shards).
    ``exchange``/``async_k`` take the same values as `run_sharded_sim`,
    including the async spellings — the coverage matrix is what the
    `async_ticks.ttc_percentiles` staleness probe bounds."""
    origins = np.asarray(origins, dtype=np.int32).reshape(-1)
    s = origins.shape[0]
    n_share_shards = mesh.shape[SHARES_AXIS]
    # One pass: pad per-shard chunks so all origins fit in a single pass.
    per_shard = -(-s // n_share_shards)
    chunk_size = bitmask.num_words(max(per_shard, chunk_size)) * bitmask.WORD_BITS
    # Record only the live slots: at most min(s, chunk_size) per shard
    # (shard k holds global slots [k*chunk, (k+1)*chunk)); counting the
    # dead padding every tick would cost up to chunk_size/s extra work.
    cov_slots = bitmask.num_words(min(s, chunk_size)) * bitmask.WORD_BITS
    sched = Schedule(graph.n, origins, np.zeros(s, dtype=np.int32))
    dg = ShardedDeviceGraph.build(
        graph, mesh, constant_delay=constant_delay, ell_delays=ell_delays,
        ring_mode=ring_mode, exchange=exchange,
        bucket_min_rows=bucket_min_rows, chunk_size=chunk_size, block=block,
        async_k=async_k, hub_rows=hub_rows, aux_cache=aux_cache,
    )
    churn_start, churn_end = (
        (dg.churn_start, dg.churn_end) if churn is None
        else _padded_churn(churn, dg.n_padded, dg.n_node_shards)
    )
    delta_on = dg.exchange_mode in ("delta", "hub")
    tel = telemetry.rings_enabled()
    runner, pass_size = dg.runner(
        chunk_size, horizon_ticks,
        loss=loss.static_cfg if loss is not None else None,
        record_coverage=True, cov_slots=cov_slots, telemetry_on=tel,
    )
    o, g_ticks = sched.padded(pass_size, horizon_ticks)
    _rss_log("runner built")
    with telemetry.span(
        "dispatch", kernel="parallel.engine_sharded.flood_runner"
    ):
        *out, _ = runner(
            dg.ell_args, dg.degree, churn_start, churn_end,
            o, g_ticks, np.int32(0), np.int32(0),
            np.zeros((0,), dtype=np.int32), *dg.exchange_operands(),
        )
    digest_head = None
    r, snt, cov = out[0], out[1], out[3]
    if tel:
        met, dstream = out[4], out[5]
        met_np = np.asarray(met)
        dig_np = np.asarray(dstream)
        for k in range(n_share_shards):
            tel_rings.emit_ring(
                "parallel.engine_sharded.run_sharded_flood_coverage",
                met_np[k], t0=0, shard=k,
            )
            nz = np.flatnonzero(dig_np[k])
            tel_digest.emit_digest(
                "parallel.engine_sharded.run_sharded_flood_coverage",
                dig_np[k], t0=0,
                ticks=int(nz[-1]) + 1 if nz.size else 0, shard=k,
            )
            if k == 0 and nz.size:
                digest_head = int(dig_np[0][nz[-1]])
    _rss_log("runner executed")
    generated = effective_generated(sched, horizon_ticks, churn)
    received = np.asarray(r, dtype=np.int64)[: graph.n]
    stats = NodeStats(
        generated=generated,
        received=received,
        forwarded=received.copy(),
        sent=np.asarray(snt, dtype=np.int64)[: graph.n],
        processed=generated + received,
        degree=graph.degree.astype(np.int64),
    )
    # Reassemble global slot order: shard k recorded its first cov_slots
    # local slots = global slots [k*chunk, k*chunk + cov_slots).
    cov = np.asarray(cov)
    parts = []
    for k in range(n_share_shards):
        live_k = min(max(s - k * chunk_size, 0), chunk_size)
        parts.append(cov[:, k * cov_slots : k * cov_slots + live_k])
    coverage = np.concatenate(parts, axis=1)
    telemetry.emit_progress(
        "parallel.engine_sharded.run_sharded_flood_coverage",
        chunk=0, chunks_total=1, ticks_done=int(coverage.shape[0]),
        coverage_pct=(
            float(coverage[-1].mean()) / graph.n * 100.0
            if coverage.size else None
        ),
        digest_head=digest_head,
    )
    stats.extra["coverage"] = coverage
    stats.extra["ring"] = dict(dg.ring_extra)
    exchange_extra = dict(dg.exchange_extra)
    if delta_on:
        ec = np.asarray(out[-1], dtype=np.uint64)  # (shards, 8)
        counters = (
            int(bitmask.combine_u64(ec[:, 0], ec[:, 1]).sum()),
            int(ec[:, 2].sum()),
            int(ec[:, 3].sum()),
        )
        exchange_extra = _achieved_exchange_report(
            exchange_extra, counters, int(ec[:, 4].sum()),
            dg.n_node_shards, dg.n_padded // dg.n_node_shards, dg.w,
            dg.capacity, hub_count=dg.hub_count,
        )
    stats.extra["exchange"] = exchange_extra
    return stats, coverage

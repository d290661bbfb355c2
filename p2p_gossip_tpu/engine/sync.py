"""Synchronous TPU tick engine — the flagship execution path.

This replaces the NS-3 event loop (`Simulator::Schedule`/`Run`) with a
synchronous graph message-passing simulation designed for XLA:

- one **tick** delivers every in-flight message at once: a gather-OR over the
  ELL adjacency reading a ring buffer of past frontiers (`ops.ell.propagate`)
  — per-edge latency as delay *lines*, not per-message events;
- the per-node seen-set (p2pnode.h:38) is a (N x S/32) uint32 bitmask;
- generation events (`GenerateAndGossipShare`, p2pnode.cc:106) are
  pre-sampled host-side and scattered into the frontier at their tick;
- counters (p2pnode.h:40-43) update via `lax.population_count` each tick;
- time advances under `lax.while_loop` with a convergence predicate (the
  chunk ends as soon as no message is in flight and no generation is
  pending); coverage-history runs record per-tick coverage into a
  preallocated buffer inside the same loop, so they exit early too.

Arbitrary total share counts are processed in fixed-size chunks — shares are
independent, counters are additive — so every XLA compilation sees static
shapes and one compiled step serves every chunk.

Semantics are tick-exact against the event engine (`engine.event`): same
graph + schedule + integer delays => identical per-node counters. That is
the "NS-3 stats parity" axis from BASELINE.json.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from p2p_gossip_tpu.models.churn import (
    effective_generated,
    to_device as churn_to_device,
    up_mask_jnp,
)
from p2p_gossip_tpu.models.generation import Schedule
from p2p_gossip_tpu.models.topology import Graph
from p2p_gossip_tpu.ops import bitmask
from p2p_gossip_tpu.ops.ell import (
    build_bucket_major,
    detect_uniform_delay,
    fused_slots,
    gather_slot_report,
    propagate,
    propagate_bucketed,
    propagate_uniform,
    scan_block,
    scanned_width,
    tuned_degree_block,
)
from p2p_gossip_tpu.staticcheck.registry import audited, register_entry
from p2p_gossip_tpu import telemetry
from p2p_gossip_tpu.telemetry import digest as tel_digest
from p2p_gossip_tpu.telemetry import rings as tel_rings
from p2p_gossip_tpu.utils import logging as p2plog
from p2p_gossip_tpu.utils.stats import NodeStats

log = p2plog.get_logger("Engine.Sync")

DEFAULT_CHUNK_SIZE = 4096

# Narrower share chunks leave the seen/hist minor dimension under the
# TPU's 128-lane tile width, which demotes the hot row gather to a slow
# path (measured ~15x worse bytes/s at 32 words vs 128). Auto-sizing
# never shrinks below this; an explicit smaller chunk_size is honored.
MIN_CHUNK_SHARES = 4096


@dataclasses.dataclass(frozen=True)
class DeviceGraph:
    """Graph + latency model staged onto the device in ELL layout.

    A bucketed staging numbers the nodes bucket-major
    (`ops.ell.build_bucket_major`): ``order[new] = old`` and its inverse
    ``inv`` are staged with it, and every per-node array here
    (``degree``, the buckets) is in the staged numbering. `to_staged` and
    `from_staged` map caller data in and results out; a full-width
    staging keeps the caller's numbering (``order`` None) and both are
    the identity."""

    n: int
    ell_idx: jnp.ndarray    # (N, dmax) int32
    ell_delay: jnp.ndarray  # (N, dmax) int32, >= 1
    ell_mask: jnp.ndarray   # (N, dmax) bool
    degree: jnp.ndarray     # (N,) int32
    ring_size: int          # D = max delay + 1
    uniform_delay: int | None = None  # set when every edge has this delay
    buckets: tuple | None = None  # degree-bucketed ELL (ops/ell.py)
    order: jnp.ndarray | None = None  # (N,) int32 staged id -> caller id
    inv: jnp.ndarray | None = None    # (N,) int32 caller id -> staged id

    @staticmethod
    def build(
        graph: Graph,
        ell_delays: np.ndarray | None = None,
        constant_delay: int = 1,
        *,
        bucketed: bool | None = None,
    ) -> "DeviceGraph":
        """``bucketed=None`` (default) enables degree-bucketed ELL staging
        for large graphs — identical results, the gathered slots within 8%
        of the edges where one full-width ELL pads every row to the max
        degree, in the bucket-major numbering that reassembles each tick's
        arrivals by concatenation (see `ops.ell.build_bucket_major`).
        """
        if bucketed is None:
            bucketed = graph.n >= 4096
        placeholder = np.ones((1, 1), dtype=np.int32)
        buckets = order = None
        if ell_delays is None and bucketed:
            # Uniform delay + bucketed staging: bucket ELLs come straight
            # from CSR (ops.ell._host_degree_buckets) — the (N, dmax)
            # global ELL and its O(nnz) coordinate transients are never
            # built (~25 GB of host memory at 1M nodes / 500M edges).
            uniform = constant_delay
            dmax_delay = constant_delay
            buckets, order = build_bucket_major(graph, None)
            ell_idx = ell_delays = placeholder
            ell_mask = placeholder.astype(bool)
        else:
            ell_idx, ell_mask = graph.ell()
            if ell_delays is None:
                ell_delays = np.full(
                    ell_idx.shape, constant_delay, dtype=np.int32
                )
            dmax_delay = int(ell_delays.max()) if ell_delays.size else 1
            uniform = detect_uniform_delay(ell_delays, ell_mask)
            if bucketed:
                buckets, order = build_bucket_major(
                    graph,
                    None if uniform is not None else ell_delays,
                    ell=(ell_idx, ell_mask),
                )
                # The bucketed path never reads the full-width arrays.
                ell_idx = ell_delays = placeholder
                ell_mask = placeholder.astype(bool)
            elif uniform is not None:
                # The fast path never reads per-edge delays: stage a
                # placeholder instead of an (N, dmax) array of dead HBM.
                ell_delays = placeholder
        degree = graph.degree
        inv = None
        if order is not None:
            degree = degree[order]
            inv = np.empty(graph.n, dtype=np.int32)
            inv[order] = np.arange(graph.n, dtype=np.int32)
        return DeviceGraph(
            n=graph.n,
            ell_idx=jnp.asarray(ell_idx, dtype=jnp.int32),
            ell_delay=jnp.asarray(ell_delays, dtype=jnp.int32),
            ell_mask=jnp.asarray(ell_mask),
            degree=jnp.asarray(degree, dtype=jnp.int32),
            ring_size=dmax_delay + 1,
            uniform_delay=uniform,
            buckets=buckets,
            order=None if order is None else jnp.asarray(order, jnp.int32),
            inv=None if inv is None else jnp.asarray(inv),
        )

    def to_staged(self, x, node_axis: int | None = None):
        """Caller data into the staged numbering, on the device: node ids
        (``node_axis=None``) become their staged ids; an array with one
        entry per node along ``node_axis`` is reordered into staged
        order. The identity for a staging in the caller's numbering."""
        if self.order is None:
            return x
        if node_axis is None:
            return jnp.take(self.inv, x, axis=0)
        return jnp.take(x, self.order, axis=node_axis)

    def from_staged(self, x, node_axis: int = -1):
        """Per-node results along ``node_axis`` back into the caller's
        numbering, on the device (the inverse of `to_staged`)."""
        if self.order is None:
            return x
        return jnp.take(x, self.inv, axis=node_axis)

    @functools.cached_property
    def host_degree(self) -> np.ndarray:
        """(N,) degree in the caller's numbering on the host, read off
        the device (`from_staged`) once per staging: every run's
        ``finish`` reports it."""
        return np.asarray(self.from_staged(self.degree))

    def _gather_shapes(self) -> list:
        if self.buckets is not None:
            return [b[1].shape for b in self.buckets]
        return [self.ell_idx.shape]

    def gather_slots(self, block: int) -> int:
        """Neighbour slots the gather-OR fetches a tick at degree block
        ``block``: rows times the scanned width (`ops.ell.scanned_width`),
        summed over the staged buckets (or the one full-width ELL). Every
        slot is a frontier row read, masked off where it is padding. Host
        arithmetic on the staged shapes."""
        return sum(
            int(rows) * scanned_width(int(cap), block)
            for rows, cap in self._gather_shapes()
        )

    def gather_fused_slots(self, block: int, w: int) -> int:
        """Those of `gather_slots` whose fetched rows stay on chip until
        the OR at ``w`` words (`ops.ell.fused_slots`)."""
        return sum(
            fused_slots(int(rows), int(cap), block, w)
            for rows, cap in self._gather_shapes()
        )


def flood_resident_hbm_bytes(
    degree: np.ndarray,
    w: int,
    block: int,
    ring_size: int = 2,
    uniform_delay: bool = True,
) -> int:
    """Modeled peak RESIDENT device memory of one flood chunk at W words —
    the fit check. Computable from the host-side degree array BEFORE
    staging, so callers can size the share chunk without building a
    DeviceGraph first.

    Terms (all bytes):
      * ELL staging — modeled as each node padded to ceil(d/block)*block
        entries of int32 index + bool mask (+ int32 delay when per-edge),
        more than the degree buckets stage on the BASELINE overlays;
        resident for the whole run, independent of W.
      * blocked gather — one scan step materializes (block, rows, W)
        uint32 plus the (rows, W) OR accumulator.
      * persistent state — the (ring, N, W) frontier-history ring and the
        (N, W) seen bitmask.
      * scratch — arrivals/newly/gen frontier copies alive across a tick
        (~3 more (N, W) buffers).

    Validation point: at the 1M-node ER north star (mean degree ~1000,
    block 8, W=128) this models ~12.6 GB — the configuration that crashed
    the 16 GB v5e worker on 2026-07-31 (4096 shares in one pass: staging
    succeeded, then "TPU worker process crashed" mid-flood); at W=64 it
    models ~8.8 GB. A model, not a measurement: XLA workspace, transfer
    staging, and fusion choices move the true number by O(GB)."""
    degree = np.asarray(degree, dtype=np.int64)
    n = int(degree.shape[0])
    entries = int((-(-degree // block) * block).sum())
    row = w * 4
    ell = entries * 5 + (0 if uniform_delay else entries * 4)
    gather = n * (block + 1) * row
    state = (ring_size + 1) * n * row
    scratch = 3 * n * row
    return ell + gather + state + scratch


def auto_chunk_shares(
    degree: np.ndarray,
    shares: int,
    block: int,
    budget_bytes: float,
    ring_size: int = 2,
    uniform_delay: bool = True,
    min_chunk: int = 512,
) -> int | None:
    """Bitmask pad width (in shares) whose modeled resident footprint
    (``flood_resident_hbm_bytes``) fits ``budget_bytes`` — or ``None``
    when the engine's default lane pad (``max(shares, MIN_CHUNK_SHARES)``,
    what run_flood_coverage would stage anyway) already fits, or when
    budgeting is disabled (``budget_bytes`` falsy). None tells the caller
    to leave ``chunk_size`` at its default so an enabled-but-satisfied
    budget changes nothing observable.

    When the default pad does NOT fit, halves from it as little as
    possible: narrow chunks underfill the 128-lane tile (the
    MIN_CHUNK_SHARES rationale — measured ~15x worse gather bytes/s at 32
    words vs 128), so each halving trades bandwidth efficiency for
    fitting at all. Floors at ``min_chunk`` — below that the model's
    fixed terms (the ELL) dominate and halving further cannot help. The
    returned value may exceed ``shares`` (e.g. 64 shares at the 1M shape
    returns 2048): it is the PAD target, so the caller still runs one
    64-origin pass, just at the widest W that fits."""
    if not budget_bytes:
        return None
    default_pad = max(32, shares, MIN_CHUNK_SHARES)
    chunk = default_pad
    while chunk > min_chunk:
        w = bitmask.num_words(chunk)
        if flood_resident_hbm_bytes(
            degree, w, block, ring_size, uniform_delay
        ) <= budget_bytes:
            break
        chunk = max(min_chunk, chunk // 2)
    if chunk < default_pad:
        floor_model = flood_resident_hbm_bytes(
            degree, bitmask.num_words(chunk), block, ring_size, uniform_delay
        )
        if floor_model > budget_bytes:
            # The min_chunk floor is NOT a fit: the model's fixed terms
            # (the staged ELL) alone exceed the budget, so the returned
            # pad is merely the least-bad staging. Callers log staging
            # plans from this value — without an explicit signal the
            # plan reads as budget-approved (round-4 advisor finding).
            import warnings

            warnings.warn(
                f"auto_chunk_shares: budget {budget_bytes / 1e9:.1f} GB "
                f"cannot be met — pad {chunk} still models "
                f"{floor_model / 1e9:.1f} GB (fixed ELL terms dominate); "
                "returning the floor anyway",
                RuntimeWarning,
                stacklevel=2,
            )
    return None if chunk == default_pad else chunk


def _resolve_block(dg: DeviceGraph, block: int | None) -> int:
    """``block=None`` means auto: the swept TPU optimum
    (`ops.ell.tuned_degree_block`), which each gather narrows to its own
    cap. Results are bitwise identical for any block — this only picks the
    fastest gather shape."""
    if block is not None:
        return block
    return tuned_degree_block(dg.ell_idx.devices())


def _canonical_delays(dg: DeviceGraph) -> np.ndarray:
    """Per-edge delays in CSR order, independent of how they were staged —
    bucketed and full-width stagings of the same logical delays fingerprint
    identically (resume must survive a staging-layout change)."""
    if dg.uniform_delay is not None:
        return np.asarray([dg.uniform_delay], dtype=np.int64)
    if dg.buckets is None:
        mask = np.asarray(dg.ell_mask)
        return np.asarray(dg.ell_delay)[mask]
    ids = (
        np.arange(dg.n, dtype=np.int64)
        if dg.order is None
        else np.asarray(dg.order, dtype=np.int64)
    )
    src, dst, delay = [], [], []
    for rows, b_idx, b_mask, b_delay in dg.buckets:
        mask_np = np.asarray(b_mask)
        row_ids = ids[np.asarray(rows)]
        src.append(np.broadcast_to(row_ids[:, None], mask_np.shape)[mask_np])
        dst.append(ids[np.asarray(b_idx)[mask_np]])
        delay.append(np.asarray(b_delay)[mask_np])
    # CSR order: by node, then by neighbour id (CSR rows are sorted).
    src, dst = np.concatenate(src), np.concatenate(dst)
    return np.concatenate(delay)[np.lexsort((dst, src))]


# Pytree registration: arrays (including the nested bucket tuples and the
# staged permutation) are leaves; (n, ring_size, uniform_delay) ride along
# as static aux data — so a DeviceGraph passes straight through
# jit/shard_map and path selection on uniform_delay/buckets/order (None or
# not) stays trace-time.
jax.tree_util.register_pytree_node(
    DeviceGraph,
    lambda dg: (
        (dg.ell_idx, dg.ell_delay, dg.ell_mask, dg.degree, dg.buckets,
         dg.order, dg.inv),
        (dg.n, dg.ring_size, dg.uniform_delay),
    ),
    lambda aux, ch: DeviceGraph(
        n=aux[0], ell_idx=ch[0], ell_delay=ch[1], ell_mask=ch[2],
        degree=ch[3], ring_size=aux[1], uniform_delay=aux[2], buckets=ch[4],
        order=ch[5], inv=ch[6],
    ),
)


def filter_snapshot_boundaries(snapshot_ticks, horizon_ticks) -> list[int]:
    """Boundaries past the horizon never fire on the event engine (its
    final flush is at horizon_ticks) — drop them everywhere for parity."""
    if not snapshot_ticks:
        return []
    return sorted(b for b in snapshot_ticks if b <= horizon_ticks)


def assemble_snapshots(schedule, churn, boundaries, snap_received, connections):
    """The periodic-stats entries (PrintPeriodicStats, p2pnetwork.cc:231)
    from per-boundary received totals — the one snapshot-dict convention
    shared by the sync and sharded engines (the parity tests compare these
    against the event engine's)."""
    snapshots = []
    for i, b in enumerate(boundaries):
        gen_b = int(effective_generated(schedule, b, churn).sum())
        snapshots.append(
            {
                "tick": int(b),
                "generated": gen_b,
                "processed": gen_b + int(snap_received[i].sum()),
                "connections": int(connections),
            }
        )
    return snapshots


def apply_tick_updates(
    seen, arrivals, gen_bits, gen_cnt, received, sent, degree,
):
    """The shared counter semantics of one tick (reference: p2pnode.cc
    ReceiveShare/GenerateAndGossipShare): dedup against ``seen``, count
    first-time receives, and charge one send per peer per processed share.
    Returns (seen, newly_out, received, sent) where ``newly_out`` is the
    frontier this node contributes for the next delay-line slot. Used by
    both the single-device and the sharded engines — the bitwise-parity
    contract between them lives here.

    Deliberately plain jnp: a fused Pallas formulation of this stage lost
    0.50x to the XLA graph on a v5e chip (round-4 kernel bake-off, 100K
    rows x 256 words: 2.97 ms Pallas vs 1.48 ms XLA) — XLA already fuses
    this chain optimally."""
    newly = arrivals & ~seen
    newly_cnt = bitmask.popcount_rows(newly)
    seen = seen | arrivals | gen_bits
    newly_out = newly | gen_bits
    received = received + newly_cnt
    sent = sent + (newly_cnt + gen_cnt) * degree
    return seen, newly_out, received, sent


def _tick_body(
    dg: DeviceGraph, block: int, state, origins, slots, gen_ticks, churn=None,
    loss=None, connect_tick: int = 0, loss_seed=None, telemetry: bool = False,
):
    """One synchronous tick. state = (t, seen, hist, received, sent) ->
    state'. Coverage-recording callers derive the tick's coverage delta
    from the hist slot this tick writes (it IS the newly_out frontier).
    Everything per node — the state, ``origins``, ``churn`` — is in the
    staged numbering (`DeviceGraph.to_staged`).

    ``telemetry`` (static) additionally returns the tick's metric-ring
    row (telemetry/rings.py flood_row) as ``(state', row)`` — callers
    must gate on the same `tel_rings.active` answer. When False the
    return shape and the traced program are exactly the pre-telemetry
    ones (the zero-cost contract staticcheck enforces); the only cost of
    telemetry-on is the row's integer reductions plus, under a loss
    model, a second loss-free gather that prices ``loss_dropped``.

    ``churn`` is an optional ``(down_start, down_end)`` pair of (N, K)
    interval arrays (models/churn.py): a down node's arrivals are lost
    (never enter ``seen``) and its generations are skipped, which zeroes
    its forward/send contribution for the tick automatically.

    ``loss`` is an optional static (threshold, seed) pair — the per-link
    erasure model (models/linkloss.py), applied edge-wise inside the
    gather before the OR-reduce. ``loss_seed`` (optional traced uint32
    scalar) overrides the static seed — the campaign engine vmaps it so
    every replica draws an independent erasure stream.
    """
    t, seen, hist, received, sent = state
    n, w = seen.shape

    def _gather(loss_cfg, lseed):
        if dg.buckets is not None:
            return propagate_bucketed(
                hist, t, dg.buckets, node_ids=dg.order,
                ring_size=dg.ring_size, uniform_delay=dg.uniform_delay,
                block=block, loss=loss_cfg, loss_seed=lseed,
            )
        full_block = scan_block(dg.ell_idx.shape[1], block)
        if dg.uniform_delay is not None:
            return propagate_uniform(
                hist, t, dg.ell_idx, dg.ell_mask,
                ring_size=dg.ring_size, uniform_delay=dg.uniform_delay,
                block=full_block, loss=loss_cfg, loss_seed=lseed,
            )
        return propagate(
            hist, t, dg.ell_idx, dg.ell_delay, dg.ell_mask,
            ring_size=dg.ring_size, block=full_block, loss=loss_cfg,
            loss_seed=lseed,
        )

    with jax.named_scope("gossip.gather"):
        arrivals = _gather(loss, loss_seed)
    tel = tel_rings.active(telemetry)
    if tel:
        received_in = received
        arrivals_raw = arrivals  # post-loss, pre-churn — the wire view
        arrivals_nl = _gather(None, None) if loss is not None else None
    with jax.named_scope("gossip.update"):
        gen_active = gen_ticks == t
        if churn is not None:
            up = up_mask_jnp(churn[0], churn[1], t)
            arrivals = jnp.where(up[:, None], arrivals, jnp.uint32(0))
            gen_active = gen_active & up[origins]
        gen_bits = bitmask.slot_scatter(n, w, origins, slots, gen_active)
        gen_cnt = (
            jnp.zeros((n,), dtype=jnp.int32)
            .at[origins]
            .add(gen_active.astype(jnp.int32))
        )
        if connect_tick:
            # Socket warm-up window (p2pnetwork.cc:93-96): a whole tick is
            # either pre- or post-connect. Pre-connect generations enter
            # the origin's seen-set (generated++ happens host-side) but
            # are never broadcast — no frontier contribution, no `sent`
            # charge (GossipShareToPeers skips missing sockets,
            # p2pnode.cc:131-135).
            pre = t < connect_tick
            live_bits = jnp.where(pre, jnp.uint32(0), gen_bits)
            live_cnt = jnp.where(pre, 0, gen_cnt)
            seen, newly_out, received, sent = apply_tick_updates(
                seen, arrivals, live_bits, live_cnt, received, sent,
                dg.degree,
            )
            seen = seen | jnp.where(pre, gen_bits, jnp.uint32(0))
        else:
            seen, newly_out, received, sent = apply_tick_updates(
                seen, arrivals, gen_bits, gen_cnt, received, sent, dg.degree,
            )
    with jax.named_scope("gossip.delay_line"):
        hist = hist.at[jnp.mod(t, dg.ring_size)].set(newly_out)
    if tel:
        with jax.named_scope("gossip.telemetry"):
            met = tel_rings.flood_row(
                arrivals_raw, newly_out, received - received_in, dg.degree,
                arrivals_lossless=arrivals_nl,
            )
    with jax.named_scope("gossip.update"):
        t_next = t + 1
    if tel:
        return (t_next, seen, hist, received, sent), met
    return (t_next, seen, hist, received, sent)


def _stage_inputs(dg: DeviceGraph, origins, churn, node_axis: int = 0):
    """A chunk program's origins and churn intervals (nodes along
    ``node_axis``) in the staged numbering, inside the program."""
    if churn is not None:
        churn = tuple(dg.to_staged(c, node_axis=node_axis) for c in churn)
    return dg.to_staged(origins), churn


@audited("engine.sync._run_chunk_while", spec=lambda: _audit_spec_chunk_while())
@functools.partial(
    jax.jit,
    static_argnames=(
        "chunk_size", "horizon", "block", "loss", "connect_tick", "telemetry",
    ),
)
def _run_chunk_while(
    dg: DeviceGraph,
    origins: jnp.ndarray,    # (S,) int32
    gen_ticks: jnp.ndarray,  # (S,) int32 (>= horizon entries never fire)
    t_start: jnp.ndarray,    # scalar int32
    last_gen: jnp.ndarray,   # scalar int32
    churn=None,              # optional ((N, K), (N, K)) downtime intervals
    snap_ticks=None,         # optional (K,) int32 periodic-stats boundaries
    *,
    chunk_size: int,
    horizon: int,
    block: int,
    loss: tuple | None = None,
    connect_tick: int = 0,
    telemetry: bool = False,
):
    """Run one share chunk to quiescence (or the horizon) under while_loop.

    With ``snap_ticks``, also returns (K, N) received counts captured the
    moment the tick counter reaches each boundary — i.e. totals over all
    ticks strictly before it, matching the event engine's snapshot timing
    (PrintPeriodicStats, p2pnetwork.cc:231).

    ``telemetry`` (static) carries a (horizon, NUM_METRICS) metric ring
    plus a (horizon,) digest ring (telemetry/digest.py — one uint32 state
    digest per tick, the flight recorder) through the loop and returns
    them as extra trailing outputs, ring first — rows [t_start, exit)
    hold per-tick values, harvested by the host once per chunk
    (telemetry/rings.py). Off by default; the disabled jaxpr is
    byte-identical to the pre-telemetry program.

    ``origins`` and ``churn`` are in the caller's numbering, and so are
    the counters and snapshots returned; ``seen`` (first output) stays in
    the staged numbering (`DeviceGraph`).
    """
    n, w = dg.n, bitmask.num_words(chunk_size)
    k = 0 if snap_ticks is None else snap_ticks.shape[0]
    tel = tel_rings.active(telemetry)
    dig = tel_digest.active(telemetry)
    with jax.named_scope("gossip.chunk_io"):
        origins, churn = _stage_inputs(dg, origins, churn)
        slots = jnp.arange(chunk_size, dtype=jnp.int32)
        state = (
            t_start,
            jnp.zeros((n, w), dtype=jnp.uint32),
            jnp.zeros((dg.ring_size, n, w), dtype=jnp.uint32),
            jnp.zeros((n,), dtype=jnp.int32),
            jnp.zeros((n,), dtype=jnp.int32),
            jnp.zeros((k, n), dtype=jnp.int32),
        )
        if tel:
            state = state + (tel_rings.init(horizon),)
        if dig:
            state = state + (tel_digest.init(horizon),)
    dig_i = 6 + (1 if tel else 0)

    @jax.named_scope("gossip.quiescence")
    def cond(state):
        t, hist = state[0], state[2]
        in_flight = jnp.any(hist != 0)
        pending = t <= last_gen
        return (t < horizon) & (in_flight | pending)

    def body(state):
        t, seen, hist, received, sent, snaps = state[:6]
        if k:
            with jax.named_scope("gossip.update"):
                snaps = jnp.where(
                    (snap_ticks == t)[:, None], received[None, :], snaps
                )
        if tel:
            (t_n, seen, hist, received, sent), met_row = _tick_body(
                dg, block, (t, seen, hist, received, sent), origins, slots,
                gen_ticks, churn, loss, connect_tick, telemetry=True,
            )
        else:
            t_n, seen, hist, received, sent = _tick_body(
                dg, block, (t, seen, hist, received, sent), origins, slots,
                gen_ticks, churn, loss, connect_tick,
            )
        out = (t_n, seen, hist, received, sent, snaps)
        with jax.named_scope("gossip.telemetry"):
            if tel:
                out = out + (tel_rings.write(state[6], t, met_row),)
            if dig:
                # Digest of the POST-tick state at index t: seen words
                # plus the received/sent counters (flood carries a plain
                # int32 sent — low word only, matching the host twins).
                out = out + (tel_digest.write(
                    state[dig_i], t,
                    tel_digest.tick_digest(
                        seen, received, sent, node_ids=dg.order
                    ),
                ),)
        return out

    with jax.named_scope("gossip.loop"):
        out = jax.lax.while_loop(cond, body, state)
    with jax.named_scope("gossip.chunk_io"):
        t, seen, hist, received, sent, snaps = out[:6]
        if k:
            # Boundaries at/after quiescence see the (unchanging) final
            # counts.
            snaps = jnp.where(
                (snap_ticks >= t)[:, None], received[None, :], snaps
            )
        # t - t_start = ticks actually executed (quiescence can stop well
        # before the horizon): stats.extra["ticks_executed"], which the
        # benchmark's byte count multiplies per-tick bytes by.
        ret = (
            seen, dg.from_staged(received), dg.from_staged(sent),
            dg.from_staged(snaps), t - t_start,
        )
    if tel:
        ret = ret + (out[6],)
    if dig:
        ret = ret + (out[dig_i],)
    return ret


@audited(
    "engine.sync._run_chunk_coverage",
    spec=lambda: _audit_spec_chunk_coverage(),
)
@functools.partial(
    jax.jit,
    static_argnames=(
        "chunk_size", "horizon", "block", "use_pallas", "coverage_slots",
        "loss", "telemetry",
    ),
)
def _run_chunk_coverage(
    dg: DeviceGraph,
    origins: jnp.ndarray,
    gen_ticks: jnp.ndarray,
    churn=None,
    *,
    chunk_size: int,
    horizon: int,
    block: int,
    use_pallas: bool = False,
    coverage_slots: int | None = None,
    loss: tuple | None = None,
    telemetry: bool = False,
):
    """Coverage-recording run from t=0 — drives the time-to-coverage
    metrics. Returns per-tick coverage (horizon, S) but exits the tick loop
    at quiescence (coverage is constant once nothing is in flight; the
    remaining rows are filled with the final value), so a generous horizon
    costs nothing extra.

    Coverage is accumulated INCREMENTALLY: each (node, share) bit enters
    the ``newly_out`` frontier at most once (dedup makes ticks disjoint),
    so per-tick coverage is a running sum of the frontier's per-slot
    counts — reading the just-written (N, cov_w) hist slot instead of
    re-reducing the full seen bitmask.
    ``use_pallas`` selects the one-pass coverage kernel for the delta
    reduction on TPU. ``coverage_slots`` limits the recorded coverage to
    the first S slots (the live shares) — the chunk itself may be
    lane-padded far wider (MIN_CHUNK_SHARES). ``telemetry`` and the
    numbering of inputs and outputs as in `_run_chunk_while` (trailing
    metric-ring + digest-ring outputs); coverage counts nodes, so no
    numbering moves it."""
    n, w = dg.n, bitmask.num_words(chunk_size)
    tel = tel_rings.active(telemetry)
    dig = tel_digest.active(telemetry)
    cov_slots = chunk_size if coverage_slots is None else coverage_slots
    cov_w = bitmask.num_words(cov_slots)

    def cov_delta_of(newly_out):
        live = newly_out[:, :cov_w]
        if use_pallas:
            from p2p_gossip_tpu.ops.pallas_kernels import coverage_per_slot_pallas

            return coverage_per_slot_pallas(live, cov_slots)
        return bitmask.coverage_per_slot(live, cov_slots)

    with jax.named_scope("gossip.chunk_io"):
        origins, churn = _stage_inputs(dg, origins, churn)
        slots = jnp.arange(chunk_size, dtype=jnp.int32)
        last_gen = jnp.max(jnp.where(gen_ticks < horizon, gen_ticks, 0))
        state = (
            jnp.zeros((), dtype=jnp.int32),
            jnp.zeros((n, w), dtype=jnp.uint32),
            jnp.zeros((dg.ring_size, n, w), dtype=jnp.uint32),
            jnp.zeros((n,), dtype=jnp.int32),
            jnp.zeros((n,), dtype=jnp.int32),
            jnp.zeros((cov_slots,), dtype=jnp.int32),   # running coverage
            jnp.zeros((horizon, cov_slots), dtype=jnp.int32),
        )
        if tel:
            state = state + (tel_rings.init(horizon),)
        if dig:
            state = state + (tel_digest.init(horizon),)
    dig_i = 7 + (1 if tel else 0)

    @jax.named_scope("gossip.quiescence")
    def cond(full_state):
        t, hist = full_state[0], full_state[2]
        return (t < horizon) & (jnp.any(hist != 0) | (t <= last_gen))

    def step(full_state):
        t, seen, hist, received, sent, cov_run, cov_hist = full_state[:7]
        if tel:
            new_state, met_row = _tick_body(
                dg, block, (t, seen, hist, received, sent), origins, slots,
                gen_ticks, churn, loss, telemetry=True,
            )
        else:
            new_state = _tick_body(
                dg, block, (t, seen, hist, received, sent), origins, slots,
                gen_ticks, churn, loss,
            )
        with jax.named_scope("gossip.update"):
            # hist slot (t mod D) was written by this tick: it IS the
            # newly_out frontier.
            cov_delta = cov_delta_of(new_state[2][jnp.mod(t, dg.ring_size)])
            cov_run = cov_run + cov_delta
            cov_hist = jax.lax.dynamic_update_slice(
                cov_hist, cov_run[None], (t, 0)
            )
        out = (*new_state, cov_run, cov_hist)
        with jax.named_scope("gossip.telemetry"):
            if tel:
                out = out + (tel_rings.write(full_state[7], t, met_row),)
            if dig:
                out = out + (tel_digest.write(
                    full_state[dig_i], t,
                    tel_digest.tick_digest(
                        new_state[1], new_state[3], new_state[4],
                        node_ids=dg.order,
                    ),
                ),)
        return out

    with jax.named_scope("gossip.loop"):
        out = jax.lax.while_loop(cond, step, state)
    with jax.named_scope("gossip.chunk_io"):
        t, seen, _, received, sent, cov_run, cov_hist = out[:7]
        # Rows past quiescence hold the (monotone, now constant) final
        # coverage.
        ticks = jnp.arange(horizon, dtype=jnp.int32)[:, None]
        coverage = jnp.where(ticks >= t, cov_run[None, :], cov_hist)
        ret = (seen, dg.from_staged(received), dg.from_staged(sent), coverage)
    if tel:
        ret = ret + (out[7],)
    if dig:
        ret = ret + (out[dig_i],)
    return ret


def run_sync_sim(
    graph: Graph,
    schedule: Schedule,
    horizon_ticks: int,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    chunk_size: int = DEFAULT_CHUNK_SIZE,
    block: int | None = None,
    device_graph: DeviceGraph | None = None,
    checkpoint_path: str | None = None,
    checkpoint_every: int = 1,
    stop_after_chunks: int | None = None,
    churn=None,
    snapshot_ticks: list[int] | None = None,
    loss=None,
    connect_tick: int = 0,
) -> NodeStats:
    """Run the full simulation on the synchronous engine.

    Drop-in counterpart of `engine.event.run_event_sim`: same inputs,
    identical per-node counters (the parity tests assert exactly this).

    With ``checkpoint_path``, accumulated counters are written atomically
    every ``checkpoint_every`` chunks, and a run restarted with the same
    inputs resumes after the last completed chunk (a checkpoint from any
    *different* configuration is detected by fingerprint and ignored —
    see utils/checkpoint.py). ``stop_after_chunks`` ends the run early
    after that many chunks this call (simulating interruption; used by
    tests and incremental drivers).

    ``churn`` is an optional `models.churn.ChurnModel`: nodes lose arrivals
    and skip generations while inside a downtime interval (same semantics,
    and identical counters, as the event engines run with the same model).

    ``snapshot_ticks`` requests periodic-stats snapshots
    (PrintPeriodicStats, p2pnetwork.cc:231): ``stats.extra["snapshots"]``
    gets one entry per boundary with the totals over all ticks strictly
    before it — identical values to the event engines' snapshots.

    ``loss`` is an optional `models.linkloss.LinkLossModel`: messages
    crossing a directed link during one of its erasure ticks are dropped
    in flight (sender still counts the send). Deterministic — identical
    counters on the event engines under the same model.

    ``connect_tick`` models the reference's socket warm-up window (see
    run_event_sim): pre-connect generations are counted and marked seen
    at their origin but never broadcast.
    """
    dg = device_graph or DeviceGraph.build(graph, ell_delays, constant_delay)
    block = _resolve_block(dg, block)
    loss_cfg = loss.static_cfg if loss is not None else None
    churn_dev = churn_to_device(churn)
    chunk_size = min(chunk_size, max(MIN_CHUNK_SHARES, schedule.num_shares))
    # Round chunk size up to whole words.
    chunk_size = bitmask.num_words(chunk_size) * bitmask.WORD_BITS
    boundaries = filter_snapshot_boundaries(snapshot_ticks, horizon_ticks)
    snap_ticks_dev = (
        jnp.asarray(boundaries, dtype=jnp.int32) if boundaries else None
    )
    snap_received = np.zeros((len(boundaries), graph.n), dtype=np.int64)

    log.info(
        f"starting sync simulation: {graph.n} nodes, {graph.num_edges} links, "
        f"{schedule.num_shares} shares in chunks of {chunk_size}, horizon "
        f"{horizon_ticks} ticks, ring {dg.ring_size}"
        + (f", uniform delay {dg.uniform_delay}" if dg.uniform_delay else "")
    )
    received = np.zeros(graph.n, dtype=np.int64)
    sent = np.zeros(graph.n, dtype=np.int64)
    ticks_executed = 0

    checkpointer = None
    if checkpoint_path is not None:
        from p2p_gossip_tpu.utils.checkpoint import ChunkCheckpointer, fingerprint

        # Fingerprint the *effective* delays (dg may have been passed in
        # directly, overriding ell_delays/constant_delay) in canonical CSR
        # order, so the fingerprint doesn't depend on staging layout.
        ckpt_fp = fingerprint(
            "sync_sim", graph.n, graph.edges(), schedule.origins,
            schedule.gen_ticks, horizon_ticks, chunk_size,
            _canonical_delays(dg), dg.uniform_delay, dg.ring_size,
            churn.down_start if churn is not None else None,
            churn.down_end if churn is not None else None,
            # Loss model (appended only when on, preserving pre-existing
            # fingerprints of loss-free runs).
            *([np.asarray(loss_cfg, dtype=np.int64)] if loss_cfg else []),
            # Appended only when snapshots are on, so checkpoints from
            # snapshot-free runs keep their pre-existing fingerprints.
            *([np.asarray(boundaries, dtype=np.int64)] if boundaries else []),
            # Warm-up window changes the results; appended only when on.
            *(["connect", connect_tick] if connect_tick else []),
        )
        checkpointer = ChunkCheckpointer(
            checkpoint_path, ckpt_fp,
            {"received": received, "sent": sent,
             "snap_received": snap_received},
            checkpoint_every,
        )

    from p2p_gossip_tpu.utils.checkpoint import checkpointed_chunks

    tel = telemetry.rings_enabled()
    chunks = schedule.chunk(chunk_size)
    for ci, chunk in checkpointed_chunks(chunks, checkpointer, stop_after_chunks):
        live = chunk.gen_ticks < horizon_ticks
        if live.any():
            with telemetry.span("prepare", chunk=ci):
                origins, gen_ticks = chunk.padded(chunk_size, horizon_ticks)
                first_t = int(chunk.gen_ticks[live].min())
                last_t = int(chunk.gen_ticks[live].max())
                origins = jnp.asarray(origins)
                gen_ticks = jnp.asarray(gen_ticks)
                t_start = jnp.asarray(first_t, dtype=jnp.int32)
                last_gen = jnp.asarray(last_t, dtype=jnp.int32)
            if log.enabled(p2plog.LOG_DEBUG):
                log.debug(
                    f"chunk {ci}: {int(live.sum())} live shares, gen ticks "
                    f"[{first_t}, {last_t}]"
                )
            with telemetry.span(
                "dispatch", kernel="engine.sync._run_chunk_while", chunk=ci
            ):
                out = _run_chunk_while(
                    dg, origins, gen_ticks, t_start, last_gen, churn_dev,
                    snap_ticks_dev,
                    chunk_size=chunk_size, horizon=horizon_ticks, block=block,
                    loss=loss_cfg, connect_tick=connect_tick, telemetry=tel,
                )
            if tel:
                _, r, s, snaps, t_run, met, dstream = out
            else:
                _, r, s, snaps, t_run = out
            with telemetry.span("d2h", chunk=ci):
                received += np.asarray(r, dtype=np.int64)
                sent += np.asarray(s, dtype=np.int64)
                ticks_executed += int(t_run)
                if boundaries:
                    snap_received += np.asarray(snaps, dtype=np.int64)
            digest_head = None
            if tel:
                tel_rings.emit_ring(
                    "engine.sync.run_sync_sim", np.asarray(met),
                    t0=first_t, ticks=int(t_run), chunk=ci,
                )
                dvals = np.asarray(dstream)
                tel_digest.emit_digest(
                    "engine.sync.run_sync_sim", dvals,
                    t0=first_t, ticks=int(t_run), chunk=ci,
                )
                if int(t_run) > 0:
                    digest_head = int(dvals[first_t + int(t_run) - 1])
            telemetry.emit_progress(
                "engine.sync.run_sync_sim", chunk=ci,
                chunks_total=len(chunks), ticks_done=ticks_executed,
                digest_head=digest_head,
            )

    with telemetry.span("finish"):
        generated = effective_generated(schedule, horizon_ticks, churn)
        degree = np.asarray(dg.host_degree, dtype=np.int64)
        # Generation itself also broadcasts (GossipShareToPeers,
        # p2pnode.cc:123): already folded into `sent` on-device via gen_cnt.
        stats = NodeStats(
            generated=generated,
            received=received,
            forwarded=received.copy(),
            sent=sent,
            processed=generated + received,
            degree=degree,
        )
        stats.extra["ticks_executed"] = ticks_executed
        stats.extra.update(gather_slot_report(
            dg.gather_slots(block),
            dg.gather_fused_slots(block, bitmask.num_words(chunk_size)),
            int(graph.indices.shape[0]),
        ))
        if snapshot_ticks is not None:
            # Present (possibly empty) whenever snapshots were requested,
            # like the event engines.
            stats.extra["snapshots"] = assemble_snapshots(
                schedule, churn, boundaries, snap_received, degree.sum()
            )
    return stats


def pallas_coverage_selected(dg: DeviceGraph, chunk_size: int) -> bool:
    """Whether the flood-coverage run counts per-tick coverage with the
    Pallas kernel (ops/pallas_kernels.py) instead of XLA: only where the
    graph lives on a TPU, N is under the kernel's validated row bound
    (PALLAS_COVERAGE_MAX_ROWS), and the bitmask is at least one full
    128-lane tile wide — every on-chip validation ran at W >= 128, and a
    sub-lane W is a Mosaic shape never compiled on hardware."""
    from p2p_gossip_tpu.ops.pallas_kernels import coverage_rows_ok

    on_tpu = any(d.platform == "tpu" for d in dg.ell_idx.devices())
    w_words = bitmask.num_words(chunk_size)
    use_pallas = on_tpu and coverage_rows_ok(dg.n) and w_words >= 128
    if on_tpu and not use_pallas:
        reason = (
            f"N={dg.n} exceeds PALLAS_COVERAGE_MAX_ROWS, the measured "
            "100K crossover"
            if not coverage_rows_ok(dg.n)
            else f"W={w_words} words under the 128-lane tile, a shape "
            "never validated on hardware"
        )
        log.info(f"coverage: Pallas kernel on the XLA path ({reason})")
    return use_pallas


def run_flood_coverage(
    graph: Graph,
    origins: np.ndarray | list[int],
    horizon_ticks: int,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    block: int | None = None,
    device_graph: DeviceGraph | None = None,
    churn=None,
    loss=None,
    chunk_size: int | None = None,
):
    """Flood coverage-time experiment: one share per origin, all at t=0.

    Returns (stats, coverage) where coverage is (horizon, num_origins) node
    counts per tick — the time-to-99%-share-coverage curve from
    BASELINE.json's headline config.

    ``chunk_size=None`` pads the bitmask to MIN_CHUNK_SHARES for full
    128-lane tiles; an explicit smaller value is honored (same contract as
    run_sync_sim) so memory-bound shapes — the 1M-node north star, where
    every (N, W) buffer at W=128 costs 512 MB — can trade gather
    bandwidth for fitting in HBM (see flood_resident_hbm_bytes).
    """
    origins = np.asarray(origins, dtype=np.int32).reshape(-1)
    s = origins.shape[0]
    floor = MIN_CHUNK_SHARES if chunk_size is None else chunk_size
    chunk_size = bitmask.num_words(max(s, floor)) * bitmask.WORD_BITS
    dg = device_graph or DeviceGraph.build(graph, ell_delays, constant_delay)
    block = _resolve_block(dg, block)
    sched = Schedule(graph.n, origins, np.zeros(s, dtype=np.int32))
    o, g = sched.padded(chunk_size, horizon_ticks)
    use_pallas = pallas_coverage_selected(dg, chunk_size)
    churn_dev = churn_to_device(churn)
    loss_cfg = loss.static_cfg if loss is not None else None
    tel = telemetry.rings_enabled()
    with telemetry.span(
        "dispatch", kernel="engine.sync._run_chunk_coverage"
    ):
        out = _run_chunk_coverage(
            dg, jnp.asarray(o), jnp.asarray(g), churn_dev,
            chunk_size=chunk_size, horizon=horizon_ticks, block=block,
            use_pallas=use_pallas, coverage_slots=s, loss=loss_cfg,
            telemetry=tel,
        )
    digest_head = None
    if tel:
        _, r, snt, cov, met, dstream = out
        tel_rings.emit_ring(
            "engine.sync.run_flood_coverage", np.asarray(met), t0=0,
        )
        dvals = np.asarray(dstream)
        # The coverage kernel doesn't report its exit tick; rows past
        # quiescence were never written and read as zero. Emit the full
        # horizon and let compare/report trim.
        tel_digest.emit_digest(
            "engine.sync.run_flood_coverage", dvals,
            t0=0, ticks=int(dvals.shape[0]),
        )
        nz = np.flatnonzero(dvals)
        digest_head = int(dvals[nz[-1]]) if nz.size else 0
    else:
        _, r, snt, cov = out
    generated = effective_generated(sched, horizon_ticks, churn)
    received = np.asarray(r, dtype=np.int64)
    stats = NodeStats(
        generated=generated,
        received=received,
        forwarded=received.copy(),
        sent=np.asarray(snt, dtype=np.int64),
        processed=generated + received,
        degree=np.asarray(dg.host_degree, dtype=np.int64),
    )
    coverage = np.asarray(cov)[:, :s]
    telemetry.emit_progress(
        "engine.sync.run_flood_coverage", chunk=0, chunks_total=1,
        ticks_done=int(coverage.shape[0]),
        coverage_pct=(
            float(coverage[-1].mean()) / dg.n * 100.0 if coverage.size else None
        ),
        digest_head=digest_head,
    )
    stats.extra["coverage"] = coverage
    return stats, coverage


# --- staticcheck audit specs (p2p_gossip_tpu/staticcheck/) ----------------
# Tiny-shape operand builders for the registered kernels above. Evaluated
# lazily at audit time only (the @audited decorator stores a thunk), so
# they cost nothing at import and may use anything defined in this module.

def _audit_inputs(chunk: int = 32, horizon: int = 16):
    from p2p_gossip_tpu.models.topology import erdos_renyi

    graph = erdos_renyi(48, 0.2, seed=0)
    dg = DeviceGraph.build(graph)
    sched = Schedule(
        graph.n,
        np.arange(4, dtype=np.int32) * 7 % graph.n,
        np.arange(4, dtype=np.int32) % 3,
    )
    origins, gen_ticks = sched.padded(chunk, horizon)
    return dg, jnp.asarray(origins), jnp.asarray(gen_ticks)


def _audit_spec_chunk_while(telemetry: bool = False):
    from p2p_gossip_tpu.staticcheck.registry import AuditSpec
    from p2p_gossip_tpu.telemetry.schema import NUM_METRICS

    chunk, horizon = 32, 16
    dg, origins, gen_ticks = _audit_inputs(chunk, horizon)
    kwargs = dict(chunk_size=chunk, horizon=horizon, block=8)
    words: tuple | int = bitmask.num_words(chunk)
    if telemetry:
        # The metric ring rides the signature as a (horizon, M) uint32
        # output — its minor axis is a declared width, not a leak.
        kwargs["telemetry"] = True
        words = (words, NUM_METRICS)
    return AuditSpec(
        args=(
            dg, origins, gen_ticks,
            jnp.asarray(0, dtype=jnp.int32), jnp.asarray(2, dtype=jnp.int32),
        ),
        kwargs=kwargs,
        integer_only=True,
        bitmask_words=words,
    )


def _audit_spec_chunk_coverage(telemetry: bool = False):
    from p2p_gossip_tpu.staticcheck.registry import AuditSpec
    from p2p_gossip_tpu.telemetry.schema import NUM_METRICS

    chunk, horizon = 32, 16
    dg, origins, gen_ticks = _audit_inputs(chunk, horizon)
    kwargs = dict(
        chunk_size=chunk, horizon=horizon, block=8, coverage_slots=4,
    )
    words: tuple | int = bitmask.num_words(chunk)
    if telemetry:
        kwargs["telemetry"] = True
        words = (words, NUM_METRICS)
    return AuditSpec(
        args=(dg, origins, gen_ticks),
        kwargs=kwargs,
        integer_only=True,
        bitmask_words=words,
    )


# Telemetry-on variants of the chunk kernels: same callables, audited
# with the metric ring threaded — the instrumented surfaces are first-
# class registry entries, not a blind spot (satellite of ISSUE 4).
register_entry(
    "engine.sync._run_chunk_while[telemetry]",
    _run_chunk_while,
    spec=lambda: _audit_spec_chunk_while(telemetry=True),
)
register_entry(
    "engine.sync._run_chunk_coverage[telemetry]",
    _run_chunk_coverage,
    spec=lambda: _audit_spec_chunk_coverage(telemetry=True),
)


def time_to_coverage(coverage: np.ndarray, n: int, fraction: float = 0.99):
    """First tick at which each share reaches ``fraction`` of nodes (-1 if
    never). coverage: (T, S)."""
    if coverage.shape[0] == 0:
        # Zero-tick history: argmax over an empty axis raises in numpy.
        return np.full(coverage.shape[1], -1, dtype=np.int64)
    target = int(np.ceil(fraction * n))
    hit = coverage >= target
    first = np.where(hit.any(axis=0), hit.argmax(axis=0), -1)
    return first

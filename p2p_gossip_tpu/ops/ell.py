"""ELL gather-OR frontier propagation — the hot op of the tick engine.

This is the TPU-native replacement for the reference's receive/forward message
path (`GossipShareToPeers` -> socket -> `HandleRead`, p2pnode.cc:127-199):
instead of per-message events, one tick delivers ALL in-flight messages at
once as

    arrivals[dst] = OR_{k in nbrs(dst)} hist[(t - delay[dst,k]) mod D, src[dst,k]]

where ``hist`` is a ring buffer of the last D newly-acquired frontiers — the
per-edge latency "delay lines" from BASELINE.json, realized as *reads into the
past* (gather) rather than scatters into the future, which keeps the op a pure
gather + OR-reduce that XLA tiles well.

The rows are cut into tiles and each tile's degree axis into blocks
(`_tiled_gather_or`). XLA's TPU backend never fuses a gather into its
consumer: each fetch is a fusion that writes its whole ``(rows, B, W)``
output. With every bucket's rows in one fetch, that block is gigabytes and
goes out to HBM, and the OR reads it back (3 GB a block on the 100K ER
overlay). A tile's block is at most ``GATHER_TILE_BYTES``, so XLA keeps it
in on-chip VMEM between the fetch and the OR, and only each tile's
``(rows, W)`` result goes to HBM. Rows under ``TILE_MIN_WORDS`` words keep
the whole block, which measured faster there.

Every gather-OR runs under the ``gossip.gather`` name scope, so each of its
device ops carries the scope in its HLO ``op_name`` and on the profiler's
trace, with sub-scopes ``fetch`` (the row gather), ``or_reduce`` (mask and
OR over a degree block), ``bucket<i>`` (one per degree bucket) and
``scatter_back`` (bucket reassembly). docs/OBSERVABILITY.md lists every
``gossip.*`` scope of the tick.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from p2p_gossip_tpu.staticcheck.registry import audited

DEFAULT_DEGREE_BLOCK = 8

# Swept on a real v5e chip (engine-level, 100K-node p=0.001 ER graph,
# 8192-share chunks): degree block 64 > 32 > 16 > 8 in node-updates/s —
# wider gathers amortize per-row overhead and shorten the degree scan.
TUNED_TPU_BLOCK = 64

# Bytes of fetched frontier rows one gather-OR tile holds (`tile_rows`):
# small enough that XLA keeps the block in on-chip memory between the
# fetch and the OR. Swept on a v5e (100K ER p=0.001, W = 256 and 128):
# 8 MiB +16-18% node-updates/s, 4 MiB +10%; at 16 MiB the W = 256 block
# no longer fits beside the frontier and both widths lose.
GATHER_TILE_BYTES = 8 << 20

# Rows narrower than one 128-lane vector register keep one tile, the
# untiled block: on the 1M BA overlay at W = 64 the tiled form measured
# -15% on a v5e (XLA turns the state to a (W, N) layout and transposes
# every tile's result back).
TILE_MIN_WORDS = 128

# Degree-bucket levels keep this many significant bits of a row's count
# (see bucket_rows_by_count): exact up to 8, then steps of a quarter of
# the count's leading power of two (..., 64, 80, 96, 112, 128, 160, ...).
BUCKET_LEVEL_BITS = 3

# A level group short of ``min_rows`` merges upward only while the merged
# cap stays within this ratio of the group's own cap.
BUCKET_MERGE_RATIO = 1.5

# Per-shard bucket rows are padded this many standard deviations of a
# random labelling's share above the mean (see shard_row_capacity).
SHARD_ROWS_SIGMAS = 4


def tuned_degree_block(devices) -> int:
    """Pick the widest degree-block for the gather-OR scan: the swept TPU
    optimum, and the conservative default off-TPU where the sweep doesn't
    apply. Each gather narrows it to its own cap (`scan_block`)."""
    if not any(d.platform == "tpu" for d in devices):
        return DEFAULT_DEGREE_BLOCK
    return TUNED_TPU_BLOCK


def detect_uniform_delay(ell_delays, ell_mask) -> int | None:
    """The single source of truth for choosing the uniform-delay fast path:
    returns the delay when every VALID edge shares it, else None."""
    import numpy as np

    ell_delays = np.asarray(ell_delays)
    ell_mask = np.asarray(ell_mask)
    valid = ell_delays[ell_mask] if ell_mask.size else ell_delays
    if valid.size and (valid == valid.flat[0]).all():
        return int(valid.flat[0])
    return None


def scan_block(cap: int, block: int) -> int:
    """The degree block a gather over ``cap`` columns scans with: the
    fewest blocks of at most ``block`` columns, evened out so the padded
    axis exceeds ``cap`` by less than the number of blocks (a cap of 104
    at block 64 scans 2 x 52, where ``min(block, cap)`` would pad it to
    128). Both engines size every bucket's scan with this."""
    cap = max(int(cap), 1)
    nblocks = -(-cap // max(int(block), 1))
    return -(-cap // nblocks)


def scanned_width(cap: int, block: int) -> int:
    """Columns the gather fetches per row of a ``cap``-column ELL scanned
    at `scan_block` (``cap`` plus the scan's padding)."""
    b = scan_block(cap, block)
    return -(-int(cap) // b) * b


def fused_slots(rows: int, cap: int, block: int, w: int) -> int:
    """Slots of a ``rows`` x ``cap`` gather at degree block ``block`` and
    ``w`` words that the tiled form fetches, so their rows stay on chip
    until the OR: all its scanned slots from ``TILE_MIN_WORDS`` words up
    where a tile's block (`tile_rows`) fits ``GATHER_TILE_BYTES``, none
    below that width or where even one tile of 8 rows overflows it."""
    b = scan_block(cap, block)
    if (int(w) < TILE_MIN_WORDS
            or tile_rows(rows, b, w) * b * int(w) * 4 > GATHER_TILE_BYTES):
        return 0
    return int(rows) * scanned_width(cap, block)


def gather_slot_report(slots: int, fused: int, nnz: int) -> dict:
    """The ``stats.extra`` entries both flood engines report: the slots
    the gather-OR fetches a tick (``gather_slots``), those of them fetched
    into on-chip tiles (``gather_fused_slots``, `fused_slots`), and the
    fetched slots' ratio to the graph's directed edges
    (``gather_slot_overhead``, 1.0 when no slot is padding)."""
    return {
        "gather_slots": int(slots),
        "gather_fused_slots": int(fused),
        "gather_slot_overhead": int(slots) / max(int(nnz), 1),
    }


def _degree_blocks(arr: jnp.ndarray, block: int, fill) -> jnp.ndarray:
    """(N_out, dmax) -> (nblocks, N_out, B): the degree axis padded to a
    multiple of ``block`` and cut into blocks, so scan slices are
    contiguous. Row-major within a block: a slot-major (B, N_out) block
    makes XLA transpose the gathered rows (1.15-1.7x slower on a v5e)."""
    n_out, dmax = arr.shape
    pad = (-dmax) % block
    if pad:
        arr = jnp.pad(arr, ((0, 0), (0, pad)), constant_values=fill)
    nblocks = arr.shape[1] // block
    return arr.reshape(n_out, nblocks, block).transpose(1, 0, 2)


def _masked_or(gathered, b_msk, b_idx, dst_ids, tick, loss, loss_seed,
               src_ids=None):
    """(N_out, W): the OR over the block axis of the gathered (N_out, B, W)
    rows, masked to valid (and, under a loss model, unerased) edges.
    ``src_ids`` maps a source row to the node id the loss coin hashes
    (default: the row itself)."""
    keep = b_msk
    if loss is not None:
        src = b_idx if src_ids is None else src_ids[b_idx]
        keep = keep & _loss_keep(src, dst_ids, tick, loss, loss_seed)
    gathered = jnp.where(keep[..., None], gathered, jnp.uint32(0))
    return lax.reduce(gathered, jnp.uint32(0), lax.bitwise_or, (1,))


def tile_rows(n_out: int, block: int, w: int) -> int:
    """Destination rows of one gather-OR tile: as many as keep the tile's
    ``(rows, block, w)`` uint32 block of fetched frontier rows within
    ``GATHER_TILE_BYTES``, a multiple of 8 (one sublane tile), at least
    8 and at most ``n_out``; all ``n_out`` rows below ``TILE_MIN_WORDS``
    words."""
    if int(w) < TILE_MIN_WORDS:
        return max(int(n_out), 1)
    rows = GATHER_TILE_BYTES // max(4 * int(block) * int(w), 1)
    rows = max(rows - rows % 8, 8)
    return max(min(int(n_out), rows), 1)


def _tiled_gather_or(fetch, cols, ell_mask, *, w, block, tick, loss,
                     dst_ids, loss_seed, src_ids, rows=None):
    """(N_out, W): each destination row's OR over the frontier rows its
    valid slots read; ``fetch(*slot_blocks)`` maps (R, B) slot arrays to
    the (R, B, W) rows they read.

    ``cols`` pairs each (N_out, cap) slot array (neighbour ids first) with
    its padding fill. The rows are cut into tiles of `tile_rows` (``rows``
    overrides it, for tests), the degree axis into blocks of ``block``; a
    ``lax.map`` over the tiles scans each tile's blocks, so one fetch's
    ``(R, B, W)`` block is at most ``GATHER_TILE_BYTES`` and XLA keeps it
    in on-chip memory between the fetch and the OR: only each tile's
    (R, W) result goes out to HBM. Rows past the last whole tile form one
    smaller tile. Rows narrower than ``TILE_MIN_WORDS`` are one tile, the
    untiled block form."""
    n_out = ell_mask.shape[0]
    if loss is not None and dst_ids is None:
        dst_ids = jnp.arange(n_out, dtype=jnp.int32)
    rows = rows or tile_rows(n_out, block, w)
    n_tiles = n_out // rows
    split = n_tiles * rows
    blocked = [_degree_blocks(a, block, fill) for a, fill in cols]
    blocked.append(_degree_blocks(ell_mask, block, False))

    def tile(blks, t_dst):
        def body(acc, blk):
            *b_cols, b_msk = blk
            with jax.named_scope("fetch"):
                gathered = fetch(*b_cols)  # (R, B, W)
            with jax.named_scope("or_reduce"):
                acc = acc | _masked_or(
                    gathered, b_msk, b_cols[0], t_dst, tick, loss, loss_seed,
                    src_ids,
                )
            return acc, None

        init = jnp.zeros((blks[-1].shape[1], w), dtype=jnp.uint32)
        return lax.scan(body, init, tuple(blks))[0]

    if rows >= n_out:
        return tile(blocked, dst_ids)
    parts = []
    if n_tiles:
        # (nblocks, n_tiles * R, B) -> (n_tiles, nblocks, R, B)
        whole = [
            b[:, :split].reshape(b.shape[0], n_tiles, rows, b.shape[2])
            .transpose(1, 0, 2, 3)
            for b in blocked
        ]
        dst = None if dst_ids is None else dst_ids[:split].reshape(-1, rows)
        out = lax.map(lambda xs: tile(*xs), (whole, dst))
        parts.append(out.reshape(split, w))
    if split < n_out:
        parts.append(tile(
            [b[:, split:] for b in blocked],
            None if dst_ids is None else dst_ids[split:],
        ))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _loss_keep(b_idx, dst_ids, tick, loss, loss_seed=None):
    """(N_out, B) bool: True where the directed link (src=b_idx -> dst) is
    NOT suffering a loss-model erasure at arrival tick ``tick``
    (models/linkloss.py spec). ``loss`` is the static (threshold, seed)
    pair; ``loss_seed`` (optional traced uint32 scalar) overrides the
    static seed — the per-replica erasure streams of the campaign engine,
    where the seed must be a vmapped operand, not a compile-time
    constant. Identical coins either way (same hash)."""
    from p2p_gossip_tpu.models.linkloss import drop_mask_jnp

    threshold, seed = loss
    if loss_seed is not None:
        seed = loss_seed
    return ~drop_mask_jnp(b_idx, dst_ids[:, None], tick, threshold, seed)


@audited("ops.ell.propagate", spec=lambda: _audit_spec_propagate("per_edge"))
@functools.partial(jax.jit, static_argnames=("ring_size", "block", "loss"))
def propagate(
    hist: jnp.ndarray,      # (D, N, W) uint32 — newly-frontier history ring
    tick: jnp.ndarray,      # scalar int32 — current tick t
    ell_idx: jnp.ndarray,   # (N, dmax) int32 — neighbor ids
    ell_delay: jnp.ndarray, # (N, dmax) int32 — per-edge delay in ticks (>= 1)
    ell_mask: jnp.ndarray,  # (N, dmax) bool
    *,
    ring_size: int,
    block: int = DEFAULT_DEGREE_BLOCK,
    loss: tuple | None = None,
    dst_ids: jnp.ndarray | None = None,
    loss_seed: jnp.ndarray | None = None,
    src_ids: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Returns arrivals: (N_out, W) uint32 — shares arriving per tick.

    ``hist`` spans all N_src source rows; the ELL arrays span the N_out
    destination rows being computed. Single-device: N_out == N_src. Sharded
    engine: N_out is the local row shard while hist holds the all_gathered
    global frontier history (neighbor ids stay global).

    ``loss`` = (threshold, seed) enables the per-link erasure model
    (models/linkloss.py); ``dst_ids`` gives the global node id of each of
    the N_out rows (defaults to 0..N_out-1 — pass explicitly whenever rows
    are a shard or bucket of the global graph). ``loss_seed`` (traced
    uint32 scalar) overrides the static seed so each campaign replica can
    draw an independent erasure stream (see _loss_keep). ``src_ids``
    (N_src,) gives the node id of each source row where the staging
    renumbered the nodes (defaults to the row index).
    """
    d, n_src, w = hist.shape
    assert d == ring_size
    with jax.named_scope("gossip.gather"):
        flat = hist.reshape(d * n_src, w)

        def fetch(b_idx, b_dly):
            slot = jnp.mod(tick - b_dly, ring_size)
            return flat[slot * n_src + b_idx]

        return _tiled_gather_or(
            fetch, ((ell_idx, 0), (ell_delay, 1)), ell_mask, w=w,
            block=block, tick=tick, loss=loss, dst_ids=dst_ids,
            loss_seed=loss_seed, src_ids=src_ids,
        )


@audited(
    "ops.ell.gather_or_frontier",
    spec=lambda: _audit_spec_propagate("frontier"),
)
@functools.partial(jax.jit, static_argnames=("block", "loss"))
def gather_or_frontier(
    frontier: jnp.ndarray,  # (N_src, W) uint32 — ONE delay slice of history
    tick: jnp.ndarray,      # scalar int32 — arrival tick (loss coin input)
    ell_idx: jnp.ndarray,   # (N_out, dmax) int32
    ell_mask: jnp.ndarray,  # (N_out, dmax) bool
    *,
    block: int = DEFAULT_DEGREE_BLOCK,
    loss: tuple | None = None,
    dst_ids: jnp.ndarray | None = None,
    loss_seed: jnp.ndarray | None = None,
    src_ids: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """OR-gather arrivals from a single source frontier.

    The shared core of `propagate_uniform` and the sharded engine's
    sharded-ring read path: the caller has already resolved WHICH past
    frontier each edge reads (one slice per uniform delay value), so this
    is a pure (N_out, dmax)-edge gather-OR over one (N_src, W) array.
    ``tick`` is the ARRIVAL tick — the loss coin hashes (src, dst, t), so
    it must be the same t every engine uses, regardless of which past
    slice is being read. ``loss_seed`` and ``src_ids`` as in
    `propagate`."""
    with jax.named_scope("gossip.gather"):
        return _tiled_gather_or(
            lambda b_idx: frontier[b_idx], ((ell_idx, 0),), ell_mask,
            w=frontier.shape[-1], block=block, tick=tick, loss=loss,
            dst_ids=dst_ids, loss_seed=loss_seed, src_ids=src_ids,
        )


@audited(
    "ops.ell.propagate_uniform",
    spec=lambda: _audit_spec_propagate("uniform"),
)
@functools.partial(
    jax.jit, static_argnames=("ring_size", "block", "uniform_delay", "loss")
)
def propagate_uniform(
    hist: jnp.ndarray,      # (D, N_src, W) uint32
    tick: jnp.ndarray,      # scalar int32
    ell_idx: jnp.ndarray,   # (N_out, dmax) int32
    ell_mask: jnp.ndarray,  # (N_out, dmax) bool
    *,
    ring_size: int,
    uniform_delay: int = 1,
    block: int = DEFAULT_DEGREE_BLOCK,
    loss: tuple | None = None,
    dst_ids: jnp.ndarray | None = None,
    loss_seed: jnp.ndarray | None = None,
    src_ids: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Fast path for a uniform per-edge delay (the reference's constant-link
    -latency model): the delay-line slot is one scalar per tick, so the
    per-edge delay gather — and the whole (N, dmax) delay array read from
    HBM — disappears. ``loss``/``dst_ids``/``loss_seed``/``src_ids`` as
    in `propagate`."""
    d = hist.shape[0]
    assert d == ring_size
    with jax.named_scope("gossip.gather"), jax.named_scope("fetch"):
        # One source frontier for the whole tick.
        src = hist[jnp.mod(tick - uniform_delay, ring_size)]  # (N_src, W)
    return gather_or_frontier(
        src, tick, ell_idx, ell_mask, block=block, loss=loss, dst_ids=dst_ids,
        loss_seed=loss_seed, src_ids=src_ids,
    )


def split_ell_by_delay(ell_idx, ell_delay, ell_mask):
    """Partition ELL columns by delay value — the sharded-ring read plan.

    Per-edge delays are STATIC host data, so the set of distinct values is
    known before compile; splitting the ELL into one (idx, mask) pair per
    delay value turns the per-edge-delay gather into a handful of
    single-frontier gathers (`gather_or_frontier`), each reading ONE past
    slice of a source-sharded history ring. Each pair is packed left
    (valid edges first) and trimmed to its own max row count, so the total
    gather traffic stays ~the full ELL's plus per-delay padding.

    Returns a tuple of ``(delay_value, idx_d, mask_d)``; the masks
    partition the valid entries of ``ell_mask``.
    """
    import numpy as np

    ell_idx = np.asarray(ell_idx)
    ell_delay = np.asarray(ell_delay)
    ell_mask = np.asarray(ell_mask)
    values = np.unique(ell_delay[ell_mask])
    if values.size == 0:
        # Degenerate (all rows padding): one vacuous pair keeps the
        # consumer's loop non-empty.
        return ((1, ell_idx[:, :1], np.zeros_like(ell_mask[:, :1])),)
    n = ell_idx.shape[0]
    out = []
    for d in values:
        # O(nnz) packing via nonzero coordinates. The obvious
        # alternative — a stable argsort of ~m along the degree axis +
        # take_along_axis — materializes an (N, dmax) int64 permutation:
        # 36 GB of transient at the 1M-node BA shape (dmax 4517), which
        # OOM-killed the 1M scale-free mesh rehearsal twice on a 125 GB
        # host. np.nonzero walks row-major, so per-row column order (and
        # therefore the packed layout) is the same valid-first stable
        # order; padding slots hold index 0 (in-bounds) under a False
        # mask, which the gather's OR-aggregation ignores.
        m = ell_delay == d
        m &= ell_mask
        counts = m.sum(axis=1, dtype=np.int64)
        cap = max(int(counts.max()), 1)
        rows, cols = np.nonzero(m)
        pos = (
            np.arange(rows.shape[0], dtype=np.int64)
            - (np.cumsum(counts) - counts)[rows]
        )
        idx_d = np.zeros((n, cap), dtype=ell_idx.dtype)
        msk_d = np.zeros((n, cap), dtype=bool)
        idx_d[rows, pos] = ell_idx[rows, cols]
        msk_d[rows, pos] = True
        out.append((int(d), idx_d, msk_d))
    return tuple(out)


def bucket_rows_by_count(cnt, min_rows: int):
    """THE bucketing policy, shared by `build_bucket_major` (single
    device) and `shard_bucket_ell` (sharded engine) so a tuning change
    cannot drift between them. Every padded slot of a bucket is a whole
    frontier row fetched from HBM, so the levels track the counts:
    per-row valid-entry counts are rounded up to ``BUCKET_LEVEL_BITS``
    significant bits (exact up to 8), and a level group short of
    ``min_rows`` rows merges upward only while the merged cap stays
    within ``BUCKET_MERGE_RATIO`` of its own cap; a group that cannot
    (the hubs) stands alone. Reads nothing but the counts, so the groups'
    sizes and caps depend only on their multiset: every relabelling of a
    graph stages the same shapes. Returns a list of row-index arrays in
    ascending level order; they partition ``range(len(cnt))``."""
    import numpy as np

    cnt = np.asarray(cnt, dtype=np.int64)
    if cnt.size == 0:
        return []  # empty input partitions into no groups
    bits = np.floor(np.log2(np.maximum(cnt, 1))).astype(np.int64) + 1
    step = np.left_shift(1, np.maximum(bits - BUCKET_LEVEL_BITS, 0))
    level = -(-cnt // step) * step
    order = np.argsort(level, kind="stable")
    groups = np.split(order, np.flatnonzero(np.diff(level[order])) + 1)
    merged: list[np.ndarray] = []
    pending: list[np.ndarray] = []
    pending_rows = base_cap = 0
    for g in groups:
        cap = max(int(cnt[g].max()), 1)
        if pending and cap > BUCKET_MERGE_RATIO * base_cap:
            # Too far above the pending groups' own cap: they stand alone
            # rather than gather this group's padding every tick.
            merged.append(np.concatenate(pending))
            pending, pending_rows = [], 0
        if not pending:
            base_cap = cap
        pending.append(g)
        pending_rows += g.shape[0]
        if pending_rows >= min_rows:
            merged.append(np.concatenate(pending))
            pending, pending_rows = [], 0
    if pending:
        # Leftovers keep their own bucket: folding a tail into the previous
        # bucket would raise that bucket's cap for every row.
        merged.append(np.concatenate(pending))
    return merged


def _host_degree_buckets(graph, ell_delays, min_rows: int, ell):
    """The degree buckets as host arrays, in ascending level order: one
    ``(rows, ell_idx, ell_mask, ell_delay)`` per `bucket_rows_by_count`
    group, each ELL cut to the group's max degree (``ell_delay`` None when
    ``ell_delays`` is None)."""
    import numpy as np

    deg = np.asarray(graph.degree)
    # With no per-edge delays and no pre-materialized ELL, each bucket's
    # arrays come straight from CSR (Graph.ell_rows) — the global (N, dmax)
    # ELL is never built. Delay staging still needs the full ELL-aligned
    # delay array, so that path keeps the global ELL.
    if ell is None and ell_delays is not None:
        ell = graph.ell()
    ell_idx, ell_mask = ell if ell is not None else (None, None)
    for rows in bucket_rows_by_count(deg, min_rows):
        # Cap at the bucket's true max degree, not its level: the scan
        # (`scan_block`) pads it by less than its block count.
        cap = max(int(deg[rows].max()), 1)
        if ell_idx is not None:
            b_idx = np.ascontiguousarray(ell_idx[rows, :cap])
            b_mask = np.ascontiguousarray(ell_mask[rows, :cap])
        else:
            b_idx, b_mask = graph.ell_rows(rows, cap)
        b_delay = (
            np.ascontiguousarray(ell_delays[rows, :cap])
            if ell_delays is not None
            else None
        )
        yield rows, b_idx, b_mask, b_delay


def _device_bucket(rows, b_idx, b_mask, b_delay) -> tuple:
    import numpy as np

    return (
        jnp.asarray(rows.astype(np.int32)),
        jnp.asarray(b_idx),
        jnp.asarray(b_mask),
        None if b_delay is None else jnp.asarray(b_delay),
    )


def build_bucket_major(
    graph,
    ell_delays=None,
    *,
    min_rows: int = 2048,
    ell: tuple | None = None,
):
    """Group nodes into degree buckets for padding-free ELL propagation,
    numbered bucket-major.

    The single full-width ELL pads every row to the global max degree; on a
    1M-node BA graph (mean degree 6, dmax 4,517) nearly every gathered slot
    would be padding, and the gather is most of the tick. Here nodes are
    grouped by `bucket_rows_by_count` so each group's ELL is padded only to
    its own max degree: the gathered slots stay within 8% of the edges on
    the 100K ER and 1M BA overlays (1.075x in 5 buckets, 1.044x in 25).

    Nodes are numbered bucket by bucket, in the buckets' order, so each
    bucket's ``rows`` is one contiguous range of the new ids and the
    ranges follow each other from 0 to n: `propagate_bucketed`
    reassembles the arrivals by concatenation.

    Returns ``(buckets, order)``: one ``(rows, ell_idx, ell_mask,
    ell_delay)`` per bucket (``ell_delay`` None when ``ell_delays`` is
    None), and ``order[new] = old`` (int64), the same for every
    ``min_rows`` (bucket_rows_by_count's groups concatenate to one stable
    sort by level). Each bucket's neighbour ids are renumbered and each
    row's valid ids sorted, padding last, its per-edge delays moved with
    them: rows within a bucket have near-equal degree, so the k-th sorted
    neighbour of each row sits near the same quantile of the id space and
    the per-slot gather touches a narrow band of source rows, which
    measurably improves gather locality. O(slots) on the host, with one
    sort within each row; with no per-edge delays and no ``ell`` the
    (N, dmax) ELL is never built.

    ``ell`` lets the caller pass an already-materialized ``(ell_idx,
    ell_mask)`` pair so the (N, dmax) arrays aren't rebuilt from CSR.
    """
    import numpy as np

    host = list(_host_degree_buckets(graph, ell_delays, min_rows, ell))
    n = graph.n
    order = (
        np.concatenate([b[0] for b in host]).astype(np.int64)
        if host
        else np.zeros(0, dtype=np.int64)
    )
    inv = np.empty(n, dtype=np.int32)
    inv[order] = np.arange(n, dtype=np.int32)
    buckets, start = [], 0
    for rows, b_idx, b_mask, b_delay in host:
        # Padding keys sort after every id, so valid entries stay
        # front-packed.
        key = np.where(b_mask, inv[b_idx], np.int32(n))
        if b_delay is None:
            key.sort(axis=1)
        else:
            perm = np.argsort(key, axis=1, kind="stable")
            key = np.take_along_axis(key, perm, axis=1)
            b_delay = np.take_along_axis(b_delay, perm, axis=1)
        b_mask = key < n
        b_idx = np.where(b_mask, key, 0).astype(np.int32)
        new_rows = np.arange(start, start + rows.shape[0])
        start += rows.shape[0]
        buckets.append(_device_bucket(new_rows, b_idx, b_mask, b_delay))
    return tuple(buckets), order


def shard_bucket_ell(
    ell_idx,
    ell_mask,
    n_shards: int,
    *,
    min_rows: int = 2048,
):
    """Bucket one ELL (idx, mask) pair per node-shard with shard-uniform
    shapes — degree bucketing for the `shard_map` engine.

    The sharded engine's gathers used to pad every row shard to the
    pair's global column cap: on the 1M scale-free graph (dmax 4517,
    mean degree 6) that is ~750x masked gather traffic, the dominant
    per-tick cost of the mesh path. `build_bucket_major` fixes this on
    one device, but its per-bucket shapes are data-dependent — under
    SPMD every shard must run the same program on same-shaped operands.
    Here rows are bucketed by their VALID-ENTRY count (works for the raw
    ELL and for delay-split pairs alike) with a GLOBAL level structure:
    the same policy as `build_bucket_major` (`bucket_rows_by_count`),
    with ``min_rows * n_shards`` as the merge floor, and every bucket's
    row capacity taken from its size (`shard_row_capacity`), so every
    relabelling of a graph stages the same shapes.

    Returns a tuple of buckets ``(rows, idx, mask)`` with leading shard
    axis: rows ``(S, R)`` int32 LOCAL row ids padded with ``n_loc`` (out
    of range, so a ``mode="drop"`` scatter ignores them), idx/mask
    ``(S, R, C)`` sliced from the pair's leading (front-packed) columns.
    Zero-count rows appear in no bucket — they gather nothing, and the
    consumer's scatter leaves their arrivals zero.
    """
    import numpy as np

    ell_idx = np.asarray(ell_idx)
    ell_mask = np.asarray(ell_mask)
    n_padded, width = ell_idx.shape
    cnt = ell_mask.sum(axis=1).astype(np.int64)
    return _fill_shard_buckets(
        cnt, width, n_shards, min_rows,
        lambda rows, cap: (ell_idx[rows, :cap], ell_mask[rows, :cap]),
    )


def shard_bucket_csr(graph, n_shards: int, *, min_rows: int = 2048):
    """`shard_bucket_ell` of the graph's whole ELL, padded to a multiple
    of ``n_shards`` rows, built straight from CSR: the degrees come from
    ``graph.degree`` and each bucket's ``(S, R, C)`` block from
    `Graph.ell_rows`, so the global ``(N, dmax)`` ELL and its mask are
    never built (22.6 GB at the 1M-node BA overlay, dmax 4,517). Bitwise
    equal to ``shard_bucket_ell(pad(idx), pad(mask), n_shards)``."""
    import numpy as np

    n_padded = -(-graph.n // n_shards) * n_shards
    cnt = np.zeros(n_padded, dtype=np.int64)
    cnt[: graph.n] = graph.degree
    return _fill_shard_buckets(
        cnt, graph.ell_width, n_shards, min_rows, graph.ell_rows
    )


def shard_row_capacity(rows: int, n_shards: int) -> int:
    """Rows a shard's slice of a ``rows``-row bucket is padded to: a
    function of the bucket's size alone, ``mean + SHARD_ROWS_SIGMAS *
    sqrt(mean)`` with ``mean = rows / n_shards``, at most ``rows``. The
    most rows any one shard holds moves with the node labels, so a
    capacity taken from it gives every relabelling of a graph its own
    shapes, and its own compiled program; a random labelling exceeds
    this bound with probability ~3e-5 (then the true max is taken)."""
    mean = rows / n_shards
    return max(
        min(rows, math.ceil(mean + SHARD_ROWS_SIGMAS * math.sqrt(mean))), 1
    )


def _fill_shard_buckets(cnt, width: int, n_shards: int, min_rows: int,
                        ell_rows):
    """The shard-uniform bucket layout of `shard_bucket_ell` over per-row
    valid-entry counts ``cnt`` (length a multiple of ``n_shards``), each
    block filled by ``ell_rows(global_rows, cap)`` -> (idx, mask)."""
    import numpy as np

    n_padded = cnt.shape[0]
    assert n_padded % n_shards == 0, (n_padded, n_shards)
    n_loc = n_padded // n_shards
    # Zero-count rows are excluded up front (they gather nothing and the
    # consumer's scatter leaves them zero); the shared policy then groups
    # the rest. min_rows scales by n_shards: the threshold bounds the
    # TOTAL bucket count (each bucket is one gather per tick on every
    # shard), not any one shard's rows.
    nz = np.flatnonzero(cnt > 0)
    row_groups = (
        [nz[g] for g in bucket_rows_by_count(cnt[nz], min_rows * n_shards)]
        if nz.size
        else [np.zeros(0, dtype=np.int64)]  # vacuous all-empty pair
    )

    shard_of = np.arange(n_padded, dtype=np.int64) // n_loc
    local = (np.arange(n_padded, dtype=np.int64) % n_loc).astype(np.int32)
    buckets = []
    for grows in row_groups:
        # Tight cap (max valid count in the group), as build_bucket_major.
        grp_max = int(cnt[grows].max()) if grows.size else 1
        cap = min(max(grp_max, 1), width)
        per_shard = [
            local[grows[shard_of[grows] == s]] for s in range(n_shards)
        ]
        r_cap = max(
            max(r.size for r in per_shard),
            shard_row_capacity(grows.size, n_shards),
        )
        rows_arr = np.full((n_shards, r_cap), n_loc, dtype=np.int32)
        idx_arr = np.zeros((n_shards, r_cap, cap), dtype=np.int32)
        msk_arr = np.zeros((n_shards, r_cap, cap), dtype=bool)
        for s, r in enumerate(per_shard):
            if not r.size:
                continue
            rows_arr[s, : r.size] = r
            idx_arr[s, : r.size], msk_arr[s, : r.size] = ell_rows(
                r.astype(np.int64) + s * n_loc, cap
            )
        buckets.append((rows_arr, idx_arr, msk_arr))
    return tuple(buckets)


def propagate_bucketed(
    hist: jnp.ndarray,
    tick: jnp.ndarray,
    buckets,
    *,
    node_ids: jnp.ndarray,
    ring_size: int,
    uniform_delay: int | None = None,
    block: int = DEFAULT_DEGREE_BLOCK,
    loss: tuple | None = None,
    loss_seed: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Gather-OR over bucket-major degree buckets (see
    `build_bucket_major`).

    Bitwise-identical to `propagate`/`propagate_uniform` on the full ELL
    in the bucket-major numbering: each bucket computes its rows' arrivals
    over its own (tight) ELL, and since the buckets' rows are consecutive
    ranges in bucket order, the arrivals are the buckets' results
    concatenated, one sequential copy, under the ``scatter_back``
    sub-scope. ``node_ids[i]`` (n,) is the caller's id of node ``i``,
    which the loss coin hashes for both ends of each link. ``loss``/
    ``loss_seed`` as in `propagate`.
    """
    parts, start = [], 0
    for i, (_rows, b_idx, b_mask, b_delay) in enumerate(buckets):
        # Sized to the bucket's own cap: a cap-8 bucket at block 64, or a
        # cap-104 one scanned 64 + 64, would fetch masked rows back in —
        # exactly what bucketing removes.
        b_block = scan_block(b_idx.shape[1], block)
        size = b_idx.shape[0]
        with jax.named_scope("gossip.gather"), jax.named_scope(f"bucket{i}"):
            dst_ids = src_ids = None
            if loss:
                dst_ids = lax.slice_in_dim(node_ids, start, start + size)
                src_ids = node_ids
            if uniform_delay is not None:
                part = propagate_uniform(
                    hist, tick, b_idx, b_mask,
                    ring_size=ring_size, uniform_delay=uniform_delay,
                    block=b_block, loss=loss, dst_ids=dst_ids,
                    loss_seed=loss_seed, src_ids=src_ids,
                )
            else:
                part = propagate(
                    hist, tick, b_idx, b_delay, b_mask,
                    ring_size=ring_size, block=b_block,
                    loss=loss, dst_ids=dst_ids,
                    loss_seed=loss_seed, src_ids=src_ids,
                )
        parts.append(part)
        start += size
    with jax.named_scope("gossip.gather"), jax.named_scope("scatter_back"):
        return jnp.concatenate(parts)


# --- staticcheck audit specs (p2p_gossip_tpu/staticcheck/) ----------------

def _audit_spec_propagate(kind: str):
    """Tiny ELL gather for the jaxpr auditor: 8 rows, degree cap 3, W=2
    words, with the loss coin on and a traced loss seed (the campaign
    path) so the erasure hash is part of the audited graph."""
    import numpy as np

    from p2p_gossip_tpu.staticcheck.registry import AuditSpec

    rng = np.random.default_rng(0)
    n, dmax, w, ring = 8, 3, 2, 2
    hist = jnp.zeros((ring, n, w), dtype=jnp.uint32)
    idx = jnp.asarray(rng.integers(0, n, (n, dmax)), dtype=jnp.int32)
    msk = jnp.asarray(rng.random((n, dmax)) < 0.8)
    tick = jnp.asarray(1, dtype=jnp.int32)
    lseed = jnp.uint32(3)
    common = dict(
        integer_only=True,
        bitmask_words=w,
    )
    if kind == "frontier":
        return AuditSpec(
            args=(hist[0], tick, idx, msk),
            kwargs=dict(block=2, loss=(1 << 20, None), loss_seed=lseed),
            **common,
        )
    if kind == "uniform":
        return AuditSpec(
            args=(hist, tick, idx, msk),
            kwargs=dict(
                ring_size=ring, uniform_delay=1, block=2,
                loss=(1 << 20, None), loss_seed=lseed,
            ),
            **common,
        )
    dly = jnp.asarray(rng.integers(1, ring, (n, dmax)), dtype=jnp.int32)
    return AuditSpec(
        args=(hist, tick, idx, dly, msk),
        kwargs=dict(
            ring_size=ring, block=2, loss=(1 << 20, None), loss_seed=lseed,
        ),
        **common,
    )


def propagate_reference(hist, tick, ell_idx, ell_delay, ell_mask, *, ring_size):
    """Straight-line jnp version (materializes (N_out, dmax, W)) — oracle for
    tests and for the Pallas kernel."""
    d, n_src, w = hist.shape
    slot = jnp.mod(tick - ell_delay, ring_size)
    gathered = hist.reshape(d * n_src, w)[slot * n_src + ell_idx]
    gathered = jnp.where(ell_mask[..., None], gathered, jnp.uint32(0))
    return lax.reduce(gathered, jnp.uint32(0), lax.bitwise_or, (1,))

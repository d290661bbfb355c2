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

The degree axis is processed in blocks under ``lax.scan`` so the gathered
(N, B, W) intermediate stays small instead of materializing (N, dmax, W).

Every gather-OR runs under the ``gossip.gather`` name scope, so each of its
device ops carries the scope in its HLO ``op_name`` and on the profiler's
trace, with sub-scopes ``fetch`` (the row gather), ``or_reduce`` (mask and
OR over a degree block), ``bucket<i>`` (one per degree bucket) and
``scatter_back`` (bucket reassembly). docs/OBSERVABILITY.md lists every
``gossip.*`` scope of the tick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from p2p_gossip_tpu.staticcheck.registry import audited

DEFAULT_DEGREE_BLOCK = 8

# Swept on a real v5e chip (engine-level, 100K-node p=0.001 ER graph,
# 8192-share chunks): degree block 64 > 32 > 16 > 8 in node-updates/s —
# wider gathers amortize per-row overhead and shorten the degree scan.
TUNED_TPU_BLOCK = 64

# Degree-bucket levels above this are quantized to powers of two (see
# build_degree_buckets) and always form standalone buckets.
GEOMETRIC_LEVEL_THRESHOLD = 8


def tuned_degree_block(dmax: int, devices) -> int:
    """Pick the degree-block for the gather-OR scan: the swept TPU optimum,
    but never wider than the max degree rounded up to the default block (a
    degree-4 lattice at block 64 would gather 16x masked zeros), and the
    conservative default off-TPU where the sweep doesn't apply."""
    if not any(d.platform == "tpu" for d in devices):
        return DEFAULT_DEGREE_BLOCK
    padded = -(-max(dmax, 1) // DEFAULT_DEGREE_BLOCK) * DEFAULT_DEGREE_BLOCK
    return min(TUNED_TPU_BLOCK, padded)


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


def _pad_degree_axis(arr: jnp.ndarray, block: int, fill) -> jnp.ndarray:
    dmax = arr.shape[1]
    pad = (-dmax) % block
    if pad:
        arr = jnp.pad(arr, ((0, 0), (0, pad)), constant_values=fill)
    return arr


def _masked_or(gathered, b_msk, b_idx, dst_ids, tick, loss, loss_seed):
    """(N_out, W): the OR over the block axis of the gathered (N_out, B, W)
    rows, masked to valid (and, under a loss model, unerased) edges."""
    keep = b_msk
    if loss is not None:
        keep = keep & _loss_keep(b_idx, dst_ids, tick, loss, loss_seed)
    gathered = jnp.where(keep[..., None], gathered, jnp.uint32(0))
    return lax.reduce(gathered, jnp.uint32(0), lax.bitwise_or, (1,))


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
    draw an independent erasure stream (see _loss_keep).
    """
    d, n_src, w = hist.shape
    n_out = ell_idx.shape[0]
    assert d == ring_size
    with jax.named_scope("gossip.gather"):
        flat = hist.reshape(d * n_src, w)
        if loss is not None and dst_ids is None:
            dst_ids = jnp.arange(n_out, dtype=jnp.int32)

        idx = _pad_degree_axis(ell_idx, block, 0)
        dly = _pad_degree_axis(ell_delay, block, 1)
        msk = _pad_degree_axis(ell_mask, block, False)
        nblocks = idx.shape[1] // block
        # (nblocks, N_out, B) so scan slices are contiguous.
        idx = idx.reshape(n_out, nblocks, block).transpose(1, 0, 2)
        dly = dly.reshape(n_out, nblocks, block).transpose(1, 0, 2)
        msk = msk.reshape(n_out, nblocks, block).transpose(1, 0, 2)

        def body(acc, blk):
            b_idx, b_dly, b_msk = blk
            with jax.named_scope("fetch"):
                slot = jnp.mod(tick - b_dly, ring_size)
                gathered = flat[slot * n_src + b_idx]  # (N_out, B, W)
            with jax.named_scope("or_reduce"):
                acc = acc | _masked_or(
                    gathered, b_msk, b_idx, dst_ids, tick, loss, loss_seed
                )
            return acc, None

        init = jnp.zeros((n_out, w), dtype=jnp.uint32)
        arrivals, _ = lax.scan(body, init, (idx, dly, msk))
        return arrivals


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
) -> jnp.ndarray:
    """OR-gather arrivals from a single source frontier.

    The shared core of `propagate_uniform` and the sharded engine's
    sharded-ring read path: the caller has already resolved WHICH past
    frontier each edge reads (one slice per uniform delay value), so this
    is a pure (N_out, dmax)-edge gather-OR over one (N_src, W) array.
    ``tick`` is the ARRIVAL tick — the loss coin hashes (src, dst, t), so
    it must be the same t every engine uses, regardless of which past
    slice is being read. ``loss_seed`` as in `propagate`."""
    n_out = ell_idx.shape[0]
    w = frontier.shape[-1]
    with jax.named_scope("gossip.gather"):
        if loss is not None and dst_ids is None:
            dst_ids = jnp.arange(n_out, dtype=jnp.int32)

        idx = _pad_degree_axis(ell_idx, block, 0)
        msk = _pad_degree_axis(ell_mask, block, False)
        nblocks = idx.shape[1] // block
        idx = idx.reshape(n_out, nblocks, block).transpose(1, 0, 2)
        msk = msk.reshape(n_out, nblocks, block).transpose(1, 0, 2)

        def body(acc, blk):
            b_idx, b_msk = blk
            with jax.named_scope("fetch"):
                gathered = frontier[b_idx]  # (N_out, B, W)
            with jax.named_scope("or_reduce"):
                acc = acc | _masked_or(
                    gathered, b_msk, b_idx, dst_ids, tick, loss, loss_seed
                )
            return acc, None

        init = jnp.zeros((n_out, w), dtype=jnp.uint32)
        arrivals, _ = lax.scan(body, init, (idx, msk))
        return arrivals


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
) -> jnp.ndarray:
    """Fast path for a uniform per-edge delay (the reference's constant-link
    -latency model): the delay-line slot is one scalar per tick, so the
    per-edge delay gather — and the whole (N, dmax) delay array read from
    HBM — disappears. ``loss``/``dst_ids``/``loss_seed`` as in
    `propagate`."""
    d = hist.shape[0]
    assert d == ring_size
    with jax.named_scope("gossip.gather"), jax.named_scope("fetch"):
        # One source frontier for the whole tick.
        src = hist[jnp.mod(tick - uniform_delay, ring_size)]  # (N_src, W)
    return gather_or_frontier(
        src, tick, ell_idx, ell_mask, block=block, loss=loss, dst_ids=dst_ids,
        loss_seed=loss_seed,
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


def bucket_rows_by_count(cnt, block: int, min_rows: int):
    """THE bucketing policy, shared by `build_degree_buckets` (single
    device) and `shard_bucket_ell` (sharded engine) so a tuning change
    cannot drift between them: quantize per-row valid-entry counts to
    levels (linear multiples of ``block``; geometric powers of two past
    ``GEOMETRIC_LEVEL_THRESHOLD`` so heavy tails stay < 2x padded),
    then merge small LINEAR-level groups upward until each holds
    ``min_rows`` rows — tail levels always stand alone (merging would
    pad hundreds of small rows to the hub cap). Returns a list of
    row-index arrays in ascending level order; they partition
    ``range(len(cnt))``."""
    import numpy as np

    cnt = np.asarray(cnt, dtype=np.int64)
    if cnt.size == 0:
        return []  # empty input partitions into no groups
    level = -(-cnt // block)
    high = level > GEOMETRIC_LEVEL_THRESHOLD
    if high.any():
        level = np.where(
            high,
            1 << np.ceil(np.log2(np.maximum(level, 1))).astype(np.int64),
            level,
        )
    order = np.argsort(level, kind="stable")
    sorted_level = level[order]
    change = np.flatnonzero(np.diff(sorted_level)) + 1
    groups = np.split(order, change)
    merged: list[np.ndarray] = []
    pending: list[np.ndarray] = []
    pending_count = 0
    for g in groups:
        if level[g[0]] > GEOMETRIC_LEVEL_THRESHOLD:  # geometric group
            if pending:
                merged.append(np.concatenate(pending))
                pending, pending_count = [], 0
            merged.append(g)
            continue
        pending.append(g)
        pending_count += g.shape[0]
        if pending_count >= min_rows:
            merged.append(np.concatenate(pending))
            pending, pending_count = [], 0
    if pending:
        # Leftovers keep their own bucket: folding a tail into the previous
        # bucket would raise that bucket's cap for every row.
        merged.append(np.concatenate(pending))
    return merged


def build_degree_buckets(
    graph,
    ell_delays=None,
    *,
    block: int = DEFAULT_DEGREE_BLOCK,
    min_rows: int = 2048,
    ell: tuple | None = None,
):
    """Group nodes into degree buckets for padding-free ELL propagation.

    The single full-width ELL pads every row to the global max degree; on a
    100K-node p=0.001 ER graph that is ~45% wasted gather traffic (mean
    degree ~100, dmax ~145 — and the gather is the whole tick cost). Here
    nodes are grouped by ``ceil(degree / block)`` so each group's ELL is
    padded only to its own cap; groups smaller than ``min_rows`` are merged
    upward (into the next cap) so tiny graphs collapse back to one bucket.

    Returns a tuple of ``(rows, ell_idx, ell_mask, ell_delay)`` per bucket
    (``ell_delay`` is None when ``ell_delays`` is None); the ``rows`` arrays
    partition ``range(n)``. A nice side effect: rows within a bucket have
    near-equal degree, so the k-th sorted neighbor of each row sits near the
    same quantile of the id space — the per-slot gather touches a narrow
    band of source rows, which measurably improves gather locality.

    ``ell`` lets the caller pass an already-materialized ``(ell_idx,
    ell_mask)`` pair so the (N, dmax) arrays aren't rebuilt from CSR.
    """
    import numpy as np

    deg = np.asarray(graph.degree)
    # With no per-edge delays and no pre-materialized ELL, each bucket's
    # arrays come straight from CSR (Graph.ell_rows) — the global (N, dmax)
    # ELL is never built. Delay staging still needs the full ELL-aligned
    # delay array, so that path keeps the global ELL.
    if ell is None and ell_delays is not None:
        ell = graph.ell()
    ell_idx, ell_mask = ell if ell is not None else (None, None)
    merged = bucket_rows_by_count(deg, block, min_rows)
    buckets = []
    for rows in merged:
        # Cap at the bucket's true max degree, block-rounded: geometric
        # (power-of-two) levels can sit up to ~2x above it, and hub
        # buckets must not gather masked padding every tick.
        cap = max(-(-int(deg[rows].max()) // block) * block, block)
        if ell_idx is not None:
            b_idx = np.ascontiguousarray(ell_idx[rows, :cap])
            b_mask = np.ascontiguousarray(ell_mask[rows, :cap])
        else:
            b_idx, b_mask = graph.ell_rows(rows, cap)
        buckets.append(
            (
                jnp.asarray(rows.astype(np.int32)),
                jnp.asarray(b_idx),
                jnp.asarray(b_mask),
                jnp.asarray(np.ascontiguousarray(ell_delays[rows, :cap]))
                if ell_delays is not None
                else None,
            )
        )
    return tuple(buckets)


def shard_bucket_ell(
    ell_idx,
    ell_mask,
    n_shards: int,
    *,
    block: int = DEFAULT_DEGREE_BLOCK,
    min_rows: int = 2048,
):
    """Bucket one ELL (idx, mask) pair per node-shard with shard-uniform
    shapes — degree bucketing for the `shard_map` engine.

    The sharded engine's gathers used to pad every row shard to the
    pair's global column cap: on the 1M scale-free graph (dmax 4517,
    mean degree 6) that is ~750x masked gather traffic, the dominant
    per-tick cost of the mesh path. `build_degree_buckets` fixes this on
    one device, but its per-bucket shapes are data-dependent — under
    SPMD every shard must run the same program on same-shaped operands.
    Here rows are bucketed by their VALID-ENTRY count (works for the raw
    ELL and for delay-split pairs alike) with a GLOBAL level structure:
    the same count->level quantization as `build_degree_buckets` (linear
    levels of ``block``, geometric past ``GEOMETRIC_LEVEL_THRESHOLD``),
    levels merged upward until a group holds ``min_rows * n_shards``
    rows, and every bucket's row capacity taken as the max over shards.

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
    assert n_padded % n_shards == 0, (n_padded, n_shards)
    n_loc = n_padded // n_shards
    cnt = ell_mask.sum(axis=1).astype(np.int64)
    # Zero-count rows are excluded up front (they gather nothing and the
    # consumer's scatter leaves them zero); the shared policy then groups
    # the rest. min_rows scales by n_shards: the threshold bounds the
    # TOTAL bucket count (each bucket is one gather per tick on every
    # shard), not any one shard's rows.
    nz = np.flatnonzero(cnt > 0)
    row_groups = (
        [nz[g] for g in bucket_rows_by_count(cnt[nz], block,
                                             min_rows * n_shards)]
        if nz.size
        else [np.zeros(0, dtype=np.int64)]  # vacuous all-empty pair
    )

    shard_of = np.arange(n_padded, dtype=np.int64) // n_loc
    local = (np.arange(n_padded, dtype=np.int64) % n_loc).astype(np.int32)
    buckets = []
    for grows in row_groups:
        # Tight cap (block-rounded max valid count in the group), same
        # clamp as build_degree_buckets.
        grp_max = int(cnt[grows].max()) if grows.size else 1
        cap = min(max(-(-grp_max // block) * block, 1), width)
        per_shard = [
            local[grows[shard_of[grows] == s]] for s in range(n_shards)
        ]
        r_cap = max(max(r.size for r in per_shard), 1)
        rows_arr = np.full((n_shards, r_cap), n_loc, dtype=np.int32)
        idx_arr = np.zeros((n_shards, r_cap, cap), dtype=ell_idx.dtype)
        msk_arr = np.zeros((n_shards, r_cap, cap), dtype=bool)
        for s, r in enumerate(per_shard):
            if not r.size:
                continue
            rows_arr[s, : r.size] = r
            gsl = r.astype(np.int64) + s * n_loc
            idx_arr[s, : r.size] = ell_idx[gsl, :cap]
            msk_arr[s, : r.size] = ell_mask[gsl, :cap]
        buckets.append((rows_arr, idx_arr, msk_arr))
    return tuple(buckets)


def propagate_bucketed(
    hist: jnp.ndarray,
    tick: jnp.ndarray,
    buckets,
    *,
    n_out: int,
    ring_size: int,
    uniform_delay: int | None = None,
    block: int = DEFAULT_DEGREE_BLOCK,
    loss: tuple | None = None,
    loss_seed: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Gather-OR over degree buckets (see `build_degree_buckets`).

    Bitwise-identical to `propagate`/`propagate_uniform` on the full ELL —
    each bucket computes its rows' arrivals over its own (tight) ELL and the
    results are scattered back into node order. ``loss``/``loss_seed`` as
    in `propagate` (each bucket's global row ids are its dst_ids).
    """
    w = hist.shape[-1]
    parts = []
    for i, (rows, b_idx, b_mask, b_delay) in enumerate(buckets):
        # Clamp to the bucket's own cap: a cap-8 bucket at block 64 would
        # pad 8x masked zeros back in — exactly what bucketing removes.
        b_block = min(block, b_idx.shape[1])
        with jax.named_scope("gossip.gather"), jax.named_scope(f"bucket{i}"):
            if uniform_delay is not None:
                part = propagate_uniform(
                    hist, tick, b_idx, b_mask,
                    ring_size=ring_size, uniform_delay=uniform_delay,
                    block=b_block, loss=loss, dst_ids=rows if loss else None,
                    loss_seed=loss_seed,
                )
            else:
                part = propagate(
                    hist, tick, b_idx, b_delay, b_mask,
                    ring_size=ring_size, block=b_block,
                    loss=loss, dst_ids=rows if loss else None,
                    loss_seed=loss_seed,
                )
        parts.append(part)
    with jax.named_scope("gossip.gather"), jax.named_scope("scatter_back"):
        # One combined scatter back to node order (the rows arrays
        # partition range(n_out)) instead of one full-array update per
        # bucket.
        order = jnp.concatenate([b[0] for b in buckets])
        arrivals = jnp.zeros((n_out, w), dtype=jnp.uint32)
        return arrivals.at[order].set(jnp.concatenate(parts), mode="drop")


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

"""Multi-chip random-partner protocols: shard_map push-pull and fanout push.

Scales models/protocols.py the way engine_sharded.py scales the flood
engine: graph rows, seen state, and counters shard along ``nodes``;
independent share chunks along ``shares``. The partner-pick hash
(models/partnersel.py) is a pure function of (global node id, round, pick,
seed), so every shard selects exactly the partners the single-device
engine would — seeded sharded runs are bitwise-identical to seeded
single-device runs, which the tests assert.

Collectives per round, riding ICI:

- the **push** direction scatters rows into arbitrary global partners, so
  each shard scatter-ORs into a global-width buffer and the shards combine
  with an all_to_all "reduce-scatter-OR" (split the buffer by destination
  shard, exchange, OR the received stack) — each device ends with only its
  own rows;
- the state **exchange**: each shard all_gathers its updated local state
  (seen for push-pull, newly-frontier for fanout push) into the global
  history ring that next round's delay-line reads index.

Like the flood engine, the history ring has a ``ring_mode``:

- ``"replicated"`` — full (ring, N, W) ring per chip, write-time
  all_gather, local reads (above);
- ``"sharded"`` — per-chip (ring, N/shards, W). Fanout push reads only
  its OWN rows' past frontiers, so the sharded ring drops the exchange
  all_gather entirely — strictly less ICI traffic AND less HBM. The
  anti-entropy protocols read the PARTNER's past state: the sharded ring
  reconstructs the (t − d) global slice per distinct delay value d at
  read time (one all_gather each; exactly one for the constant-delay
  default) and selects each node's partner row from the slice matching
  its edge delay.

``"auto"`` picks sharded for fanout push always, for anti-entropy under
uniform delay (same traffic, 1/shards HBM), and otherwise replicated
until the ring would exceed RING_REPLICATED_MAX_BYTES per chip.

On the sharded ring, ``exchange="delta"`` replaces the anti-entropy
read-time slice all_gathers with the sparse frontier-delta exchange
(`parallel/exchange.py`). The ring holds cumulative seen-state, so each
tick's delta vs the previous slot is small in steady state; one
all_gather of fixed-capacity (idx, val) buffers moves it (partner picks
are global-random — every shard needs every delta, so all_to_all buys
nothing here), and each shard maintains L per-delay MIRRORS of the
global (t - d) slices, advanced incrementally by OR-ing the received
deltas — exact because seen is OR-monotone. A capacity overflow
anywhere raises the slot's mesh-uniform flag and the affected mirror
advance dense-resets from a full slice all_gather (the hist slot IS the
cumulative slice, so the reset is exact). Bitwise-identical counters on
every path; fanout push's sharded ring reads no remote state at all
("none" — nothing to compress).

``exchange="async"`` (bounded-staleness async ticks,
parallel/async_ticks.py) removes the read-side exchange barrier for the
anti-entropy protocols: partners are global-random — no locality to
preserve — so async(K) is the same protocol with every partner-read
delay clamped host-side to ``max(d, K)`` (`clamp_partner_delays`,
applied by the driver BEFORE staging so the compiled runner, the
checkpoint fingerprint, and the synchronous parity reference all see
the same delays). With every read then >= K ticks deep, the exchange
collective for round t+1's reads can be issued at the END of round t —
a full round before its first reader. The delta path's per-delay
mirrors already ARE that double-buffer (the mirror advance touches only
slots finalized this round or earlier); the dense path grows a
``landed`` carry of prefetched (t - d) global slices that replaces the
read-time per-delay all_gathers, advanced the same way. ``pushk``
pushes same-round digests — there is nothing to overlap — and raises.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from p2p_gossip_tpu.parallel import async_ticks

from p2p_gossip_tpu.engine.sync import MIN_CHUNK_SHARES
from p2p_gossip_tpu.models.churn import effective_generated, up_mask_jnp
from p2p_gossip_tpu.models.generation import Schedule
from p2p_gossip_tpu.models.linkloss import drop_mask_jnp
from p2p_gossip_tpu.models.partnersel import pick_index_jnp
from p2p_gossip_tpu.models.topology import Graph
from p2p_gossip_tpu.ops import bitmask
from p2p_gossip_tpu.ops.segment import scatter_or
from p2p_gossip_tpu.parallel.engine_sharded import (
    _padded_churn,
    _padded_device_graph,
)
from p2p_gossip_tpu.parallel.mesh import NODES_AXIS, SHARES_AXIS
from p2p_gossip_tpu import telemetry
from p2p_gossip_tpu.telemetry import digest as tel_digest
from p2p_gossip_tpu.telemetry import rings as tel_rings
from p2p_gossip_tpu.utils.stats import NodeStats


def _reduce_scatter_or(pushed_global: jnp.ndarray, n_shards: int, n_loc: int):
    """(n_padded, W) per-device push buffers -> (n_loc, W) OR of every
    device's pushes into THIS device's rows. all_to_all moves each
    destination shard's slice to its owner; the OR folds the stack."""
    w = pushed_global.shape[-1]
    parts = pushed_global.reshape(n_shards, n_loc, w)
    recv = lax.all_to_all(parts, NODES_AXIS, split_axis=0, concat_axis=0)
    return lax.reduce(recv, jnp.uint32(0), lax.bitwise_or, (0,))


@functools.lru_cache(maxsize=32)
def build_partnered_runner(
    mesh: Mesh,
    protocol: str,            # "pushpull" | "pull" | "pushk"
    n_padded: int,
    ring_size: int,
    chunk_size: int,
    horizon: int,
    fanout: int = 1,
    loss: tuple | None = None,
    record_coverage: bool = False,
    ring_mode: str = "replicated",
    delay_values: tuple | None = None,
    telemetry_on: bool = False,
    exchange_mode: str = "dense",
    delta_capacity: int = 0,
    hub_count: int = 0,
    delta_aggregate: bool = False,
    replica_axis: str | None = None,
    local_replicas: int = 1,
    per_replica_loss: bool = False,
    async_k: int = 0,
    async_staleness: tuple = (),
):
    """Compile the per-pass runner for a random-partner protocol over the
    mesh. Memoized on mesh/shapes like engine_sharded.build_sharded_runner.

    ``replica_axis`` switches to CAMPAIGN mode over a factorized
    (replica, node) mesh, exactly like
    engine_sharded.build_sharded_runner: the round step is vmapped over
    each replica shard's ``local_replicas`` batch inside one shared
    fori_loop. Per-replica operands grow a leading replica dim —
    origins/gen_ticks (R, chunk), churn intervals (R, n_padded, K, still
    replicated over nodes: partner up-checks need every node), the
    protocol seed becomes an (R,) vector, and ``per_replica_loss``
    appends an (R,) uint32 loss-seed vector (static ``loss`` then
    (threshold, None); traced seeds feed the same coin, so solo runs
    with the matching static seed are bitwise-identical). Outputs keep
    the replica axis instead of the share-shard stack; second return
    value is the per-replica pass width (``chunk_size``).

    Counters come back stacked per share-shard — (n_share_shards, n_padded)
    int32 received and uint32 sent lo/hi pairs — and the host folds them in
    int64 (a psum of the raw u64 halves would drop carries).

    ``telemetry_on`` (static) carries a (horizon, NUM_METRICS) metric
    ring through the round loop (rows psum'ed over node shards; one ring
    per share-shard, stacked like the counters) — one extra trailing
    output.

    ``exchange_mode`` "delta" (sharded ring, anti-entropy protocols
    only) swaps the per-delay slice all_gathers for the sparse
    seen-state delta exchange (module docstring): one fixed-capacity
    all_gather of changed-word buffers per round plus L incrementally
    advanced mirrors of the delayed global slices — bitwise-identical
    counters, one extra trailing (1, 8) uint32 counter output
    [used_entries_lo, used_entries_hi, overflow_write_ticks,
    dense_fallback_reads, exchange_ticks, 0, 0, 0] per share-shard.

    ``exchange_mode`` "hub" rides the same machinery with the
    degree-split transport on top: ``hub_count`` top-degree rows per
    shard ship their delta words index-free via a per-round all_gather
    into a slot-aligned hub ring, and the sparse buffers carry only the
    tail cut — the caller appends three operands (``need_tail``
    (n_padded, 1) bool, ``hub_local`` (k, h), ``hub_global`` (k, h))
    after the base eight. The mirror advance overlays the slot's hub
    block onto the scattered tail canvas (disjoint row sets) before the
    OR — bitwise-identical by OR-monotonicity. ``hub_count == 0``
    degenerates to the plain delta program. ``delta_aggregate`` selects
    compress_deltas's destination-major pack (host-side
    `exchange.choose_aggregate` decision; outputs are bitwise-identical
    either way).

    ``async_k`` > 0 (sharded ring, anti-entropy only — the driver feeds
    delays already clamped to >= K via `clamp_partner_delays`) enables
    the bounded-staleness async read side (module docstring): on the
    dense transport a ``landed`` carry of prefetched (t - d) global
    slices replaces the read-time all_gathers, advanced at the end of
    each round from the just-written ring (exact for every d >= 1 —
    slot t + 1 - d is final once round t's write lands); the delta
    mirrors need no restructuring. ``async_staleness`` pairs each
    ``delay_values`` entry with its added-lateness amount
    (`protocol_staleness_amounts` — the builder only sees clamped
    delays, so the pre-clamp bookkeeping must ride in) for the
    ``staleness``/``stale_folds`` telemetry columns."""
    if protocol not in ("pushpull", "pull", "pushk"):
        raise ValueError(f"unknown protocol {protocol!r}")
    if fanout < 1:
        raise ValueError(f"fanout must be >= 1, got {fanout}")
    tel = tel_rings.active(telemetry_on)
    dig = tel_digest.active(telemetry_on)
    campaign = replica_axis is not None
    if campaign:
        if local_replicas < 1:
            raise ValueError(
                f"local_replicas must be >= 1, got {local_replicas}"
            )
        n_share_shards = 1
    else:
        n_share_shards = mesh.shape[SHARES_AXIS]
    if per_replica_loss and (not campaign or loss is None):
        raise ValueError(
            "per_replica_loss requires replica_axis and a loss model"
        )
    rb = local_replicas if campaign else 1
    n_node_shards = mesh.shape[NODES_AXIS]
    n_loc = n_padded // n_node_shards
    w = bitmask.num_words(chunk_size)
    k = fanout if protocol == "pushk" else 1
    # "pushpull" and "pull" share the anti-entropy shape (one partner, ring
    # of seen-states); "pull" skips the push direction and credits `sent`
    # to the responder (see run_pushpull_sim's mode="pull" docs).
    anti = protocol in ("pushpull", "pull")
    sharded_ring = ring_mode == "sharded"
    hist_rows = (n_padded // n_node_shards) if sharded_ring else n_padded
    # "hub" rides the delta machinery: tail rows on the sparse buffers,
    # hub rows on a dense per-round all_gather block (hub_count == 0
    # degenerates to the plain delta program — no zero-size collectives).
    delta = exchange_mode in ("delta", "hub")
    hub = exchange_mode == "hub" and hub_count > 0
    if delta and not (sharded_ring and anti):
        raise ValueError(
            "exchange_mode='delta' needs the sharded ring and an "
            "anti-entropy protocol (fanout push reads no remote state)"
        )
    if delta and delta_capacity < 1:
        raise ValueError(f"delta_capacity must be >= 1, got {delta_capacity}")
    if delta and ring_size < 2:
        # The per-tick delta compares against the previous slot, which
        # must survive this tick's write.
        raise ValueError("exchange_mode='delta' needs ring_size >= 2")
    if delta:
        from p2p_gossip_tpu.parallel import exchange as exch
    if async_k > 0:
        if not (sharded_ring and anti):
            raise ValueError(
                "async_k > 0 needs the sharded ring and an anti-entropy "
                "protocol (fanout push exchanges same-round digests — "
                "nothing to overlap; parallel/async_ticks.py)"
            )
        if not delay_values or len(async_staleness) != len(delay_values):
            raise ValueError(
                "async_k > 0 needs delay_values and a matching "
                "async_staleness tuple (one amount per distinct delay)"
            )
    # Dense transport under async: the landed double-buffer replaces the
    # read-time slice all_gathers (the delta mirrors already are one).
    landed_on = async_k > 0 and not delta
    n_groups = len(delay_values) if delay_values else 1

    def pass_fn(
        ell_idx, ell_delay, degree, churn_start, churn_end,
        origins, gen_ticks, seed, *extra_args,
    ):
        # Local: ell_* (n_loc, dmax), degree (n_loc,), origins/gen_ticks
        # (chunk_size,). Replicated: churn_* (n_padded, K) — partner up
        # checks need every node's intervals — and the seed scalar.
        # Campaign mode prepends a local replica dim rb to churn_*,
        # origins, gen_ticks and the seed, and appends the per-replica
        # loss-seed vector (rb,) when per_replica_loss. The hub split
        # appends (need_tail, hub_local, hub_global) after that.
        base_extra = 1 if (campaign and per_replica_loss) else 0
        lseeds = extra_args[0] if base_extra else None
        if hub:
            need_tail = extra_args[base_extra]          # (n_loc, 1) bool
            hub_rows_l = extra_args[base_extra + 1][0]  # (h,) local rows
            hub_global = extra_args[base_extra + 2]     # (k, h) global
        row_offset = lax.axis_index(NODES_AXIS).astype(jnp.int32) * n_loc
        node_ids = row_offset + jnp.arange(n_loc, dtype=jnp.int32)
        slots = jnp.arange(chunk_size, dtype=jnp.int32)
        rows_l = jnp.arange(n_loc, dtype=jnp.int32)
        live_row = degree > 0  # ELL padding rows never exchange

        state = (
            jnp.zeros((n_loc, w), dtype=jnp.uint32),              # seen
            # History ring: global rows (replicated) or this shard's rows
            # only (sharded — read_slice reassembles what's needed).
            jnp.zeros((ring_size, hist_rows, w), dtype=jnp.uint32),
            jnp.zeros((n_loc,), dtype=jnp.int32),                 # received
            jnp.zeros((n_loc,), dtype=jnp.uint32),                # sent lo
            jnp.zeros((n_loc,), dtype=jnp.uint32),                # sent hi
            jnp.zeros(
                (horizon if record_coverage else 0,
                 chunk_size if record_coverage else 0),
                dtype=jnp.int32,
            ),                                                    # coverage
        )
        if tel:
            state = state + (tel_rings.init(horizon),)            # metrics
        dig_i = 6 + (1 if tel else 0)
        if dig:
            state = state + (tel_digest.init(horizon),)           # digests
        ex_i = 6 + (1 if tel else 0) + (1 if dig else 0)
        if delta:
            # Every shard needs every delta (global-random partners):
            # one buffer per shard, all rows candidates, self included.
            # Under the hub split the dense block ships the hub rows,
            # so the sparse buffers carry only the tail cut.
            need_all = (
                need_tail if hub
                else jnp.ones((n_loc, 1), dtype=jnp.bool_)
            )
            state = state + (
                # Per-delay mirrors of the global (t - d) seen slices —
                # invariant at entry to body(t): mirrors[j] equals the
                # all_gathered hist[(t - delay_values[j]) mod ring].
                jnp.zeros(
                    (len(delay_values), n_padded, w), dtype=jnp.uint32
                ),
                # Received-delta rings, slot-aligned with hist; axis 1
                # is the source shard. idx -1 = empty.
                jnp.full(
                    (ring_size, n_node_shards, delta_capacity),
                    -1, dtype=jnp.int32,
                ),
                jnp.zeros(
                    (ring_size, n_node_shards, delta_capacity),
                    dtype=jnp.uint32,
                ),
                jnp.zeros((ring_size,), dtype=jnp.bool_),  # overflow flags
                # [used_lo, used_hi, overflow_writes, fallback_reads,
                #  exchange_ticks, 0, 0, 0]
                jnp.zeros((8,), dtype=jnp.uint32),
            )
        if hub:
            # Hub delta-word blocks, slot-aligned with didx_ring: every
            # shard's hub-row d_words, all_gathered each round. OR-ing a
            # slot's block into a mirror is exact (deltas are
            # OR-monotone; unwritten slots hold zeros — a no-op).
            state = state + (
                jnp.zeros(
                    (ring_size, n_node_shards * hub_count, w),
                    dtype=jnp.uint32,
                ),
            )
        landed_i = (
            6 + (1 if tel else 0) + (1 if dig else 0)
            + (5 if delta else 0) + (1 if hub else 0)
        )
        if landed_on:
            # Async landed double-buffer: one prefetched global (t - d)
            # seen-slice per distinct delay. Zeros-init is exact — at
            # t=0 every read targets pre-history (all-zero) slices.
            state = state + (
                jnp.zeros(
                    (len(delay_values), n_padded, w), dtype=jnp.uint32
                ),
            )
        if campaign:
            # One state copy per local replica: the round step is
            # vmapped over this leading rb axis inside the fori_loop.
            state = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (rb,) + a.shape), state
            )

        def tick(rstate, origins_r, gen_ticks_r, seed_r, lseed_r,
                 churn_start_r, churn_end_r, t):
            # ONE replica's round over its node shard — the solo body
            # verbatim; all collectives address NODES_AXIS only, so the
            # campaign vmap batches them per replica.
            seen, hist, received, sent_lo, sent_hi, cov_hist = rstate[:6]
            if delta:
                (mirrors, didx_ring, dval_ring, dflag_ring,
                 ectr) = rstate[ex_i:ex_i + 5]
                hub_ring = rstate[ex_i + 5] if hub else None
            landed = rstate[landed_i] if landed_on else None
            # The remote views THIS round folds in (pre-advance) — what
            # the staleness telemetry charges against.
            views_in = mirrors if delta else landed  # None unless async
            t = jnp.int32(t)
            if anti:
                kidx = pick_index_jnp(node_ids, t, 0, degree, seed_r)
                partners = ell_idx[rows_l, kidx]          # (n_loc,) global
                delay = ell_delay[rows_l, kidx]
            else:
                picks = jnp.arange(k, dtype=jnp.int32)[None, :]
                kidx = pick_index_jnp(
                    node_ids[:, None], t, picks, degree[:, None], seed_r
                )
                partners = ell_idx[rows_l[:, None], kidx]  # (n_loc, k)
                delay = ell_delay[rows_l[:, None], kidx]

            slot = jnp.mod(t - delay, ring_size)
            if sharded_ring:
                # Own-row reads are local in the sharded layout.
                loc_flat = hist.reshape(ring_size * hist_rows, w)
                if anti:
                    my_old = loc_flat[slot * hist_rows + rows_l]
                    # Partner state: reassemble the (t - d) global slice
                    # per distinct delay value and select each node's
                    # partner row from the slice its edge dictates. The
                    # delta path reads the incrementally-advanced
                    # mirrors instead — no per-delay all_gather.
                    remote = jnp.zeros((n_loc, w), dtype=jnp.uint32)
                    for j, dval in enumerate(delay_values):
                        if delta:
                            f_d = mirrors[j]
                        elif landed_on:
                            # The prefetched slice — its all_gather was
                            # issued at the end of the PREVIOUS round.
                            f_d = landed[j]
                        else:
                            f_d = lax.all_gather(
                                hist[jnp.mod(t - dval, ring_size)],
                                NODES_AXIS, axis=0, tiled=True,
                            )
                        remote = jnp.where(
                            (delay == dval)[:, None], f_d[partners], remote
                        )
                else:
                    my_old = loc_flat[slot * hist_rows + rows_l[:, None]]
            else:
                flat = hist.reshape(ring_size * n_padded, w)
                if anti:
                    remote = flat[slot * n_padded + partners]          # pull
                    my_old = flat[slot * n_padded + node_ids]          # push
                else:
                    my_old = flat[slot * n_padded + node_ids[:, None]]  # (n_loc,k,W)

            up = up_mask_jnp(churn_start_r, churn_end_r, t)   # (n_padded,)
            self_ids = node_ids if anti else node_ids[:, None]
            attempted = (
                up[self_ids] & up[partners]
                & (live_row if anti else live_row[:, None])
            )
            pull_ok = push_ok = attempted
            if loss is not None:
                thr = loss[0]
                lseed = loss[1] if lseed_r is None else lseed_r
                push_ok = attempted & ~drop_mask_jnp(
                    self_ids, partners, t, thr, lseed
                )
                if anti:
                    pull_ok = attempted & ~drop_mask_jnp(
                        partners, node_ids, t, thr, lseed
                    )

            if anti:
                # Responder credit for pull mode, before loss masking.
                pc_remote = bitmask.popcount_rows(remote)
                remote = jnp.where(pull_ok[:, None], remote, jnp.uint32(0))
                if protocol == "pull":
                    pushed_local = jnp.zeros((n_loc, w), dtype=jnp.uint32)
                    # Each attempted pull credits the (possibly remote)
                    # responder; contributions sum across node shards.
                    # uint32 accumulator — the driver guards
                    # degree x chunk < 2^32 (see _check_pull_credit_bound).
                    sent_add = lax.dynamic_slice_in_dim(
                        lax.psum(
                            jnp.zeros((n_padded,), dtype=jnp.uint32)
                            .at[partners]
                            .add(
                                jnp.where(attempted, pc_remote, 0)
                                .astype(jnp.uint32)
                            ),
                            NODES_AXIS,
                        ),
                        row_offset, n_loc,
                    )
                else:
                    pushed = scatter_or(
                        n_padded, partners,
                        jnp.where(push_ok[:, None], my_old, jnp.uint32(0)),
                    )
                    pushed_local = _reduce_scatter_or(
                        pushed, n_node_shards, n_loc
                    )
                    sent_add = jnp.where(
                        attempted, bitmask.popcount_rows(my_old), 0
                    )
            else:
                payload_ok = jnp.where(
                    push_ok[..., None], my_old, jnp.uint32(0)
                )
                pushed = scatter_or(
                    n_padded, partners.reshape(-1),
                    payload_ok.reshape(n_loc * k, w),
                )
                pushed_local = _reduce_scatter_or(pushed, n_node_shards, n_loc)
                pick_cnt = bitmask.popcount_rows(
                    my_old.reshape(n_loc * k, w)
                ).reshape(n_loc, k)
                sent_add = jnp.sum(jnp.where(attempted, pick_cnt, 0), axis=1)

            sent_lo, sent_hi = bitmask.add_u64(sent_lo, sent_hi, sent_add)

            local_origin_rows = origins_r - row_offset
            in_shard = (local_origin_rows >= 0) & (local_origin_rows < n_loc)
            gen_active = (gen_ticks_r == t) & in_shard & up[origins_r]
            gen_bits = bitmask.slot_scatter(
                n_loc, w, local_origin_rows, slots, gen_active
            )

            if anti:
                incoming = (remote | pushed_local) & ~seen
                newly_cnt = bitmask.popcount_rows(incoming)
                if tel:
                    newbits = incoming | (gen_bits & ~seen)
                    gathered = tel_rings.total_bits(remote | pushed_local)
                    if loss is None:
                        dropped = jnp.uint32(0)
                    else:
                        dropped = tel_rings.u32sum(
                            jnp.where(attempted & ~pull_ok, pc_remote, 0)
                        )
                        if protocol != "pull":
                            dropped = dropped + tel_rings.u32sum(
                                jnp.where(
                                    attempted & ~push_ok,
                                    bitmask.popcount_rows(my_old), 0,
                                )
                            )
                received = received + newly_cnt
                seen = seen | incoming | gen_bits
                exchange = seen                       # hist holds seen-state
            else:
                newly = pushed_local & ~seen
                newly_cnt = bitmask.popcount_rows(newly)
                if tel:
                    newbits = newly | (gen_bits & ~seen)
                    gathered = tel_rings.total_bits(pushed_local)
                    dropped = (
                        jnp.uint32(0)
                        if loss is None
                        else tel_rings.u32sum(
                            jnp.where(attempted & ~push_ok, pick_cnt, 0)
                        )
                    )
                received = received + newly_cnt
                seen = seen | newly | gen_bits
                exchange = newly | gen_bits           # hist holds frontier
            if sharded_ring:
                if delta:
                    # The previous slot's cumulative slice — read before
                    # this tick's write (distinct slots: ring_size >= 2).
                    prev = hist[jnp.mod(t - 1, ring_size)]
                # Local write; reads reassemble at read time (or stay
                # local entirely for fanout push).
                hist = hist.at[jnp.mod(t, ring_size)].set(exchange)
            else:
                full = lax.all_gather(exchange, NODES_AXIS, axis=0, tiled=True)
                hist = hist.at[jnp.mod(t, ring_size)].set(full)
            if delta:
                # Write-time sparse exchange: the seen-state is
                # cumulative, so this tick's delta vs the previous slot
                # is exactly the words OR-advancing every mirror needs.
                d_words = exchange & ~prev
                cidx, cval, dcounts = exch.compress_deltas(
                    d_words, need_all, delta_capacity,
                    aggregate=delta_aggregate,
                )
                idx_recv = lax.all_gather(cidx, NODES_AXIS, axis=0, tiled=True)
                val_recv = lax.all_gather(cval, NODES_AXIS, axis=0, tiled=True)
                ovf = lax.psum(
                    jnp.any(dcounts > delta_capacity).astype(jnp.int32),
                    NODES_AXIS,
                ) > 0
                slot_w = jnp.mod(t, ring_size)
                didx_ring = didx_ring.at[slot_w].set(idx_recv)
                dval_ring = dval_ring.at[slot_w].set(val_recv)
                dflag_ring = dflag_ring.at[slot_w].set(ovf)
                if hub:
                    # Index-free hub leg: the hub rows' delta words ride
                    # a plain all_gather into the slot-aligned hub ring.
                    hub_all = lax.all_gather(
                        d_words[hub_rows_l], NODES_AXIS, axis=0, tiled=True
                    )
                    hub_ring = hub_ring.at[slot_w].set(hub_all)
                # Advance each mirror to the slice next round reads:
                # u = t + 1 - d. A flagged slot dense-resets from a full
                # slice all_gather (the hist slot IS the cumulative
                # slice — exact); otherwise OR in the slot's received
                # deltas (an unwritten slot holds -1 indices -> no-op,
                # matching the all-zero pre-history slices).
                new_mirrors = []
                fb_t = jnp.zeros((), dtype=jnp.uint32)
                for j, dv in enumerate(delay_values):
                    slot_u = jnp.mod(t + 1 - dv, ring_size)

                    def dense_m(_, s=slot_u):
                        return lax.all_gather(
                            hist[s], NODES_AXIS, axis=0, tiled=True
                        )

                    def sparse_m(_, s=slot_u, mj=mirrors[j]):
                        recon = exch.scatter_deltas(
                            didx_ring[s], dval_ring[s], n_loc, w, n_padded
                        )
                        if hub:
                            # Overlay the slot's hub block onto the tail
                            # canvas (disjoint rows — the tail plan
                            # excludes hub rows), then OR the combined
                            # delta into the mirror.
                            recon = exch.overlay_hub(
                                recon, hub_global, hub_ring[s]
                            )
                        return mj | recon

                    new_mirrors.append(
                        lax.cond(
                            dflag_ring[slot_u], dense_m, sparse_m,
                            operand=None,
                        )
                    )
                    fb_t = fb_t + dflag_ring[slot_u].astype(jnp.uint32)
                mirrors = jnp.stack(new_mirrors)
                used_t = lax.psum(
                    jnp.sum(jnp.minimum(dcounts, delta_capacity)),
                    NODES_AXIS,
                ).astype(jnp.uint32)
                u_lo, u_hi = bitmask.add_u64(ectr[0], ectr[1], used_t)
                ectr = jnp.stack((
                    u_lo, u_hi,
                    ectr[2] + ovf.astype(jnp.uint32),
                    ectr[3] + fb_t,
                    ectr[4] + jnp.uint32(1),
                    ectr[5], ectr[6], ectr[7],
                ))
            if landed_on:
                # Advance the double-buffer to the slices the NEXT round
                # reads (u = t + 1 - d): one background all_gather per
                # distinct delay, issued a full round before its first
                # reader — the read-side barrier the async mode removes.
                # The post-write ring is exact for every d >= 1: slot u
                # was finalized by this round's write (d = 1) or an
                # earlier one, and no later write touches it before the
                # read (ring_size >= dmax + 1).
                landed = jnp.stack([
                    lax.all_gather(
                        hist[jnp.mod(t + 1 - dv, ring_size)],
                        NODES_AXIS, axis=0, tiled=True,
                    )
                    for dv in delay_values
                ])
            if record_coverage:
                cov = lax.psum(
                    bitmask.coverage_per_slot(seen, chunk_size), NODES_AXIS
                )
                cov_hist = lax.dynamic_update_slice(
                    cov_hist, cov[None], (t, 0)
                )
            out = (seen, hist, received, sent_lo, sent_hi, cov_hist)
            if tel:
                # Per-chip state-slice exchange words received this
                # round (schema docstring; push-direction all_to_all
                # traffic is not included); psum'ed into the mesh total
                # with the rest of the row.
                if delta:
                    ex_words = (
                        jnp.uint32(
                            (n_node_shards - 1)
                            * (2 * delta_capacity
                               + (hub_count * w if hub else 0))
                        )
                        + fb_t * jnp.uint32((n_node_shards - 1) * n_loc * w)
                    )
                elif sharded_ring:
                    ex_words = jnp.uint32(
                        n_groups * (n_node_shards - 1) * n_loc * w
                        if anti else 0
                    )
                else:
                    ex_words = jnp.uint32((n_node_shards - 1) * n_loc * w)
                # Async staleness accounting (schema docstring): each
                # delay bucket folding remote state later than its
                # original delay charges its added lateness whenever the
                # remote (cross-shard) part of the consumed view held
                # any bit. Static zeros on every sync path.
                stale_t = jnp.uint32(0)
                folds_t = jnp.uint32(0)
                if async_k > 0 and any(a > 0 for a in async_staleness):
                    remote_row = (
                        jnp.arange(n_padded, dtype=jnp.int32) // n_loc
                        != lax.axis_index(NODES_AXIS).astype(jnp.int32)
                    )
                    for j, amt in enumerate(async_staleness):
                        if amt <= 0:
                            continue
                        pending = jnp.any(
                            jnp.where(
                                remote_row[:, None], views_in[j],
                                jnp.uint32(0),
                            ) != 0
                        ).astype(jnp.uint32)
                        stale_t = stale_t + jnp.uint32(amt) * pending
                        folds_t = folds_t + pending
                pc_newbits = bitmask.popcount_rows(newbits)
                met_row = lax.psum(
                    tel_rings.row(
                        frontier_bits=tel_rings.u32sum(pc_newbits),
                        frontier_nodes=tel_rings.u32sum(pc_newbits > 0),
                        newly_infected=tel_rings.u32sum(newly_cnt),
                        msgs_gathered=gathered,
                        or_work=tel_rings.u32sum(sent_add),
                        loss_dropped=dropped,
                        exchange_words=ex_words,
                        staleness=stale_t,
                        stale_folds=folds_t,
                    ),
                    NODES_AXIS,
                )
                out = out + (tel_rings.write(rstate[6], t, met_row),)
            if dig:
                # Global node ids keep the salts mesh-shape-invariant; the
                # ELL-pad rows stay all-zero and the sparse fold skips
                # them, so this equals the solo protocol digest.
                dval = tel_digest.tick_digest_sharded(
                    seen, received, sent_lo,
                    node_ids=node_ids, axis_name=NODES_AXIS,
                    sent_hi=sent_hi,
                )
                out = out + (tel_digest.write(rstate[dig_i], t, dval),)
            if delta:
                out = out + (mirrors, didx_ring, dval_ring, dflag_ring, ectr)
            if hub:
                out = out + (hub_ring,)
            if landed_on:
                out = out + (landed,)
            return out

        if campaign:
            def body(t, state):
                if per_replica_loss:
                    return jax.vmap(
                        lambda rs, o, g, sd, ls, cs, ce:
                            tick(rs, o, g, sd, ls, cs, ce, t)
                    )(state, origins, gen_ticks, seed, lseeds,
                      churn_start, churn_end)
                return jax.vmap(
                    lambda rs, o, g, sd, cs, ce:
                        tick(rs, o, g, sd, None, cs, ce, t)
                )(state, origins, gen_ticks, seed, churn_start, churn_end)
        else:
            def body(t, state):
                return tick(state, origins, gen_ticks, seed, None,
                            churn_start, churn_end, t)

        loop_out = lax.fori_loop(0, horizon, body, state)
        received, sent_lo, sent_hi = loop_out[2], loop_out[3], loop_out[4]
        cov_hist = loop_out[5]
        if campaign:
            # Campaign outputs already carry the leading replica axis.
            out = (received, sent_lo, sent_hi, cov_hist)
            if tel:
                out = out + (loop_out[6],)
            if dig:
                out = out + (loop_out[dig_i],)
            if delta:
                out = out + (loop_out[ex_i + 4],)
            return out
        # Stack per share-shard (host folds in int64; psum of u32 halves
        # would drop carries).
        out = (received[None], sent_lo[None], sent_hi[None], cov_hist[None])
        if tel:
            out = out + (loop_out[6][None],)
        if dig:
            out = out + (loop_out[dig_i][None],)
        if delta:
            # Achieved-exchange counters (uniform across node shards).
            out = out + (loop_out[ex_i + 4][None],)
        return out

    if campaign:
        in_specs = (
            P(NODES_AXIS, None),  # ell_idx
            P(NODES_AXIS, None),  # ell_delay
            P(NODES_AXIS),        # degree
            # Churn is per replica but still replicated over nodes
            # (partner up-checks need every node's intervals).
            P(replica_axis, None, None),  # churn_start (R, n_padded, K)
            P(replica_axis, None, None),  # churn_end
            P(replica_axis, None),        # origins (R, chunk)
            P(replica_axis, None),        # gen_ticks
            P(replica_axis),              # seed (R,)
        ) + ((P(replica_axis),) if per_replica_loss else ()) + ((
            P(NODES_AXIS, None),  # need_tail (n_padded, 1)
            P(NODES_AXIS, None),  # hub_local (k, h)
            P(None, None),        # hub_global (k, h) replicated
        ) if hub else ())
        out_specs: tuple = (
            P(replica_axis, NODES_AXIS),
            P(replica_axis, NODES_AXIS),
            P(replica_axis, NODES_AXIS),
            P(replica_axis, None, None),  # coverage (psum'ed over nodes)
        )
        if tel:
            out_specs = out_specs + (P(replica_axis, None, None),)
        if dig:
            out_specs = out_specs + (P(replica_axis, None),)
        if delta:
            out_specs = out_specs + (P(replica_axis, None),)
    else:
        in_specs = (
            P(NODES_AXIS, None),  # ell_idx
            P(NODES_AXIS, None),  # ell_delay
            P(NODES_AXIS),        # degree
            P(),                  # churn_start (replicated: partner checks)
            P(),                  # churn_end
            P(SHARES_AXIS),       # origins
            P(SHARES_AXIS),       # gen_ticks
            P(),                  # seed
        ) + ((
            P(NODES_AXIS, None),  # need_tail (n_padded, 1)
            P(NODES_AXIS, None),  # hub_local (k, h)
            P(None, None),        # hub_global (k, h) replicated
        ) if hub else ())
        out_specs = (
            P(SHARES_AXIS, NODES_AXIS),
            P(SHARES_AXIS, NODES_AXIS),
            P(SHARES_AXIS, NODES_AXIS),
            P(SHARES_AXIS, None, None),  # coverage (psum'ed over nodes)
        ) + (
            ((P(SHARES_AXIS, None, None),) if tel else ())
            + ((P(SHARES_AXIS, None),) if dig else ())
            + ((P(SHARES_AXIS, None),) if delta else ())  # exchange ctrs
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

def _audit_spec_partnered_runner(
    protocol: str, telemetry_on: bool = False, exchange: str = "dense",
    campaign: bool = False, async_k: int = 0,
):
    """Stage + build the sharded partnered runner on tiny shapes (same
    mesh policy as the flood audit spec). The u64 ``sent`` counter halves
    come back as (n_share_shards, n_padded) uint32 stacks, so the allowed
    uint32 minor dims include the padded row count alongside the bitmask
    word width. ``exchange`` "delta" audits the sparse seen-delta path
    (sharded ring; both mirror-advance cond branches trace). ``campaign``
    audits the replica-factorized mode on a (replicas, nodes) mesh — the
    jit surface run_sharded_protocol_campaign dispatches. ``async_k``
    > 0 audits the bounded-staleness landed-carry path on the dense
    transport (clamped delays, parallel/async_ticks.py)."""
    from p2p_gossip_tpu.models.topology import erdos_renyi
    from p2p_gossip_tpu.parallel.engine_sharded import (
        _audit_campaign_mesh,
        _audit_mesh,
    )
    from p2p_gossip_tpu.staticcheck.registry import AuditSpec
    from p2p_gossip_tpu.telemetry.schema import NUM_METRICS

    if campaign:
        from p2p_gossip_tpu.parallel.mesh import REPLICAS_AXIS

        mesh = _audit_campaign_mesh()
        local_replicas = 2
        r_batch = mesh.shape[REPLICAS_AXIS] * local_replicas
    else:
        mesh, _ = _audit_mesh()
    n_node_shards = mesh.shape[NODES_AXIS]
    graph = erdos_renyi(16, 0.3, seed=0)
    chunk, horizon = 32, 8
    ell_idx, ell_delays, degree, ring = _padded_device_graph(
        graph, None, 1, n_node_shards,
    )
    n_padded = ell_idx.shape[0]
    churn_start, churn_end = _padded_churn(None, n_padded, n_node_shards)
    capacity = 0
    hub_args: tuple = ()
    if exchange == "delta":
        from p2p_gossip_tpu.parallel import exchange as exch

        n_loc = n_padded // n_node_shards
        w = bitmask.num_words(chunk)
        capacity = exch.delta_capacity(n_loc, n_loc, w)
        runner, pass_size = build_partnered_runner(
            mesh, protocol, n_padded, ring, chunk, horizon, 1,
            (1 << 20, 7), False, ring_mode="sharded", delay_values=(1,),
            telemetry_on=telemetry_on, exchange_mode="delta",
            delta_capacity=capacity,
            replica_axis=("replicas" if campaign else None),
            local_replicas=(local_replicas if campaign else 1),
        )
    elif exchange == "hub":
        # Forced split — the tiny ER graph has no natural hubs, so the
        # honest planner would pick h = 0 and skip the hub program.
        from p2p_gossip_tpu.parallel import exchange as exch

        n_loc = n_padded // n_node_shards
        w = bitmask.num_words(chunk)
        hplan = exch.plan_partnered_hub_split(
            degree, n_node_shards, n_loc, w, hub_rows=2
        )
        capacity = hplan["capacity"]
        runner, pass_size = build_partnered_runner(
            mesh, protocol, n_padded, ring, chunk, horizon, 1,
            (1 << 20, 7), False, ring_mode="sharded", delay_values=(1,),
            telemetry_on=telemetry_on, exchange_mode="hub",
            delta_capacity=capacity, hub_count=hplan["hub_count"],
            delta_aggregate=True,
        )
        hub_args = (
            hplan["need_tail"], hplan["hub_local"], hplan["hub_global"],
        )
    elif async_k:
        ell_delays = async_ticks.clamp_partner_delays(ell_delays, async_k)
        ring = async_ticks.effective_ring(ring, async_k)
        runner, pass_size = build_partnered_runner(
            mesh, protocol, n_padded, ring, chunk, horizon, 1,
            (1 << 20, 7), False, ring_mode="sharded",
            delay_values=(max(1, async_k),), telemetry_on=telemetry_on,
            async_k=async_k, async_staleness=(max(0, async_k - 1),),
        )
    else:
        runner, pass_size = build_partnered_runner(
            mesh, protocol, n_padded, ring, chunk, horizon,
            2 if protocol == "pushk" else 1,
            (1 << 20, 7), False,
            ring_mode=("sharded" if campaign else "replicated"),
            delay_values=((1,) if campaign else None),
            telemetry_on=telemetry_on,
            replica_axis=("replicas" if campaign else None),
            local_replicas=(local_replicas if campaign else 1),
        )
    if campaign:
        origins = np.zeros((r_batch, pass_size), dtype=np.int32)
        gen_ticks = np.full((r_batch, pass_size), horizon, dtype=np.int32)
        gen_ticks[:, :2] = 0
        churn_start = np.zeros((r_batch, n_padded, 1), dtype=np.int32)
        churn_end = churn_start.copy()
    else:
        origins = np.zeros(pass_size, dtype=np.int32)
        gen_ticks = np.full(pass_size, horizon, dtype=np.int32)
        gen_ticks[:2] = 0
    words: tuple = (bitmask.num_words(chunk), n_padded)
    if telemetry_on:
        # Stacked per-shard digest rings are (1, horizon) uint32 — the
        # horizon is a declared minor width, like NUM_METRICS.
        words = words + (NUM_METRICS, horizon)
    if exchange in ("delta", "hub"):
        # Delta buffers (capacity minor dim) and the (1, 8) counter row.
        words = words + (capacity, 8)
    seed = (
        np.full(r_batch, 42, dtype=np.uint32) if campaign
        else np.uint32(42)
    )
    return AuditSpec(
        fn=runner,
        args=(
            ell_idx, ell_delays, degree, churn_start, churn_end,
            origins, gen_ticks, seed,
        ) + hub_args,
        integer_only=True,
        bitmask_words=words,
    )


from p2p_gossip_tpu.staticcheck.registry import register_entry  # noqa: E402

register_entry(
    "parallel.protocols_sharded.pushpull_runner",
    spec=lambda: _audit_spec_partnered_runner("pushpull"),
)
register_entry(
    "parallel.protocols_sharded.pushk_runner",
    spec=lambda: _audit_spec_partnered_runner("pushk"),
)
register_entry(
    "parallel.protocols_sharded.pushpull_runner[telemetry]",
    spec=lambda: _audit_spec_partnered_runner("pushpull", telemetry_on=True),
)
register_entry(
    "parallel.protocols_sharded.pushk_runner[telemetry]",
    spec=lambda: _audit_spec_partnered_runner("pushk", telemetry_on=True),
)
register_entry(
    "parallel.protocols_sharded.pushpull_runner[delta]",
    spec=lambda: _audit_spec_partnered_runner("pushpull", exchange="delta"),
)
register_entry(
    "parallel.protocols_sharded.pushpull_runner[campaign]",
    spec=lambda: _audit_spec_partnered_runner("pushpull", campaign=True),
)
register_entry(
    "parallel.protocols_sharded.pushpull_runner[async]",
    spec=lambda: _audit_spec_partnered_runner("pushpull", async_k=2),
)
register_entry(
    "parallel.protocols_sharded.pushpull_runner[hub]",
    spec=lambda: _audit_spec_partnered_runner("pushpull", exchange="hub"),
)


def _resolve_partnered_exchange(
    exchange: str,
    protocol: str,
    ring_mode: str,
    ell_delays: np.ndarray,
    ring: int,
    n_padded: int,
    n_node_shards: int,
    w: int,
    degree: np.ndarray,
    k_async: int = 0,
    stale_values: tuple = (),
    stale_amounts: tuple = (),
    hub_rows: int | None = None,
) -> tuple:
    """Shared exchange/ring resolution for the partnered drivers (solo
    and campaign — batch/campaign_sharded.py calls this too): pick the
    ring layout, resolve "auto", plan the delta capacity — and under
    ``exchange="hub"`` the degree split
    (`exchange.plan_partnered_hub_split`; partner picks are
    global-random, so node degree ranks the hub set, and the honest
    cost model usually picks h = 0 unless ``hub_rows`` pins it) — and
    assemble the ``stats.extra['exchange']`` report skeleton.

    Returns ``(ring_mode, ring_bytes, delay_values, exchange, capacity,
    hub_ops, aggregate, delta_on, exchange_extra, async_staleness)``
    where ``hub_ops`` is None or ``(hub_count, need_tail, hub_local,
    hub_global)`` — the builder static plus the three input operands the
    runner dispatch appends after the base eight."""
    from p2p_gossip_tpu.parallel import exchange as exch_mod
    from p2p_gossip_tpu.parallel.engine_sharded import resolve_ring_mode

    if exchange not in ("dense", "delta", "auto", "hub"):
        raise ValueError(f"unknown exchange mode {exchange!r}")
    anti = protocol in ("pushpull", "pull")
    if exchange in ("delta", "hub") and anti:
        # The sparse paths compress the sharded ring's read exchange.
        ring_mode = "sharded"
    distinct = tuple(int(v) for v in np.unique(ell_delays))
    if ring_mode == "auto" and protocol == "pushk":
        # Fanout push reads only its own rows' history: the sharded ring
        # drops the exchange all_gather outright.
        ring_mode = "sharded"
    ring_mode, ring_bytes = resolve_ring_mode(
        ring_mode, distinct[0] if len(distinct) == 1 else None,
        ring, n_padded, n_node_shards, w,
    )
    delay_values = distinct if ring_mode == "sharded" and anti else None
    if exchange == "auto":
        exchange = (
            "delta"
            if anti and ring_mode == "sharded" and n_node_shards > 1
            else "dense"
        )
    delta_on = (
        exchange in ("delta", "hub") and anti and ring_mode == "sharded"
    )
    n_loc = n_padded // n_node_shards
    hub_ops = None
    hub_report = None
    if exchange == "hub" and delta_on:
        hplan = exch_mod.plan_partnered_hub_split(
            degree, n_node_shards, n_loc, w,
            delay_splits=len(delay_values), hub_rows=hub_rows,
        )
        capacity = hplan["capacity"]
        hub_report = hplan["report"]
        if hplan["hub_count"] > 0:
            hub_ops = (
                hplan["hub_count"], hplan["need_tail"],
                hplan["hub_local"], hplan["hub_global"],
            )
        # hub_count == 0 degenerates to plain delta on the full cut.
    elif delta_on:
        # Worst case every local row changes — the anti-entropy delta
        # has no static cut to restrict it (global-random partners).
        capacity = exch_mod.delta_capacity(
            n_loc, n_loc, w, len(delay_values)
        )
    else:
        capacity = 0
    # Host-side default for compress_deltas(aggregate=...): modeled
    # scatter-address words (single destination bin here — the delta
    # rides an all_gather, not an all_to_all).
    aggregate = exch_mod.choose_aggregate(1, capacity) if delta_on else False
    dense_kind = (
        ("dense" if anti else "none")
        if ring_mode == "sharded" else "replicated"
    )
    exchange_extra = {
        "mode": ("hub" if hub_ops else "delta") if delta_on else dense_kind,
        "capacity": capacity,
        "modeled_dense_words_per_tick": (
            exch_mod.modeled_exchange_words_per_tick(
                dense_kind, n_shards=n_node_shards, n_loc=n_loc, w=w,
                delay_splits=len(delay_values) if delay_values else 1,
            )
        ),
    }
    if delta_on:
        exchange_extra["aggregated"] = aggregate
        exchange_extra["modeled_delta_words_per_tick"] = (
            exch_mod.modeled_exchange_words_per_tick(
                "delta", n_shards=n_node_shards, n_loc=n_loc, w=w,
                capacity=capacity,
            )
        )
    if hub_report is not None:
        exchange_extra.update({
            "hub_count": hub_report["hub_count"],
            "hub_rows_forced": hub_report["hub_rows_forced"],
            "crossover_h": hub_report["crossover_h"],
            "modeled_hub_words_per_tick": (
                hub_report["modeled_hub_words_per_tick"]
            ),
            "modeled_delta_words_per_tick": (
                hub_report["modeled_delta_words_per_tick"]
            ),
        })
    if k_async:
        exchange_extra.update(async_ticks.modeled_overlap_report(
            ("hub" if hub_ops else "delta") if delta_on else "dense",
            delay_values, k_async, n_node_shards, n_loc, w, capacity,
            hub_count=hub_ops[0] if hub_ops else 0,
        ))
        # group_offsets sees only clamped delays (amounts all 0 there);
        # the real added-lateness bookkeeping is pre-clamp.
        exchange_extra["staleness_amounts"] = list(stale_amounts)
    amounts_by_value = dict(zip(stale_values, stale_amounts))
    async_staleness = (
        tuple(amounts_by_value.get(v, 0) for v in delay_values)
        if k_async else ()
    )
    return (
        ring_mode, ring_bytes, delay_values, exchange, capacity,
        hub_ops, aggregate, delta_on, exchange_extra, async_staleness,
    )


def run_sharded_partnered_sim(
    graph: Graph,
    schedule: Schedule,
    horizon_ticks: int,
    mesh: Mesh,
    protocol: str = "pushpull",
    fanout: int = 2,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    chunk_size: int = 4096,
    seed: int = 0,
    churn=None,
    loss=None,
    record_coverage: bool = False,
    checkpoint_path: str | None = None,
    checkpoint_every: int = 1,
    stop_after_chunks: int | None = None,
    ring_mode: str = "auto",
    exchange: str = "dense",
    async_k: int = 2,
    hub_rows: int | None = None,
):
    """Drop-in counterpart of run_pushpull_sim / run_pushk_sim on a device
    mesh: identical per-node counters for any mesh shape (the counter-based
    partner hash keys on global node ids, so shard boundaries change
    nothing), including under churn and link loss.

    ``chunk_size`` is per share-shard, as in run_sharded_sim. With
    ``record_coverage`` also returns the (horizon, num_shares) per-tick
    node-coverage history (psum'ed over node shards, identical values to
    the single-device engines); returns stats alone otherwise, matching
    run_sharded_sim. ``checkpoint_path``/``checkpoint_every``/
    ``stop_after_chunks`` give run_sharded_sim's pass-boundary
    checkpoint/resume contract (mesh shape is fingerprinted — a resume on
    a different mesh starts fresh; not combinable with
    ``record_coverage``).

    ``exchange`` selects the anti-entropy cross-shard state exchange:
    "dense" (per-delay slice all_gathers, the default), "delta" (sparse
    seen-delta buffers + mirrors, module docstring — forces the sharded
    ring, bitwise-identical counters), "auto" (delta whenever the
    anti-entropy ring is sharded across >1 node shards), or "hub" (the
    degree-split transport: the ``hub_rows``-or-planned top-degree rows
    per shard ship their deltas index-free via a per-round all_gather
    block while the sparse buffers carry only the tail —
    `exchange.plan_partnered_hub_split`; the honest cost model usually
    picks h = 0 here, so ``hub_rows`` pins the split for parity tests).
    Fanout push reads no remote state on the sharded ring, so "delta"
    and "hub" degrade to that free path. Resolved mode, modeled
    traffic, and achieved counters land in
    ``stats.extra['exchange']``.

    "async" / "async-dense" / "async-delta" switch the anti-entropy
    read side to the bounded-staleness async path with ``async_k`` = K
    (module and `parallel/async_ticks.py` docstrings): every partner
    read delay is clamped host-side to ``max(d, K)``
    (`clamp_partner_delays` — the exact parity reference is the same
    runner on the pre-clamped delay array), the ring grows to
    ``max(dmax, K) + 1`` slots, and the exchange collectives are issued
    a round ahead of their readers. ``async_k`` is ignored on the
    synchronous spellings. ``pushk`` raises — fanout push exchanges
    same-round digests, nothing to overlap.
    """
    if protocol not in ("pushpull", "pull", "pushk"):
        raise ValueError(f"unknown protocol {protocol!r}")
    if protocol == "pull":
        from p2p_gossip_tpu.models.protocols import _check_pull_credit_bound

        _check_pull_credit_bound(graph, chunk_size, schedule)
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
    n_node_shards = mesh.shape[NODES_AXIS]
    chunk_size = min(chunk_size, max(MIN_CHUNK_SHARES, schedule.num_shares))
    chunk_size = bitmask.num_words(chunk_size) * bitmask.WORD_BITS

    # Shared staging with the flood engine; partner picks index per-edge
    # delays (no placeholder) and always land on valid entries (no mask).
    ell_idx, ell_delays, degree, ring = _padded_device_graph(
        graph, ell_delays, constant_delay, n_node_shards,
    )
    n_padded = ell_idx.shape[0]
    churn_start, churn_end = _padded_churn(churn, n_padded, n_node_shards)
    if k_async:
        # The async clamp happens BEFORE everything downstream — the
        # distinct-delay set, the ring size, the compiled runner, and
        # the checkpoint fingerprint all see the clamped array, so the
        # synchronous run on the same clamped delays is the bitwise
        # parity reference.
        stale_values, stale_amounts = async_ticks.protocol_staleness_amounts(
            ell_delays, k_async
        )
        ell_delays = async_ticks.clamp_partner_delays(ell_delays, k_async)
        ring = async_ticks.effective_ring(ring, k_async)
    else:
        stale_values, stale_amounts = (), ()

    # Ring layout (module docstring). The distinct-delay set comes from
    # the padded ELL delay array — a superset of the valid entries (row
    # padding fills with 1), which costs at most one spare slice
    # all_gather per round and can never miss a real delay.
    w = bitmask.num_words(chunk_size)
    (ring_mode, ring_bytes, delay_values, exchange, capacity, hub_ops,
     aggregate, delta_on, exchange_extra, async_staleness) = (
        _resolve_partnered_exchange(
            exchange, protocol, ring_mode, ell_delays, ring, n_padded,
            n_node_shards, w, degree, k_async, stale_values,
            stale_amounts, hub_rows,
        )
    )
    n_loc = n_padded // n_node_shards

    tel = telemetry.rings_enabled()
    runner, pass_size = build_partnered_runner(
        mesh, protocol, n_padded, ring, chunk_size, horizon_ticks,
        fanout if protocol == "pushk" else 1,
        loss.static_cfg if loss is not None else None,
        record_coverage,
        ring_mode=ring_mode, delay_values=delay_values, telemetry_on=tel,
        exchange_mode=exchange if delta_on else "dense",
        delta_capacity=capacity,
        hub_count=hub_ops[0] if hub_ops else 0,
        delta_aggregate=aggregate,
        async_k=k_async, async_staleness=async_staleness,
    )
    seed_arr = np.uint32(seed & 0xFFFFFFFF)
    n_share_shards = mesh.shape[SHARES_AXIS]

    received = np.zeros(n_padded, dtype=np.int64)
    sent = np.zeros(n_padded, dtype=np.int64)

    from p2p_gossip_tpu.utils.checkpoint import (
        checkpointed_chunks,
        make_checkpointer,
    )

    checkpointer = make_checkpointer(
        checkpoint_path, checkpoint_every, record_coverage,
        lambda: (
            "sharded_partnered_sim", protocol,
            fanout if protocol == "pushk" else 1,
            graph.n, graph.edges(), schedule.origins, schedule.gen_ticks,
            horizon_ticks, chunk_size,
            mesh.shape[SHARES_AXIS], mesh.shape[NODES_AXIS],
            ell_delays, int(seed) & 0xFFFFFFFF,
            churn.down_start if churn is not None else None,
            churn.down_end if churn is not None else None,
            np.asarray(loss.static_cfg, dtype=np.int64)
            if loss is not None
            else None,
        ),
        {"received": received, "sent": sent},
    )

    cov_chunks = []
    exch_counters = np.zeros(3, dtype=np.int64)  # used, ovf, fallback
    exch_ticks = 0
    chunks = schedule.chunk(pass_size) or [schedule]
    for ci, chunk in checkpointed_chunks(chunks, checkpointer, stop_after_chunks):
        origins, gen_ticks = chunk.padded(pass_size, horizon_ticks)
        with telemetry.span(
            "dispatch",
            kernel=f"parallel.protocols_sharded.{protocol}_runner", chunk=ci,
        ):
            args = (
                ell_idx, ell_delays, degree, churn_start, churn_end,
                origins, gen_ticks, seed_arr,
            )
            if hub_ops:
                args = args + (hub_ops[1], hub_ops[2], hub_ops[3])
            out = runner(*args)
        digest_head = None
        if delta_on:
            ec = np.asarray(out[-1], dtype=np.uint64)  # (shards, 8)
            exch_counters[0] += int(
                bitmask.combine_u64(ec[:, 0], ec[:, 1]).sum()
            )
            exch_counters[1] += int(ec[:, 2].sum())
            exch_counters[2] += int(ec[:, 3].sum())
            exch_ticks += int(ec[:, 4].sum())
        if tel:
            r, s_lo, s_hi, cov, met, dstream = out[:6]
            met_np = np.asarray(met)
            dig_np = np.asarray(dstream)
            for k in range(n_share_shards):
                tel_rings.emit_ring(
                    f"parallel.protocols_sharded.{protocol}_runner",
                    met_np[k], t0=0, ticks=horizon_ticks, chunk=ci, shard=k,
                )
                tel_digest.emit_digest(
                    f"parallel.protocols_sharded.{protocol}_runner",
                    dig_np[k], t0=0, ticks=horizon_ticks, chunk=ci, shard=k,
                )
            digest_head = int(dig_np[0][-1])
        else:
            r, s_lo, s_hi, cov = out[:4]
        telemetry.emit_progress(
            f"parallel.protocols_sharded.{protocol}_runner",
            chunk=ci, chunks_total=len(chunks),
            ticks_done=horizon_ticks * (ci + 1), digest_head=digest_head,
        )
        received += np.asarray(r, dtype=np.int64).sum(axis=0)
        sent += bitmask.combine_u64(
            jnp.asarray(s_lo), jnp.asarray(s_hi)
        ).reshape(-1, n_padded).sum(axis=0)
        if record_coverage:
            # Reassemble global slot order: shard k's local slots are the
            # pass's global slots [k*chunk_size, (k+1)*chunk_size).
            cov = np.asarray(cov)  # (n_share_shards, horizon, chunk_size)
            parts = []
            for k in range(n_share_shards):
                live = min(
                    max(chunk.num_shares - k * chunk_size, 0), chunk_size
                )
                parts.append(cov[k, :, :live])
            cov_chunks.append(np.concatenate(parts, axis=1))

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
    stats.extra["ring"] = {
        "mode": ring_mode,
        "bytes_per_chip": ring_bytes,
        "slots": ring,
        "delay_splits": len(delay_values) if delay_values else 1,
    }
    if delta_on:
        from p2p_gossip_tpu.parallel.engine_sharded import (
            _achieved_exchange_report,
        )

        exchange_extra = _achieved_exchange_report(
            exchange_extra, exch_counters, exch_ticks,
            n_node_shards, n_loc, w, capacity,
            hub_count=hub_ops[0] if hub_ops else 0,
        )
    stats.extra["exchange"] = exchange_extra
    if record_coverage:
        return stats, np.concatenate(cov_chunks, axis=1)
    return stats

"""vmap-replicated simulation campaigns on the synchronous tick engine.

One compiled XLA program runs R independent replicas of ``engine.sync``:
the replica axis is a leading ``vmap`` dimension over (origins, gen_ticks,
churn intervals) and therefore over every piece of loop state the tick
body carries (seen bitmask, history ring, counters, coverage history).
Nothing about the tick semantics changes — the batched kernels ``vmap``
the SAME ``_tick_body`` the solo engine jits (the bitwise-parity contract
of ``apply_tick_updates``) inside one shared ``while_loop`` — so replica
*i* is bitwise-identical to a solo run with the same seed. A replica past
its own quiescence has an all-zero frontier, making every further update
an exact identity; the batch runs until the slowest replica converges.
(``vmap`` over the solo jitted loop would work too, but JAX's batched-
while transform adds per-element selects on every carried array — the
shared-loop form measured ~4x cheaper to compile and run.)

What varies per replica (the seed ensemble): the generation schedule
(origins + gen ticks) and the churn downtime intervals, both sampled
host-side from the replica's seed with the same stream offsets the CLI
uses (so ``--seed s`` solo runs reproduce replica ``s`` exactly), and —
optionally — the link-loss seed: ``loss_seeds`` threads one uint32 seed
per replica as a traced operand through the gather's erasure coin
(ops/ell.py), so each replica draws an independent loss stream that a
solo run with the same loss seed reproduces bitwise. Without
``loss_seeds``, loss stays the shared static (threshold, seed) pair
baked into the compiled program (the cell-config reading). The graph and
the delay model are always shared.

The random-partner protocols (push-pull / pull / fanout push) batch the
same way via ``run_protocol_campaign``: one jitted ``vmap`` of the solo
round scan in ``models/protocols.py`` over (schedule, partner-pick seed,
loss seed, churn) — the counter-based pick hash keys on (node, round,
seed), so per-replica partner streams decorrelate while each replica
matches its solo run's choices bitwise.

Long campaigns checkpoint at replica-batch boundaries: accumulated
per-replica counters (and coverage rows) are snapshotted atomically
every ``checkpoint_every`` batches, fingerprinted over the replica seed
list and the full cell config, so an interrupted campaign resumes after
its last completed batch instead of restarting from zero
(utils/checkpoint.py).

Replicas are chunked to a static ``batch_size`` so XLA compiles one
program regardless of R; padding replicas get the never-fires gen-tick
sentinel and converge on tick one. With ``mesh``, the replica axis is
sharded over the existing (shares, nodes) device mesh — replicas are
embarrassingly parallel, so SPMD partitioning along the batch dimension
needs no collectives beyond the loop predicate's OR.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

import jax
import jax.numpy as jnp

from p2p_gossip_tpu.engine.sync import (
    MIN_CHUNK_SHARES,
    DeviceGraph,
    _resolve_block,
    _stage_inputs,
    _tick_body,
)
from p2p_gossip_tpu.models.churn import ChurnModel, effective_generated, random_churn
from p2p_gossip_tpu.models.generation import Schedule, uniform_renewal_schedule
from p2p_gossip_tpu.models.seeds import churn_stream_seed
from p2p_gossip_tpu.models.topology import Graph
from p2p_gossip_tpu.ops import bitmask
from p2p_gossip_tpu.staticcheck.registry import audited, register_entry
from p2p_gossip_tpu import telemetry
from p2p_gossip_tpu.telemetry import digest as tel_digest
from p2p_gossip_tpu.telemetry import rings as tel_rings
from p2p_gossip_tpu.utils import logging as p2plog
from p2p_gossip_tpu.utils.stats import NodeStats

log = p2plog.get_logger("Batch.Campaign")


@dataclasses.dataclass(frozen=True)
class ReplicaSet:
    """Host-side per-replica inputs of one campaign cell.

    ``origins``/``gen_ticks`` are (R, S) int32 — every replica padded to a
    common share count S with the never-fires sentinel (gen_tick ==
    horizon). ``churn`` stacks each replica's downtime intervals into a
    pair of (R, N, K) int32 arrays (None = no churn anywhere).
    """

    n: int
    origins: np.ndarray
    gen_ticks: np.ndarray
    seeds: np.ndarray  # (R,) int64 — provenance of each replica
    churn: tuple[np.ndarray, np.ndarray] | None = None

    def __post_init__(self):
        if self.origins.shape != self.gen_ticks.shape or self.origins.ndim != 2:
            raise ValueError(
                f"origins/gen_ticks must be matching (R, S) arrays, got "
                f"{self.origins.shape} and {self.gen_ticks.shape}"
            )
        if self.seeds.shape[0] != self.origins.shape[0]:
            raise ValueError("one seed per replica required")

    @property
    def num_replicas(self) -> int:
        return int(self.origins.shape[0])

    @property
    def shares_per_replica(self) -> int:
        return int(self.origins.shape[1])

    def replica_schedule(self, r: int, horizon: int) -> Schedule:
        """Replica ``r``'s schedule with sentinel padding stripped — what a
        solo engine run of this replica takes."""
        live = self.gen_ticks[r] < horizon
        return Schedule(self.n, self.origins[r][live], self.gen_ticks[r][live])

    def replica_churn(self, r: int) -> ChurnModel | None:
        if self.churn is None:
            return None
        return ChurnModel(
            n=self.n, down_start=self.churn[0][r], down_end=self.churn[1][r]
        )


def _stack_churn(
    n: int, horizon: int, seeds, churn_prob: float,
    mean_down_ticks: float, max_outages: int,
) -> tuple[np.ndarray, np.ndarray] | None:
    """Per-replica churn intervals, sampled with the CLI's churn stream
    offset (models/seeds.py) so replica seeds reproduce solo
    ``--churnProb`` runs."""
    if churn_prob <= 0.0:
        return None
    models = [
        random_churn(
            n, horizon, outage_prob=churn_prob,
            mean_down_ticks=mean_down_ticks, max_outages=max_outages,
            seed=churn_stream_seed(s),
        )
        for s in seeds
    ]
    return (
        np.stack([m.down_start for m in models]),
        np.stack([m.down_end for m in models]),
    )


def flood_replicas(
    graph: Graph,
    shares_per_replica: int,
    seeds,
    horizon: int,
    churn_prob: float = 0.0,
    mean_down_ticks: float = 10.0,
    max_outages: int = 1,
) -> ReplicaSet:
    """Seed ensemble for the flood coverage-time experiment: each replica
    floods S shares from seed-sampled random origins at t=0 — the same
    origin stream as the CLI's ``--floodCoverage`` (``default_rng(seed)
    .integers(0, n, S)``), so a solo run with the same seed is the exact
    reference for each replica."""
    seeds = np.asarray(seeds, dtype=np.int64).reshape(-1)
    origins = np.stack(
        [
            np.random.default_rng(int(s))
            .integers(0, graph.n, shares_per_replica)
            .astype(np.int32)
            for s in seeds
        ]
    )
    gen_ticks = np.zeros_like(origins)
    return ReplicaSet(
        n=graph.n, origins=origins, gen_ticks=gen_ticks, seeds=seeds,
        churn=_stack_churn(
            graph.n, horizon, seeds, churn_prob, mean_down_ticks, max_outages
        ),
    )


def gossip_replicas(
    graph: Graph,
    sim_time: float,
    tick_dt: float,
    seeds,
    horizon: int,
    gen_lo: float = 2.0,
    gen_hi: float = 5.0,
    churn_prob: float = 0.0,
    mean_down_ticks: float = 10.0,
    max_outages: int = 1,
) -> ReplicaSet:
    """Seed ensemble for the reference gossip workload: each replica
    samples its own uniform-renewal generation schedule (the reference's
    U(genLo, genHi) process). Schedules have different lengths across
    seeds; all are padded to the longest with the never-fires sentinel."""
    seeds = np.asarray(seeds, dtype=np.int64).reshape(-1)
    scheds = [
        uniform_renewal_schedule(
            graph.n, sim_time, tick_dt, gen_lo, gen_hi, seed=int(s)
        )
        for s in seeds
    ]
    s_max = max(s.num_shares for s in scheds)
    origins = np.zeros((len(scheds), s_max), dtype=np.int32)
    gen_ticks = np.full((len(scheds), s_max), horizon, dtype=np.int32)
    for r, sched in enumerate(scheds):
        origins[r, : sched.num_shares] = sched.origins
        gen_ticks[r, : sched.num_shares] = sched.gen_ticks
    return ReplicaSet(
        n=graph.n, origins=origins, gen_ticks=gen_ticks, seeds=seeds,
        churn=_stack_churn(
            graph.n, horizon, seeds, churn_prob, mean_down_ticks, max_outages
        ),
    )


@dataclasses.dataclass
class CampaignResult:
    """Per-replica outputs of one campaign cell, plus provenance.

    ``coverage`` is (R, horizon, S) per-tick node counts (None for gossip
    campaigns, which track counters only); counter arrays are (R, N).
    """

    n: int
    seeds: np.ndarray
    generated: np.ndarray
    received: np.ndarray
    sent: np.ndarray
    degree: np.ndarray
    horizon: int
    wall_s: float
    batch_size: int
    coverage: np.ndarray | None = None
    #: Run-level reports that don't fit the per-replica arrays (the
    #: sharded campaign's resolved ring / exchange modes, achieved delta
    #: counters, mesh shape) — mirrors ``NodeStats.extra``.
    extra: dict = dataclasses.field(default_factory=dict)

    @property
    def num_replicas(self) -> int:
        return int(self.seeds.shape[0])

    def replica_stats(self, r: int) -> NodeStats:
        """Replica ``r``'s counters as a NodeStats — the bridge into
        ``utils.analysis`` (redundancy, conservation checks)."""
        received = self.received[r]
        return NodeStats(
            generated=self.generated[r],
            received=received,
            forwarded=received.copy(),
            sent=self.sent[r],
            processed=self.generated[r] + received,
            degree=self.degree,
        )

    def totals_per_replica(self) -> dict[str, np.ndarray]:
        """(R,) totals of each counter — the samples the ensemble CIs and
        redundancy distributions in ``batch.stats`` reduce over."""
        return {
            "generated": self.generated.sum(axis=1),
            "received": self.received.sum(axis=1),
            "sent": self.sent.sum(axis=1),
            "processed": (self.generated + self.received).sum(axis=1),
        }


def _replica_sharding(mesh, ndim: int):
    """NamedSharding placing the leading replica axis across every mesh
    device (replicas are embarrassingly parallel — pure data parallelism
    over the flattened (shares, nodes) mesh)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from p2p_gossip_tpu.parallel.mesh import NODES_AXIS, SHARES_AXIS

    return NamedSharding(
        mesh, P((SHARES_AXIS, NODES_AXIS), *([None] * (ndim - 1)))
    )


def _shard_batch(mesh, arrays):
    """Place each (B, ...) array with its replica axis sharded over the
    mesh. B must divide by the device count (the batch padding in
    ``_iter_batches`` guarantees it when a mesh is passed)."""
    if mesh is None:
        return arrays
    return tuple(
        None
        if a is None
        else jax.device_put(a, _replica_sharding(mesh, a.ndim))
        for a in arrays
    )


def _batched_tick(dg, block, t, seen, hist, received, sent,
                  origins_b, gen_ticks_b, churn_b, slots, loss,
                  loss_seeds_b=None, telemetry_on: bool = False,
                  digest_on: bool = False):
    """One global tick over the whole (B, ...) replica batch: ``vmap`` of
    the solo engine's ``_tick_body`` (which carries the shared counter
    semantics) over the replica axis, at a COMMON tick counter ``t``.

    The common counter is what keeps the compiled loop cheap: a vmap over
    the solo ``while_loop`` would trigger JAX's batched-while transform
    (per-element select on every carried array, measured ~4x the compile
    and run cost at R=8). Instead ONE while_loop carries the batched
    state; a replica past its own quiescence simply has an all-zero
    frontier, so every update it computes is the identity — bitwise, not
    approximately — and the batch runs until the slowest replica settles.

    ``loss_seeds_b`` (optional (B,) uint32) vmaps a per-replica loss seed
    into the gather's erasure coin; ``loss`` is then (threshold, None).
    ``telemetry_on`` (static) additionally returns the per-replica
    (B, NUM_METRICS) metric rows the batched kernels write into their
    rings — vmap of the solo tick's row, so replica r's telemetry equals
    its solo run's. ``digest_on`` (static) appends the per-replica (B,)
    post-tick state digests (telemetry/digest.py) the same way — XOR
    folds are lane-local, so replica r's digest stream is bitwise its
    solo run's.
    """

    def tick_one(seen, hist, received, sent, origins, gen_ticks, churn,
                 lseed=None):
        if telemetry_on:
            (_, seen, hist, received, sent), met = _tick_body(
                dg, block, (t, seen, hist, received, sent), origins, slots,
                gen_ticks, churn, loss, 0, lseed, telemetry=True,
            )
        else:
            _, seen, hist, received, sent = _tick_body(
                dg, block, (t, seen, hist, received, sent), origins, slots,
                gen_ticks, churn, loss, 0, lseed,
            )
        out = (seen, hist, received, sent)
        if telemetry_on:
            out = out + (met,)
        if digest_on:
            out = out + (tel_digest.tick_digest(
                seen, received, sent, node_ids=dg.order
            ),)
        return out

    args = [seen, hist, received, sent, origins_b, gen_ticks_b]
    if churn_b is None and loss_seeds_b is None:
        fn = lambda se, h, r, sn, o, g: tick_one(se, h, r, sn, o, g, None)
    elif loss_seeds_b is None:
        fn = tick_one
        args.append(churn_b)
    elif churn_b is None:
        fn = lambda se, h, r, sn, o, g, ls: tick_one(
            se, h, r, sn, o, g, None, ls
        )
        args.append(loss_seeds_b)
    else:
        fn = tick_one
        args += [churn_b, loss_seeds_b]
    return jax.vmap(fn)(*args)


@audited(
    "batch.campaign._run_coverage_batch",
    spec=lambda: _audit_spec_batch("coverage"),
    count_compiles=True,
)
@functools.partial(
    jax.jit,
    static_argnames=(
        "chunk_size", "horizon", "block", "loss", "coverage_slots",
        "telemetry",
    ),
)
def _run_coverage_batch(
    dg: DeviceGraph,
    origins_b: jnp.ndarray,    # (B, S) int32
    gen_ticks_b: jnp.ndarray,  # (B, S) int32
    churn_b=None,              # optional ((B, N, K), (B, N, K))
    loss_seeds_b=None,         # optional (B,) uint32 per-replica loss seeds
    *,
    chunk_size: int,
    horizon: int,
    block: int,
    loss: tuple | None = None,
    coverage_slots: int | None = None,
    telemetry: bool = False,
):
    """Coverage-recording replica batch — the campaign counterpart of
    ``engine.sync._run_chunk_coverage`` with a leading replica axis on
    every piece of loop state. Pallas coverage stays off: the kernel's
    batching rule is unvalidated on hardware (ROADMAP open item).
    ``telemetry`` (static) carries a per-replica (B, horizon,
    NUM_METRICS) metric ring and returns it as one extra trailing
    output. Origins, churn and counters are in the caller's numbering,
    ``seen`` in the staged one, as in the solo chunk programs."""
    n, w = dg.n, bitmask.num_words(chunk_size)
    b = origins_b.shape[0]
    tel = tel_rings.active(telemetry)
    dig = tel_digest.active(telemetry)
    cov_slots = chunk_size if coverage_slots is None else coverage_slots
    cov_w = bitmask.num_words(cov_slots)
    origins_b, churn_b = _stage_inputs(dg, origins_b, churn_b, node_axis=1)
    slots = jnp.arange(chunk_size, dtype=jnp.int32)
    # Global pending-generations bound: any replica pending <=> t <= max.
    last_gen = jnp.max(jnp.where(gen_ticks_b < horizon, gen_ticks_b, 0))

    def cov_delta_of(newly_out):
        # scan-form reduction: bitwise-equal to coverage_per_slot, ~2x
        # cheaper to compile inside this while body (ops/bitmask.py).
        return jax.vmap(
            lambda rows: bitmask.coverage_per_slot_scan(rows, cov_slots)
        )(newly_out[:, :, :cov_w])

    state = (
        jnp.zeros((), dtype=jnp.int32),
        jnp.zeros((b, n, w), dtype=jnp.uint32),
        jnp.zeros((b, dg.ring_size, n, w), dtype=jnp.uint32),
        jnp.zeros((b, n), dtype=jnp.int32),
        jnp.zeros((b, n), dtype=jnp.int32),
        jnp.zeros((b, cov_slots), dtype=jnp.int32),
        jnp.zeros((b, horizon, cov_slots), dtype=jnp.int32),
    )
    if tel:
        state = state + (tel_rings.init_batched(b, horizon),)
    if dig:
        state = state + (tel_digest.init_batched(b, horizon),)
    dig_i = 7 + (1 if tel else 0)

    def cond(full_state):
        t, hist = full_state[0], full_state[2]
        return (t < horizon) & (jnp.any(hist != 0) | (t <= last_gen))

    def step(full_state):
        t, seen, hist, received, sent, cov_run, cov_hist = full_state[:7]
        seen, hist, received, sent, *extras = _batched_tick(
            dg, block, t, seen, hist, received, sent,
            origins_b, gen_ticks_b, churn_b, slots, loss, loss_seeds_b,
            telemetry_on=tel, digest_on=dig,
        )
        cov_run = cov_run + cov_delta_of(hist[:, jnp.mod(t, dg.ring_size)])
        cov_hist = jax.lax.dynamic_update_slice(
            cov_hist, cov_run[:, None, :], (0, t, 0)
        )
        out = (t + 1, seen, hist, received, sent, cov_run, cov_hist)
        if tel:
            out = out + (tel_rings.write_batched(full_state[7], t, extras[0]),)
        if dig:
            out = out + (tel_digest.write_batched(
                full_state[dig_i], t, extras[-1]
            ),)
        return out

    out = jax.lax.while_loop(cond, step, state)
    t, seen, _, received, sent, cov_run, cov_hist = out[:7]
    # Rows past global quiescence hold the (monotone, constant) final
    # coverage — identical to the solo engine's per-replica fill, since a
    # replica's cov_run stops changing at ITS quiescence.
    ticks = jnp.arange(horizon, dtype=jnp.int32)[None, :, None]
    coverage = jnp.where(ticks >= t, cov_run[:, None, :], cov_hist)
    ret = (seen, dg.from_staged(received), dg.from_staged(sent), coverage)
    if tel:
        ret = ret + (out[7],)
    if dig:
        ret = ret + (out[dig_i],)
    return ret


@audited(
    "batch.campaign._run_while_batch",
    spec=lambda: _audit_spec_batch("while"),
    count_compiles=True,
)
@functools.partial(
    jax.jit,
    static_argnames=("chunk_size", "horizon", "block", "loss", "telemetry"),
)
def _run_while_batch(
    dg: DeviceGraph,
    origins_b: jnp.ndarray,
    gen_ticks_b: jnp.ndarray,
    t_start: jnp.ndarray,   # scalar int32 — min live gen tick of the batch
    last_gen: jnp.ndarray,  # scalar int32 — max live gen tick of the batch
    churn_b=None,
    loss_seeds_b=None,      # optional (B,) uint32 per-replica loss seeds
    *,
    chunk_size: int,
    horizon: int,
    block: int,
    loss: tuple | None = None,
    telemetry: bool = False,
):
    """Counter-only replica batch (no coverage history) — the gossip-
    campaign counterpart of ``engine.sync._run_chunk_while``. The tick
    counter is global: ticks before a replica's own first generation are
    identity updates (empty frontier, no firing gens), exactly as the
    solo engine's earlier ``t_start`` would skip them. ``telemetry`` as
    in `_run_coverage_batch` (extra (B, horizon, M) trailing output)."""
    n, w = dg.n, bitmask.num_words(chunk_size)
    b = origins_b.shape[0]
    origins_b, churn_b = _stage_inputs(dg, origins_b, churn_b, node_axis=1)
    slots = jnp.arange(chunk_size, dtype=jnp.int32)
    tel = tel_rings.active(telemetry)
    dig = tel_digest.active(telemetry)
    state = (
        t_start,
        jnp.zeros((b, n, w), dtype=jnp.uint32),
        jnp.zeros((b, dg.ring_size, n, w), dtype=jnp.uint32),
        jnp.zeros((b, n), dtype=jnp.int32),
        jnp.zeros((b, n), dtype=jnp.int32),
    )
    if tel:
        state = state + (tel_rings.init_batched(b, horizon),)
    if dig:
        state = state + (tel_digest.init_batched(b, horizon),)
    dig_i = 5 + (1 if tel else 0)

    def cond(state):
        t, hist = state[0], state[2]
        return (t < horizon) & (jnp.any(hist != 0) | (t <= last_gen))

    def body(state):
        t, seen, hist, received, sent = state[:5]
        seen, hist, received, sent, *extras = _batched_tick(
            dg, block, t, seen, hist, received, sent,
            origins_b, gen_ticks_b, churn_b, slots, loss, loss_seeds_b,
            telemetry_on=tel, digest_on=dig,
        )
        out = (t + 1, seen, hist, received, sent)
        if tel:
            out = out + (tel_rings.write_batched(state[5], t, extras[0]),)
        if dig:
            out = out + (tel_digest.write_batched(
                state[dig_i], t, extras[-1]
            ),)
        return out

    out = jax.lax.while_loop(cond, body, state)
    _, seen, _, received, sent = out[:5]
    ret = (seen, dg.from_staged(received), dg.from_staged(sent))
    if tel:
        ret = ret + (out[5],)
    if dig:
        ret = ret + (out[dig_i],)
    return ret


def _iter_batches(
    replicas: ReplicaSet, batch_size: int, horizon: int, loss_seeds=None
):
    """Slice the replica axis into static-size batches. The last batch is
    padded with sentinel replicas (gen_ticks == horizon everywhere): they
    generate nothing, converge immediately under the batched while_loop,
    and their rows are dropped on the host side. Yields
    ``(lo, live, origins, gen_ticks, churn, seeds, lseeds)`` — ``seeds``
    the replicas' own seeds masked to uint32 (the partner-pick streams of
    the protocol campaigns), ``lseeds`` the per-replica loss seeds (None
    when ``loss_seeds`` is None); both zero-padded like the schedules."""
    r_total = replicas.num_replicas
    seeds_u32 = (replicas.seeds & 0xFFFFFFFF).astype(np.uint32)
    lseeds_u32 = (
        None
        if loss_seeds is None
        else (np.asarray(loss_seeds, dtype=np.int64) & 0xFFFFFFFF).astype(
            np.uint32
        )
    )
    for lo in range(0, r_total, batch_size):
        hi = min(lo + batch_size, r_total)
        live = hi - lo
        origins = replicas.origins[lo:hi]
        gen_ticks = replicas.gen_ticks[lo:hi]
        seeds = seeds_u32[lo:hi]
        lseeds = None if lseeds_u32 is None else lseeds_u32[lo:hi]
        churn = (
            None
            if replicas.churn is None
            else (replicas.churn[0][lo:hi], replicas.churn[1][lo:hi])
        )
        if live < batch_size:
            pad = batch_size - live
            origins = np.concatenate(
                [origins, np.zeros((pad, origins.shape[1]), dtype=np.int32)]
            )
            gen_ticks = np.concatenate(
                [gen_ticks,
                 np.full((pad, gen_ticks.shape[1]), horizon, dtype=np.int32)]
            )
            seeds = np.concatenate([seeds, np.zeros(pad, dtype=np.uint32)])
            if lseeds is not None:
                lseeds = np.concatenate(
                    [lseeds, np.zeros(pad, dtype=np.uint32)]
                )
            if churn is not None:
                zpad = np.zeros((pad,) + churn[0].shape[1:], dtype=np.int32)
                churn = (
                    np.concatenate([churn[0], zpad]),
                    np.concatenate([churn[1], zpad.copy()]),
                )
        yield lo, live, origins, gen_ticks, churn, seeds, lseeds


def _resolve_loss(loss, loss_seeds, r_total: int):
    """The one conversion point between the loss model and the batched
    kernels: returns ``(static_cfg, lseed_array)``.

    - no loss:            ``(None, None)`` — coins off.
    - shared (cell) loss: ``((threshold, seed), None)`` — the static pair,
      bitwise the pre-existing campaign behavior.
    - per-replica loss:   ``((threshold, None), (R,) int64 seeds)`` — the
      threshold stays compile-time config, the seed rides the batch axis
      so each replica draws an independent erasure stream (a solo run
      with ``LinkLossModel(prob, seed=loss_seeds[r])`` reproduces replica
      r bitwise).
    """
    if loss_seeds is not None:
        if loss is None:
            raise ValueError("loss_seeds requires a loss model")
        arr = np.asarray(loss_seeds, dtype=np.int64).reshape(-1)
        if arr.shape[0] != r_total:
            raise ValueError(
                f"loss_seeds must have one seed per replica ({r_total}), "
                f"got {arr.shape[0]}"
            )
        return (loss.threshold, None), arr
    return (loss.static_cfg if loss is not None else None), None


def _campaign_checkpointer(
    checkpoint_path, checkpoint_every, kind: str, graph, replicas: ReplicaSet,
    horizon: int, chunk: int, dg: DeviceGraph, batch_size: int,
    loss_cfg, loss_seed_arr, arrays: dict, extra: tuple = (),
):
    """Batch-boundary checkpointing shared by every campaign runner: the
    accumulated per-replica arrays (counters, and coverage rows — a
    completed batch's coverage is whole, unlike the share-chunk engines
    where skipped chunks would lose history) keyed by a fingerprint over
    the replica seed list and everything else that determines the run —
    including ``batch_size``, which determines the batch partitioning the
    resume index counts in."""
    if checkpoint_path is None:
        return None
    from p2p_gossip_tpu.engine.sync import _canonical_delays
    from p2p_gossip_tpu.utils.checkpoint import ChunkCheckpointer, fingerprint

    fp = fingerprint(
        "campaign", kind, graph.n, graph.edges(), replicas.origins,
        replicas.gen_ticks, replicas.seeds, horizon, chunk,
        _canonical_delays(dg), dg.uniform_delay, dg.ring_size, batch_size,
        replicas.churn[0] if replicas.churn is not None else None,
        replicas.churn[1] if replicas.churn is not None else None,
        *(["loss", loss_cfg[0], loss_cfg[1]] if loss_cfg else []),
        *(["lseeds", loss_seed_arr] if loss_seed_arr is not None else []),
        *extra,
    )
    return ChunkCheckpointer(checkpoint_path, fp, arrays, checkpoint_every)


def _resolve_batch(replicas: ReplicaSet, batch_size: int | None, mesh) -> int:
    if batch_size is None:
        batch_size = replicas.num_replicas
    if batch_size < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")
    if mesh is not None:
        n_dev = int(np.prod(mesh.devices.shape))
        if batch_size % n_dev:
            # Round up so the replica axis divides the device count —
            # sentinel padding absorbs the overhang.
            batch_size += n_dev - batch_size % n_dev
    return batch_size


def _campaign_generated(
    replicas: ReplicaSet, horizon: int
) -> np.ndarray:
    """(R, N) effective per-node generated counters (churn-aware) — pure
    host arithmetic shared by both campaign flavors."""
    return np.stack(
        [
            effective_generated(
                replicas.replica_schedule(r, horizon), horizon,
                replicas.replica_churn(r),
            )
            for r in range(replicas.num_replicas)
        ]
    )


def run_coverage_campaign(
    graph: Graph,
    replicas: ReplicaSet,
    horizon: int,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    loss=None,
    loss_seeds=None,
    batch_size: int | None = None,
    chunk_size: int | None = None,
    block: int | None = None,
    device_graph: DeviceGraph | None = None,
    mesh=None,
    checkpoint_path: str | None = None,
    checkpoint_every: int = 1,
    stop_after_batches: int | None = None,
) -> CampaignResult:
    """Coverage-recording campaign: every replica runs the flood/coverage
    experiment (``engine.sync.run_flood_coverage`` semantics — arbitrary
    gen ticks allowed) and records its per-tick coverage history.

    Returns per-replica counters plus a (R, horizon, S) coverage tensor.
    Bitwise contract: row r equals the solo engine's output for replica
    r's schedule/churn under the same loss model — the batch axis is a
    throughput lever only. Results are also invariant to the share pad
    width (padded slots carry the never-fires sentinel), which is what
    lets ``chunk_size=None`` pick a platform-aware default: on TPU the
    solo engine's MIN_CHUNK_SHARES lane pad (full 128-lane tiles), on
    CPU a packed pad near the actual share count — at S=4, R=32, N=1024
    the packed pad measured ~20x faster end-to-end (the replica axis
    supplies the parallelism the lane pad existed to buy).

    ``loss_seeds`` (one per replica) switches the erasure coin to
    per-replica streams; ``checkpoint_path``/``checkpoint_every`` enable
    batch-boundary snapshots and resume (``stop_after_batches`` simulates
    interruption) — see the module docstring.
    """
    s = replicas.shares_per_replica
    dg = device_graph or DeviceGraph.build(graph, ell_delays, constant_delay)
    if chunk_size is None:
        on_tpu = any(d.platform == "tpu" for d in dg.ell_idx.devices())
        floor = MIN_CHUNK_SHARES if on_tpu else min(MIN_CHUNK_SHARES, 128)
    else:
        floor = chunk_size
    chunk = bitmask.num_words(max(s, floor)) * bitmask.WORD_BITS
    block = _resolve_block(dg, block)
    loss_cfg, lseed_arr = _resolve_loss(loss, loss_seeds, replicas.num_replicas)
    batch_size = _resolve_batch(replicas, batch_size, mesh)
    r_total = replicas.num_replicas
    log.info(
        f"coverage campaign: {r_total} replicas x {graph.n} nodes x {s} "
        f"shares, batch {batch_size}, horizon {horizon}"
        + (f", mesh {mesh.devices.shape}" if mesh is not None else "")
    )

    received = np.zeros((r_total, graph.n), dtype=np.int64)
    sent = np.zeros((r_total, graph.n), dtype=np.int64)
    coverage = np.zeros((r_total, horizon, s), dtype=np.int32)
    checkpointer = _campaign_checkpointer(
        checkpoint_path, checkpoint_every, "coverage", graph, replicas,
        horizon, chunk, dg, batch_size, loss_cfg, lseed_arr,
        {"received": received, "sent": sent, "coverage": coverage},
    )
    from p2p_gossip_tpu.utils.checkpoint import checkpointed_chunks

    tel = telemetry.rings_enabled()
    batches = list(_iter_batches(replicas, batch_size, horizon, lseed_arr))
    t0 = time.perf_counter()
    for _bi, batch in checkpointed_chunks(
        batches, checkpointer, stop_after_batches
    ):
        lo, live, origins, gen_ticks, churn, _seeds, lseeds = batch
        pad_o = np.zeros((batch_size, chunk), dtype=np.int32)
        pad_g = np.full((batch_size, chunk), horizon, dtype=np.int32)
        pad_o[:, :s] = origins
        pad_g[:, :s] = gen_ticks
        pad_o, pad_g, lseeds, *churn_parts = _shard_batch(
            mesh,
            (pad_o, pad_g, lseeds)
            + (churn if churn is not None else (None, None)),
        )
        churn_dev = (
            None if churn_parts[0] is None else tuple(churn_parts)
        )
        lseeds_dev = None if lseeds is None else jnp.asarray(lseeds)
        with telemetry.span(
            "dispatch", kernel="batch.campaign._run_coverage_batch",
            batch=_bi,
        ):
            out = _run_coverage_batch(
                dg, jnp.asarray(pad_o), jnp.asarray(pad_g), churn_dev,
                lseeds_dev,
                chunk_size=chunk, horizon=horizon, block=block, loss=loss_cfg,
                coverage_slots=s, telemetry=tel,
            )
        if tel:
            _, r, snt, cov, met, dstream = out
        else:
            _, r, snt, cov = out
        with telemetry.span("d2h", batch=_bi):
            received[lo : lo + live] = np.asarray(r)[:live]
            sent[lo : lo + live] = np.asarray(snt)[:live]
            coverage[lo : lo + live] = np.asarray(cov)[:live, :, :s]
        digest_head = None
        if tel:
            met_np = np.asarray(met)
            dig_np = np.asarray(dstream)
            for i in range(live):
                tel_rings.emit_ring(
                    "batch.campaign.run_coverage_campaign", met_np[i],
                    t0=0, replica=lo + i, seed=int(replicas.seeds[lo + i]),
                )
                tel_digest.emit_digest(
                    "batch.campaign.run_coverage_campaign", dig_np[i],
                    t0=0, ticks=horizon, replica=lo + i,
                    seed=int(replicas.seeds[lo + i]),
                )
            nz = np.flatnonzero(dig_np[0]) if live else np.array([])
            digest_head = int(dig_np[0][nz[-1]]) if nz.size else None
        telemetry.emit_progress(
            "batch.campaign.run_coverage_campaign", chunk=_bi,
            chunks_total=len(batches), digest_head=digest_head,
        )
    wall = time.perf_counter() - t0

    return CampaignResult(
        n=graph.n,
        seeds=replicas.seeds,
        generated=_campaign_generated(replicas, horizon),
        received=received,
        sent=sent,
        degree=np.asarray(dg.host_degree, dtype=np.int64),
        horizon=horizon,
        wall_s=wall,
        batch_size=batch_size,
        coverage=coverage,
    )


def run_gossip_campaign(
    graph: Graph,
    replicas: ReplicaSet,
    horizon: int,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    loss=None,
    loss_seeds=None,
    batch_size: int | None = None,
    chunk_size: int = 4096,
    block: int | None = None,
    device_graph: DeviceGraph | None = None,
    mesh=None,
    checkpoint_path: str | None = None,
    checkpoint_every: int = 1,
    stop_after_batches: int | None = None,
) -> CampaignResult:
    """Counter-only campaign of the full gossip workload: R replicas of
    the reference simulation (per-replica generation schedules, arbitrary
    share counts) chunked over the share axis like the solo engine —
    counters are additive across chunks per replica. Per-replica counters
    are bitwise-identical to solo ``run_sync_sim`` with the same seed.
    ``loss_seeds``/checkpoint args as in `run_coverage_campaign`
    (checkpoints land at replica-batch boundaries, each batch running all
    its share chunks)."""
    s_max = replicas.shares_per_replica
    chunk = min(chunk_size, max(MIN_CHUNK_SHARES, s_max))
    chunk = bitmask.num_words(chunk) * bitmask.WORD_BITS
    dg = device_graph or DeviceGraph.build(graph, ell_delays, constant_delay)
    block = _resolve_block(dg, block)
    loss_cfg, lseed_arr = _resolve_loss(loss, loss_seeds, replicas.num_replicas)
    batch_size = _resolve_batch(replicas, batch_size, mesh)
    r_total = replicas.num_replicas
    n_chunks = max(1, -(-s_max // chunk))
    log.info(
        f"gossip campaign: {r_total} replicas x {graph.n} nodes, up to "
        f"{s_max} shares in {n_chunks} chunk(s) of {chunk}, batch "
        f"{batch_size}, horizon {horizon}"
    )

    received = np.zeros((r_total, graph.n), dtype=np.int64)
    sent = np.zeros((r_total, graph.n), dtype=np.int64)
    checkpointer = _campaign_checkpointer(
        checkpoint_path, checkpoint_every, "gossip", graph, replicas,
        horizon, chunk, dg, batch_size, loss_cfg, lseed_arr,
        {"received": received, "sent": sent},
    )
    from p2p_gossip_tpu.utils.checkpoint import checkpointed_chunks

    tel = telemetry.rings_enabled()
    batches = list(_iter_batches(replicas, batch_size, horizon, lseed_arr))
    t0 = time.perf_counter()
    for _bi, batch in checkpointed_chunks(
        batches, checkpointer, stop_after_batches
    ):
        lo, live, origins, gen_ticks, churn, _seeds, lseeds = batch
        for ci in range(n_chunks):
            o_slice = origins[:, ci * chunk : (ci + 1) * chunk]
            g_slice = gen_ticks[:, ci * chunk : (ci + 1) * chunk]
            if not (g_slice < horizon).any():
                continue
            pad_o = np.zeros((batch_size, chunk), dtype=np.int32)
            pad_g = np.full((batch_size, chunk), horizon, dtype=np.int32)
            pad_o[:, : o_slice.shape[1]] = o_slice
            pad_g[:, : g_slice.shape[1]] = g_slice
            # Global loop bounds: first and last live gen tick across the
            # batch. Replicas whose own window is narrower just execute
            # identity ticks at the edges (empty frontier, no gens).
            live_ticks = pad_g[pad_g < horizon]
            t_start = np.int32(live_ticks.min())
            last_gen = np.int32(live_ticks.max())
            pad_o, pad_g, lseeds_s, *churn_parts = _shard_batch(
                mesh,
                (pad_o, pad_g, lseeds)
                + (churn if churn is not None else (None, None)),
            )
            churn_dev = (
                None if churn_parts[0] is None else tuple(churn_parts)
            )
            lseeds_dev = None if lseeds_s is None else jnp.asarray(lseeds_s)
            with telemetry.span(
                "dispatch", kernel="batch.campaign._run_while_batch",
                batch=_bi, chunk=ci,
            ):
                out = _run_while_batch(
                    dg, jnp.asarray(pad_o), jnp.asarray(pad_g),
                    jnp.asarray(t_start), jnp.asarray(last_gen), churn_dev,
                    lseeds_dev,
                    chunk_size=chunk, horizon=horizon, block=block,
                    loss=loss_cfg, telemetry=tel,
                )
            if tel:
                _, r, snt, met, dstream = out
            else:
                _, r, snt = out
            with telemetry.span("d2h", batch=_bi, chunk=ci):
                received[lo : lo + live] += np.asarray(r, dtype=np.int64)[:live]
                sent[lo : lo + live] += np.asarray(snt, dtype=np.int64)[:live]
            digest_head = None
            if tel:
                met_np = np.asarray(met)
                dig_np = np.asarray(dstream)
                for i in range(live):
                    tel_rings.emit_ring(
                        "batch.campaign.run_gossip_campaign", met_np[i],
                        t0=int(t_start), chunk=ci, replica=lo + i,
                        seed=int(replicas.seeds[lo + i]),
                    )
                    tel_digest.emit_digest(
                        "batch.campaign.run_gossip_campaign", dig_np[i],
                        t0=int(t_start), ticks=horizon - int(t_start),
                        chunk=ci, replica=lo + i,
                        seed=int(replicas.seeds[lo + i]),
                    )
                nz = np.flatnonzero(dig_np[0]) if live else np.array([])
                digest_head = int(dig_np[0][nz[-1]]) if nz.size else None
            telemetry.emit_progress(
                "batch.campaign.run_gossip_campaign", chunk=_bi,
                chunks_total=len(batches), digest_head=digest_head,
            )
    wall = time.perf_counter() - t0

    return CampaignResult(
        n=graph.n,
        seeds=replicas.seeds,
        generated=_campaign_generated(replicas, horizon),
        received=received,
        sent=sent,
        degree=np.asarray(dg.host_degree, dtype=np.int64),
        horizon=horizon,
        wall_s=wall,
        batch_size=batch_size,
        coverage=None,
    )


# --- staticcheck audit specs (p2p_gossip_tpu/staticcheck/) ----------------

def _audit_spec_batch(kind: str, telemetry_on: bool = False):
    """Tiny replica batch for the jaxpr auditor: B=2 replicas x 48 nodes,
    one 32-share chunk — same operand structure the campaign drivers
    stage, loss seeds riding the batch axis so the traced-seed path is
    the audited one."""
    from p2p_gossip_tpu.engine.sync import _audit_inputs
    from p2p_gossip_tpu.staticcheck.registry import AuditSpec
    from p2p_gossip_tpu.telemetry.schema import NUM_METRICS

    chunk, horizon, b = 32, 16, 2
    dg, origins, gen_ticks = _audit_inputs(chunk, horizon)
    origins_b = jnp.broadcast_to(origins, (b, chunk))
    gen_ticks_b = jnp.broadcast_to(gen_ticks, (b, chunk))
    lseeds_b = jnp.arange(b, dtype=jnp.uint32)
    common = dict(chunk_size=chunk, horizon=horizon, block=8, loss=(1 << 20, None))
    words: tuple = (bitmask.num_words(chunk),)
    if telemetry_on:
        # Per-replica digest rings come back (B, horizon) uint32 — the
        # horizon is a declared minor width, like NUM_METRICS.
        common["telemetry"] = True
        words = words + (NUM_METRICS, horizon)
    if kind == "coverage":
        return AuditSpec(
            args=(dg, origins_b, gen_ticks_b, None, lseeds_b),
            kwargs=dict(**common, coverage_slots=4),
            integer_only=True,
            bitmask_words=words,
        )
    return AuditSpec(
        args=(
            dg, origins_b, gen_ticks_b,
            jnp.asarray(0, dtype=jnp.int32), jnp.asarray(2, dtype=jnp.int32),
            None, lseeds_b,
        ),
        kwargs=common,
        integer_only=True,
        bitmask_words=words,
    )


# Telemetry-on variants of the batched campaign kernels.
register_entry(
    "batch.campaign._run_coverage_batch[telemetry]",
    _run_coverage_batch,
    spec=lambda: _audit_spec_batch("coverage", telemetry_on=True),
)
register_entry(
    "batch.campaign._run_while_batch[telemetry]",
    _run_while_batch,
    spec=lambda: _audit_spec_batch("while", telemetry_on=True),
)


def run_protocol_campaign(
    graph: Graph,
    replicas: ReplicaSet,
    horizon: int,
    protocol: str = "pushpull",
    fanout: int = 2,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    loss=None,
    loss_seeds=None,
    batch_size: int | None = None,
    chunk_size: int | None = None,
    device_graph: DeviceGraph | None = None,
    record_coverage: bool = True,
    mesh=None,
    checkpoint_path: str | None = None,
    checkpoint_every: int = 1,
    stop_after_batches: int | None = None,
) -> CampaignResult:
    """Replica campaign of the random-partner protocols — the Demers trio
    minus eager push: ``pushpull``/``pull`` anti-entropy and ``pushk``
    fanout push (``models/protocols.py``), R replicas in one jitted vmap
    per share chunk.

    Bitwise contract (the one the flood campaigns carry): row r of every
    output equals a solo ``run_pushpull_sim``/``run_pushk_sim`` run with
    ``seed=replicas.seeds[r]`` and replica r's schedule/churn under the
    same loss model, including the coverage history. Partner picks are
    the counter-based hash keyed on (node, round, seed) — a traced
    per-replica operand — so replica streams decorrelate exactly as R
    solo seeds do. ``loss_seeds`` gives each replica an independent
    erasure stream (solo reference: ``LinkLossModel(prob,
    seed=loss_seeds[r])``); without it the cell-shared static pair
    applies to every replica, matching the sweep's sequential path
    bitwise.

    ``chunk_size=None`` picks the platform-aware pass width of
    `run_coverage_campaign` (solo lane pad on TPU, packed pad on CPU —
    the packed pad is most of the measured CPU speedup, since a solo run
    pads S=4 shares to a 4096-wide bitmask); shares beyond one pass run
    in chunks with exactly-additive counters. Checkpoints land at
    replica-batch boundaries (each batch runs all its chunks), same
    contract as `run_coverage_campaign`.
    """
    from p2p_gossip_tpu.models.protocols import (
        _run_pushk_replicas,
        _run_pushpull_replicas,
        check_pull_credit_width,
    )

    if protocol not in ("pushpull", "pull", "pushk"):
        raise ValueError(
            f"protocol must be pushpull|pull|pushk, got {protocol!r}"
        )
    if protocol == "pushk" and fanout < 1:
        raise ValueError(f"fanout must be >= 1, got {fanout}")
    # Partner selection indexes the full-width ELL (models/protocols.py) —
    # bucketed staging is not usable here, same rule as the solo driver.
    dg = device_graph or DeviceGraph.build(
        graph, ell_delays, constant_delay, bucketed=False
    )
    if dg.buckets is not None:
        raise ValueError(
            "protocol campaigns require a DeviceGraph built with "
            "bucketed=False (partner selection reads the full ELL)"
        )
    s = replicas.shares_per_replica
    if chunk_size is None:
        on_tpu = any(d.platform == "tpu" for d in dg.ell_idx.devices())
        if on_tpu:
            chunk_size = MIN_CHUNK_SHARES  # full 128-lane tiles
        else:
            # Packed pad: word-round the actual share count (capped at
            # 128-share passes). Narrow rows keep the push direction on
            # the bit scatter-add (ops/segment.py) — at S=4 the pad is
            # one uint32 word vs the solo engine's 128, which is most of
            # the campaign's CPU advantage.
            chunk_size = min(max(s, 1), min(MIN_CHUNK_SHARES, 128))
    chunk = bitmask.num_words(max(chunk_size, 1)) * bitmask.WORD_BITS
    if protocol == "pull":
        check_pull_credit_width(graph, chunk)
    loss_cfg, lseed_arr = _resolve_loss(loss, loss_seeds, replicas.num_replicas)
    loss_thr = loss_cfg[0] if loss_cfg is not None else 0
    if lseed_arr is None:
        # The batched kernels take the loss seed as an operand either way;
        # the shared (cell-config) seed simply rides it uniformly — the
        # same coins as the solo static path (identical hash).
        shared = loss_cfg[1] if loss_cfg is not None else 0
        lseed_arr = np.full(replicas.num_replicas, shared, dtype=np.int64)
    batch_size = _resolve_batch(replicas, batch_size, mesh)
    r_total = replicas.num_replicas
    n_chunks = max(1, -(-max(s, 1) // chunk))
    log.info(
        f"{protocol} campaign: {r_total} replicas x {graph.n} nodes x {s} "
        f"shares in {n_chunks} chunk(s) of {chunk}, batch {batch_size}, "
        f"horizon {horizon}"
        + (f", mesh {mesh.devices.shape}" if mesh is not None else "")
    )

    received = np.zeros((r_total, graph.n), dtype=np.int64)
    sent = np.zeros((r_total, graph.n), dtype=np.int64)
    coverage = (
        np.zeros((r_total, horizon, s), dtype=np.int32)
        if record_coverage
        else None
    )
    arrays = {"received": received, "sent": sent}
    if record_coverage:
        arrays["coverage"] = coverage
    checkpointer = _campaign_checkpointer(
        checkpoint_path, checkpoint_every, "protocol", graph, replicas,
        horizon, chunk, dg, batch_size, loss_cfg, lseed_arr, arrays,
        extra=(protocol, fanout if protocol == "pushk" else None),
    )
    from p2p_gossip_tpu.utils.checkpoint import checkpointed_chunks

    tel = telemetry.rings_enabled()
    batches = list(_iter_batches(replicas, batch_size, horizon, lseed_arr))
    t0 = time.perf_counter()
    for _bi, batch in checkpointed_chunks(
        batches, checkpointer, stop_after_batches
    ):
        lo, live, origins, gen_ticks, churn, seeds, lseeds = batch
        for ci in range(n_chunks):
            o_slice = origins[:, ci * chunk : (ci + 1) * chunk]
            g_slice = gen_ticks[:, ci * chunk : (ci + 1) * chunk]
            live_s = o_slice.shape[1]
            pad_o = np.zeros((batch_size, chunk), dtype=np.int32)
            pad_g = np.full((batch_size, chunk), horizon, dtype=np.int32)
            pad_o[:, :live_s] = o_slice
            pad_g[:, :live_s] = g_slice
            pad_o, pad_g, seeds_s, lseeds_s, *churn_parts = _shard_batch(
                mesh,
                (pad_o, pad_g, seeds, lseeds)
                + (churn if churn is not None else (None, None)),
            )
            churn_dev = (
                None if churn_parts[0] is None else tuple(churn_parts)
            )
            with telemetry.span(
                "dispatch", kernel=f"batch.campaign.{protocol}_replicas",
                batch=_bi, chunk=ci,
            ):
                if protocol == "pushk":
                    out = _run_pushk_replicas(
                        dg, jnp.asarray(pad_o), jnp.asarray(pad_g),
                        jnp.asarray(seeds_s), jnp.asarray(lseeds_s), churn_dev,
                        fanout=fanout, chunk_size=chunk, horizon=horizon,
                        record_coverage=record_coverage,
                        loss_threshold=loss_thr, telemetry=tel,
                    )
                else:
                    out = _run_pushpull_replicas(
                        dg, jnp.asarray(pad_o), jnp.asarray(pad_g),
                        jnp.asarray(seeds_s), jnp.asarray(lseeds_s), churn_dev,
                        chunk_size=chunk, horizon=horizon,
                        record_coverage=record_coverage,
                        loss_threshold=loss_thr, mode=protocol, telemetry=tel,
                    )
            if tel:
                _, r, (s_lo, s_hi), cov, met, dstream = out
            else:
                _, r, (s_lo, s_hi), cov = out
            with telemetry.span("d2h", batch=_bi, chunk=ci):
                received[lo : lo + live] += np.asarray(r, dtype=np.int64)[:live]
                sent[lo : lo + live] += bitmask.combine_u64(s_lo, s_hi)[:live]
                if record_coverage:
                    coverage[
                        lo : lo + live, :, ci * chunk : ci * chunk + live_s
                    ] = np.asarray(cov)[:live, :, :live_s]
            digest_head = None
            if tel:
                met_np = np.asarray(met)
                dig_np = np.asarray(dstream)
                for i in range(live):
                    tel_rings.emit_ring(
                        f"batch.campaign.run_protocol_campaign[{protocol}]",
                        met_np[i], t0=0, ticks=horizon, chunk=ci,
                        replica=lo + i, seed=int(replicas.seeds[lo + i]),
                    )
                    tel_digest.emit_digest(
                        f"batch.campaign.run_protocol_campaign[{protocol}]",
                        dig_np[i], t0=0, ticks=horizon, chunk=ci,
                        replica=lo + i, seed=int(replicas.seeds[lo + i]),
                    )
                if live:
                    digest_head = int(dig_np[0][-1])
            telemetry.emit_progress(
                f"batch.campaign.run_protocol_campaign[{protocol}]",
                chunk=_bi, chunks_total=len(batches),
                digest_head=digest_head,
            )
    wall = time.perf_counter() - t0

    return CampaignResult(
        n=graph.n,
        seeds=replicas.seeds,
        generated=_campaign_generated(replicas, horizon),
        received=received,
        sent=sent,
        degree=np.asarray(dg.host_degree, dtype=np.int64),
        horizon=horizon,
        wall_s=wall,
        batch_size=batch_size,
        coverage=coverage,
    )

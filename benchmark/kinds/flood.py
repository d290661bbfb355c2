"""Traffic kind ``flood``: shares from uniform origins, flooded over links
that each delay one tick, up to a horizon. A mix (``traffic/<name>.json``
with ``"kind": "flood"``) sets:

- ``shares`` a fixed count, or ``shares_per_node_per_s`` with
  ``sim_time_s`` and ``tick_s``: the reference's Poisson process
  (``--genModel poisson``), conditioned on its expected total
  ``rate * nodes * sim_time`` so every seed floods the same count;
- ``gen_window_ticks`` (or ``sim_time_s / tick_s``): generation ticks are
  uniform in ``[0, window)``;
- ``tail_ticks``: the horizon is ``window + tail_ticks``, the CLI's
  ``--simTime`` cut. The tail outlasts the eccentricity bound of every
  origin (``reference.Reach``), so every share generated in the window
  reaches every node;
- ``probe_hops`` and ``probes_per_hop``: that many of the last shares are
  generated instead at ``horizon - 1 - hops``, so the horizon cuts each of
  them exactly ``hops`` out from its origin. Which nodes they reach, and
  so every counter, depends on when each hop arrives;
- ``chunk_size``: shares per engine chunk (static shape);
- ``pool``: how many distinct schedules set-up draws; call ``i`` floods
  schedule ``i % pool``;
- ``traffic_seed``: the seed the pool is drawn with, in the overlay's own
  node ids. ``--seed`` renames the nodes (``graphs.permutation``), and the
  origins with them: every seed floods the same origins of the same graph
  at the same ticks, so a call does the same work, under other names.
  Drawn from ``--seed`` itself, the origins' eccentricities, and with them
  the ticks a flood needs, moved the power-law cell's call time by up to
  5% from seed to seed (my chip run, PR 22).

Copied from ``bench.headline_workload`` (uniform origins, uniform ticks).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.graphs import rng
from benchmark.reference import Reach


@dataclasses.dataclass(frozen=True)
class Mix:
    shares: int
    window: int
    chunk_size: int
    horizon: int
    probes: tuple   # hops, one entry per probe share
    pool: int
    seed: int


def mix(traffic: dict, n: int) -> Mix:
    if "shares" in traffic:
        shares = int(traffic["shares"])
        window = int(traffic["gen_window_ticks"])
    else:
        sim = float(traffic["sim_time_s"])
        shares = int(round(float(traffic["shares_per_node_per_s"]) * n * sim))
        window = int(round(sim / float(traffic["tick_s"])))
    hops = sorted((int(h) for h in traffic.get("probe_hops", ())), reverse=True)
    probes = tuple(h for h in hops for _ in range(int(traffic.get("probes_per_hop", 1))))
    tail = int(traffic["tail_ticks"])
    if probes and max(probes) >= tail:
        raise ValueError("a probe must be generated after the window")
    return Mix(
        shares=shares,
        window=window,
        chunk_size=int(traffic["chunk_size"]),
        horizon=window + tail,
        probes=probes,
        pool=int(traffic.get("pool", 1)),
        seed=int(traffic.get("traffic_seed", 0)),
    )


def schedules(m: Mix, perm: np.ndarray) -> list[tuple[np.ndarray, np.ndarray]]:
    """``m.pool`` (origins, gen_ticks) pairs, int32, sorted by tick, with
    the origins renamed by ``perm``; the probes come last."""
    n = perm.shape[0]
    out = []
    for i in range(m.pool):
        r = rng(m.seed, 1 + i)
        ticks = np.sort(r.integers(0, m.window, m.shares)).astype(np.int32)
        origins = perm[r.integers(0, n, m.shares)].astype(np.int32)
        if m.probes:
            ticks[-len(m.probes):] = [m.horizon - 1 - h for h in m.probes]
        out.append((origins, ticks))
    return out


def reference(indptr: np.ndarray, indices: np.ndarray) -> Reach:
    return Reach(indptr, indices)


def expected(ref: Reach, origins, ticks, m: Mix) -> dict:
    """The plain reference's counters for one schedule."""
    return ref.counters(origins, ticks, m.horizon)


def control(ref: Reach, origins, ticks, m: Mix) -> dict:
    """The reference with the latency guarantee broken: the last share that
    travels at least one hop arrives at every node one tick late."""
    late = ticks.copy()
    i = int(np.flatnonzero(ticks < m.horizon - 1)[-1])
    late[i] += 1
    return ref.counters(origins, late, m.horizon)

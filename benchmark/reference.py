"""Plain reference for a flood's per-node counters, and the comparison.

It imports nothing of the program and reads only the overlay's CSR and the
schedule (the inputs both sides get). The semantics are the reference's
(p2pnode.cc): a node that generates a share counts it as generated and
sends it to every peer; a node that first receives a share counts it as
received and forwarded and sends it to every peer; a repeat is dropped by
the seen-set; processed = generated + received, sent = processed * degree.
Every link delays one tick, so node ``v`` first holds share ``s`` at tick
``gen(s) + dist(origin(s), v)``, and it counts only while that tick is
below the horizon: share ``s`` reaches exactly the ball of radius
``horizon - 1 - gen(s)`` around its origin.

So when a share arrives is part of the answer. A share whose radius is at
least its origin's eccentricity reaches every node; ``Reach`` bounds the
eccentricity from one breadth-first search from the highest-degree node
(``ecc(o) <= dist(o, hub) + ecc(hub)``). Every other share, among them the
mixes' probe shares cut one to a few hops out, gets a breadth-first search
of its own, to its radius.
"""

from __future__ import annotations

import numpy as np

from benchmark.graphs import edge_blocks

COUNTERS = ("generated", "received", "forwarded", "sent", "processed")


def bfs_depths(indptr: np.ndarray, indices: np.ndarray, src: int,
               limit: int | None = None) -> np.ndarray:
    """Hop distance from ``src`` to every node within ``limit`` hops; -1
    elsewhere (unreachable or farther).

    Each level takes the cheaper of two exact ways to the next: the
    frontier's rows mark their neighbours, or, when the nodes not reached
    yet hold fewer entries than the frontier, each of them looks for a
    neighbour in the frontier.
    """
    n = indptr.shape[0] - 1
    deg = np.diff(indptr)
    dist = np.full(n, -1, dtype=np.int64)
    dist[src] = 0
    frontier = np.array([src], dtype=np.int64)
    unreached = int(indptr[-1] - deg[src])  # entries in rows not reached
    depth = 0
    while frontier.size and (limit is None or depth < limit):
        depth += 1
        if int(deg[frontier].sum()) <= unreached:
            hit = np.zeros(n, dtype=bool)
            for rows in edge_blocks(indptr, frontier):
                hit[indices[_ranges(indptr[rows], indptr[rows + 1])]] = True
            frontier = np.flatnonzero(hit & (dist < 0))
        else:
            on = dist == depth - 1
            found = [np.zeros(0, dtype=np.int64)]
            for rows in edge_blocks(indptr, np.flatnonzero(dist < 0)):
                near = on[indices[_ranges(indptr[rows], indptr[rows + 1])]]
                got = np.zeros(rows.size, dtype=bool)
                got[np.repeat(np.arange(rows.size), deg[rows])[near]] = True
                found.append(rows[got])
            frontier = np.concatenate(found)
        dist[frontier] = depth
        unreached -= int(deg[frontier].sum())
    return dist


def _ranges(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Concatenation of ``arange(s, e)`` for each pair."""
    lens = ends - starts
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    offs = np.repeat(starts - np.concatenate(([0], np.cumsum(lens)[:-1])), lens)
    return offs + np.arange(total, dtype=np.int64)


class Reach:
    """An upper bound on each node's eccentricity (``inf`` where unknown)."""

    def __init__(self, indptr: np.ndarray, indices: np.ndarray):
        self.indptr, self.indices = indptr, indices
        hub = int(np.argmax(np.diff(indptr)))
        dist = bfs_depths(indptr, indices, hub)
        if (dist < 0).any():  # disconnected: prove nothing, search each share
            self.ecc_bound = np.full(dist.shape, np.inf)
        else:
            self.ecc_bound = (dist + dist.max()).astype(np.float64)
        self.searches = 0  # breadth-first searches of single shares

    def counters(self, origins: np.ndarray, ticks: np.ndarray,
                 horizon: int) -> dict:
        """Each node's counters after the schedule runs to ``horizon``."""
        n = self.indptr.shape[0] - 1
        live = ticks < horizon
        origins, radius = origins[live], (horizon - 1) - ticks[live].astype(np.int64)
        generated = np.bincount(origins, minlength=n).astype(np.int64)
        full = radius >= self.ecc_bound[origins]
        received = int(full.sum()) - np.bincount(origins[full], minlength=n)
        part = np.stack([origins[~full], radius[~full]], axis=1).astype(np.int64)
        pairs, times = np.unique(part, axis=0, return_counts=True)
        for (o, r), k in zip(pairs, times):
            dist = bfs_depths(self.indptr, self.indices, int(o), limit=int(r))
            received[dist > 0] += int(k)
            self.searches += 1
        received = received.astype(np.int64)
        processed = generated + received
        return {
            "generated": generated,
            "received": received,
            "forwarded": received.copy(),
            "sent": processed * np.diff(self.indptr).astype(np.int64),
            "processed": processed,
        }


def compare(got: dict, want: dict) -> tuple[int, int]:
    """(entries that differ, largest absolute gap) over all counters."""
    bad, gap = 0, 0
    for k in COUNTERS:
        d = np.abs(np.asarray(got[k], dtype=np.int64) - want[k])
        bad += int(np.count_nonzero(d))
        gap = max(gap, int(d.max()) if d.size else 0)
    return bad, gap

"""The overlay of a configuration, relabeled by ``--seed``.

The configuration fixes the overlay: its family, its size and the seed
(``graph_seed``) the repo's native C++ builder draws it with. A run then
renames the nodes by a permutation drawn from ``--seed``: every seed
floods the same graph, so the engine stages the same shapes and every
program comes from the compile cache, while the node ids, and with them
the staged arrays and the memory access pattern, change from seed to
seed.
"""

from __future__ import annotations

import numpy as np

from benchmark import spec


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent streams of one ``--seed`` (any int, however large)."""
    return np.random.default_rng([seed % (1 << 64), seed >> 64 & 0xFFFF, stream])


def native_csr(config: dict) -> tuple[np.ndarray, np.ndarray]:
    """(indptr int64, indices int32) from ``topologies/<topology>.py``."""
    g = spec.module("topologies", config["topology"]).csr(config)
    if g is None:
        raise RuntimeError("native library not built (make -C native)")
    return g.indptr, g.indices


def permutation(n: int, seed: int) -> np.ndarray:
    """The renaming of a run: node ``v`` becomes ``perm[v]``."""
    return rng(seed, 0).permutation(n).astype(np.int64)


#: Entries one step of the host phases takes at a time: a bound on every
#: temporary array, so none of them grows with the overlay's nnz.
BLOCK = 1 << 22


def relabel(indptr: np.ndarray, indices: np.ndarray, perm: np.ndarray):
    """The same graph with node ``v`` renamed ``perm[v]``; rows sorted.

    Row ``perm[v]`` is row ``v`` renamed and sorted. No global sort: the
    rows are moved in blocks of rows of one degree, each block a ``(rows,
    degree)`` array sorted along its rows, so the work is O(nnz log dmax)
    and no temporary holds more than ``BLOCK`` entries.
    """
    n = indptr.shape[0] - 1
    old = np.empty(n, dtype=np.int64)
    old[perm] = np.arange(n)  # new id -> old id
    deg = np.diff(indptr)[old]
    new_indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(deg, out=new_indptr[1:])
    rename = perm.astype(np.int32)
    out = np.empty(indices.shape[0], dtype=np.int32)
    by_degree = np.argsort(deg, kind="stable")
    cuts = np.flatnonzero(np.diff(deg[by_degree])) + 1
    for lo, hi in zip(np.r_[0, cuts], np.r_[cuts, n]):
        d = int(deg[by_degree[lo]])
        if d == 0:
            continue
        cols, step = np.arange(d), max(1, BLOCK // d)
        for a in range(lo, hi, step):
            rows = by_degree[a:min(hi, a + step)]
            block = rename[indices[indptr[old[rows], None] + cols]]
            block.sort(axis=1)
            out[new_indptr[rows, None] + cols] = block
    return new_indptr, out


def edge_blocks(indptr: np.ndarray, rows: np.ndarray):
    """``rows`` in consecutive pieces of at most ``BLOCK`` entries each (a
    longer row alone)."""
    ends = np.cumsum(indptr[rows + 1] - indptr[rows])
    a = 0
    while a < rows.size:
        base = ends[a - 1] if a else 0
        b = max(int(np.searchsorted(ends, base + BLOCK, side="right")), a + 1)
        yield rows[a:b]
        a = b


def check_csr(indptr: np.ndarray, indices: np.ndarray) -> None:
    """The overlay the reference assumes: undirected, simple, sorted.

    One pass over blocks of rows. Ids in range, no self-loop, and each row
    strictly increasing (so no repeated edge) are tested exactly.

    Symmetry, ``M == M.T`` for the 0/1 adjacency matrix ``M``, is tested
    by hashing: ``a @ M @ b == b @ M @ a`` modulo 2^64, for ``a`` and ``b``
    drawn uniformly from the 64-bit words, one of each per node. For a
    simple overlay that is not symmetric, ``N = M - M.T`` has a row ``u``
    with an entry of +1 or -1, so ``(N @ b)[u]`` is uniform modulo 2^64,
    and ``a @ (N @ b)`` then vanishes with probability at most 2^(k - 64)
    where 2^k divides ``(N @ b)[u]``. Summed over k, an asymmetric overlay
    passes with probability below 2^-58 over the draw, which comes from a
    fixed seed that no overlay depends on. Every sum and product is
    integer and wraps: no rounding enters.
    """
    n = indptr.shape[0] - 1
    ab_of = np.random.default_rng(0x5EED).integers(
        0, 1 << 64, (n, 2), dtype=np.uint64, endpoint=False)
    a, b = ab_of[:, 0], ab_of[:, 1]
    node = ab_of.view("V16")[:, 0]  # a node's (a, b) as one item: one gather
    ab = ba = 0  # a @ M @ b and b @ M @ a
    for rows in edge_blocks(indptr, np.arange(n)):
        r0, r1 = int(rows[0]), int(rows[-1]) + 1
        lo, hi = int(indptr[r0]), int(indptr[r1])
        if lo == hi:
            continue
        idx = indices[lo:hi]
        if int(idx.min()) < 0 or int(idx.max()) >= n:
            raise ValueError("overlay has a neighbour id out of range")
        deg = np.diff(indptr[r0:r1 + 1])
        if (np.repeat(np.arange(r0, r1, dtype=indices.dtype), deg) == idx).any():
            raise ValueError("overlay has a self-loop")
        full = deg > 0
        starts = indptr[r0:r1][full] - lo
        step_ok = np.diff(idx) > 0
        step_ok[starts[1:] - 1] = True  # where a new row starts
        if not step_ok.all():
            raise ValueError("overlay has a repeated or unsorted edge")
        near = node[idx].view(np.uint64).reshape(-1, 2)
        ab += int(np.sum(a[r0:r1][full] * np.add.reduceat(near[:, 1], starts)))
        ba += int(np.sum(b[r0:r1][full] * np.add.reduceat(near[:, 0], starts)))
    if ab % 2**64 != ba % 2**64:
        raise ValueError("overlay is not symmetric")


def overlay(config: dict, seed: int):
    """(indptr, indices, perm): the configuration's overlay, renamed."""
    indptr, indices = native_csr(config)
    perm = permutation(indptr.shape[0] - 1, seed)
    return (*relabel(indptr, indices, perm), perm)

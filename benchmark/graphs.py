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


def relabel(indptr: np.ndarray, indices: np.ndarray, perm: np.ndarray):
    """The same graph with node ``v`` renamed ``perm[v]``; rows sorted."""
    n = indptr.shape[0] - 1
    deg = np.diff(indptr)
    src = np.repeat(perm, deg)
    dst = perm[indices]
    order = np.argsort(src * n + dst, kind="stable")
    new_indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=new_indptr[1:])
    return new_indptr, dst[order].astype(np.int32)


def check_csr(indptr: np.ndarray, indices: np.ndarray) -> None:
    """The overlay the reference assumes: undirected, simple, sorted."""
    n = indptr.shape[0] - 1
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    dst = indices.astype(np.int64)
    key = src * n + dst
    if (src == dst).any() or (np.diff(key) <= 0).any():
        raise ValueError("overlay has a self-loop, a repeated or unsorted edge")
    if not np.array_equal(np.sort(dst * n + src), key):
        raise ValueError("overlay is not symmetric")


def overlay(config: dict, seed: int):
    """(indptr, indices, perm): the configuration's overlay, renamed."""
    indptr, indices = native_csr(config)
    perm = permutation(indptr.shape[0] - 1, seed)
    return (*relabel(indptr, indices, perm), perm)

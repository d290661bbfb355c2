"""Bytes one flood tick has to move, counted from the overlay, not from
how the program stages it.

Per simulated tick, with ``nnz = 2|E|`` directed edges from the CSR, ``N``
nodes and a share chunk of ``W = ceil(chunk_shares / 32)`` uint32 words:

- ``nnz * (4W + 4)``: one frontier row and one int32 neighbour index per
  directed edge (the gather-OR);
- ``3 * N * 4W``: read the seen-set, write the seen-set, write the
  frontier.

A call moves that times the ticks it executed, summed over its chunks
(``stats.extra["ticks_executed"]`` of ``engine.sync.run_sync_sim``). Padding
or re-bucketing the ELL does not change the count, so a change that
removes padding shows as a higher share of the roofline. A change that
skips frontier rows (a sparse step) does less than this count assumes;
it needs a ``benchmark`` PR to recount.
"""

from __future__ import annotations


def words(chunk_shares: int) -> int:
    return -(-int(chunk_shares) // 32)


def tick_bytes(nnz: int, n: int, w: int) -> int:
    return int(nnz) * (4 * w + 4) + 3 * int(n) * 4 * w


def call_bytes(nnz: int, n: int, chunk_shares: int, ticks: int) -> int:
    return int(ticks) * tick_bytes(nnz, n, words(chunk_shares))

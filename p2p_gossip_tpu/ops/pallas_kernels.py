"""Pallas TPU kernels for the bitmask-frontier ops.

Two design notes, recorded after profiling on a real v5e chip:

1. The hot frontier propagation (gather-OR over the ELL adjacency,
   ops/ell.py) is left to XLA. XLA does materialize the gathered block:
   each gather is a fusion of its own that writes its whole output, and
   the OR is a second fusion that reads it back. A v5e trace of the 100K
   ER flood showed the fetch writing each bucket's (rows, B, W) block to
   HBM and the OR reading it back, near HBM bandwidth both ways. ops/ell.py
   therefore gathers in row tiles whose block XLA keeps in VMEM, which
   needs no Pallas; a per-edge DMA kernel (one descriptor per slot) would
   still have to beat XLA's row gather.

2. What XLA does badly is the per-slot coverage reduction
   (`bitmask.coverage_per_slot`): it materializes a (N, W, 32) int32
   bit-expansion — 32x the traffic of the seen-bitmask itself. The kernel
   here computes per-bit column sums in ONE pass over the bitmask with the
   (32, W) accumulator resident in VMEM, which is what the coverage-time
   metric (BASELINE.json: "time-to-99% share coverage") runs every tick.

Kernels fall back to the jnp reference implementation off-TPU; tests compare
against it in interpret mode. Measured on v5e (100K x 128 words, 50 chained
ops): naive jnp expansion 14.5 ms/op, per-bit-loop kernel 19.2 ms/op
(sublane-hostile accumulator), this vectorized kernel 13.5 ms/op.

A fused tick-update kernel (and a +coverage variant) lived here through
round 3 with interpret-mode parity; the round-4 on-chip bake-off measured
it at 0.50x/0.60x of the fused XLA graph (100K rows x 256 words on v5e:
2.97 vs 1.48 ms, and 2.55 vs 1.53 ms with coverage) — XLA fuses the
arrivals->newly->seen->popcount chain better than the hand tiling — so it
was deleted rather than left as a permanently-gated code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from p2p_gossip_tpu.ops.bitmask import WORD_BITS, num_words

DEFAULT_ROW_TILE = 256

# Row bound for using the coverage kernel on real TPU (override with the
# P2P_PALLAS_COVERAGE_MAX_ROWS env var; 0 disables the kernel). This is a
# MEASURED crossover, not a caution bound: the round-4 on-chip bake-off
# on v5e (256 words) has the kernel winning 1.61x at 100K rows (2.34 vs
# 3.76 ms) and losing 0.28x at 250K (6.70 vs 1.85 ms; 0.835x on a round-5
# re-run) — XLA's per-bit reduction amortizes better as the row grid
# grows past ~400 revisited-output steps.
PALLAS_COVERAGE_MAX_ROWS = 100_000


def _rows_ok(n_rows: int, env_name: str, default_limit: int) -> bool:
    """Shared row-bound gate for hardware-validated kernel sizes."""
    import os
    import warnings

    raw = os.environ.get(env_name)
    limit = default_limit
    if raw is not None:
        try:
            limit = int(raw)
        except ValueError:
            warnings.warn(
                f"{env_name}={raw!r} is not an integer; "
                f"using the default {default_limit}"
            )
    return 0 < n_rows <= limit


def coverage_rows_ok(n_rows: int) -> bool:
    """Whether the coverage kernel should be used for ``n_rows`` (see
    PALLAS_COVERAGE_MAX_ROWS)."""
    return _rows_ok(
        n_rows, "P2P_PALLAS_COVERAGE_MAX_ROWS", PALLAS_COVERAGE_MAX_ROWS
    )


def _bit_column_counts(tile: jnp.ndarray) -> jnp.ndarray:
    """(TILE_N, W) uint32 -> (32, W) int32 per-bit column counts. The bit
    expansion is one broadcast shift over the VMEM-resident tile (measured
    faster than 32 per-bit strided accumulator updates, which are
    sublane-hostile); the (TILE_N, 32, W) transient lives on-chip only.
    Shared by every kernel that accumulates per-slot coverage."""
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (WORD_BITS, 1), 0)
    bits = (
        (tile[:, None, :] >> shifts[None, :, :]) & jnp.uint32(1)
    ).astype(jnp.int32)
    return jnp.sum(bits, axis=0)


def _coverage_kernel(seen_ref, acc_ref):
    """Grid: row tiles. seen_ref: (TILE_N, W) uint32 in VMEM. acc_ref:
    (32, W) int32 — the same output block revisited by every grid step,
    accumulated in place (classic TPU revisited-output pattern)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += _bit_column_counts(seen_ref[:])


@functools.partial(jax.jit, static_argnames=("n_slots", "row_tile", "interpret"))
def coverage_per_slot_pallas(
    seen: jnp.ndarray,
    n_slots: int,
    row_tile: int = DEFAULT_ROW_TILE,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-share coverage counts: (N, W) uint32 -> (S,) int32.

    Drop-in for `bitmask.coverage_per_slot` (same contract), one-pass.
    """
    n, w = seen.shape
    pad = (-n) % row_tile
    if pad:
        seen = jnp.pad(seen, ((0, pad), (0, 0)))
    grid = (seen.shape[0] // row_tile,)
    acc = pl.pallas_call(
        _coverage_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, w), lambda i: (i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec((WORD_BITS, w), lambda i: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((WORD_BITS, w), jnp.int32),
        interpret=interpret,
    )(seen)
    # acc[b, w] = count of slot w*32+b -> transpose to slot-major.
    return acc.T.reshape(w * WORD_BITS)[:n_slots]


def _popcount_rows_kernel(words_ref, out_ref):
    """Row-wise popcount: (TILE_N, W) uint32 -> (TILE_N, 1) int32."""
    counts = jax.lax.population_count(words_ref[:]).astype(jnp.int32)
    out_ref[:] = jnp.sum(counts, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def popcount_rows_pallas(
    words: jnp.ndarray, row_tile: int = DEFAULT_ROW_TILE, interpret: bool = False
) -> jnp.ndarray:
    """Drop-in for `bitmask.popcount_rows` as a fused single-pass kernel."""
    n, w = words.shape
    pad = (-n) % row_tile
    if pad:
        words = jnp.pad(words, ((0, pad), (0, 0)))
    grid = (words.shape[0] // row_tile,)
    out = pl.pallas_call(
        _popcount_rows_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, w), lambda i: (i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=pl.BlockSpec((row_tile, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((words.shape[0], 1), jnp.int32),
        interpret=interpret,
    )(words)
    return out[:n, 0]

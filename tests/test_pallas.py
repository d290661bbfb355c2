"""Pallas kernel tests — interpret mode on CPU against the jnp oracles."""

import numpy as np
import jax.numpy as jnp
import pytest

from p2p_gossip_tpu.ops import bitmask
from p2p_gossip_tpu.ops.pallas_kernels import (
    coverage_per_slot_pallas,
    popcount_rows_pallas,
)


@pytest.mark.parametrize("n,w,slots", [(100, 2, 40), (1024, 4, 128), (1000, 1, 32)])
def test_coverage_kernel_matches_oracle(n, w, slots):
    rng = np.random.default_rng(0)
    seen = jnp.asarray(
        rng.integers(0, 2**32, size=(n, w), dtype=np.uint64).astype(np.uint32)
    )
    want = np.asarray(bitmask.coverage_per_slot(seen, slots))
    got = np.asarray(
        coverage_per_slot_pallas(seen, slots, row_tile=256, interpret=True)
    )
    np.testing.assert_array_equal(got, want)


def test_coverage_kernel_multi_tile_accumulation():
    # Rows split over several grid steps must accumulate, not overwrite.
    rng = np.random.default_rng(1)
    seen = jnp.asarray(
        rng.integers(0, 2**32, size=(1000, 2), dtype=np.uint64).astype(np.uint32)
    )
    want = np.asarray(bitmask.coverage_per_slot(seen, 64))
    got = np.asarray(coverage_per_slot_pallas(seen, 64, row_tile=128, interpret=True))
    np.testing.assert_array_equal(got, want)
    assert want.sum() > 0


def test_popcount_kernel_matches_oracle():
    rng = np.random.default_rng(2)
    words = jnp.asarray(
        rng.integers(0, 2**32, size=(777, 3), dtype=np.uint64).astype(np.uint32)
    )
    want = np.asarray(bitmask.popcount_rows(words))
    got = np.asarray(popcount_rows_pallas(words, row_tile=256, interpret=True))
    np.testing.assert_array_equal(got, want)


def test_popcount_kernel_zero_and_full():
    words = jnp.concatenate(
        [
            jnp.zeros((10, 2), dtype=jnp.uint32),
            jnp.full((10, 2), 0xFFFFFFFF, dtype=jnp.uint32),
        ]
    )
    got = np.asarray(popcount_rows_pallas(words, row_tile=8, interpret=True))
    assert (got[:10] == 0).all() and (got[10:] == 64).all()


def test_coverage_rows_gate(monkeypatch):
    from p2p_gossip_tpu.ops.pallas_kernels import (
        PALLAS_COVERAGE_MAX_ROWS,
        coverage_rows_ok,
    )

    monkeypatch.delenv("P2P_PALLAS_COVERAGE_MAX_ROWS", raising=False)
    assert coverage_rows_ok(100_000)
    assert coverage_rows_ok(PALLAS_COVERAGE_MAX_ROWS)
    assert not coverage_rows_ok(PALLAS_COVERAGE_MAX_ROWS + 1)
    monkeypatch.setenv("P2P_PALLAS_COVERAGE_MAX_ROWS", "50")
    assert coverage_rows_ok(50) and not coverage_rows_ok(51)
    monkeypatch.setenv("P2P_PALLAS_COVERAGE_MAX_ROWS", "0")
    assert not coverage_rows_ok(10)  # 0 disables the kernel outright
    monkeypatch.setenv("P2P_PALLAS_COVERAGE_MAX_ROWS", "256k")
    import warnings

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert coverage_rows_ok(100_000)  # bad override -> default + warning
    assert any("P2P_PALLAS_COVERAGE_MAX_ROWS" in str(x.message) for x in w)


# The fused tick-update kernels (tick_update_pallas, tick_update_cov_pallas)
# and their interpret-mode parity tests were deleted after the round-4
# on-chip bake-off measured them at 0.50x/0.60x of the fused XLA graph
# (v5e, 100K rows x 256 words) — apply_tick_updates' plain jnp
# formulation IS the product path on every backend.

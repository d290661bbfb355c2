"""Compile the main path for a described (not attached) v5e at real
widths — what the chip's compiler would refuse shows up here at no chip
time: both Pallas kernels (a ``tpu_custom_call`` must be present), the
engine/sync.py chunk program at the headline 100K x 8192 width, and one
sharded flood pass on a 2x2 mesh.

The topology is described inside a module fixture, never at import:
only the xdist worker that runs this file loads the TPU compiler."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    """Arrays -> ShapeDtypeStructs placed on a described device."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


@pytest.mark.parametrize("words", [128, 256])
@pytest.mark.parametrize("kernel", ["coverage", "popcount"])
def test_pallas_kernel_compiles_for_v5e(one_chip, kernel, words):
    from p2p_gossip_tpu.ops.pallas_kernels import (
        coverage_per_slot_pallas,
        popcount_rows_pallas,
    )

    seen = jax.ShapeDtypeStruct((100_000, words), jnp.uint32, sharding=one_chip)
    if kernel == "coverage":
        lowered = coverage_per_slot_pallas.lower(seen, words * 32)
    else:
        lowered = popcount_rows_pallas.lower(seen)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_headline_chunk_program_compiles_for_v5e(topo, one_chip):
    """`_run_chunk_while` at bench.py's headline shapes (100K ER p=0.001,
    8,192-share chunk = 256 words, horizon 64) fits one chip."""
    from bench import headline_workload
    from p2p_gossip_tpu.engine.sync import DeviceGraph, _run_chunk_while
    from p2p_gossip_tpu.ops.ell import tuned_degree_block

    graph, sched = headline_workload(100_000, 0.001, 0, 8192, 16)
    dg = DeviceGraph.build(graph)
    dmax = max(b[1].shape[1] for b in dg.buckets)
    block = tuned_degree_block(dmax, topo.devices[:1])
    origins, gen_ticks = sched.padded(8192, 64)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _run_chunk_while.lower(
        _abstract(dg, one_chip),
        _abstract(origins, one_chip), _abstract(gen_ticks, one_chip),
        scalar, scalar, chunk_size=8192, horizon=64, block=block,
    ).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16 * 10**9, used


def test_sharded_flood_pass_compiles_on_2x2(topo):
    """One pass of the sharded flood runner (dense exchange) over a
    (shares=2, nodes=2) mesh of described v5e chips: the collectives the
    four-chip phase of chip_smoke.py runs."""
    import p2p_gossip_tpu as pg
    from p2p_gossip_tpu.ops import bitmask
    from p2p_gossip_tpu.parallel.engine_sharded import (
        _resolve_and_stage_ring,
        _stage_sharded_inputs,
        build_sharded_runner,
    )
    from p2p_gossip_tpu.parallel.mesh import NODES_AXIS, SHARES_AXIS, make_mesh

    mesh = make_mesh(2, 2, devices=topo.devices)
    graph = pg.barabasi_albert(4096, 3, seed=0)
    chunk, horizon = 4096, 64
    (ell_idx, ell_delay, ell_mask, degree, ring, uniform, n_padded, block,
     churn_start, churn_end) = _stage_sharded_inputs(
        graph, None, 1, mesh, None, None
    )
    ring_mode, ell_args, delay_values, bucket_counts, _, plan = (
        _resolve_and_stage_ring(
            "auto", uniform, ring, n_padded, mesh.shape[NODES_AXIS],
            bitmask.num_words(chunk), ell_idx, ell_delay, ell_mask,
            block=block,
        )
    )
    runner, pass_size = build_sharded_runner(
        mesh, n_padded, ring, chunk, horizon, block, uniform, 0, None,
        ring_mode=ring_mode, delay_values=delay_values,
        bucket_counts=bucket_counts, exchange_mode=plan[0],
    )
    rows = NamedSharding(mesh, P(NODES_AXIS))
    shares = NamedSharding(mesh, P(SHARES_AXIS))
    whole = NamedSharding(mesh, P())

    def rows_of(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rows)

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=whole)
    lowered = runner.lower(
        jax.tree.map(rows_of, ell_args), rows_of(degree),
        rows_of(churn_start), rows_of(churn_end),
        jax.ShapeDtypeStruct((pass_size,), jnp.int32, sharding=shares),
        jax.ShapeDtypeStruct((pass_size,), jnp.int32, sharding=shares),
        scalar, scalar,
        jax.ShapeDtypeStruct((0,), np.int32, sharding=whole),
    )
    text = lowered.compile().as_text()
    assert "all-gather" in text or "all-reduce" in text

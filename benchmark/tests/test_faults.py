"""Drive whole runs with the timed path broken underneath: the check has
to read each fault as not correct. One test per fault a cell can have."""

import dataclasses

import jax
import numpy as np
import pytest
from conftest import SHARDED

from p2p_gossip_tpu.engine import sync
from p2p_gossip_tpu.models.generation import Schedule
from p2p_gossip_tpu.parallel import engine_sharded

ONE_CHIP = ["er100k.flood_burst", "ba1m.flood_burst", "er100k.poisson_stream"]


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()  # a patched tick body must be traced anew
    yield
    jax.clear_caches()


def _wrap_sim(monkeypatch, before=None, after=None):
    real = sync.run_sync_sim

    def sim(graph, schedule, *a, **k):
        stats = real(graph, before(schedule) if before else schedule, *a, **k)
        return after(stats) if after else stats

    monkeypatch.setattr(sync, "run_sync_sim", sim)


@pytest.mark.parametrize("name", ONE_CHIP)
def test_state_left_unchanged(monkeypatch, run_tiny, name):
    def frozen(dg, block, state, *a, **k):
        return (state[0] + 1,) + tuple(state[1:])  # time moves, nothing else

    monkeypatch.setattr(sync, "_tick_body", frozen)
    assert run_tiny(name)["correct"] is False


@pytest.mark.parametrize("name", ONE_CHIP)
def test_half_the_shares_left_out(monkeypatch, run_tiny, name):
    def half(s):
        k = s.num_shares // 2
        return Schedule(s.n_nodes, s.origins[:k], s.gen_ticks[:k])

    _wrap_sim(monkeypatch, before=half)
    out = run_tiny(name)
    assert out["correct"] is False and out["failed"] == out["attempted"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_answer_altered_where_produced(monkeypatch, run_tiny, name):
    def bump(stats):
        stats.received[np.argmax(stats.degree)] += 1
        return stats

    _wrap_sim(monkeypatch, after=bump)
    assert run_tiny(name)["correct"] is False


def _wrap_build(monkeypatch, fault):
    real = sync.DeviceGraph.build

    def build(graph, *a, **k):
        return fault(real(graph, *a, **k), graph, *a, **k)

    monkeypatch.setattr(sync.DeviceGraph, "build", staticmethod(build))


@pytest.mark.parametrize("name", ONE_CHIP)
def test_rows_read_from_other_nodes(monkeypatch, run_tiny, name):
    """The gather reads the neighbour rows of other nodes of one degree
    bucket (the fullest): every node keeps its degree, the overlay stays
    connected, and the bulk of the shares still reaches every node."""
    def shuffled(dg, graph, *a, **k):
        assert dg.buckets is not None  # the cells stage degree buckets
        i = max(range(len(dg.buckets)), key=lambda j: dg.buckets[j][0].shape[0])
        rows, idx, mask, delay = dg.buckets[i]
        p = np.random.default_rng(0).permutation(rows.shape[0])
        buckets = list(dg.buckets)
        buckets[i] = (rows, idx[p], mask[p], delay)
        return dataclasses.replace(dg, buckets=tuple(buckets))

    _wrap_build(monkeypatch, shuffled)
    assert run_tiny(name)["correct"] is False


@pytest.mark.parametrize("name", ONE_CHIP)
@pytest.mark.parametrize("delay", [2, 3])
def test_links_deliver_late(monkeypatch, run_tiny, name, delay):
    """Every link delivers ``delay`` ticks after the send, not one."""
    real = sync.DeviceGraph.build
    _wrap_build(monkeypatch, lambda dg, graph, *a, **k: real(
        graph, constant_delay=delay))
    assert run_tiny(name)["correct"] is False


def test_exchange_between_chips_left_out(monkeypatch, run_tiny):
    lax = engine_sharded.lax

    def local_only(x, axis_name, *, axis=0, tiled=False, **_):
        """What all_gather returns with no exchange: only this shard's
        slice, zeros where the other shards' slices belong."""
        size, me = lax.axis_size(axis_name), lax.axis_index(axis_name)
        if not tiled:
            x = jax.numpy.expand_dims(x, axis)
        shape = list(x.shape)
        shape[axis] *= size
        out = jax.numpy.zeros(shape, x.dtype)
        return lax.dynamic_update_slice_in_dim(out, x, me * x.shape[axis], axis)

    monkeypatch.setattr(engine_sharded.lax, "all_gather", local_only)
    assert run_tiny(SHARDED)["correct"] is False


def test_sound_runs_are_correct(run_tiny):
    for name in ONE_CHIP + [SHARDED]:
        assert run_tiny(name)["correct"] is True

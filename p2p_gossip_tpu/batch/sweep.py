"""Parameter-grid campaign sweeps.

Expands a JSON spec into a grid of cells over {protocol, p (or topology
density knob), lossProb, churnProb, fanout}, runs each cell as a seed
ensemble, and emits one JSON record per cell plus a human-readable report
(``batch.stats.format_campaign_report``). One compile serves every cell
that shares shapes — the replica batch is chunked to a static size, so
XLA sees a handful of programs across an arbitrarily large campaign.

Spec format (scalars are 1-element axes; ``example_spec()`` is runnable):

    {
      "numNodes": 256, "topology": "er",
      "p": [0.05, 0.1],              # grid axis
      "protocol": ["push", "pushk"], # grid axis
      "fanout": [2],                 # grid axis (pushk only)
      "lossProb": [0.0, 0.1],        # grid axis
      "churnProb": [0.0],            # grid axis
      "replicas": 8,                 # or explicit [seed, ...] list
      "shares": 4, "horizon": 64, "Latency": 5.0,
      "coverageFraction": 0.99, "baseSeed": 0
    }

Every protocol rides the vmapped campaign engine (``engine: "vmap"``):
``push`` through ``batch.campaign.run_coverage_campaign``, the
random-partner protocols (pushpull / pull / pushk) through
``run_protocol_campaign``. The per-seed sequential path
(`_run_partnered_cell`) is kept as the cross-engine reference — the
schema tests assert both engines emit identical records, and the
``engine`` field reports honestly whichever one actually ran.
"""

from __future__ import annotations

import itertools
import json
import time

import numpy as np

from p2p_gossip_tpu.batch import stats as bstats
from p2p_gossip_tpu.batch.campaign import (
    CampaignResult,
    flood_replicas,
    run_coverage_campaign,
    run_protocol_campaign,
)
from p2p_gossip_tpu.models import topology as topo
from p2p_gossip_tpu.models.generation import Schedule
from p2p_gossip_tpu.models.linkloss import LinkLossModel
from p2p_gossip_tpu.models.seeds import churn_stream_seed, loss_stream_seed
from p2p_gossip_tpu import telemetry
from p2p_gossip_tpu.utils import logging as p2plog

log = p2plog.get_logger("Batch.Sweep")

# The grid axes a spec may vectorize, in report order.
GRID_AXES = ("protocol", "p", "lossProb", "churnProb", "fanout")

_DEFAULTS = {
    "numNodes": 256,
    "topology": "er",
    "protocol": "push",
    "p": 0.05,
    "lossProb": 0.0,
    "churnProb": 0.0,
    "fanout": 2,
    "replicas": 8,
    "shares": 4,
    "horizon": 64,
    "Latency": 5.0,
    "coverageFraction": 0.99,
    "baseSeed": 0,
    "churnDowntimeTicks": 10.0,
    "churnOutages": 1,
}


def example_spec() -> dict:
    """A small CPU-runnable campaign: 2 protocols x 2 loss rates x 8
    seeds on a 256-node graph — the worked example in the README."""
    return {
        "numNodes": 256,
        "p": 0.05,
        "protocol": ["push", "pushk"],
        "fanout": [3],
        "lossProb": [0.0, 0.1],
        "replicas": 8,
        "shares": 4,
        "horizon": 64,
    }


def expand_grid(spec: dict) -> list[dict]:
    """Spec -> list of fully-scalar cell configs (cartesian product of the
    list-valued grid axes; unknown keys are rejected loudly rather than
    silently ignored — a typoed axis must not collapse the grid)."""
    unknown = set(spec) - set(_DEFAULTS)
    if unknown:
        raise ValueError(
            f"unknown sweep keys {sorted(unknown)}; axes are "
            f"{sorted(_DEFAULTS)}"
        )
    merged = {**_DEFAULTS, **spec}
    for key in set(merged) - set(GRID_AXES):
        if isinstance(merged[key], list) and key != "replicas":
            raise ValueError(f"'{key}' cannot be a grid axis (only {GRID_AXES})")
    axes = [
        (k, merged[k] if isinstance(merged[k], list) else [merged[k]])
        for k in GRID_AXES
    ]
    cells = []
    for values in itertools.product(*(v for _, v in axes)):
        cell = {**merged, **dict(zip((k for k, _ in axes), values))}
        if cell["protocol"] != "pushk":
            # fanout only parameterizes pushk — collapse it so the grid
            # does not duplicate push/pushpull cells per fanout value.
            cell["fanout"] = _DEFAULTS["fanout"]
        cells.append(cell)
    # Dedup post-collapse duplicates, preserving order.
    seen, unique = set(), []
    for cell in cells:
        key = json.dumps(cell, sort_keys=True)
        if key not in seen:
            seen.add(key)
            unique.append(cell)
    return unique


def _cell_seeds(cell: dict) -> np.ndarray:
    reps = cell["replicas"]
    if isinstance(reps, list):
        return np.asarray(reps, dtype=np.int64)
    return np.arange(int(reps), dtype=np.int64) + int(cell["baseSeed"])


def _build_graph(cell: dict):
    kind = cell["topology"]
    n, seed = cell["numNodes"], int(cell["baseSeed"])
    if kind == "er":
        return topo.erdos_renyi(n, cell["p"], seed=seed)
    if kind == "ba":
        return topo.barabasi_albert(n, m=max(1, int(round(cell["p"]))), seed=seed)
    if kind == "ring":
        return topo.ring_graph(n)
    if kind == "complete":
        return topo.complete_graph(n)
    raise ValueError(f"sweep topology must be er|ba|ring|complete, got {kind}")


def _cell_loss(cell: dict) -> LinkLossModel | None:
    if cell["lossProb"] <= 0.0:
        return None
    # Same stream derivation as the CLI so cell results reproduce solo runs.
    return LinkLossModel(
        cell["lossProb"], seed=loss_stream_seed(cell["baseSeed"])
    )


def _run_partnered_cell(cell, graph, seeds, loss) -> CampaignResult:
    """Sequential seed ensemble for the random-partner protocols: one solo
    engine run per seed, stacked into the same CampaignResult schema the
    vmapped path produces. No longer the production path (protocol cells
    ride ``run_protocol_campaign``) — kept as the cross-engine reference
    the record-schema and bitwise-equality tests compare against."""
    from p2p_gossip_tpu.models.churn import random_churn
    from p2p_gossip_tpu.models.protocols import run_pushk_sim, run_pushpull_sim

    horizon, s = cell["horizon"], cell["shares"]
    coverage = np.zeros((len(seeds), horizon, s), dtype=np.int32)
    generated = np.zeros((len(seeds), graph.n), dtype=np.int64)
    received = np.zeros_like(generated)
    sent = np.zeros_like(generated)
    t0 = time.perf_counter()
    for r, seed in enumerate(seeds):
        rng = np.random.default_rng(int(seed))
        origins = rng.integers(0, graph.n, s).astype(np.int32)
        sched = Schedule(graph.n, origins, np.zeros(s, dtype=np.int32))
        churn = (
            random_churn(
                graph.n, horizon, outage_prob=cell["churnProb"],
                mean_down_ticks=cell["churnDowntimeTicks"],
                max_outages=cell["churnOutages"],
                seed=churn_stream_seed(seed),
            )
            if cell["churnProb"] > 0.0
            else None
        )
        if cell["protocol"] == "pushk":
            stats, cov = run_pushk_sim(
                graph, sched, horizon, fanout=cell["fanout"], seed=int(seed),
                churn=churn, loss=loss, record_coverage=True,
            )
        else:
            stats, cov = run_pushpull_sim(
                graph, sched, horizon, seed=int(seed), churn=churn,
                loss=loss, record_coverage=True, mode=cell["protocol"],
            )
        coverage[r] = cov[:horizon, :s]
        generated[r] = stats.generated
        received[r] = stats.received
        sent[r] = stats.sent
    return CampaignResult(
        n=graph.n, seeds=seeds, generated=generated, received=received,
        sent=sent, degree=graph.degree.astype(np.int64), horizon=horizon,
        wall_s=time.perf_counter() - t0, batch_size=1, coverage=coverage,
    )


def run_cell(
    cell: dict, batch_size: int | None = None, mesh=None
) -> tuple[dict, CampaignResult]:
    """Run one grid cell end to end; returns (record, result). The record
    is one strict-JSON line: cell config, engine/platform labels (a CPU
    run is labeled CPU, never a chip number), and the ensemble summary."""
    import jax

    seeds = _cell_seeds(cell)
    graph = _build_graph(cell)
    loss = _cell_loss(cell)
    t0 = time.perf_counter()
    if cell["protocol"] not in ("push", "pushpull", "pull", "pushk"):
        raise ValueError(f"unknown protocol {cell['protocol']!r}")
    replicas = flood_replicas(
        graph, cell["shares"], seeds, cell["horizon"],
        churn_prob=cell["churnProb"],
        mean_down_ticks=cell["churnDowntimeTicks"],
        max_outages=cell["churnOutages"],
    )
    with telemetry.span(
        "cell", protocol=cell["protocol"], p=cell["p"],
        lossProb=cell["lossProb"], churnProb=cell["churnProb"],
        replicas=len(seeds),
    ):
        if cell["protocol"] == "push":
            result = run_coverage_campaign(
                graph, replicas, cell["horizon"], loss=loss,
                batch_size=batch_size, mesh=mesh,
            )
        else:
            result = run_protocol_campaign(
                graph, replicas, cell["horizon"], protocol=cell["protocol"],
                fanout=cell["fanout"], loss=loss, batch_size=batch_size,
                mesh=mesh,
            )
    engine = "vmap"
    wall = time.perf_counter() - t0

    summary = bstats.ensemble_summary(result, cell["coverageFraction"])
    record = {
        "cell": {
            k: cell[k]
            for k in (
                "numNodes", "topology", "protocol", "p", "lossProb",
                "churnProb", "fanout", "shares", "horizon", "Latency",
                "coverageFraction",
            )
        },
        "seeds": [int(s) for s in seeds],
        "engine": engine,
        "platform": jax.devices()[0].platform,
        "edges": int(graph.num_edges),
        "summary": summary,
        "wall_s": round(wall, 4),
    }
    return record, result


def run_sweep(
    spec: dict,
    batch_size: int | None = None,
    mesh=None,
    emit=None,
) -> list[dict]:
    """Run every cell of the grid; returns the records in grid order.
    ``emit`` (optional callable) receives each record as it lands — the
    CLI streams them as JSON lines so a long campaign is tail-able."""
    cells = expand_grid(spec)
    log.info(f"sweep: {len(cells)} cells")
    records = []
    for i, cell in enumerate(cells):
        record, _ = run_cell(cell, batch_size=batch_size, mesh=mesh)
        log.info(
            f"cell {i + 1}/{len(cells)}: {record['cell']['protocol']} "
            f"p={record['cell']['p']:g} loss={record['cell']['lossProb']:g} "
            f"({record['wall_s']:.2f}s)"
        )
        records.append(record)
        if emit is not None:
            emit(record)
    telemetry.emit_jit_cache_counters()
    return records

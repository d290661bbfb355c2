"""Engine path ``sharded``: one ``parallel.engine_sharded.run_sharded_sim``
per call on a nodes mesh of ``mesh_node_shards`` chips, with the cell's
``exchange`` and ``ring_mode``. The engine stages its ELL on every call,
as users pay it. It reports no tick count."""

import numpy as np

from benchmark.reference import COUNTERS


class Path:
    def __init__(self, graph, mix, params: dict, devices):
        from p2p_gossip_tpu.parallel.mesh import make_mesh

        self.graph, self.mix, self.params = graph, mix, params
        shards = int(params["mesh_node_shards"])
        self.mesh = make_mesh(shards, 1, devices=devices[:shards])

    def __call__(self, schedule):
        from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim

        stats = run_sharded_sim(
            self.graph, schedule, self.mix.horizon, self.mesh,
            chunk_size=self.mix.chunk_size,
            exchange=self.params.get("exchange", "dense"),
            ring_mode=self.params.get("ring_mode", "auto"),
        )
        return {k: np.asarray(getattr(stats, k)) for k in COUNTERS}, None

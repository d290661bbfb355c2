"""Engine path ``mesh``: one ``parallel.engine_sharded.run_sharded_sim``
per call on a nodes mesh of ``mesh_node_shards`` chips, with the cell's
``exchange`` and ``ring_mode``, from a ``ShardedDeviceGraph`` built once
in set-up (the graph staged on the mesh, as a deployment holds it). A
call returns the five per-node counters forced to the host, and the
ticks it executed, summed over its passes."""

import numpy as np

from benchmark.reference import COUNTERS


class Path:
    def __init__(self, graph, mix, params: dict, devices):
        from p2p_gossip_tpu.parallel.engine_sharded import ShardedDeviceGraph
        from p2p_gossip_tpu.parallel.mesh import make_mesh

        self.graph, self.mix = graph, mix
        shards = int(params["mesh_node_shards"])
        self.mesh = make_mesh(shards, 1, devices=devices[:shards])
        self.dg = ShardedDeviceGraph.build(
            graph, self.mesh, constant_delay=1,
            ring_mode=params.get("ring_mode", "auto"),
            exchange=params.get("exchange", "dense"),
            chunk_size=mix.chunk_size,
        )

    def __call__(self, schedule):
        from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim

        stats = run_sharded_sim(
            self.graph, schedule, self.mix.horizon, self.mesh,
            chunk_size=self.mix.chunk_size, device_graph=self.dg,
        )
        counters = {k: np.asarray(getattr(stats, k)) for k in COUNTERS}
        return counters, stats.extra["ticks_executed"]

"""Engine path ``sync``: one ``engine.sync.run_sync_sim`` per call on a
``DeviceGraph`` staged once in set-up (the one-chip flood path). A call
returns the five per-node counters forced to the host, and the ticks it
executed, summed over its chunks."""

import numpy as np

from benchmark.reference import COUNTERS


class Path:
    def __init__(self, graph, mix, params: dict, devices):
        from p2p_gossip_tpu.engine.sync import DeviceGraph

        self.graph, self.mix = graph, mix
        self.dg = DeviceGraph.build(graph, constant_delay=1)

    def __call__(self, schedule):
        from p2p_gossip_tpu.engine import sync

        stats = sync.run_sync_sim(
            self.graph, schedule, self.mix.horizon,
            chunk_size=self.mix.chunk_size, device_graph=self.dg,
        )
        counters = {k: np.asarray(getattr(stats, k)) for k in COUNTERS}
        return counters, stats.extra["ticks_executed"]

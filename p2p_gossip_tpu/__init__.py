"""p2p_gossip_tpu — TPU-native P2P gossip network simulation framework.

A ground-up rebuild of the capabilities of the NS-3 reference simulation
(rahulrangers/P2P-Gossip-Simulation-NS3): random P2P topologies, share
generation, gossip flooding with duplicate suppression, per-node statistics,
and NetAnim-style visualization — re-architected for TPU:

- the discrete-event loop becomes a synchronous tick simulation under
  ``jax.lax.scan`` (``engine.sync``), with the per-node seen-set collapsed
  into a (nodes x shares) bitmask and per-edge latency modeled as
  frontier-history delay lines;
- the hot per-tick op is a fused gather-OR frontier propagation
  (``ops.ell``, ``ops.pallas_kernels``);
- multi-chip scale comes from ``jax.sharding.Mesh`` + ``shard_map``
  (``parallel.engine_sharded``) with XLA collectives over ICI;
- a native C++ discrete-event engine (``runtime.native``) provides the
  exact event-driven path (the NS-3 role) for parity checks and CPU
  baselines.
"""

from p2p_gossip_tpu.models.topology import (
    Graph,
    erdos_renyi,
    barabasi_albert,
    ring_graph,
    complete_graph,
    watts_strogatz,
    grid_graph,
)
from p2p_gossip_tpu.models.generation import uniform_renewal_schedule, poisson_schedule, Schedule
from p2p_gossip_tpu.models.churn import ChurnModel, from_intervals, random_churn
from p2p_gossip_tpu.models.latency import (
    constant_delays,
    lognormal_delays,
    serialization_delays,
)
from p2p_gossip_tpu.models.linkloss import LinkLossModel
from p2p_gossip_tpu.utils.stats import NodeStats

# The compute engines stay behind explicit module imports: importing jax
# is safe (backends init lazily) but the engines' first device use claims
# the chip — keeping them out of the root import lets the event/native
# backends run without touching a device at all:
#   from p2p_gossip_tpu.engine.sync import run_sync_sim
#   from p2p_gossip_tpu.engine.event import run_event_sim
#   from p2p_gossip_tpu.models.protocols import run_pushpull_sim, run_pushk_sim
#   from p2p_gossip_tpu.parallel.engine_sharded import run_sharded_sim

__version__ = "0.1.0"

__all__ = [
    "Graph",
    "erdos_renyi",
    "barabasi_albert",
    "ring_graph",
    "complete_graph",
    "watts_strogatz",
    "grid_graph",
    "Schedule",
    "uniform_renewal_schedule",
    "poisson_schedule",
    "ChurnModel",
    "from_intervals",
    "random_churn",
    "constant_delays",
    "lognormal_delays",
    "serialization_delays",
    "LinkLossModel",
    "NodeStats",
]

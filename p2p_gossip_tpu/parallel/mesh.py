"""Device-mesh helpers.

The scale axis of the reference is one CPU core; ours is a
``jax.sharding.Mesh`` over TPU chips. Two logical axes:

- ``nodes``  — partitions graph rows (adjacency, seen-bitmask, counters);
  the per-tick frontier exchange rides ICI along it. Two wire formats:
  the **dense** path `all_gather`s full state slices (one per delay
  group), the **delta** path ships fixed-capacity sparse
  (word-index, word-value) buffers via `all_to_all`/`all_gather` and
  falls back to a dense gather on capacity overflow
  (`parallel/exchange.py`). The shared traffic model the cost
  observatory and the sharded engine price against is
  `exchange.modeled_exchange_words_per_tick`.
- ``shares`` — partitions share chunks (independent work, embarrassingly
  parallel); counters `psum` along it at the end.
"""

from __future__ import annotations

import os

import numpy as np

import jax
from jax.sharding import Mesh

NODES_AXIS = "nodes"
SHARES_AXIS = "shares"
REPLICAS_AXIS = "replicas"

#: Default per-device HBM budget the automatic (replica, node) axis split
#: sizes the node axis against (v5e chips carry 16 GB). Overridable per
#: call (``hbm_bytes``) or process-wide via P2P_HBM_BUDGET_GB.
DEFAULT_HBM_BYTES = 16 * 10**9


def estimate_node_bytes(
    n_padded: int, max_degree: int, words: int, ring_size: int = 2
) -> int:
    """Rough whole-graph device footprint of one sharded-engine replica:
    the int32 ELL triple (idx/delay/mask at the padded column cap), the
    seen bitmask, the sharded history ring, and the three counter rows.
    Feed it to ``make_mesh(replicas="auto", node_bytes=...)`` — it only
    has to land on the right power-of-two shard count, not be exact."""
    return 4 * n_padded * (3 * max_degree + words * (1 + ring_size) + 3)


def auto_axis_split(
    n_devices: int,
    node_bytes: int | None = None,
    hbm_bytes: int | None = None,
) -> tuple[int, int]:
    """Choose the (replica_shards, node_shards) factorization of
    ``n_devices``: the SMALLEST node-shard count whose per-device slice of
    ``node_bytes`` fits the HBM budget, handing every remaining device to
    the replica axis (replica parallelism is free; node sharding buys HBM
    at the price of per-tick exchange traffic). ``node_bytes`` None means
    "fits anywhere" — all devices go to replicas. Candidate counts are the
    divisors of ``n_devices`` so the mesh always fills; if even the full
    mesh can't fit the graph, the full mesh is returned (the caller's RSS
    preflight owns that failure)."""
    if n_devices < 1:
        raise ValueError(f"need at least one device, got {n_devices}")
    if hbm_bytes is None:
        hbm_bytes = int(
            float(os.environ.get("P2P_HBM_BUDGET_GB", 0)) * 1e9
        ) or DEFAULT_HBM_BYTES
    node_shards = 1
    if node_bytes is not None:
        for d in sorted(
            d for d in range(1, n_devices + 1) if n_devices % d == 0
        ):
            node_shards = d
            if node_bytes / d <= hbm_bytes:
                break
    return n_devices // node_shards, node_shards


def make_mesh(
    n_node_shards: int | None = None,
    n_share_shards: int = 1,
    devices=None,
    replicas: int | str | None = None,
    node_bytes: int | None = None,
    hbm_bytes: int | None = None,
) -> Mesh:
    """Build a (shares, nodes) mesh. Defaults to all devices on the nodes
    axis (frontier exchange prefers the faster/denser axis).

    ``replicas`` switches to the FACTORIZED 2-D ``(replicas, nodes)``
    mesh the sharded campaign drivers (batch/campaign_sharded.py) run on:
    seed-ensemble replicas ride the first axis, graph rows the second.
    Pass an int for an explicit replica-shard count (node shards default
    to the remaining devices), or ``"auto"`` to derive the split from the
    graph footprint vs per-device HBM (``auto_axis_split``:
    ``node_bytes`` is the estimated whole-graph device footprint — see
    ``estimate_node_bytes`` — and ``hbm_bytes`` the per-device budget,
    default $P2P_HBM_BUDGET_GB or 16 GB). An explicit ``n_node_shards``
    overrides the automatic node-axis choice either way."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if replicas is not None:
        n_dev = len(devices)
        if replicas == "auto":
            replica_shards, auto_nodes = auto_axis_split(
                n_dev, node_bytes=node_bytes, hbm_bytes=hbm_bytes
            )
            if n_node_shards is not None:  # explicit override wins
                replica_shards = n_dev // n_node_shards
            else:
                n_node_shards = auto_nodes
        else:
            replica_shards = int(replicas)
            if replica_shards < 1:
                raise ValueError(
                    f"replicas must be >= 1 or 'auto', got {replicas!r}"
                )
            if n_node_shards is None:
                n_node_shards = n_dev // replica_shards
        want = replica_shards * n_node_shards
        if want < 1 or want > n_dev:
            raise ValueError(
                f"mesh {replica_shards}x{n_node_shards} (replicas x nodes) "
                f"needs {want} devices, have {n_dev}"
            )
        dev_array = np.array(devices[:want]).reshape(
            replica_shards, n_node_shards
        )
        return Mesh(dev_array, (REPLICAS_AXIS, NODES_AXIS))
    if n_node_shards is None:
        n_node_shards = len(devices) // n_share_shards
    want = n_node_shards * n_share_shards
    if want > len(devices):
        raise ValueError(
            f"mesh {n_share_shards}x{n_node_shards} needs {want} devices, "
            f"have {len(devices)}"
        )
    dev_array = np.array(devices[:want]).reshape(n_share_shards, n_node_shards)
    return Mesh(dev_array, (SHARES_AXIS, NODES_AXIS))


def make_slot_mesh(
    slots: int,
    devices=None,
    node_bytes: int | None = None,
    hbm_bytes: int | None = None,
) -> Mesh:
    """Slot→mesh placement for the serving scheduler (serve/server.py):
    a factorized ``(replicas, nodes)`` mesh whose replica axis width
    DIVIDES the server's slot count, so every dispatch of ``slots``
    vmap rows splits evenly across replica shards (the server requires
    ``slots % replica_shards == 0``).

    Starts from ``auto_axis_split``'s HBM-driven factorization and then
    shrinks the replica axis to the largest divisor of the device count
    that also divides ``slots`` — surplus devices go to the node axis
    when that still fills the mesh, otherwise they sit out (a 6-device
    host serving slots=8 runs a 2x3 mesh, not a broken 6-wide replica
    axis)."""
    if slots < 1:
        raise ValueError(f"slots must be >= 1, got {slots}")
    probe = make_mesh(devices=devices, replicas=1)
    devices = list(probe.devices.flat)
    n_dev = len(devices)
    replica_shards, node_shards = auto_axis_split(
        n_dev, node_bytes=node_bytes, hbm_bytes=hbm_bytes
    )
    while replica_shards > 1 and slots % replica_shards != 0:
        replica_shards -= 1
        while n_dev % replica_shards != 0:
            replica_shards -= 1
        node_shards = n_dev // replica_shards
    return make_mesh(
        n_node_shards=node_shards, devices=devices, replicas=replica_shards
    )


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> tuple[int, int]:
    """Multi-host bootstrap — the role NCCL/MPI init plays in a
    GPU-cluster framework, done the JAX way: one
    ``jax.distributed.initialize`` per process, after which
    ``jax.devices()`` spans every host and the same ``shard_map`` engine
    code runs unchanged with XLA routing collectives over ICI within a
    slice and DCN across slices.

    On TPU pods (and Slurm/GKE) every argument autodetects from the
    environment — call with no arguments BEFORE anything touches the
    XLA backend. Idempotent (a second call is a no-op), and a plain
    single-process run with nothing to autodetect degrades cleanly.
    An out-of-order call (backend already initialized by earlier device
    use) raises — silently degrading a pod launch to N independent
    single-process sims would corrupt results on every host.
    Returns ``(process_index, process_count)``."""
    already = False
    try:
        from jax._src import distributed as _dist

        already = getattr(_dist.global_state, "client", None) is not None
    except ImportError:  # private-module layout changed; fall through
        pass
    if not already:
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id,
            )
        except RuntimeError as e:
            msg = str(e).lower()
            if "already initialized" in msg:
                pass  # raced another caller — fine
            elif "before any jax calls" in msg:
                # The ordering bug, explicit args or not: the backend
                # was touched first, so a real pod launch can no longer
                # be wired up. Never swallow.
                raise
            elif coordinator_address or num_processes:
                raise  # explicit config rejected — a real error
            # else: no-arg call with nothing to autodetect — a plain
            # single-process run; jax works fine un-distributed.
    return jax.process_index(), jax.process_count()


def make_multihost_mesh(
    n_node_shards: int | None = None,
    n_share_shards: int | None = None,
    devices=None,
) -> Mesh:
    """(shares, nodes) mesh over ALL processes' devices, axes placed for
    the interconnect hierarchy:

    - the **shares** axis spans DCN (host-to-host): share shards are
      embarrassingly parallel — zero per-tick communication, one counter
      ``psum`` at the end — so the slow network carries almost nothing;
    - the **nodes** axis stays inside each process's local devices (a
      slice's ICI): it carries the per-tick frontier exchange — the
      dense state-slice ``all_gather``s or, under ``exchange="delta"``,
      the sparse frontier-delta ``all_to_all``/``all_gather`` buffers
      (see ``exchange.modeled_exchange_words_per_tick`` for the bytes
      each path puts on the wire).

    Defaults: one share shard per process, nodes axis = one process's
    local devices (``process_is_granule`` — on a multi-host slice each
    host is its own granule, so the layout also holds when several
    processes share a slice). Falls back to the plain ``make_mesh``
    device policy when not actually distributed. ``devices`` (optional)
    pins an explicit device list — e.g. a caller that already resolved a
    host-CPU fallback set — instead of the global ``jax.devices()``."""
    nproc = jax.process_count()
    if nproc > 1:
        from jax.experimental import mesh_utils

        if devices is None:
            devices = jax.devices()
        per_process_nodes = len(jax.local_devices())
        if n_share_shards is None:
            n_share_shards = nproc
        if n_node_shards is None:
            n_node_shards = len(devices) // n_share_shards
        if (
            n_share_shards == nproc
            and n_node_shards == per_process_nodes
        ):
            # Canonical layout: granule = process, shares across
            # granules (DCN), nodes within each granule's devices.
            dev_array = mesh_utils.create_hybrid_device_mesh(
                mesh_shape=(1, n_node_shards),
                dcn_mesh_shape=(n_share_shards, 1),
                devices=devices,
                process_is_granule=True,
            )
            return Mesh(dev_array, (SHARES_AXIS, NODES_AXIS))
        return make_mesh(n_node_shards, n_share_shards, devices=devices)
    # Single process: an explicit device list passes straight through;
    # otherwise inherit make_mesh's device-selection policy
    # (JAX_PLATFORMS / default-device pollution guard) by NOT passing a
    # bare jax.devices() list down.
    return make_mesh(n_node_shards, n_share_shards or 1, devices=devices)


def pad_to_multiple(x: np.ndarray, multiple: int, axis: int = 0, fill=0):
    """Pad an array so its ``axis`` length divides evenly across shards."""
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths, constant_values=fill)

"""Bindings to the native C++ runtime (``native/gossip_native.cc``).

The reference's performance core is C++ on the NS-3 event scheduler; ours is
a dependency-free C++ discrete-event engine with the same app-layer semantics
(binary-heap scheduler, flat seen-bitset dedup), compiled to
``native/libgossip_native.so`` (``make -C native``) and bound via ctypes —
no pybind11 required. A missing library is built on first use; if that
fails, the engine entry points raise (the graph builders return None).
"""

from __future__ import annotations

import ctypes
import os
import warnings

import numpy as np

from p2p_gossip_tpu.models.generation import Schedule
from p2p_gossip_tpu.models.topology import Graph
from p2p_gossip_tpu.utils.stats import NodeStats

def _lib_paths() -> list[str]:
    """Candidate .so paths, P2P_NATIVE_LIB first when set — the override
    scripts/native_asan.sh uses to run the test suite against a
    sanitizer-instrumented build without touching the production
    library. Evaluated per lookup so tests can monkeypatch the env."""
    paths = []
    override = os.environ.get("P2P_NATIVE_LIB")
    if override:
        paths.append(override)
    paths += [
        os.path.join(
            os.path.dirname(__file__), "..", "..", "native",
            "libgossip_native.so",
        ),
        os.path.join(os.path.dirname(__file__), "libgossip_native.so"),
    ]
    return paths

_lib = None
_lib_checked = False

# Must match gossip_abi_version() in native/gossip_native.cc. Binding a stale
# .so with a different argument layout would scribble over the wrong buffers,
# so a mismatch is treated as "not built".
ABI_VERSION = 7


def _try_autobuild() -> None:
    """Build the library if a toolchain is available (fresh checkouts don't
    ship the .so). The build targets a process-private name and is
    os.replace()d into place, so concurrent processes racing on a fresh
    checkout each install a complete library atomically. Failures are
    silent here; ``require_library`` reports them."""
    import subprocess

    makedir = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..", "native")
    )
    if not os.path.exists(os.path.join(makedir, "Makefile")):
        return
    tmp_name = f".libgossip_native.{os.getpid()}.so"
    tmp_path = os.path.join(makedir, tmp_name)
    try:
        proc = subprocess.run(
            ["make", "-C", makedir, f"OUT={tmp_name}", tmp_name],
            capture_output=True,
            timeout=120,
            check=False,
        )
        if proc.returncode == 0 and os.path.exists(tmp_path):
            os.replace(tmp_path, os.path.join(makedir, "libgossip_native.so"))
    except (OSError, subprocess.TimeoutExpired):
        pass
    finally:
        if os.path.exists(tmp_path):
            try:
                os.remove(tmp_path)
            except OSError:
                pass


def load_library():
    """Load and memoize the native library; None if unavailable.

    If the .so is missing, one `make -C native` is attempted automatically
    before giving up.
    """
    global _lib, _lib_checked
    if _lib_checked:
        return _lib
    _lib_checked = True
    lib_paths = _lib_paths()
    if not any(os.path.exists(os.path.abspath(p)) for p in lib_paths):
        _try_autobuild()
    for path in lib_paths:
        path = os.path.abspath(path)
        if os.path.exists(path):
            try:
                lib = ctypes.CDLL(path)
            except OSError as e:  # built for wrong arch etc.
                warnings.warn(f"failed to load {path}: {e}")
                continue
            try:
                version = int(lib.gossip_abi_version())
            except AttributeError:
                version = 1
            if version != ABI_VERSION:
                warnings.warn(
                    f"{path} has ABI version {version}, expected "
                    f"{ABI_VERSION}; rebuild with `make -C native`"
                )
                continue
            _configure(lib)
            _lib = lib
            break
    return _lib


def _configure(lib) -> None:
    i32p = np.ctypeslib.ndpointer(dtype=np.int32, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(dtype=np.int64, flags="C_CONTIGUOUS")
    lib.gossip_run_event_sim.restype = ctypes.c_longlong
    lib.gossip_run_event_sim.argtypes = [
        ctypes.c_int64,              # n
        i64p,                        # indptr (n+1)
        i32p,                        # indices (nnz)
        i32p,                        # csr_delays (nnz)
        ctypes.c_int64,              # num_shares
        i32p,                        # origins
        i32p,                        # gen_ticks
        ctypes.c_int64,              # horizon
        ctypes.c_int64,              # connect_tick (0 = connected at t0)
        ctypes.c_int64,              # churn_k
        i32p, i32p,                  # churn_start, churn_end (n x churn_k)
        ctypes.c_int64,              # loss_threshold (0 = off)
        ctypes.c_int64,              # loss_seed
        ctypes.c_int64,              # fifo_ser_micro (0 = off)
        ctypes.c_int64,              # num_snapshots
        i64p, i64p, i64p,            # snapshot_ticks, snap_generated, snap_processed
        i64p, i64p, i64p,            # out: generated, received, sent
    ]
    lib.gossip_run_partnered_sim.restype = ctypes.c_longlong
    lib.gossip_run_partnered_sim.argtypes = [
        ctypes.c_int64,              # n
        i64p,                        # indptr (n+1)
        i32p,                        # indices (nnz)
        i32p,                        # csr_delays (nnz)
        ctypes.c_int64,              # num_shares
        i32p,                        # origins
        i32p,                        # gen_ticks
        ctypes.c_int64,              # horizon
        ctypes.c_int64,              # protocol (0=pushpull, 1=pushk, 2=pull)
        ctypes.c_int64,              # fanout
        ctypes.c_int64,              # pick_seed
        ctypes.c_int64,              # churn_k
        i32p, i32p,                  # churn_start, churn_end (n x churn_k)
        ctypes.c_int64,              # loss_threshold (0 = off)
        ctypes.c_int64,              # loss_seed
        i64p, i64p,                  # out: received, sent
    ]
    lib.gossip_build_er.restype = ctypes.c_longlong
    lib.gossip_build_er.argtypes = [
        ctypes.c_int64, ctypes.c_double, ctypes.c_uint64,
        i64p,                        # out indptr (n+1)
        i32p,                        # out indices (cap)
        ctypes.c_int64,              # cap
    ]
    lib.gossip_build_ba.restype = ctypes.c_longlong
    lib.gossip_build_ba.argtypes = [
        ctypes.c_int64, ctypes.c_int64, ctypes.c_uint64,
        i64p, i32p, ctypes.c_int64,
    ]


def available() -> bool:
    return load_library() is not None


def require_library():
    """The loaded library; raises when it cannot be built or loaded — an
    engine entry point never quietly runs a different engine."""
    lib = load_library()
    if lib is None:
        raise RuntimeError(
            "native library not built or stale (make -C native)"
        )
    return lib


def _csr_delays(graph: Graph, ell_delays, constant_delay: int) -> np.ndarray:
    """Per-edge delays in CSR order (the native engines' layout) from the
    ELL-aligned array the Python engines use, or a constant fill."""
    if ell_delays is not None:
        rows, pos = graph.csr_rows_pos()
        return np.ascontiguousarray(ell_delays[rows, pos], dtype=np.int32)
    return np.full(graph.indices.shape[0], constant_delay, dtype=np.int32)


def _marshal_churn(churn, n: int):
    """(churn_k, start, end) C-contiguous int32 marshalling shared by the
    native entry points (k=0 with 1-element dummies when churn is off)."""
    if churn is None:
        z = np.zeros(1, dtype=np.int32)
        return 0, z, z
    if churn.n != n:
        raise ValueError(f"churn model is for {churn.n} nodes, graph has {n}")
    return (
        churn.k,
        np.ascontiguousarray(churn.down_start, dtype=np.int32),
        np.ascontiguousarray(churn.down_end, dtype=np.int32),
    )


def run_native_sim(
    graph: Graph,
    schedule: Schedule,
    horizon_ticks: int,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    snapshot_ticks: list[int] | None = None,
    churn=None,
    loss=None,
    connect_tick: int = 0,
    fifo_links=None,
) -> NodeStats:
    """Event-driven simulation on the C++ engine (counters identical to
    `engine.event.run_event_sim`, including under churn, link-loss, the
    socket warm-up window ``connect_tick``, and the opt-in FIFO link
    queueing ``fifo_links`` — a `models.latency.FifoLinkModel`). Raises
    when the library is not built."""
    lib = require_library()
    n = graph.n
    csr_delays = _csr_delays(graph, ell_delays, constant_delay)

    generated = np.zeros(n, dtype=np.int64)
    received = np.zeros(n, dtype=np.int64)
    sent = np.zeros(n, dtype=np.int64)
    origins = np.ascontiguousarray(schedule.origins, dtype=np.int32)
    gen_ticks = np.ascontiguousarray(schedule.gen_ticks, dtype=np.int32)
    from p2p_gossip_tpu.engine.sync import filter_snapshot_boundaries

    # Boundaries past the horizon never fire on the event engine; the C++
    # loop would leave their slots zero-filled — drop them for parity.
    boundaries = np.asarray(
        filter_snapshot_boundaries(snapshot_ticks, horizon_ticks),
        dtype=np.int64,
    )
    snap_gen = np.zeros(max(len(boundaries), 1), dtype=np.int64)
    snap_proc = np.zeros(max(len(boundaries), 1), dtype=np.int64)
    churn_k, churn_start, churn_end = _marshal_churn(churn, n)
    events = lib.gossip_run_event_sim(
        n,
        np.ascontiguousarray(graph.indptr, dtype=np.int64),
        np.ascontiguousarray(graph.indices, dtype=np.int32),
        csr_delays,
        schedule.num_shares,
        origins,
        gen_ticks,
        horizon_ticks,
        connect_tick,
        churn_k,
        churn_start,
        churn_end,
        loss.threshold if loss is not None else 0,
        loss.seed if loss is not None else 0,
        fifo_links.ser_micro if fifo_links is not None else 0,
        len(boundaries),
        np.ascontiguousarray(boundaries) if len(boundaries) else snap_gen,
        snap_gen,
        snap_proc,
        generated,
        received,
        sent,
    )
    stats = NodeStats(
        generated=generated,
        received=received,
        forwarded=received.copy(),
        sent=sent,
        processed=generated + received,
        degree=graph.degree.astype(np.int64),
    )
    stats.extra["events_processed"] = int(events)
    # Present (possibly empty) whenever snapshots were requested — the
    # event/sync engines set the key even when every boundary is filtered.
    if snapshot_ticks is not None:
        connections = int(graph.degree.sum())
        stats.extra["snapshots"] = [
            {
                "tick": int(boundaries[i]),
                "generated": int(snap_gen[i]),
                "processed": int(snap_proc[i]),
                "connections": connections,
            }
            for i in range(len(boundaries))
        ]
    return stats


def run_native_partnered_sim(
    graph: Graph,
    schedule: Schedule,
    horizon_ticks: int,
    protocol: str = "pushpull",
    fanout: int = 2,
    ell_delays: np.ndarray | None = None,
    constant_delay: int = 1,
    seed: int = 0,
    churn=None,
    loss=None,
) -> NodeStats:
    """Random-partner protocols (push-pull anti-entropy / fanout push) on
    the C++ engine — counters identical to models.protocols.run_pushpull_sim
    / run_pushk_sim for the same seed (partner picks and loss coins are the
    shared counter-hash specs), including under churn and link loss.
    Raises when the library is not built."""
    if protocol not in ("pushpull", "pull", "pushk"):
        raise ValueError(f"unknown protocol {protocol!r}")
    lib = require_library()
    n = graph.n
    csr_delays = _csr_delays(graph, ell_delays, constant_delay)
    received = np.zeros(n, dtype=np.int64)
    sent = np.zeros(n, dtype=np.int64)
    churn_k, churn_start, churn_end = _marshal_churn(churn, n)
    rc = lib.gossip_run_partnered_sim(
        n,
        np.ascontiguousarray(graph.indptr, dtype=np.int64),
        np.ascontiguousarray(graph.indices, dtype=np.int32),
        csr_delays,
        schedule.num_shares,
        np.ascontiguousarray(schedule.origins, dtype=np.int32),
        np.ascontiguousarray(schedule.gen_ticks, dtype=np.int32),
        horizon_ticks,
        {"pushpull": 0, "pushk": 1, "pull": 2}[protocol],
        fanout,
        int(seed) & 0xFFFFFFFF,
        churn_k,
        churn_start,
        churn_end,
        loss.threshold if loss is not None else 0,
        loss.seed if loss is not None else 0,
        received,
        sent,
    )
    if rc < 0:
        raise ValueError(f"native partnered sim rejected args (rc={rc})")
    from p2p_gossip_tpu.models.churn import effective_generated

    generated = effective_generated(schedule, horizon_ticks, churn)
    return NodeStats(
        generated=generated,
        received=received,
        forwarded=received.copy(),
        sent=sent,
        processed=generated + received,
        degree=graph.degree.astype(np.int64),
    )


def _build_native_graph(
    fn_name: str, n: int, arg, seed: int, cap: int | None = None
) -> Graph | None:
    lib = load_library()
    if lib is None:
        return None
    # Capacity guess; the builder returns required nnz (or -needed if short).
    if cap is None:
        if fn_name == "gossip_build_er":
            cap = max(1024, int(2.5 * n * max(n - 1, 1) * arg / 2) + 4 * n)
        else:
            cap = max(1024, 4 * n * int(arg) + 64)
    fn = getattr(lib, fn_name)
    for _ in range(3):
        indptr = np.zeros(n + 1, dtype=np.int64)
        indices = np.zeros(cap, dtype=np.int32)
        if fn_name == "gossip_build_er":
            nnz = fn(n, float(arg), seed, indptr, indices, cap)
        else:
            nnz = fn(n, int(arg), seed, indptr, indices, cap)
        if nnz >= 0:
            return Graph(n=n, indptr=indptr, indices=indices[:nnz].copy())
        cap = -int(nnz) + 64
    raise RuntimeError("native graph builder failed to allocate")


def native_erdos_renyi(n: int, p: float, seed: int = 0) -> Graph | None:
    """C++ ER builder (same forced-edge connectivity rule); None if unbuilt."""
    return _build_native_graph("gossip_build_er", n, p, seed)


def native_barabasi_albert(n: int, m: int = 3, seed: int = 0) -> Graph | None:
    """C++ exact BA preferential-attachment builder; None if unbuilt."""
    return _build_native_graph("gossip_build_ba", n, m, seed)

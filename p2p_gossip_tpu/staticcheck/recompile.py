"""Recompile sentinel — one compilation per distinct sweep-grid signature.

The campaign engine's whole throughput story rests on "one compile
serves every cell that shares shapes" (batch/sweep.py): a weak-type
drift (int64 seed array where the kernel saw uint32), a pad-width
wobble, or a static arg that silently varies per cell multiplies the
sweep's wall by the compile cost — and on the chip, burns chip time.
Nothing caught that before: XLA recompiles silently.

The sentinel replays a small grid through the REAL sweep runner
(`batch.sweep.run_sweep`) and counts jit-cache misses on the registered
campaign kernels (the ``count_compiles`` entries in the staticcheck
registry — jit wrappers expose ``_cache_size``). The expected count per
kernel is computed from the grid spec by the same static-signature
rules the kernels declare (`expected_compiles`); measured != expected
fails, in either direction — an over-count is a recompile leak, an
under-count means the expectation model drifted from the kernels and
must be fixed here, not suppressed.

``jax.clear_caches()`` runs before the replay so prior compilations in
the process (tests, earlier stages) can't mask a miss.
"""

from __future__ import annotations

import dataclasses


#: Kernel-name aliases: registry entry name -> short report key.
_KERNELS = {
    "batch.campaign._run_coverage_batch": "coverage_batch",
    "batch.campaign._run_while_batch": "while_batch",
    "models.protocols._run_pushpull_replicas": "pushpull_replicas",
    "models.protocols._run_pushk_replicas": "pushk_replicas",
}


def default_grid() -> dict:
    """The shipped replay grid: 6 cells spanning the flood campaign and
    the batched Demers trio, with a loss axis (static threshold — each
    distinct lossProb is one legitimate compile) — small enough for
    tier-1 (~4 s on CPU) while exercising every counted kernel."""
    return {
        "numNodes": 64,
        "p": 0.1,
        "shares": 2,
        "horizon": 16,
        "replicas": 4,
        "protocol": ["push", "pushpull", "pushk"],
        "fanout": [2],
        "lossProb": [0.0, 0.1],
    }


def expected_compiles(spec: dict) -> dict[str, int]:
    """Distinct compile signatures per counted kernel for ``spec``.

    Mirrors the static/shape config the sweep path derives per cell:
    the kernel a protocol routes to, the graph knob ``p`` (changes ELL
    operand shapes), the loss THRESHOLD (static in every kernel; the
    flood path also bakes the seed — both derive from lossProb/baseSeed),
    churn presence (changes the operand pytree structure), fanout
    (static, pushk only), the anti-entropy mode, and the shared scalar
    shape knobs. A signature set per kernel; the expected count is its
    size. If a kernel gains a new static arg that varies per cell, add
    it HERE — the sentinel failing "under-compiled expectation" is the
    reminder."""
    from p2p_gossip_tpu.batch.sweep import expand_grid

    sigs: dict[str, set] = {k: set() for k in _KERNELS.values()}
    for cell in expand_grid(spec):
        shape_sig = (
            cell["numNodes"], cell["p"], cell["shares"], cell["horizon"],
            _replica_count(cell), cell["baseSeed"],
        )
        loss_sig = cell["lossProb"]
        churn_sig = cell["churnProb"] > 0.0
        if cell["protocol"] == "push":
            sigs["coverage_batch"].add((shape_sig, loss_sig, churn_sig))
        elif cell["protocol"] in ("pushpull", "pull"):
            sigs["pushpull_replicas"].add(
                (shape_sig, loss_sig, churn_sig, cell["protocol"])
            )
        elif cell["protocol"] == "pushk":
            sigs["pushk_replicas"].add(
                (shape_sig, loss_sig, churn_sig, cell["fanout"])
            )
    return {k: len(v) for k, v in sigs.items()}


def _replica_count(cell) -> int:
    reps = cell["replicas"]
    return len(reps) if isinstance(reps, list) else int(reps)


def _counted_kernels() -> dict[str, object]:
    from p2p_gossip_tpu.staticcheck import entrypoints, registry

    entrypoints.load_all()
    out = {}
    for entry in registry.countable_entries():
        key = _KERNELS.get(entry.name, entry.name)
        out[key] = entry.jit_target()
    return out


@dataclasses.dataclass
class SentinelReport:
    ok: bool
    expected: dict[str, int]
    measured: dict[str, int]
    cells: int

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def violations(self) -> list[str]:
        out = []
        for k in sorted(set(self.expected) | set(self.measured)):
            e, m = self.expected.get(k, 0), self.measured.get(k, 0)
            if m > e:
                out.append(
                    f"recompile-sentinel: kernel '{k}' compiled {m}x for "
                    f"{e} distinct grid signature(s) — a static arg or "
                    "operand shape/dtype drifts between calls that should "
                    "share one executable"
                )
            elif m < e:
                out.append(
                    f"recompile-sentinel: kernel '{k}' compiled {m}x but "
                    f"the grid model expected {e} — expected_compiles() "
                    "drifted from the kernels; fix the model"
                )
        return out


def run_sentinel(spec: dict | None = None) -> SentinelReport:
    """Clear jit caches, replay ``spec`` through the real sweep runner,
    and compare per-kernel cache sizes against ``expected_compiles``."""
    import jax

    from p2p_gossip_tpu.batch.sweep import expand_grid, run_sweep

    if spec is None:
        spec = default_grid()
    kernels = _counted_kernels()
    expected = expected_compiles(spec)
    jax.clear_caches()
    run_sweep(spec)
    measured = {
        name: int(fn._cache_size()) for name, fn in kernels.items()
    }
    ok = all(
        measured.get(k, 0) == expected.get(k, 0)
        for k in set(expected) | set(measured)
    )
    return SentinelReport(
        ok=ok, expected=expected, measured=measured,
        cells=len(expand_grid(spec)),
    )


def measure_compiles(fn_or_name):
    """Current cache size of a counted kernel (test helper)."""
    kernels = _counted_kernels()
    if isinstance(fn_or_name, str):
        return int(kernels[fn_or_name]._cache_size())
    return int(fn_or_name._cache_size())


# --- serve-trace sentinel (serve/scheduler.py's one-compile promise) -------

def default_serve_trace() -> list[dict]:
    """The shipped mixed request trace: 2 topologies x 3 protocols x
    mixed replica counts, several requests sharing each signature so the
    replay exercises cross-request slot packing, plus a loss variant
    (one extra legitimate flood compile). Small enough for tier-1."""
    er = {"family": "erdos_renyi", "n": 64, "p": 0.1, "seed": 1}
    ws = {"family": "watts_strogatz", "n": 64, "k": 4, "beta": 0.1,
          "seed": 2}
    base = {"shares": 2, "horizon": 12}
    reqs = [
        {"topology": er, "protocol": "flood", "seeds": [0, 1, 2]},
        {"topology": er, "protocol": "flood", "seeds": [3, 4]},
        {"topology": ws, "protocol": "flood", "seeds": [5]},
        {"topology": ws, "protocol": "flood", "seeds": [6, 7, 8]},
        {"topology": er, "protocol": "pushpull", "seeds": [9, 10]},
        {"topology": er, "protocol": "pushpull", "seeds": [11]},
        {"topology": ws, "protocol": "pushk", "seeds": [12, 13]},
        {"topology": er, "protocol": "flood", "seeds": [14, 15],
         "loss_prob": 0.1},
    ]
    return [
        {"request_id": f"sentinel-{i}", **base, **r}
        for i, r in enumerate(reqs)
    ]


def _serve_compile_sig(server, req) -> tuple:
    """The jit-cache signature a request's dispatches hit: the kernel it
    routes to, the DeviceGraph's pytree structure + leaf shapes/dtypes
    (exactly what jax's cache keys on for the traced operands), the
    batch width, and every static argument the campaign runner derives
    from the request. Mirrors batch/campaign.py's derivations — if a
    campaign kernel gains a per-request static arg, add it HERE (the
    sentinel failing "under-compiled expectation" is the reminder)."""
    import jax

    from p2p_gossip_tpu.engine.sync import MIN_CHUNK_SHARES, _resolve_block
    from p2p_gossip_tpu.ops import bitmask

    dg = server._device_graph(req)
    leaves, treedef = jax.tree_util.tree_flatten(dg)
    dg_sig = (
        str(treedef),
        tuple((tuple(l.shape), str(l.dtype)) for l in leaves),
    )
    on_tpu = any(d.platform == "tpu" for d in dg.ell_idx.devices())
    s = int(req.shares)
    thr = int(round(float(req.loss_prob) * (1 << 32)))
    loss_on = req.loss_prob > 0.0
    churn_on = req.churn_prob > 0.0
    b = server.slots
    # The exchange mode is a static arg of the SHARDED campaign runners
    # only — single-device dispatches ignore it, so folding it into
    # their signatures would over-count expected compiles.
    exchange = (
        (getattr(req, "exchange", "auto")
         if getattr(req, "exchange", "auto") != "auto"
         else server.exchange)
        if server.mesh is not None else None
    )
    if req.protocol == "flood":
        floor = MIN_CHUNK_SHARES if on_tpu else min(MIN_CHUNK_SHARES, 128)
        chunk = bitmask.num_words(max(s, floor)) * bitmask.WORD_BITS
        block = _resolve_block(dg, None)
        return (
            "coverage_batch", dg_sig, b, chunk, int(req.horizon), block,
            (thr, None) if loss_on else None, loss_on, churn_on, s,
            exchange,
        )
    if on_tpu:
        chunk_size = MIN_CHUNK_SHARES
    else:
        chunk_size = min(max(s, 1), min(MIN_CHUNK_SHARES, 128))
    chunk = bitmask.num_words(max(chunk_size, 1)) * bitmask.WORD_BITS
    common = (dg_sig, b, chunk, int(req.horizon), thr, churn_on, exchange)
    if req.protocol == "pushk":
        return ("pushk_replicas",) + common + (int(req.fanout),)
    return ("pushpull_replicas",) + common + (req.protocol,)


def expected_serve_compiles(requests, server) -> dict[str, int]:
    """Distinct compile signatures per counted kernel for a request
    trace served at ``server``'s slot width."""
    sigs: dict[str, set] = {k: set() for k in _KERNELS.values()}
    for req in requests:
        sig = _serve_compile_sig(server, req)
        sigs[sig[0]].add(sig[1:])
    return {k: len(v) for k, v in sigs.items()}


def run_serve_sentinel(trace: list[dict] | None = None) -> SentinelReport:
    """Replay a mixed request trace through the serving scheduler and
    fail if any counted campaign kernel compiled more than once per
    distinct static signature — the continuous-batching premise that
    backfilled slots reuse already-compiled programs. Like
    `run_sentinel`, an under-count also fails (the expectation model
    drifted)."""
    import jax

    from p2p_gossip_tpu.serve.request import SimRequest
    from p2p_gossip_tpu.serve.server import GossipServer

    if trace is None:
        trace = default_serve_trace()
    requests = [SimRequest.from_dict(d) for d in trace]
    kernels = _counted_kernels()
    server = GossipServer(slots=4)
    expected = expected_serve_compiles(requests, server)
    jax.clear_caches()
    for req in requests:
        server.submit(req)
    server.drain()
    measured = {
        name: int(fn._cache_size()) for name, fn in kernels.items()
    }
    ok = all(
        measured.get(k, 0) == expected.get(k, 0)
        for k in set(expected) | set(measured)
    )
    return SentinelReport(
        ok=ok, expected=expected, measured=measured, cells=len(requests),
    )

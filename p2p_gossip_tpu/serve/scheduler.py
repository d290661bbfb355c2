"""Continuous-batching slot scheduler + admission control.

The serving problem is the inference server's: many small requests,
one expensive compiled program per *shape*, and a fixed number of vmap
replica slots per dispatch. The scheduler bin-packs compatible work —
requests whose `static_signature()` matches — into those slots:

- Every request decomposes into per-replica **slot units** (one seed =
  one slot). Units queue FIFO per signature.
- A **dispatch** (`next_plan`) fills up to ``slots`` units from the
  signature owning the globally oldest pending unit: freed slots at a
  batch boundary are backfilled from whatever compatible work is queued
  — continuous batching — and units from *different* requests share one
  batch whenever their signatures agree. Short of compatible work, the
  campaign runners' sentinel padding (gen_ticks == horizon) absorbs the
  idle slots, so the compiled batch shape never varies and each
  signature compiles exactly once (the recompile sentinel's
  ``run_serve_sentinel`` enforces this).
- **Admission control** prices a request before it queues, from a
  modeled per-tick HBM traffic and resident footprint
  (`modeled_request_cost`; host arithmetic on the request's shapes): a
  request whose per-replica resident footprint cannot fit the slot
  budget is rejected up front instead of OOMing mid-dispatch — work
  assignment adapting to a modeled imbalance signal rather than
  round-robin (the Tascade argument, PAPERS: arxiv 2311.15810).

Slot *indices* are semantically inert — a unit's result depends only on
its request's scenario and its own seed, never on which row of the vmap
batch it rides — which is what makes preemption cheap: evicted units
simply requeue (new arrival order, so a resume lands in different slot
indices) and produce bitwise-identical results (tests/test_serve.py,
tests/test_checkpoint.py).

This module is host-only and jax-free (mirrors serve/request.py): the
server loop (serve/server.py) owns every device interaction.
"""

from __future__ import annotations

import dataclasses
from collections import deque

from p2p_gossip_tpu.serve.request import SimRequest

_WORD_BITS = 32
_WORD_BYTES = 4
_INT_BYTES = 4


def modeled_request_cost(request: SimRequest, n: int, max_degree: int) -> dict:
    """Modeled per-slot and per-request cost of a request on its graph —
    host arithmetic only, so admission never touches a backend.

    The traffic model: each tick's dominant HBM traffic is the neighbor
    gather over the full-width ELL (``entries * (w*4 + 4)`` bytes: w
    words of remote state + the int32 index per entry) plus the elementwise OR/mask/counter passes
    (``6 * n * w * 4``). Modeled flops are the OR-reduce word ops of the
    same gather. ``resident_bytes`` is one replica slot's device
    footprint (`parallel.mesh.estimate_node_bytes`) — the number
    admission compares against the HBM budget."""
    from p2p_gossip_tpu.parallel.mesh import estimate_node_bytes

    ell_width = max(int(max_degree), 1)
    entries = int(n) * ell_width
    w = -(-int(request.shares) // _WORD_BITS)
    bytes_per_tick = (
        entries * (w * _WORD_BYTES + _INT_BYTES)
        + 6 * int(n) * w * _WORD_BYTES
    )
    flops_per_tick = entries * w
    slot_bytes = bytes_per_tick * int(request.horizon)
    return {
        "bytes_per_tick": int(bytes_per_tick),
        "flops_per_tick": int(flops_per_tick),
        "slot_bytes": int(slot_bytes),
        "request_bytes": int(slot_bytes) * request.replicas,
        "resident_bytes": int(estimate_node_bytes(n, ell_width, w)),
    }


@dataclasses.dataclass(frozen=True)
class SlotUnit:
    """One replica of one request: the scheduler's unit of work. ``seq``
    is the global arrival order — re-issued on requeue, which is why a
    resumed request lands in different slot indices."""

    request_id: str
    replica: int
    seq: int


@dataclasses.dataclass
class BatchPlan:
    """One dispatch: up to ``slots`` same-signature units. Slots beyond
    ``occupied`` are sentinel padding inside the campaign runners."""

    signature_key: str
    units: list
    slots: int

    @property
    def occupied(self) -> int:
        return len(self.units)

    @property
    def request_ids(self) -> list[str]:
        seen: dict = {}
        for u in self.units:
            seen.setdefault(u.request_id, None)
        return list(seen)


class SlotScheduler:
    """Per-signature FIFO unit queues + the slot packer. The server owns
    request state; the scheduler owns only pending units and the
    admission arithmetic."""

    def __init__(self, slots: int = 8):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self.slots = int(slots)
        self._queues: dict[str, deque] = {}
        self._seq = 0

    # -- admission ---------------------------------------------------------

    def admit(
        self,
        request: SimRequest,
        n: int,
        max_degree: int,
        hbm_budget_bytes: int | None = None,
        max_request_bytes: int | None = None,
    ) -> tuple[bool, dict, str | None]:
        """(admitted, cost, reason). A full dispatch holds ``slots``
        resident replicas, so the fit test is
        ``resident_bytes * slots <= hbm_budget_bytes``;
        ``max_request_bytes`` optionally caps a single request's total
        modeled traffic (a service-level knob, off by default)."""
        if hbm_budget_bytes is None:
            from p2p_gossip_tpu.parallel.mesh import DEFAULT_HBM_BYTES

            hbm_budget_bytes = DEFAULT_HBM_BYTES
        cost = modeled_request_cost(request, n, max_degree)
        batch_resident = cost["resident_bytes"] * self.slots
        if batch_resident > hbm_budget_bytes:
            return False, cost, (
                f"modeled batch footprint {batch_resident} bytes "
                f"({cost['resident_bytes']} x {self.slots} slots) exceeds "
                f"the {hbm_budget_bytes}-byte HBM budget"
            )
        if max_request_bytes is not None and \
                cost["request_bytes"] > max_request_bytes:
            return False, cost, (
                f"modeled request traffic {cost['request_bytes']} bytes "
                f"exceeds the per-request cap {max_request_bytes}"
            )
        return True, cost, None

    # -- queue surface -----------------------------------------------------

    def enqueue(self, request: SimRequest,
                replicas: "list[int] | None" = None) -> int:
        """Queue one unit per replica (or per entry of ``replicas`` — the
        resume path queues only the not-yet-done subset). Returns the
        number of units queued."""
        key = request.signature_key()
        q = self._queues.setdefault(key, deque())
        idxs = range(request.replicas) if replicas is None else replicas
        count = 0
        for r in idxs:
            q.append(SlotUnit(request.request_id, int(r), self._seq))
            self._seq += 1
            count += 1
        return count

    def remove(self, request_id: str) -> int:
        """Drop every pending unit of a request (the eviction half of
        preemption). Units already dispatched are the server's problem —
        dispatches are atomic at batch boundaries."""
        dropped = 0
        for key in list(self._queues):
            q = self._queues[key]
            kept = deque(u for u in q if u.request_id != request_id)
            dropped += len(q) - len(kept)
            if kept:
                self._queues[key] = kept
            else:
                del self._queues[key]
        return dropped

    def queue_depth(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def pending_requests(self) -> set:
        return {
            u.request_id for q in self._queues.values() for u in q
        }

    def next_plan(self) -> BatchPlan | None:
        """The next dispatch: the signature owning the globally oldest
        pending unit, packed FIFO up to ``slots`` units. None when
        idle."""
        best_key, best_seq = None, None
        for key, q in self._queues.items():
            if q and (best_seq is None or q[0].seq < best_seq):
                best_key, best_seq = key, q[0].seq
        if best_key is None:
            return None
        q = self._queues[best_key]
        units = [q.popleft() for _ in range(min(self.slots, len(q)))]
        if not q:
            del self._queues[best_key]
        return BatchPlan(signature_key=best_key, units=units,
                         slots=self.slots)

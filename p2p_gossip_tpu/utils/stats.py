"""Per-node statistics and reporting.

Mirrors the reference's counter set (p2pnode.h:40-43) and the exact report
formats of `PrintStatistics` (p2pnetwork.cc:253-285) and
`PrintPeriodicStats` (p2pnetwork.cc:231-250), so a user of the reference can
diff outputs line-for-line.
"""

from __future__ import annotations

import dataclasses
import io
from typing import Any

import numpy as np

#: ``extra`` entries that describe one tick of the staged graph
#: (`ops.ell.gather_slot_report`), kept rather than summed by ``+``.
PER_TICK_EXTRA = ("gather_slots", "gather_slot_overhead")


@dataclasses.dataclass
class NodeStats:
    """Per-node counter vectors — one array column per reference counter."""

    generated: np.ndarray  # sharesGenerated  (p2pnode.cc:118)
    received: np.ndarray   # sharesReceived   (p2pnode.cc:157)
    forwarded: np.ndarray  # sharesForwarded  (p2pnode.cc:163)
    sent: np.ndarray       # sharesSent       (p2pnode.cc:145)
    processed: np.ndarray  # processedShares.size() (p2pnode.cc:241)
    degree: np.ndarray     # peers.size()
    extra: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def n(self) -> int:
        return int(self.generated.shape[0])

    def totals(self) -> dict[str, int]:
        return {
            "generated": int(self.generated.sum()),
            "received": int(self.received.sum()),
            "forwarded": int(self.forwarded.sum()),
            "sent": int(self.sent.sum()),
            "processed": int(self.processed.sum()),
            "connections": int(self.degree.sum()),
        }

    def check_conservation(self) -> None:
        """Invariants implied by the reference semantics (see SURVEY.md §1).
        Under the parallel-link quirk (``with_parallel_links``) each
        broadcast also sends one copy per duplicated peer-list entry."""
        assert (self.received == self.forwarded).all(), "received != forwarded"
        assert (self.processed == self.generated + self.received).all()
        fan = self.degree + self.extra.get("peer_extra", 0)
        assert (self.sent == (self.generated + self.forwarded) * fan).all()

    def with_parallel_links(self, peer_extra: np.ndarray) -> "NodeStats":
        """Counters under the reference's parallel-link REGISTER quirk
        (`models.topology.parallel_link_extra` explains the mechanism and
        cites the reference lines). The quirk does not change the gossip
        dynamics — the duplicate copy arrives the same tick and is
        dropped by the seen-set without touching any counter
        (p2pnode.cc:189-193) — so it is applied as a pure reporting
        transform: each broadcast charges one extra `sent` per duplicated
        peer-list entry (p2pnode.cc:129-146), and "Peer count" prints
        `peers.size()` including duplicates while "Socket connections"
        stays deduplicated (p2pnode.cc:248)."""
        peer_extra = np.asarray(peer_extra, dtype=self.sent.dtype)
        assert peer_extra.shape == self.sent.shape
        out = NodeStats(
            generated=self.generated,
            received=self.received,
            forwarded=self.forwarded,
            sent=self.sent + (self.generated + self.forwarded) * peer_extra,
            processed=self.processed,
            degree=self.degree,
            extra=dict(self.extra),
        )
        out.extra["peer_extra"] = peer_extra
        return out

    def __add__(self, other: "NodeStats") -> "NodeStats":
        """Chunk-wise accumulation (shares are independent, counters add).
        Summable ``extra`` entries are combined; array-valued ones are kept
        only when a single operand carries them. Exception: ``peer_extra``
        is a per-node property of the GRAPH (not a per-chunk counter) —
        both operands must carry the same value (kept, not summed), and a
        one-sided ``peer_extra`` is rejected loudly (it means summing
        quirk-transformed stats with untransformed stats)."""
        assert np.array_equal(self.degree, other.degree), "stats from different graphs"
        out = NodeStats(
            generated=self.generated + other.generated,
            received=self.received + other.received,
            forwarded=self.forwarded + other.forwarded,
            sent=self.sent + other.sent,
            processed=self.processed + other.processed,
            degree=self.degree,
        )
        for key in set(self.extra) | set(other.extra):
            a, b = self.extra.get(key), other.extra.get(key)
            if a is not None and b is not None:
                if key == "peer_extra":
                    # peer_extra is a per-node property of the GRAPH, not a
                    # per-chunk counter: both operands passed through
                    # with_parallel_links on the same topology, so the
                    # arrays must match — keep one so the summed stats
                    # still satisfy check_conservation's fan math
                    # ((g1+f1)*fan + (g2+f2)*fan == (g+f)*fan). Silently
                    # dropping it made a sum of two conserving chunks
                    # fail conservation (round-3 advisor finding).
                    # np.array_equal also covers the scalar representation
                    # check_conservation supports (extra.get(..., 0)) —
                    # scalar peer_extra must be KEPT too, never summed.
                    assert np.array_equal(a, b), (
                        "peer_extra differs between operands — stats from "
                        "different quirk transforms cannot be summed"
                    )
                    out.extra[key] = a
                elif key in PER_TICK_EXTRA:
                    # Per-tick properties of the staged graph: two runs on
                    # one staging agree, and their sum means nothing.
                    if a == b:
                        out.extra[key] = a
                elif np.isscalar(a) and np.isscalar(b):
                    out.extra[key] = a + b
                # other array-valued pairs (e.g. arrival_ticks for
                # different share chunks) have no well-defined merge — drop.
            else:
                # One-sided peer_extra means one operand was quirk-
                # transformed and the other was not: the sum would pair an
                # inflated Peer count with partially-uncharged sends. Fail
                # here, where the cause is nameable, not later in
                # check_conservation's generic fan assert.
                assert key != "peer_extra", (
                    "peer_extra present in only one operand — cannot sum "
                    "quirk-transformed stats with untransformed stats"
                )
                out.extra[key] = a if a is not None else b
        return out

    def equal_counts(self, other: "NodeStats") -> bool:
        return bool(
            (self.generated == other.generated).all()
            and (self.received == other.received).all()
            and (self.forwarded == other.forwarded).all()
            and (self.sent == other.sent).all()
            and (self.processed == other.processed).all()
        )


def format_final_statistics(stats: NodeStats, per_node: bool = True) -> str:
    """The `PrintStatistics` report (p2pnetwork.cc:253-285), byte-for-byte
    field layout (socket connections == peer count in a healthy run)."""
    out = io.StringIO()
    out.write("=== P2P Gossip Network Simulation Statistics ===\n")
    # Peer count = peers.size() — inflated by the parallel-link quirk when
    # modeled; socket connections = the deduplicated peersockets map.
    peer_count = stats.degree + stats.extra.get("peer_extra", 0)
    if per_node:
        for i in range(stats.n):
            out.write(
                f"Node {i}: Generated {stats.generated[i]}"
                f", Received {stats.received[i]}"
                f", Forwarded {stats.forwarded[i]}"
                f", Total sent {stats.sent[i]}"
                f", Total processed {stats.processed[i]}"
                f", Peer count {peer_count[i]}"
                f", Socket connections {stats.degree[i]}\n"
            )
    t = stats.totals()
    out.write(f"Total shares generated: {t['generated']}\n")
    out.write(f"Total shares received: {t['received']}\n")
    out.write(f"Total shares forwarded: {t['forwarded']}\n")
    out.write(f"Total shares sent: {t['sent']}\n")
    out.write(f"Total socket connections: {t['connections']}\n")
    return out.getvalue()


def format_periodic_stats(stats: NodeStats, sim_time: float) -> str:
    """The `PrintPeriodicStats` report (p2pnetwork.cc:231-250)."""
    t = stats.totals()
    avg = t["processed"] // max(stats.n, 1)
    return (
        f"=== Periodic Stats at {sim_time:g}s ===\n"
        f"Total shares generated: {t['generated']}\n"
        f"Average shares per node: {avg}\n"
        f"Total socket connections: {t['connections']}\n"
    )

"""The chunk program's share of the HBM roofline: the bytes a flood tick
has to move (``bytecount``, from the CSR, W and N) times the ticks the
window's calls executed, over device-busy time, over the published peak.
Nothing where the engine reports no tick count or no trace was taken."""

from benchmark import bytecount


def read(r):
    if r.trace is None or r.hbm_peak_gbps is None:
        return None
    if not r.calls or any(c.ticks is None for c in r.calls):
        return None
    moved = sum(
        bytecount.call_bytes(r.nnz, r.n, r.chunk_shares, c.ticks) for c in r.calls
    )
    return 100.0 * moved / r.trace.busy_s / (r.hbm_peak_gbps * 1e9)

"""The node-sharded flood's share of the mesh's HBM roofline: the bytes
the whole overlay's flood ticks have to move (``bytecount``, from the
CSR, W and N) times the ticks the window's calls executed, over the
device-busy time of every chip (mean busy times the chips), over one
chip's published peak. So the peak is the mesh's aggregate bandwidth.
Nothing where the engine reports no tick count or no trace was taken."""

from benchmark import bytecount


def read(r):
    if r.trace is None or r.hbm_peak_gbps is None or not r.trace.chips:
        return None
    if not r.calls or any(c.ticks is None for c in r.calls):
        return None
    moved = sum(
        bytecount.call_bytes(r.nnz, r.n, r.chunk_shares, c.ticks) for c in r.calls
    )
    busy_chip_s = r.trace.busy_s * len(r.trace.chips)
    return 100.0 * moved / busy_chip_s / (r.hbm_peak_gbps * 1e9)

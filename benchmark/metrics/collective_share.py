"""Device time of collective ops (all-gather, all-reduce, all-to-all,
collective-permute, reduce-scatter) as a share of device-busy time, mean
over the chips. Nothing where the trace holds no collective."""


def read(r):
    if r.trace is None:
        return None
    shares = [c.collective_ns / c.busy_ns for c in r.trace.chips if c.busy_ns]
    if not any(shares):
        return None
    return 100.0 * sum(shares) / len(shares)

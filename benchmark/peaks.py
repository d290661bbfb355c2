"""Published peaks per chip, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
16 GB of HBM2 at 819 GB/s per chip. Copied from ``bench.HBM_PEAK_GBPS``.
A kind that is not here is an error, not a default.
"""

HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}


def hbm_peak_gbps(device_kind: str) -> float:
    try:
        return HBM_PEAK_GBPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published HBM peak for device kind {device_kind!r}; add it "
            "to benchmark/peaks.py with its source"
        ) from None

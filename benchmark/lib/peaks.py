"""Published peaks of the chips this benchmark may run on, keyed by the exact
``device_kind`` JAX reports.  A device that is not here is an error."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"add a row with its source to benchmark/lib/peaks.py")
    return PEAKS[device_kind]


def roofline_share(flops: float, bytes_moved: float, seconds: float, peaks: dict) -> dict:
    """The least time the chip could take (the larger of flops over peak
    FLOP/s and bytes over peak bytes/s) over the time taken, in percent, and
    which of the two bounds it."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = bytes_moved / peaks["hbm_bytes_per_s"]
    return {"share_pct": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "compute" if t_flops >= t_bytes else "memory"}

"""Published peaks of the chips the benchmark may run on, by ``device_kind``.

Copied from ``bench.py``'s ``PEAK_BF16_FLOPS`` with the memory bandwidth
added.  A device that is not here is an error, not a default.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, HBM2e at
    # 819 GB/s
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise SystemExit(
            f"no peaks on record for device_kind {device_kind!r}: add it to "
            f"benchmarks/chip/peaks.py with its source "
            f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]

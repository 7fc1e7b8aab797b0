"""Published peaks per chip, keyed by jax's `device_kind`. A device that is
not in the table is an error, never a default. No metric of this benchmark
divides by them yet (kernel roofline shares wait for stable kernel names inside
the program — PERF.md §7); the table is here so that change only has to read it.

Source: Google Cloud documentation, "TPU v5e" system architecture.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}: add a row, with its source")
    return PEAKS[device_kind]

"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a
roofline or MFU against a guessed peak is no measurement.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2 at 819 GB/s per chip",
    },
}


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; KeyError naming the known kinds."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"bench/peaks.py (known: {sorted(PEAKS)})") from None

"""Published peaks of the chips the benchmark knows, keyed by ``device_kind``.

One table, no environment override, no CPU row: a device that is not here is
an error, not a default.
"""

GIB = 1 << 30

# Source: Google Cloud documentation, "TPU v5e" (system architecture table):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip. JAX names the chip
# "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {
        "chip": "v5e",
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * GIB,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind):
    """The peak row of ``device_kind``; raises on a kind the table lacks."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None

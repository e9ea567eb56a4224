"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip).  A device kind
that is not in the table is an error, never a default: a utilization
against an assumed peak is not a measurement.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"no peaks known for device kind {device_kind!r}: add it to "
            "chipbench/lib/peaks.py with its source") from None

"""Published peaks of each chip, keyed by JAX's ``device_kind``. A kind
that is not here is an error, never a default.

TPU v5e: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s
(Google Cloud documentation, "TPU v5e"). JAX names that chip "TPU v5 lite".
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise SystemExit(
            f"no peaks are known for device kind {kind!r}; add them to "
            "bench/harness/peaks.py with their source"
        ) from None

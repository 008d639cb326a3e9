"""Published peaks of one chip, keyed by `device_kind` as JAX reports it.

A device that is not in the table is an error, not a default.  There is
no published peak for int32 VPU work, which is what the Ed25519 kernel
does: PERF.md's Open question 1 fills that row in with a measured rate
and the microbenchmark that re-takes it.
"""

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add a row to "
            f"benchmark/lib/peaks.py with its source") from None

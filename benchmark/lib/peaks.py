"""Published peaks of the chips the benchmark knows, keyed by what jax
reports as `device_kind`. A device that is not here is an error, never a
default: a utilisation against a guessed peak is a guess."""

# TPU v5e: Google Cloud documentation, "TPU v5e" system architecture:
# 197 TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s per chip. jax names the
# chip "TPU v5 lite". (The same row as perfscope/cost.py's _PEAK_TABLE.)
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind, what):
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise KeyError(f"no published {what} peak for device_kind "
                       f"{device_kind!r}: add its row, with its source, to "
                       f"benchmark/lib/peaks.py") from None

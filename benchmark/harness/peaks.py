"""Published peaks of the chips the benchmark knows, keyed by JAX's
`device_kind`. A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip. PR 21 measured a
4096^3 bf16 matmul chain at 191 TFLOP/s on this installation.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
  if device_kind not in PEAKS:
    raise KeyError(
        f"no peaks for device_kind {device_kind!r}: add it to "
        "benchmark/harness/peaks.py with its source")
  return PEAKS[device_kind][what]

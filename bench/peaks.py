"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports.  A chip that is not here is an error, not a
default: a share of a guessed peak is no measurement."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 393 TOP/s int8, 197 TFLOP/s
    # bf16, 16 GB of HBM at 819 GB/s per chip.
    "TPU v5 lite": {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in bench/peaks.py "
                       f"(have {sorted(PEAKS)})") from None

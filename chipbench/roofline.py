"""Peaks of the chips the benchmark runs on, and the least time of the
matching work.

Peaks: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s per chip.  A device kind missing from the
table is an error, not a default.
"""

from __future__ import annotations

PEAKS_SOURCE = 'Google Cloud documentation, "TPU v5e"'
_V5E = {"bf16_flop_per_s": 197e12, "int8_op_per_s": 393e12,
        "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(f"no peaks for device kind {device_kind!r}; add "
                          f"it to chipbench/roofline.py from "
                          f"{PEAKS_SOURCE!r} or the chip's own data sheet"
                          ) from None


def match_bytes(n_bytes: int, n_patterns: int) -> int:
    """Bytes the sequential membership test must move at the least: each
    input byte read once, and one 4-byte transition entry per pattern per
    byte.  The same work whatever plan or kernel does it."""
    return int(n_bytes) * (1 + 4 * int(n_patterns))


def match_min_seconds(n_bytes: int, n_patterns: int, chips: int,
                      device_kind: str) -> float:
    """The least time of that work on ``chips`` chips at HBM bandwidth."""
    bw = peak(device_kind)["hbm_bytes_per_s"]
    return match_bytes(n_bytes, n_patterns) / (int(chips) * bw)

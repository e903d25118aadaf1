"""Published peaks per ``device_kind`` and the byte models of the
programs whose rooflines this benchmark reports.

Peaks: Google Cloud documentation, "TPU v5e" (per chip): 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
interconnect.  JAX reports the chip's ``device_kind`` as "TPU v5 lite".
A kind that is not in the table is an error, never a default.

Byte models: the least bytes a program must move for one request,
counted from the request's logical work and not from the arrays the
current program happens to read.  Every word is an int32 (4 bytes).
A roofline is taken over a whole program's device time, not over one
kernel's: XLA stages a small kernel's operands into on-chip memory with
copies of its own, so a kernel's events alone move none of its bytes
from HBM.
"""
from __future__ import annotations

WORD = 4

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bytes_per_s": 1600e9 / 8},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/lib/peaks.py "
                       "with their source") from None


def fast_read_bytes(ways: int) -> int:
    """One read through the fast read (``arrays._build_fast_read``): the
    key's id and its replica set (2 words in), the set's ways x (tag,
    rts), the hit way's version and grant sequence (2), its LRU touch (1
    written), and the outputs hit, version, grant sequence (3)."""
    return WORD * (2 + 2 * ways + 2 + 1 + 3)


def roofline_share(need_bytes: int, device_s: float, device_kind: str):
    """Least time the chip could take to move ``need_bytes`` at its HBM
    peak, over the program's device time, in percent; None where there
    is nothing to read (no bytes or no device time)."""
    if need_bytes <= 0 or device_s <= 0:
        return None
    least = need_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / device_s

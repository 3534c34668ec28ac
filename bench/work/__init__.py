"""Operations and bytes that each kernel's algorithm needs for one call,
from the call's shapes alone, and the chip's least time for them. One
module per kernel; its ``matches(op)`` tells the kernel's events in a
device trace by their HLO text."""
import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind`` (KeyError if unknown)."""
    table = json.loads(PEAKS.read_text())
    if device_kind == "source" or device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def least_time(ops: float, nbytes: float, peak: dict):
    """(seconds, bound): the larger of ops / the bf16 peak and bytes / HBM
    bandwidth, and which of the two it is."""
    t_ops = ops / peak["bf16_flops"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")

"""Required work of the aggregation service, from shapes alone.

These are the bytes the algorithm has to move per contribution, whatever
program or kernel implements it: a per-layer share of a peak divides them,
so a later change to the program cannot move the yardstick.  ``d`` is the
number of f32 parameters of the model whose deltas are aggregated.
"""
from __future__ import annotations

import json
from pathlib import Path

F32 = 4  # bytes of a float32 delta or parameter element
I32 = 4  # bytes of an int32 field element (32-bit secure-aggregation field)

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def encode_bytes(d: int) -> int:
    """The per-arrival encode: read the f32 delta once, write the int32 row
    into its buffer slot once (clip, weight, quantize and mask are
    elementwise and stay on chip)."""
    return d * F32 + d * I32


def flush_read_bytes(d: int) -> int:
    """The flush's modular sum reads each stored int32 row once."""
    return d * I32


def release_bytes(d: int) -> int:
    """Once per release: read and write the f32 parameters (the server
    optimizer step; FedAvg holds no further state per element)."""
    return 2 * d * F32


def step_bytes(d: int, contributions: int, releases: int) -> int:
    """Required bytes of a whole window: every folded contribution is
    encoded, stored and read back once; every release updates the params."""
    return (contributions * (encode_bytes(d) + flush_read_bytes(d))
            + releases * release_bytes(d))


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip kind; a kind not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if device_kind not in table:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} in {PEAKS_FILE.name} "
                         f"(known: {sorted(table)})")
    return table[device_kind]

"""The least work a matching job needs, and the chip's peaks.

The kernel's bitwise VPU work has no published peak, so memory is the
bound: a job has to read each edge once (``u``, ``v``, ``w``: 12 bytes),
write its recorded substream (4 bytes), and write the final bit state
(``ceil(L/8)`` bytes per vertex). The count depends on the stream and
the configuration only, not on how an engine pads or orders its slots,
so every engine is measured against the same work.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"

EDGE_IN_BYTES = 12  # int32 u, int32 v, float32 w
EDGE_OUT_BYTES = 4  # int32 recorded substream


def problem_bytes(m: int, n: int, L: int) -> int:
    """HBM bytes one whole job needs at the least."""
    return m * (EDGE_IN_BYTES + EDGE_OUT_BYTES) + n * -(-L // 8)


def peaks(device_kind: str) -> dict:
    """The peak row of ``device_kind``; a kind not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {PEAKS_FILE.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]

"""Peaks of the chips and the least work of the kernels the benchmark
reads a roofline share of."""

from __future__ import annotations

import json
import os

#: bytes of one map entry, counted at the columns both dot stores keep
#: (key hash u64, value hash u32, stamp i64, writer i32, counter u32,
#: alive flag)
ENTRY_COLUMNS = {"key": 8, "valh": 4, "ts": 8, "node": 4, "ctr": 4, "alive": 1}
ENTRY_BYTES = sum(ENTRY_COLUMNS.values())


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind that is not in the
    table is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add them to peaks.json")
    return table[device_kind]


def merge_bytes(entries: int) -> int:
    """The least bytes any store moves to merge ``entries`` delta
    entries: read the delta entry, read the receiver's entry for its
    key, write the winner. Never reckoned from table capacity."""
    return 3 * ENTRY_BYTES * int(entries)

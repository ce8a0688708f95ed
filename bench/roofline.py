"""The bytes a batch must read, and the device peaks they are held against.

A search batch reads posting rows and does little arithmetic on each, so
its least time on a chip is the bytes of the rows it needs over the chip's
memory bandwidth.  The rows are those of the keys that the planner binds
for the batch's executable subqueries, each key's rows read once however
many requests of the batch use it.  The count comes from the planner's
per-key ``est_bytes`` (the rows the key reads from the live index), never
from padded device buffers or compiler cost estimates.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def batch_posting_bytes(plans) -> int:
    """Bytes of the posting rows a batch of query plans needs: every key
    bound by an executable subquery, once."""
    seen: dict[tuple, int] = {}
    for plan in plans:
        for sub in plan.executable():
            for b in sub.bindings:
                if b.executable:
                    seen.setdefault((b.key.components, b.key.starred), int(b.est_bytes))
    return sum(seen.values())


def device_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of one device kind; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; add them with their source")
    return table[device_kind]


def least_seconds(posting_bytes: int, peaks: dict) -> float:
    return posting_bytes / float(peaks["hbm_bytes_per_s"])

"""The table of published peaks, keyed by ``device_kind``. A device that
is not in the table is an error, never a default."""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(LookupError):
    pass


@dataclass(frozen=True)
class Peaks:
    kind: str
    flops: float          # bf16 FLOP/s of one chip
    hbm_bw: float         # bytes/s of one chip
    hbm_bytes: float
    source: str


def load_table(path=_TABLE):
    with open(path) as f:
        return json.load(f)


def peaks_for(device_kind, table=None) -> Peaks:
    table = load_table() if table is None else table
    row = table.get(device_kind)
    if row is None:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in {os.path.basename(_TABLE)}"
            f" (known: {sorted(table)}); add its published peaks with their "
            f"source")
    return Peaks(device_kind, float(row["bf16_flops_per_s"]),
                 float(row["hbm_bytes_per_s"]), float(row["hbm_bytes"]),
                 row["source"])

"""Published per-chip peaks, keyed by ``device_kind`` (copied from
``bench.py:_PEAKS``, which a later PR deletes).

Source: Google Cloud TPU documentation, the per-generation system pages
("TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s interconnect
over four links).  Columns: dense bf16 matmul FLOP/s, HBM bytes/s, ICI
bytes/s per link and direction.  First substring match wins.  A device
that is not in the table is an error, not a default: a utilization over
a guessed peak is not a measurement.
"""

from __future__ import annotations

PEAKS = (
    # (kind substring, flop/s, hbm bytes/s, ici bytes/s per link)
    ("v6", 918e12, 1640e9, 112e9),  # Trillium / v6e
    ("v5p", 459e12, 2765e9, 100e9),
    ("v5", 197e12, 819e9, 50e9),  # v5e reports itself as "TPU v5 lite"
    ("v4", 275e12, 1228e9, 50e9),
)


def _peak(device_kind: str, column: int) -> float:
    kind = device_kind.lower()
    for row in PEAKS:
        if row[0] in kind:
            return row[column]
    raise LookupError(
        f"no published peaks for device_kind {device_kind!r}: add its row, "
        "with the source, to benchmarks/lib/peaks.py"
    )


def peak_flops(device_kind: str) -> float:
    return _peak(device_kind, 1)

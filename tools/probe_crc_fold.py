"""Host timing of the drain-time CRC fold (``integrity.crc32_spans``)
by span count and payload size: what places ``integrity.SPAN_MIN_BYTES``
and ``integrity.MAX_SPANS`` (PERF.md §6, PR 29).

    chiprun -- python3 tools/probe_crc_fold.py [--sizes-mb 154 308] [--spans 1 2 4 8]

One JSON line a (size, spans) pair: milliseconds a fold, median and best
of ``--reps`` calls on a touched buffer, through the same threads the
loader uses, each checked against ``zlib.crc32`` of the whole.  It needs
no chip, but it needs the chip's HOST: the cores, the memory bandwidth
and the neighbours of this sandbox are not the ones a cell runs on.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from ddl_tpu import integrity  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", type=float, nargs="+",
                    default=[8, 16, 32, 64, 154.1, 308.3])
    ap.add_argument("--spans", type=int, nargs="+",
                    default=[1, 2, 3, 4, 6, 8, 12, 16])
    ap.add_argument("--reps", type=int, default=9)
    args = ap.parse_args()
    cores = len(os.sched_getaffinity(0))
    rng = np.random.default_rng(29)
    for mb in args.sizes_mb:
        n = int(mb * 1e6)
        buf = rng.integers(0, 256, size=n, dtype=np.uint8)
        want = zlib.crc32(buf)
        for spans in args.spans:
            ms = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                got = integrity.crc32_spans(buf, spans)
                ms.append(1e3 * (time.perf_counter() - t0))
                if got != want:
                    raise SystemExit(f"fold over {spans} spans != zlib.crc32")
            print(json.dumps({
                "bytes": n, "spans": spans, "cores": cores,
                "chosen": integrity.fold_spans(n),
                "ms_median": round(statistics.median(ms), 3),
                "ms_best": round(min(ms), 3),
                "GB_per_s": round(n / 1e6 / statistics.median(ms), 2),
            }), flush=True)
    integrity.close_fold_pool()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""End-to-end window integrity: checksummed (seq, producer) slot headers.

The transport hands windows from producer to consumer through shared
memory; nothing in PR 1/2 verified that the bytes that left
``DataPusher._commit_window`` are the bytes a training step consumes.
This module closes that gap:

- Every committed window carries a 32-byte trailer header —
  ``magic | crc32 | seq | producer | flags`` — written into the ring
  slot just past the payload (slots are allocated ``HEADER_BYTES``
  larger when integrity is on, so payload geometry and every existing
  ``slot_view[:payload]`` consumer are untouched).
- The consumer verifies the header at drain (magic, producer identity,
  the expected logical sequence number, and the payload CRC), and the
  staging executor re-verifies the CRC of its slot→staging copy before
  the slot can be released early (a producer overwriting a
  not-yet-copied slot is exactly the torn-read this catches).
- A corrupt slot is quarantined and replayed: the consumer re-requests
  the window from the producer over the control channel, which rewinds
  via the same deterministic-replay contract elastic respawn uses
  (``on_init`` → ``post_init`` → ``fast_forward(seq)``).  See
  ``DistributedDataLoader._quarantine_and_replay`` and
  docs/ROBUSTNESS.md for the degradation ladder.

CRC is ``zlib.crc32``: ~0.27 ms per MB on one core of the chip's host
(3.7 GB/s; PERF.md §6, PR 29).  That is noise for a KiB-sized token
window and NOT for a 308 MB image window — on the alias path there is no
slot memcpy to hide it behind, and one serial CRC was two thirds of the
train loop's period.  So the drain-time check folds a large payload over
contiguous spans on a few threads (:func:`crc32_spans`): the same
polynomial over the same bytes, combined into the bit-identical 32-bit
value the producer committed.  ``DDL_TPU_INTEGRITY=0`` disables the whole
layer: slots shrink back, commits and drains skip the checksum, and the
loader serves exactly the PR 2 byte path.

Header layout (little-endian, 32 of 32 reserved bytes used)::

    u32 magic   u32 crc32(payload [+ scales])   u64 seq
    u32 producer_idx   u32 flags   u32 wire_code   u32 scale_bytes

The last two fields are the WIRE-FORMAT extension (``ddl_tpu.wire``):
``wire_code`` names the payload's wire dtype (0 = raw — the value old
headers carry implicitly, so pre-wire rings verify unchanged) and
``scale_bytes`` sizes the blockwise-quantization scales that travel in
the TRAILER EXTENSION, the region immediately past this header
(slots for wire-encoded windows are committed with the *encoded*
payload size, so header + scales always fit inside the raw-sized
slot).  The CRC covers the encoded payload AND the scales — integrity
verifies the *quantized* bytes, so corruption detection survives the
dtype change: a flipped wire byte mismatches the committed CRC exactly
like flipped raw bytes, and the quarantine-and-replay ladder runs
unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import queue
import struct
import threading
import time
import zlib
from concurrent.futures import Future
from typing import List, Optional

import numpy as np

from ddl_tpu.concurrency import named_lock

#: Trailer size reserved past the payload in every ring slot.
HEADER_BYTES = 32

_MAGIC = 0x44444C57  # "DDLW"
_FMT = "<IIQIIII"
_FMT_BYTES = struct.calcsize(_FMT)  # 32 (wire_code + scale_bytes appended;
# the first 24 bytes keep the pre-wire layout, so old headers parse with
# wire_code == scale_bytes == 0 — i.e. raw)


def integrity_enabled(override: Optional[bool] = None) -> bool:
    """The ``DDL_TPU_INTEGRITY`` gate (default ON; ``0``/``off`` disables)."""
    from ddl_tpu.utils import env_flag

    return env_flag("DDL_TPU_INTEGRITY", override)


def window_crc(payload: np.ndarray) -> int:
    """CRC32 of a window payload (a C-contiguous uint8 view)."""
    return zlib.crc32(np.ascontiguousarray(payload)) & 0xFFFFFFFF


def wire_crc(slot_view: np.ndarray, payload_bytes: int,
             scale_bytes: int, n_spans: int = 1) -> int:
    """The committed CRC of a (possibly wire-encoded) slot: the payload
    fold continued over the trailer-extension scales.

    THE shared implementation for both sides of the contract — the
    producer's encoded commit and :func:`verify_window`'s drain check
    call this one function, so the fold order / region layout cannot
    desynchronize between them.  ``scale_bytes == 0`` degrades to the
    plain :func:`window_crc`.  ``n_spans > 1`` (the drain check of a
    large window, :func:`fold_spans`) computes the payload's CRC over
    that many spans at once (:func:`crc32_spans`): the same value.
    """
    payload = slot_view[:payload_bytes]
    crc = window_crc(payload) if n_spans < 2 else crc32_spans(payload, n_spans)
    if scale_bytes:
        start = payload_bytes + HEADER_BYTES
        crc = zlib.crc32(
            np.ascontiguousarray(
                slot_view[start : start + scale_bytes]
            ),
            crc,
        ) & 0xFFFFFFFF
    return crc


# -- span-parallel fold of the drain-time CRC --------------------------------

#: A span is worth a thread from about this many bytes, and more than
#: ``MAX_SPANS`` of them buy little and not reliably.  Both placed by
#: ``tools/probe_crc_fold.py`` on the chip's host (PERF.md §6, PR 29):
#: 8 MB folds in 2.2 ms over 1 span, 1.4 over 2 and 0.9 over 4, and
#: spans under 2 MB stop paying for their hand-over; 308 MB folds in
#: 83 ms over 1 span, 11.5 over 8 and 8-11 over 12-16 on 13 cores.
SPAN_MIN_BYTES = 4 << 20
MAX_SPANS = 8

#: Bound on the wait for one span's CRC (tens of ms of C code): a fold
#: that cannot finish raises into the caller instead of parking it.
_FOLD_TIMEOUT_S = 60.0

_CRC_POLY = 0xEDB88320  # CRC-32, reflected: bit 31 is x^0


def _usable_cores() -> int:
    return len(os.sched_getaffinity(0))


def fold_spans(payload_bytes: int) -> int:
    """How many spans the drain-time CRC of ``payload_bytes`` folds
    over — from what the code observes, no knob: spans of at least
    ``SPAN_MIN_BYTES``, at most ``MAX_SPANS``, at most half the cores
    this process may run on.  Below 2 the check is one ``zlib.crc32``
    call on the caller's thread and no pool exists."""
    return max(
        1,
        min(payload_bytes // SPAN_MIN_BYTES, MAX_SPANS, _usable_cores() // 2),
    )


def _mulmod(a: int, b: int) -> int:
    """``a(x) * b(x) mod P`` over GF(2), reflected bit order."""
    out = 0
    bit = 1 << 31
    while a:
        if a & bit:
            out ^= b
            a ^= bit
        bit >>= 1
        b = (b >> 1) ^ _CRC_POLY if b & 1 else b >> 1
    return out


@functools.lru_cache(maxsize=32)
def _append_zeros_operator(nbytes: int) -> int:
    """The GF(2) operator "append ``nbytes`` zero bytes" on a CRC-32
    register, as the polynomial ``x^(8 * nbytes) mod P`` — computed
    once per span length (windows have one size, so a fold needs two:
    the full span's and the last span's)."""
    op = 1 << 31  # x^0
    power = 1 << 23  # x^8: one zero byte
    while nbytes:
        if nbytes & 1:
            op = _mulmod(power, op)
        power = _mulmod(power, power)
        nbytes >>= 1
    return op


def crc32_combine(crc_a: int, crc_b: int, len_b: int) -> int:
    """``zlib.crc32(a + b)`` from ``zlib.crc32(a)``, ``zlib.crc32(b)``
    and ``len(b)`` — zlib's ``crc32_combine``, which the stdlib does
    not expose."""
    return _mulmod(_append_zeros_operator(len_b), crc_a) ^ crc_b


class _SpanPool:
    """The few daemon threads that CRC spans for :func:`crc32_spans`.

    Started by the first fold that wants them, in the process that
    drains (a producer commits serially and never gets here), grown to
    the widest fold seen, stopped by :meth:`close` and started again by
    the next fold.  One lock covers "start threads + hand over the
    spans", so a ``close`` racing a fold either sees its spans queued
    (the threads finish them before they exit) or comes first (the
    fold starts fresh threads)."""

    def __init__(self) -> None:
        self._lock = named_lock("integrity.pool")
        self._jobs: "queue.SimpleQueue" = queue.SimpleQueue()
        self._threads: List[threading.Thread] = []

    def submit(self, spans: List[np.ndarray]) -> List["Future[int]"]:
        futures: List["Future[int]"] = [Future() for _ in spans]
        with self._lock:
            while len(self._threads) < len(spans):
                t = threading.Thread(
                    target=self._run, args=(self._jobs,),
                    name=f"ddl-verify-{len(self._threads)}", daemon=True,
                )
                t.start()
                self._threads.append(t)
            for job in zip(futures, spans):
                self._jobs.put(job)
        return futures

    @staticmethod
    def _run(jobs: "queue.SimpleQueue") -> None:
        while True:
            # A daemon parked on its own queue: close() posts one None
            # a thread, and nothing joins it without a timeout.
            job = jobs.get()  # ddl-lint: disable=DDL012
            if job is None:
                return
            future, span = job
            try:
                future.set_result(zlib.crc32(span))
            except Exception as e:  # ddl-lint: disable=DDL007
                # Not swallowed: the waiting fold's result() raises it.
                future.set_exception(e)

    def close(self, timeout_s: float = 5.0) -> None:
        with self._lock:
            # The stopping threads keep their queue, so the Nones reach
            # them and not the threads a later fold starts.
            threads, self._threads = self._threads, []
            jobs, self._jobs = self._jobs, queue.SimpleQueue()
        for _ in threads:
            jobs.put(None)
        deadline = time.monotonic() + timeout_s
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))


_POOL = _SpanPool()


def close_fold_pool() -> None:
    """Stop the fold's threads (``loader.shutdown()``).  A later fold,
    from another loader in this process, starts its own."""
    _POOL.close()


def crc32_spans(payload: np.ndarray, n_spans: int) -> int:
    """``zlib.crc32`` of ``payload``, bit for bit, computed over
    ``n_spans`` contiguous spans at once (``zlib.crc32`` drops the GIL)
    and combined.  The caller's thread takes the first span itself."""
    flat = np.ascontiguousarray(payload).reshape(-1).view(np.uint8)
    span = max(1, -(-flat.nbytes // n_spans))
    rest = [flat[o : o + span] for o in range(span, flat.nbytes, span)]
    futures = _POOL.submit(rest)
    crc = zlib.crc32(flat[:span])
    for part, future in zip(rest, futures):
        crc = crc32_combine(
            crc, future.result(timeout=_FOLD_TIMEOUT_S), part.nbytes
        )
    return crc


@dataclasses.dataclass(frozen=True)
class WindowHeader:
    magic: int
    crc: int
    seq: int
    producer_idx: int
    flags: int
    #: Wire-format extension (``ddl_tpu.wire``): the payload's wire
    #: dtype code (0 = raw) and the byte length of the blockwise scales
    #: stored in the trailer extension past this header.
    wire_code: int = 0
    scale_bytes: int = 0

    @property
    def valid_magic(self) -> bool:
        return self.magic == _MAGIC

    @property
    def wire_dtype(self) -> str:
        """The payload's wire dtype name ("raw" for pre-wire headers)."""
        from ddl_tpu import wire

        return wire._CODE_TO_DTYPE.get(self.wire_code, "raw")


def blob_seq(digest: str) -> int:
    """Stable 64-bit sequence tag derived from a cache-entry digest.

    The disk cache tier (``ddl_tpu/cache/store.py``) reuses the ring-slot
    trailer machinery above for its on-disk entries, with this digest-
    derived value in the header's ``seq`` field: a spill file renamed or
    hard-linked across keys then fails :func:`verify_window`'s sequence
    check even when its payload CRC is intact — stale entries can never
    alias a foreign key.
    """
    return int(digest[:16], 16) & 0xFFFFFFFFFFFFFFFF


def write_header(
    slot_view: np.ndarray,
    payload_bytes: int,
    seq: int,
    producer_idx: int,
    crc: int,
    wire_code: int = 0,
    scale_bytes: int = 0,
) -> None:
    """Stamp the trailer header into ``slot_view`` past the payload.

    ``payload_bytes`` is the size of the bytes that actually travel —
    the *encoded* size for wire-formatted windows.  ``wire_code`` /
    ``scale_bytes`` describe the encoding (``ddl_tpu.wire``); the
    scales themselves are written separately
    (:func:`write_scales`), immediately past this header.
    """
    packed = struct.pack(
        _FMT, _MAGIC, crc, seq, producer_idx, 0, wire_code, scale_bytes
    )
    slot_view[payload_bytes : payload_bytes + _FMT_BYTES] = np.frombuffer(
        packed, dtype=np.uint8
    )


def read_header(slot_view: np.ndarray, payload_bytes: int) -> WindowHeader:
    raw = bytes(slot_view[payload_bytes : payload_bytes + _FMT_BYTES])
    magic, crc, seq, producer_idx, flags, wire_code, scale_bytes = (
        struct.unpack(_FMT, raw)
    )
    return WindowHeader(
        magic, crc, seq, producer_idx, flags, wire_code, scale_bytes
    )


def write_scales(
    slot_view: np.ndarray, payload_bytes: int, scales: np.ndarray
) -> None:
    """Write the blockwise-quantization scales into the trailer
    EXTENSION — the region immediately past the 32-byte header.  The
    caller stamps the matching ``scale_bytes`` via :func:`write_header`
    and folds the scales into the committed CRC
    (``crc32(scales, crc32(payload))`` — see :func:`verify_window`)."""
    raw = np.ascontiguousarray(scales).view(np.uint8).reshape(-1)
    start = payload_bytes + HEADER_BYTES
    slot_view[start : start + raw.nbytes] = raw


def read_scales(
    slot_view: np.ndarray, payload_bytes: int, scale_bytes: int
) -> np.ndarray:
    """The trailer extension's scales as a flat fp32 array (a copy —
    the slot may be released/overwritten while the decode is live)."""
    start = payload_bytes + HEADER_BYTES
    return (
        np.array(slot_view[start : start + scale_bytes])
        .view(np.float32)
    )


def verify_window(
    slot_view: np.ndarray,
    payload_bytes: int,
    expect_seq: int,
    expect_producer: int,
) -> Optional[str]:
    """Full drain-time check.  Returns a failure description, or None.

    Ordered cheap-to-expensive: magic (a producer that never stamped a
    header — torn commit or version skew), identity and sequencing (a
    dropped/duplicated/foreign window), then the payload CRC (flipped
    bytes) — every byte of it, folded over :func:`fold_spans` spans.
    """
    hdr = read_header(slot_view, payload_bytes)
    if not hdr.valid_magic:
        return f"bad header magic 0x{hdr.magic:08x} (torn or unstamped commit)"
    if hdr.producer_idx != expect_producer:
        return (
            f"window from producer {hdr.producer_idx}, "
            f"expected producer {expect_producer}"
        )
    if hdr.seq != expect_seq:
        return f"window seq {hdr.seq}, expected {expect_seq} (drop/duplicate)"
    # The CRC covers the bytes that actually traveled: the (possibly
    # wire-encoded) payload, then the trailer-extension scales — so
    # corruption detection survives the dtype change (a flipped int8
    # wire byte or scale byte mismatches exactly like a raw one).
    got = wire_crc(
        slot_view, payload_bytes, hdr.scale_bytes, fold_spans(payload_bytes)
    )
    if got != hdr.crc:
        return (
            f"payload crc32 0x{got:08x} != committed 0x{hdr.crc:08x} "
            f"(seq {hdr.seq}, producer {hdr.producer_idx}, "
            f"wire {hdr.wire_dtype})"
        )
    return None

"""The span-parallel fold of the drain-time CRC (``ddl_tpu.integrity``).

The consumer verifies a large window's CRC-32 over contiguous spans on a
few threads and combines the partial values; the committed value, the
header and the producers' commit do not change.  These tests hold the
fold to ``zlib.crc32`` of the whole, bit for bit, hold the path choice
to what the code observes (payload size, usable cores), and hold the
threads' lifetime to the loader's.
"""

import threading
import zlib

import numpy as np
import pytest

from ddl_tpu import integrity
from ddl_tpu.observability import Metrics

SPAN = 4096  # a size to place the cases around; the fold takes any


def _bytes(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, size=n, dtype=np.uint8)


def _verify_threads():
    return [
        t for t in threading.enumerate()
        if t.name.startswith("ddl-verify") and t.is_alive()
    ]


def _slot(payload_bytes: int, scale_bytes: int = 0, seq: int = 7,
          producer: int = 3, seed: int = 0) -> np.ndarray:
    """A committed slot as a producer leaves it: payload, trailer
    header with the SERIAL ``wire_crc`` (what ``datapusher`` stamps),
    scales in the trailer extension."""
    slot = _bytes(payload_bytes + integrity.HEADER_BYTES + scale_bytes, seed)
    crc = integrity.wire_crc(slot, payload_bytes, scale_bytes)
    integrity.write_header(
        slot, payload_bytes, seq=seq, producer_idx=producer, crc=crc,
        wire_code=2 if scale_bytes else 0, scale_bytes=scale_bytes,
    )
    return slot


def _no_fold(*a, **kw):
    raise AssertionError("this verify must not reach the parallel fold")


@pytest.fixture
def low_floor(monkeypatch):
    """Lower the span floor so KiB-sized test windows take the parallel
    fold, whatever machine runs the tests."""
    monkeypatch.setattr(integrity, "SPAN_MIN_BYTES", 512)
    monkeypatch.setattr(integrity, "_usable_cores", lambda: 16)


@pytest.mark.parametrize("n_spans", range(1, 9))
@pytest.mark.parametrize(
    "size",
    [0, 1, SPAN - 1, SPAN, SPAN + 1, 12345, 8 * SPAN, 8 * SPAN + 7],
    ids=["empty", "one", "span-1", "span", "span+1", "odd", "spans",
         "spans+7"],
)
def test_fold_equals_crc_of_the_whole(size, n_spans):
    data = _bytes(size, seed=size)
    want = zlib.crc32(data.tobytes())
    assert integrity.wire_crc(data, size, 0, n_spans) == want
    assert integrity.wire_crc(data, size, 0) == want


@pytest.mark.parametrize("n_spans", [2, 3, 8])
@pytest.mark.parametrize("scale_bytes", [0, 4, 260])
def test_fold_equals_wire_crc_with_trailer_scales(scale_bytes, n_spans):
    payload = 5 * SPAN + 3
    slot = _bytes(payload + integrity.HEADER_BYTES + scale_bytes, seed=11)
    assert integrity.wire_crc(
        slot, payload, scale_bytes, n_spans
    ) == integrity.wire_crc(slot, payload, scale_bytes)


@pytest.mark.parametrize("n_spans", [1, 4])
def test_fold_of_a_non_contiguous_input(n_spans):
    base = _bytes(4 * SPAN + 10, seed=5)
    strided = base[::2]
    assert not strided.flags.c_contiguous
    assert integrity.wire_crc(
        strided, strided.size, 0, n_spans
    ) == zlib.crc32(strided.tobytes())


def test_fold_counts_bytes_of_a_wider_dtype():
    words = np.arange(3 * SPAN + 1, dtype=np.uint32)
    assert integrity.wire_crc(words, words.size, 0, 4) == zlib.crc32(
        words.tobytes()
    )


@pytest.mark.parametrize("seed", range(6))
def test_crc32_combine_is_zlibs(seed):
    rng = np.random.default_rng(seed)
    a = _bytes(int(rng.integers(0, 5000)), seed)
    b = _bytes(int(rng.integers(0, 5000)), seed + 100)
    assert integrity.crc32_combine(
        zlib.crc32(a), zlib.crc32(b), b.size
    ) == zlib.crc32(np.concatenate([a, b]))


def test_combine_operator_is_cached_by_length():
    op = integrity._append_zeros_operator
    op.cache_clear()
    data = _bytes(8 * SPAN + 7)
    integrity.wire_crc(data, data.size, 0, 8)
    first = op.cache_info()
    # One operator for the full span's length and one for the last,
    # shorter span: two lengths, whatever the number of spans.
    assert first.misses == 2 and first.currsize == 2
    integrity.wire_crc(data, data.size, 0, 8)
    again = op.cache_info()
    assert again.misses == 2 and again.hits > first.hits
    assert op(SPAN) != op(SPAN + 1)
    assert op(0) == 1 << 31  # appending nothing is the identity


# -- verify_window finds a flipped byte wherever it is -----------------------

PAYLOAD = 8 * 512 + 100  # eight spans at the lowered floor, the last partial


def test_intact_window_verifies_under_the_fold(low_floor, monkeypatch):
    slot = _slot(PAYLOAD, scale_bytes=64)
    folds = []
    fold = integrity.crc32_spans
    monkeypatch.setattr(
        integrity, "crc32_spans",
        lambda payload, n: folds.append(n) or fold(payload, n),
    )
    assert integrity.fold_spans(PAYLOAD) == 8
    assert integrity.verify_window(slot, PAYLOAD, 7, 3) is None
    assert folds == [8]


@pytest.mark.parametrize("span_idx", range(8))
def test_flipped_byte_in_each_span_is_detected(low_floor, span_idx):
    slot = _slot(PAYLOAD)
    span = -(-PAYLOAD // 8)
    slot[span_idx * span + span // 2] ^= 0x01
    err = integrity.verify_window(slot, PAYLOAD, 7, 3)
    assert err is not None and "crc32" in err


@pytest.mark.parametrize(
    "offset", [PAYLOAD - 1, PAYLOAD - 50, 0],
    ids=["last-byte", "last-partial-span", "first-byte"],
)
def test_flipped_byte_at_the_edges_is_detected(low_floor, offset):
    slot = _slot(PAYLOAD)
    slot[offset] ^= 0x80
    err = integrity.verify_window(slot, PAYLOAD, 7, 3)
    assert err is not None and "crc32" in err


def test_flipped_byte_in_the_scales_is_detected(low_floor):
    slot = _slot(PAYLOAD, scale_bytes=64)
    slot[PAYLOAD + integrity.HEADER_BYTES + 17] ^= 0x04
    err = integrity.verify_window(slot, PAYLOAD, 7, 3)
    assert err is not None and "crc32" in err


@pytest.mark.parametrize(
    "seq,producer,needle",
    [(8, 3, "seq"), (7, 4, "producer")],
    ids=["wrong-seq", "wrong-producer"],
)
def test_header_checks_come_before_the_fold(
    low_floor, monkeypatch, seq, producer, needle
):
    monkeypatch.setattr(integrity, "crc32_spans", _no_fold)
    err = integrity.verify_window(_slot(PAYLOAD), PAYLOAD, seq, producer)
    assert err is not None and needle in err


# -- the path choice: what the code observes, no knob ------------------------

MB = 1_000_000


@pytest.mark.parametrize(
    "payload,cores,want",
    [
        (131_072, 30, 1),          # a token window: the parent's one call
        (4 * 2**20 * 2 - 1, 30, 1),  # one byte under two spans' floor
        (4 * 2**20 * 2, 30, 2),
        (32 * MB, 30, 7),
        (154 * MB, 13, 6),         # the one-chip ViT window on its 13 cores
        (308 * MB, 30, 8),         # the dp4 window on the four-chip host
        (308 * MB, 4, 2),
        (308 * MB, 3, 1),          # half of three cores is one: serial
        (308 * MB, 1, 1),
    ],
)
def test_span_count_follows_payload_and_cores(monkeypatch, payload, cores, want):
    monkeypatch.setattr(integrity, "_usable_cores", lambda: cores)
    assert integrity.fold_spans(payload) == want


def test_usable_cores_is_this_process_affinity():
    import os

    assert integrity._usable_cores() == len(os.sched_getaffinity(0))


def test_token_window_takes_the_serial_call_and_no_thread(monkeypatch):
    integrity.close_fold_pool()
    monkeypatch.setattr(integrity, "_usable_cores", lambda: 30)
    monkeypatch.setattr(integrity, "crc32_spans", _no_fold)
    slot = _slot(131_072)
    assert integrity.verify_window(slot, 131_072, 7, 3) is None
    slot[131_071] ^= 0x01
    assert "crc32" in integrity.verify_window(slot, 131_072, 7, 3)
    assert _verify_threads() == []


def test_large_window_folds_in_parallel_at_the_real_floor(monkeypatch):
    integrity.close_fold_pool()
    monkeypatch.setattr(integrity, "_usable_cores", lambda: 8)
    payload = 3 * integrity.SPAN_MIN_BYTES + 12345
    slot = _slot(payload)
    assert integrity.fold_spans(payload) == 3
    assert integrity.verify_window(slot, payload, 7, 3) is None
    threads = _verify_threads()
    # The caller's thread takes one span itself.
    assert len(threads) == 2
    assert all(t.daemon for t in threads)
    slot[payload - 1] ^= 0x10
    assert "crc32" in integrity.verify_window(slot, payload, 7, 3)
    integrity.close_fold_pool()
    assert _verify_threads() == []


def test_pool_restarts_after_close_and_grows_to_the_widest_fold():
    integrity.close_fold_pool()
    data = _bytes(8 * SPAN)
    want = zlib.crc32(data)
    assert integrity.wire_crc(data, data.size, 0, 2) == want
    assert len(_verify_threads()) == 1
    assert integrity.wire_crc(data, data.size, 0, 5) == want
    assert len(_verify_threads()) == 4
    integrity.close_fold_pool()
    assert _verify_threads() == []
    assert integrity.wire_crc(data, data.size, 0, 3) == want
    assert len(_verify_threads()) == 2
    integrity.close_fold_pool()


def test_concurrent_folds_and_closes_agree_with_zlib():
    """More folding threads than cores, a closer racing them, a short
    switch interval: every fold still returns the whole's CRC (a span
    handed to a stopping thread is finished, or the fold starts fresh
    threads) and nothing is left waiting."""
    import sys

    data = _bytes(64 * SPAN + 5, seed=9)
    want = zlib.crc32(data)
    bad = []
    stop = threading.Event()

    def fold(n):
        for _ in range(40):
            if integrity.wire_crc(data, data.size, 0, n) != want:
                bad.append(n)

    def closer():
        while not stop.is_set():
            integrity.close_fold_pool()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        folders = [
            threading.Thread(target=fold, args=(2 + i % 7,)) for i in range(12)
        ]
        c = threading.Thread(target=closer)
        c.start()
        for t in folders:
            t.start()
        for t in folders:
            t.join(timeout=60.0)
        stop.set()
        c.join(timeout=10.0)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in folders) and not c.is_alive()
    assert bad == []
    integrity.close_fold_pool()
    assert _verify_threads() == []


# -- through a real loader ---------------------------------------------------


def _drain(prod, n_producers=2, n_epochs=4, batch_size=8):
    from ddl_tpu.dataloader import DistributedDataLoader
    from ddl_tpu.env import distributed_dataloader
    from ddl_tpu.types import Marker

    out, metrics, alive = [], Metrics(), {}

    @distributed_dataloader(n_producers=n_producers, mode="thread")
    def main(env):
        loader = DistributedDataLoader(
            prod, batch_size=batch_size, connection=env.connection,
            n_epochs=n_epochs, output="numpy", metrics=metrics,
        )
        for _ in range(n_epochs):
            for i in range(len(loader)):
                out.append(
                    np.concatenate([c.copy() for c in loader[i]], axis=1)
                )
                alive["streaming"] = len(_verify_threads())
                loader.mark(Marker.END_OF_BATCH)
            loader.mark(Marker.END_OF_EPOCH)
        loader.shutdown()  # the last epoch's mark has shut it down already
        alive["after"] = len(_verify_threads())

    main()
    return np.concatenate(out), metrics, alive


def _array_producer(wire_dtype, cols=64):
    from ddl_tpu.readers import ArrayProducer

    data = np.random.default_rng(0).standard_normal((64, cols)).astype(
        np.float32
    )
    prod = ArrayProducer(data, window_size=16, seed=1)
    prod.wire_dtype = wire_dtype
    return prod


@pytest.mark.parametrize("wire_dtype", ["raw", "int8"])
def test_producer_committed_header_equals_the_consumers_fold(
    low_floor, monkeypatch, wire_dtype
):
    """The producers' commit (``datapusher``: the serial ``wire_crc``,
    unchanged) against the consumer's parallel fold of the same slot,
    raw and wire-encoded (scales in the trailer extension)."""
    seen = []
    verify = integrity.verify_window

    def spy(slot_view, payload_bytes, *a, **kw):
        hdr = integrity.read_header(slot_view, payload_bytes)
        n_spans = integrity.fold_spans(payload_bytes)
        got = integrity.wire_crc(
            slot_view, payload_bytes, hdr.scale_bytes, n_spans
        )
        seen.append((got, hdr.crc, hdr.scale_bytes, n_spans))
        return verify(slot_view, payload_bytes, *a, **kw)

    monkeypatch.setattr(integrity, "verify_window", spy)
    serial, _, _ = _drain(_array_producer(wire_dtype))
    assert seen and all(got == crc for got, crc, _, _ in seen)
    assert all(n >= 2 for *_, n in seen)
    assert all(bool(sb) == (wire_dtype == "int8") for _, _, sb, _ in seen)
    monkeypatch.undo()  # the real floor: these windows take the serial call
    again, _, _ = _drain(_array_producer(wire_dtype))
    assert np.array_equal(serial, again)


def test_loader_counts_parallel_windows_and_shutdown_stops_the_threads(
    low_floor,
):
    integrity.close_fold_pool()
    _, m, alive = _drain(_array_producer("raw"))
    windows = m.counter("consumer.windows")
    assert windows > 0
    assert m.counter("consumer.verify_parallel_windows") == windows
    assert m.timer("consumer.verify").count == windows
    assert m.counter("integrity.corrupt_windows") == 0
    assert alive["streaming"] >= 1 and alive["after"] == 0
    assert _verify_threads() == []


def test_token_sized_windows_through_a_loader_start_no_thread(monkeypatch):
    """16 rows x 2,048 float32 = 131,072 bytes a window, the token
    cells' size, on a host with cores to spare: the parent's one serial
    call, timed, and no thread."""
    integrity.close_fold_pool()
    monkeypatch.setattr(integrity, "_usable_cores", lambda: 30)
    monkeypatch.setattr(integrity, "crc32_spans", _no_fold)
    _, m, alive = _drain(_array_producer("raw", cols=2048))
    assert m.counter("consumer.windows") > 0
    assert m.counter("consumer.verify_parallel_windows") == 0
    assert m.timer("consumer.verify").count == m.counter("consumer.windows")
    assert alive == {"streaming": 0, "after": 0}


# -- the benchmark's reader --------------------------------------------------


def test_verify_host_ms_reader():
    from benchmarks.layers import verify_host_ms

    assert verify_host_ms.read({"counters": {"consumer.windows": 30.0}}) is None
    assert verify_host_ms.read({"counters": {}}) is None
    assert verify_host_ms.read(
        {"counters": {"consumer.windows": 0.0, "consumer.verify.total_s": 0.0}}
    ) is None
    got = verify_host_ms.read(
        {"counters": {"consumer.windows": 40.0,
                      "consumer.verify.total_s": 0.5,
                      "consumer.verify.count": 40.0}}
    )
    assert got == pytest.approx(12.5)


def test_verify_host_ms_is_an_entry_and_names_the_vit_cells():
    """Found by name, not as the tail: later PRs append entries after it."""
    from benchmarks.lib import cells

    entry = next(
        e for e in cells.benchmark_file()["per_layer"]
        if e["name"] == "verify_host_ms"
    )
    assert entry == {
        "name": "verify_host_ms", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "window rings",
        "moves": "images_per_s",
        "workloads": ["vit-b16.images-224", "vit-b16.images-224-dp4"],
    }
    assert callable(cells.layer_reader("verify_host_ms"))

"""Wire-format suite (ISSUE 13): quantized + compressed data plane.

Covers the full wire surface: codec/quantizer units, the self-
describing exchange envelope, the integrity trailer extension (scales
next to the CRC, CRC over the ENCODED bytes), the slot wire end to end
through a THREAD loader (drift bounded AND nonzero — zero drift means
the wire silently never engaged), the lossless byte-identity matrix
(compressed shards ≡ raw across readers and modes, cache on/off),
the ICI wire accounting hand-checks + virtual-mesh transport, and the
two deterministic chaos rows (WIRE_CORRUPTION → quarantine + replay,
DECODE_FAIL → bounded retry / raw fallback).
"""

import io
import os
import sys
import threading
import zlib as _zlib

import numpy as np
import pytest

from ddl_tpu import faults, integrity, wire
from ddl_tpu.exceptions import DecodeError, DoesNotMatchError
from ddl_tpu.faults import FaultKind, FaultPlan, FaultSpec
from ddl_tpu.observability import Metrics

sys.path.insert(0, os.path.dirname(__file__))


# -- codec + quantizer units -------------------------------------------------


class TestCodecs:
    def test_zlib_always_available_and_roundtrips(self):
        assert "zlib" in wire.available_codecs()
        c = wire.get_codec("zlib")
        data = bytes(range(256)) * 64
        enc = c.encode_bytes(data, level=3)
        assert c.decode_bytes(enc, max_output=len(data)) == data

    def test_decode_is_bounded(self):
        c = wire.get_codec("zlib")
        enc = c.encode_bytes(b"x" * 10000, level=1)
        with pytest.raises(DecodeError):
            c.decode_bytes(enc, max_output=100)

    def test_zlib_decode_reads_gzip_frames_too(self, tmp_path):
        """CodecBackend maps the .gz suffix to this codec, so decode
        must auto-detect gzip framing (wbits=47) — a plain
        decompressobj() fails the gzip header check and every .gz
        shard would die persistently."""
        import gzip

        from ddl_tpu.cache import CodecBackend

        data = bytes(range(256)) * 16
        c = wire.get_codec("zlib")
        assert c.decode_bytes(
            gzip.compress(data), max_output=len(data)
        ) == data
        arr = np.arange(32, dtype=np.float32)
        buf = io.BytesIO()
        np.save(buf, arr)
        (tmp_path / "s.npy.gz").write_bytes(gzip.compress(buf.getvalue()))
        out = np.load(CodecBackend().open(str(tmp_path / "s.npy.gz")))
        assert np.array_equal(out, arr)

    def test_truncated_stream_raises_not_partial_output(self):
        """A torn partial object must FAIL decode (DecodeError → the
        retry/refetch ladders), never return silently-truncated bytes
        (review regression: decompressobj returns partial output with
        no exception on a truncated stream)."""
        c = wire.get_codec("zlib")
        enc = c.encode_bytes(b"y" * 50000, level=1)
        with pytest.raises(DecodeError, match="truncated"):
            c.decode_bytes(enc[: len(enc) // 2], max_output=1 << 20)

    def test_unknown_codec_rejected(self):
        with pytest.raises(ValueError, match="unknown codec"):
            wire.get_codec("brotli")

    def test_gated_codec_error_names_available_set(self):
        for name in ("zstd", "lz4"):
            if name in wire.available_codecs():
                continue  # host has the lib: constructor must work
            with pytest.raises(ValueError, match="available here"):
                wire.get_codec(name)

    def test_resolve_wire_codec(self, monkeypatch):
        monkeypatch.delenv("DDL_TPU_WIRE_CODEC", raising=False)
        assert wire.resolve_wire_codec(None) is None
        assert wire.resolve_wire_codec("none") is None
        assert wire.resolve_wire_codec("zlib") == "zlib"
        monkeypatch.setenv("DDL_TPU_WIRE_CODEC", "zlib")
        assert wire.resolve_wire_codec(None) == "zlib"
        # env wins over a requested name
        assert wire.resolve_wire_codec("junk") == "zlib"
        monkeypatch.delenv("DDL_TPU_WIRE_CODEC")
        with pytest.raises(ValueError):
            wire.resolve_wire_codec("junk")


class TestQuantizer:
    def test_roundtrip_drift_bounded_and_nonzero(self, rng):
        x = rng.standard_normal((16, 700)).astype(np.float32)
        q, s = wire.quantize_rows(x)
        assert q.dtype == np.int8 and s.shape == (16, 3)  # ceil(700/256)
        back = wire.dequantize_rows(q, s)
        drift = np.abs(back - x).max() / np.abs(x).max()
        assert 0.0 < drift < 1.5 / 127.0

    def test_zero_blocks_exact(self):
        x = np.zeros((4, 512), np.float32)
        q, s = wire.quantize_rows(x)
        assert np.array_equal(wire.dequantize_rows(q, s), x)

    def test_encode_window_shapes_and_sizes(self, rng):
        x = rng.standard_normal((8, 300)).astype(np.float32)
        for wd, nbytes in (
            ("raw", x.nbytes), ("bf16", x.size * 2), ("int8", x.size)
        ):
            payload, scales = wire.encode_window(x, wd)
            assert payload.nbytes == nbytes
            assert payload.nbytes == wire.encoded_nbytes(
                x.shape, x.dtype, wd
            )
            if wd == "int8":
                assert scales.nbytes == wire.scale_bytes_for(x.shape, wd)
            else:
                assert scales is None
            dec = wire.decode_window(
                payload, scales, x.shape, x.dtype, wd
            )
            if wd == "raw":
                assert np.array_equal(dec, x)
            else:
                assert np.abs(dec - x).max() < 0.05

    def test_lossy_needs_float(self):
        toks = np.arange(64, dtype=np.int32).reshape(8, 8)
        with pytest.raises(ValueError, match="float window"):
            wire.encode_window(toks, "int8")
        assert not wire.lossy_supported(np.int32)
        assert wire.lossy_supported(np.float32)

    def test_decode_into_out_buffer(self, rng):
        x = rng.standard_normal((4, 256)).astype(np.float32)
        payload, scales = wire.encode_window(x, "int8")
        out = np.empty_like(x)
        got = wire.decode_window(
            payload, scales, x.shape, x.dtype, "int8", out=out
        )
        assert got is out and np.abs(out - x).max() < 0.05


class TestEnvelope:
    @pytest.mark.parametrize("wd", ["raw", "bf16", "int8"])
    @pytest.mark.parametrize("codec", [None, "zlib"])
    def test_pack_unpack_matrix(self, rng, wd, codec):
        rows = rng.standard_normal((12, 40)).astype(np.float32)
        m = Metrics()
        buf = wire.pack_rows(rows, wd, codec=codec, level=3, metrics=m)
        out = wire.unpack_rows(buf, metrics=m)
        assert out.shape == rows.shape and out.dtype == rows.dtype
        if wd == "raw":
            assert np.array_equal(out, rows)
        else:
            assert 0.0 < np.abs(out - rows).max() < 0.1
        assert m.counter("wire.encoded_bytes") == buf.nbytes
        assert m.counter("wire.payload_bytes") == rows.nbytes

    def test_malformed_envelopes_raise_decode_error(self, rng):
        rows = rng.standard_normal((4, 8)).astype(np.float32)
        buf = wire.pack_rows(rows, "int8", codec="zlib", level=1)
        with pytest.raises(DecodeError):  # truncated
            wire.unpack_rows(buf[:10])
        bad = buf.copy()
        bad[0] ^= 0xFF  # magic
        with pytest.raises(DecodeError):
            wire.unpack_rows(bad)
        corrupt = buf.copy()
        corrupt[-3] ^= 0xFF  # compressed payload byte
        with pytest.raises(DecodeError):
            wire.unpack_rows(corrupt)

    def test_corruption_in_header_fields_still_raises_decode_error(
        self, rng
    ):
        """Flips landing in the shape/dtype-name region raise library
        types (struct.error, UnicodeDecodeError) — they must surface as
        DecodeError or every decode ladder (retry, raw fallback,
        backend refetch) misses them (review regression)."""
        rows = rng.standard_normal((4, 8)).astype(np.float32)
        buf = wire.pack_rows(rows, "int8")
        for off in range(wire._PACK_BYTES, wire._PACK_BYTES + 24):
            bad = buf.copy()
            bad[off] ^= 0xFF
            try:
                wire.unpack_rows(bad)
            except DecodeError:
                pass  # the only acceptable failure type

    def test_unpack_respects_max_output(self, rng):
        rows = (rng.integers(0, 4, (64, 64))).astype(np.float32)
        buf = wire.pack_rows(rows, "raw", codec="zlib", level=6)
        with pytest.raises(DecodeError):
            wire.unpack_rows(buf, max_output=64)


# -- integrity trailer extension ---------------------------------------------


class TestTrailerExtension:
    def _stamped_slot(self, rng, wd="int8"):
        win = rng.standard_normal((8, 300)).astype(np.float32)
        payload, scales = wire.encode_window(win, wd)
        sb = scales.nbytes if scales is not None else 0
        slot = np.zeros(win.nbytes + integrity.HEADER_BYTES, np.uint8)
        enc = payload.nbytes
        slot[:enc] = payload
        crc = integrity.window_crc(slot[:enc])
        if scales is not None:
            integrity.write_scales(slot, enc, scales)
            start = enc + integrity.HEADER_BYTES
            crc = _zlib.crc32(
                np.ascontiguousarray(slot[start : start + sb]), crc
            ) & 0xFFFFFFFF
        integrity.write_header(
            slot, enc, seq=5, producer_idx=2, crc=crc,
            wire_code=wire.WIRE_CODES[wd], scale_bytes=sb,
        )
        return win, slot, enc, sb

    def test_roundtrip_with_scales(self, rng):
        win, slot, enc, sb = self._stamped_slot(rng)
        hdr = integrity.read_header(slot, enc)
        assert hdr.valid_magic and hdr.wire_dtype == "int8"
        assert hdr.scale_bytes == sb == wire.scale_bytes_for(
            win.shape, "int8"
        )
        assert integrity.verify_window(slot, enc, 5, 2) is None
        dec = wire.decode_window(
            slot[:enc], integrity.read_scales(slot, enc, sb),
            win.shape, win.dtype, hdr.wire_dtype,
        )
        assert 0.0 < np.abs(dec - win).max() < 0.05

    def test_crc_covers_encoded_payload_and_scales(self, rng):
        _, slot, enc, sb = self._stamped_slot(rng)
        slot[3] ^= 0xFF  # encoded payload byte
        assert "crc" in integrity.verify_window(slot, enc, 5, 2)
        slot[3] ^= 0xFF
        slot[enc + integrity.HEADER_BYTES + 1] ^= 0xFF  # scale byte
        assert "crc" in integrity.verify_window(slot, enc, 5, 2)

    def test_raw_headers_backcompat(self, rng):
        """A header stamped the pre-wire way parses with wire_code 0
        ("raw") and zero scale bytes — and verifies unchanged."""
        win = rng.standard_normal((4, 64)).astype(np.float32)
        slot = np.zeros(win.nbytes + integrity.HEADER_BYTES, np.uint8)
        slot[: win.nbytes] = win.view(np.uint8).reshape(-1)
        integrity.write_header(
            slot, win.nbytes, seq=0, producer_idx=1,
            crc=integrity.window_crc(slot[: win.nbytes]),
        )
        hdr = integrity.read_header(slot, win.nbytes)
        assert hdr.wire_dtype == "raw" and hdr.scale_bytes == 0
        assert integrity.verify_window(slot, win.nbytes, 0, 1) is None


# -- slot wire end to end (THREAD loader) ------------------------------------


def _stream_loader(prod, n_epochs=4, n_producers=2, batch_size=8):
    from ddl_tpu.dataloader import DistributedDataLoader
    from ddl_tpu.env import distributed_dataloader
    from ddl_tpu.types import Marker

    out = []
    metrics = Metrics()

    @distributed_dataloader(n_producers=n_producers, mode="thread")
    def main(env):
        loader = DistributedDataLoader(
            prod, batch_size=batch_size, connection=env.connection,
            n_epochs=n_epochs, output="numpy", metrics=metrics,
        )
        for _ in range(n_epochs):
            for i in range(len(loader)):
                cols = loader[i]
                out.append(
                    np.concatenate([c.copy() for c in cols], axis=1)
                )
                loader.mark(Marker.END_OF_BATCH)
            loader.mark(Marker.END_OF_EPOCH)

    main()
    return np.concatenate(out), metrics


class TestSlotWire:
    def _producer(self, wd, seed=1):
        from ddl_tpu.readers import ArrayProducer

        data = (
            np.random.default_rng(0).standard_normal((64, 8))
        ).astype(np.float32)
        prod = ArrayProducer(data, window_size=16, seed=seed)
        prod.wire_dtype = wd
        return prod

    def test_drift_bounded_and_nonzero(self):
        raw, _ = _stream_loader(self._producer("raw"))
        for wd, tol in (("int8", 0.02), ("bf16", 0.05)):
            enc, m = _stream_loader(self._producer(wd))
            drift = np.abs(raw - enc).max() / np.abs(raw).max()
            assert 0.0 < drift < tol, (wd, drift)
            assert m.counter("wire.decoded_windows") > 0
            assert 0 < m.counter("wire.encoded_bytes") < m.counter(
                "wire.payload_bytes"
            )

    def test_parity_gate_train_e2e(self):
        """The loss-parity license on the virtual mesh: a jitted linear
        probe trained on the raw stream vs the int8-wire stream must
        stay inside the gate with NONZERO drift."""
        import jax
        import jax.numpy as jnp

        from ddl_tpu.parallel.optimizer import loss_parity

        def train(stream):
            y = jnp.sin(jnp.arange(stream.shape[1], dtype=jnp.float32))

            @jax.jit
            def step(w, x):
                def loss_fn(w):
                    return jnp.mean((x @ w - y[: x.shape[0]]) ** 2)

                loss, g = jax.value_and_grad(loss_fn)(w)
                return w - 1e-4 * g, loss

            w = jnp.zeros(stream.shape[-1])
            losses = []
            for x in stream:
                w, loss = step(w, jnp.asarray(x))
                losses.append(float(loss))
            return losses

        raw, _ = _stream_loader(self._producer("raw"))
        enc, _ = _stream_loader(self._producer("int8"))
        ref = train(raw.reshape(-1, 8, 8))
        test = train(enc.reshape(-1, 8, 8))
        parity = loss_parity(ref, test, rel_tol=2e-2)
        assert parity["parity"], parity
        assert parity["max_rel_drift"] > 0.0  # the wire really engaged

    def test_env_override_kills_reader_capability(self, monkeypatch):
        monkeypatch.setenv("DDL_TPU_WIRE_DTYPE", "raw")
        raw_ref, _ = _stream_loader(self._producer("raw"))
        forced, m = _stream_loader(self._producer("int8"))
        assert np.array_equal(raw_ref, forced)
        assert m.counter("wire.decoded_windows") == 0

    def test_lossy_wire_needs_integrity(self, monkeypatch):
        monkeypatch.setenv("DDL_TPU_INTEGRITY", "0")
        # The refusal happens at the producer handshake; the consumer
        # surfaces it as a handshake failure (the message lands in the
        # producer-side log).
        with pytest.raises(Exception, match="handshake"):
            _stream_loader(self._producer("int8"), n_epochs=1)

    def test_lossy_wire_rejects_forced_inplace(self):
        prod = self._producer("int8")
        prod.inplace_fill = True
        with pytest.raises(Exception, match="handshake"):
            _stream_loader(prod, n_epochs=1)

    def test_degenerate_geometry_refused_at_handshake(self):
        """int8 on a 1-value-per-row window pays 4 scale bytes per
        1-byte payload — encoded + trailer exceeds the raw slot, and
        the refusal must be the typed handshake failure, never a
        mid-run assert/broadcast error (review regression)."""
        from ddl_tpu.readers import ArrayProducer

        data = np.random.default_rng(0).standard_normal(
            (64, 1)
        ).astype(np.float32)
        prod = ArrayProducer(data, window_size=16)
        prod.wire_dtype = "int8"
        with pytest.raises(Exception, match="handshake"):
            _stream_loader(prod, n_epochs=1)

    def test_lossy_wire_rejects_int_windows(self):
        from ddl_tpu.readers import ArrayProducer

        data = np.arange(512, dtype=np.int32).reshape(64, 8)
        prod = ArrayProducer(data, window_size=16)
        prod.wire_dtype = "int8"
        with pytest.raises(Exception, match="handshake"):
            _stream_loader(prod, n_epochs=1)


# -- deterministic chaos rows (tier-1) ---------------------------------------


class TestWireChaos:
    def _producer(self, wd="int8"):
        from ddl_tpu.readers import ArrayProducer

        data = (
            np.random.default_rng(0).standard_normal((64, 8))
        ).astype(np.float32)
        prod = ArrayProducer(data, window_size=16, seed=1)
        prod.wire_dtype = wd
        return prod

    @pytest.mark.parametrize("late_producer", [None, 1, 2])
    def test_wire_corruption_quarantine_and_replay(
        self, crc_fold, late_producer
    ):
        """WIRE_CORRUPTION flips bytes in the ENCODED slot payload after
        the CRC was stamped: drain-time integrity (which verifies the
        quantized bytes) must quarantine, replay through the existing
        ladder, and deliver a stream identical to an uninjected run —
        whether the serial CRC or the span-parallel fold (``crc_fold``)
        finds it.

        The spec names ONE producer's second encode.  The consumer
        drains two windows of each producer while each encodes four
        (they fill ahead), and an unpinned ``at=3`` counted hits across
        both producer threads: under load one producer ran ahead, the
        third hit was its third window, which nobody drains, and the
        injection fired on bytes that were never verified.
        ``late_producer`` holds one producer's first fill back half a
        second, which is that schedule made on purpose."""
        clean, _ = _stream_loader(self._producer())
        specs = [
            FaultSpec(
                "wire.encode", FaultKind.WIRE_CORRUPTION, at=2, param=8,
                producer_idx=1,
            )
        ]
        if late_producer is not None:
            specs.append(FaultSpec(
                "producer.fill", FaultKind.PRODUCER_SLOWDOWN, at=1,
                param=0.5, producer_idx=late_producer,
            ))
        plan = FaultPlan(specs)
        with faults.armed(plan):
            got, m = _stream_loader(self._producer())
        assert any(
            f[1] == "wire_corruption" for f in plan.fired
        ), "injection never fired"
        assert m.counter("integrity.corrupt_windows") >= 1
        assert m.counter("integrity.replays") >= 1
        assert np.array_equal(clean, got)
        crc_fold(m)

    def test_decode_fail_bounded_retry(self):
        """DECODE_FAIL at the consumer edge's wire.decode: one failure
        is absorbed by the bounded retry (the stream stays identical to
        an uninjected run); the failure is counted, never silent."""
        clean, _ = _stream_loader(self._producer())
        plan = FaultPlan([
            FaultSpec("wire.decode", FaultKind.DECODE_FAIL, at=2)
        ])
        with faults.armed(plan):
            got, m = _stream_loader(self._producer())
        assert plan.fired
        assert m.counter("wire.decode_fails") == 1
        assert np.array_equal(clean, got)

    def test_exchange_decode_fail_latches_raw_fallback(self):
        """Persistent DECODE_FAIL on the exchange wire: after the
        bounded retry the shuffler latches its OUTGOING encoding to raw
        (wire.fallbacks), the round degrades node-locally, and the run
        continues — raw envelopes interoperate by construction."""
        from ddl_tpu.shuffle import Rendezvous, ThreadExchangeShuffler
        from ddl_tpu.types import Topology

        rdv = Rendezvous()
        metrics = [Metrics(), Metrics()]
        done = [None, None]
        # producer_idx=1 on instance 0 sees the armed plan; both fire
        # (the plan is process-global) — count=2 exhausts the retry.
        plan = FaultPlan([
            FaultSpec("wire.decode", FaultKind.DECODE_FAIL, at=1, count=2)
        ])

        def worker(i):
            topo = Topology(n_instances=2, instance_idx=i, n_producers=1)
            sh = ThreadExchangeShuffler(
                topo, 1, num_exchange=8, rendezvous=rdv, seed=3,
                wire_dtype="int8", exchange_timeout_s=10.0,
            )
            sh.metrics = metrics[i]
            ary = np.random.default_rng(20 + i).standard_normal(
                (16, 4)
            ).astype(np.float32)
            for _ in range(3):
                sh.global_shuffle(ary)
            done[i] = (ary, sh)

        with faults.armed(plan):
            ts = [
                threading.Thread(target=worker, args=(i,))
                for i in range(2)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30.0)
        assert all(d is not None for d in done), "a worker died"
        total_fallbacks = sum(
            m.counter("wire.fallbacks") for m in metrics
        )
        assert total_fallbacks >= 1
        latched = [sh for _, sh in done if sh._wire_raw]
        assert latched, "no shuffler latched the raw fallback"
        # Latched shufflers keep exchanging: rounds advanced to 3.
        assert all(sh.exchange_round == 3 for _, sh in done)


# -- exchange wire (lossless identity + lossy drift) -------------------------


class TestExchangeWire:
    def _run_pair(self, wd=None, codec=None, rounds=4, seed=5):
        from ddl_tpu.shuffle import Rendezvous, ThreadExchangeShuffler
        from ddl_tpu.types import Topology

        rdv = Rendezvous()
        outs = [[], []]
        metrics = [Metrics(), Metrics()]

        def worker(i):
            topo = Topology(n_instances=2, instance_idx=i, n_producers=1)
            sh = ThreadExchangeShuffler(
                topo, 1, num_exchange=8, rendezvous=rdv, seed=seed,
                wire_dtype=wd, codec=codec, exchange_timeout_s=30.0,
            )
            sh.metrics = metrics[i]
            ary = np.random.default_rng(30 + i).standard_normal(
                (16, 8)
            ).astype(np.float32)
            for _ in range(rounds):
                sh.global_shuffle(ary)
                outs[i].append(ary.copy())

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60.0)
        assert all(len(o) == rounds for o in outs)
        return outs, metrics

    def test_lossless_codec_byte_identical(self):
        raw, _ = self._run_pair()
        zz, m = self._run_pair(codec="zlib")
        for i in range(2):
            for a, b in zip(raw[i], zz[i]):
                assert np.array_equal(a, b)
        assert m[0].counter("wire.encoded_bytes") > 0

    def test_int8_exchange_drift_bounded(self):
        raw, _ = self._run_pair()
        i8, m = self._run_pair(wd="int8")
        for i in range(2):
            for a, b in zip(raw[i], i8[i]):
                d = np.abs(a - b).max() / max(np.abs(a).max(), 1e-9)
                assert d < 0.05
        assert 0 < m[0].counter("wire.encoded_bytes") < m[0].counter(
            "wire.payload_bytes"
        )

    def test_int_lanes_keep_raw_under_lossy_request(self):
        """Token (int) windows silently ride raw even when int8 is
        requested — the lossy tier never corrupts ids."""
        from ddl_tpu.shuffle import ThreadExchangeShuffler
        from ddl_tpu.types import Topology

        topo = Topology(n_instances=2, instance_idx=0, n_producers=1)
        sh = ThreadExchangeShuffler(
            topo, 1, num_exchange=8, wire_dtype="int8"
        )
        rows = np.arange(32, dtype=np.int64).reshape(4, 8)
        wd, codec = sh._wire_active(rows)
        assert wd == "raw" and codec is None


# -- lossless byte-identity matrix (compressed shards ≡ raw) -----------------


class TestCompressedShardMatrix:
    def _compress_file(self, src, dst):
        with open(src, "rb") as f:
            raw = f.read()
        with open(dst, "wb") as f:
            f.write(_zlib.compress(raw, 6))

    def _stream(self, make_prod, mode="thread", cache=None, epochs=3,
                batch_size=4):
        from ddl_tpu.dataloader import DistributedDataLoader
        from ddl_tpu.env import distributed_dataloader
        from ddl_tpu.types import Marker

        out = []

        @distributed_dataloader(n_producers=1, mode=mode)
        def main(env):
            loader = DistributedDataLoader(
                make_prod(), batch_size=batch_size,
                connection=env.connection, n_epochs=epochs,
                output="numpy",
            )
            for _ in range(epochs):
                for i in range(len(loader)):
                    cols = loader[i]
                    out.append(
                        np.concatenate(
                            [np.atleast_2d(c.copy()) for c in cols],
                            axis=-1,
                        )
                    )
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)

        main()
        return np.concatenate([o.reshape(1, -1) for o in out], axis=0)

    @pytest.mark.parametrize("cache_on", [False, True])
    def test_fileshard_thread(self, tmp_path, cache_on):
        from ddl_tpu.cache import CacheStore, CodecBackend
        from ddl_tpu.readers import FileShardProducer

        rng = np.random.default_rng(0)
        for i in range(3):
            np.save(
                tmp_path / f"shard_{i}.npy",
                (rng.integers(0, 16, (8, 16))).astype(np.float32),
            )
            self._compress_file(
                tmp_path / f"shard_{i}.npy",
                tmp_path / f"shard_{i}.npy.zz",
            )

        def raw_prod():
            return FileShardProducer(
                str(tmp_path / "shard_*.npy"), seed=0, cache=False,
                warm=False,
            )

        def zz_prod():
            cache = (
                CacheStore(ram_budget_bytes=64 << 20)
                if cache_on else False
            )
            return FileShardProducer(
                str(tmp_path / "shard_*.npy.zz"), seed=0,
                backend=CodecBackend(), cache=cache, warm=False,
            )

        raw = self._stream(raw_prod)
        zz = self._stream(zz_prod)
        assert np.array_equal(raw, zz)
        if cache_on:
            # warm epochs must serve the same bytes from the cache
            assert np.array_equal(raw, self._stream(zz_prod))

    def test_fileshard_process(self, tmp_path):
        """PROCESS mode: the CodecBackend crosses the spawn boundary by
        pickle and decodes in the worker — byte-identical to THREAD."""
        from ddl_tpu.cache import CodecBackend
        from ddl_tpu.readers import FileShardProducer

        rng = np.random.default_rng(0)
        for i in range(2):
            np.save(
                tmp_path / f"s_{i}.npy",
                (rng.integers(0, 16, (8, 8))).astype(np.float32),
            )
            self._compress_file(
                tmp_path / f"s_{i}.npy", tmp_path / f"s_{i}.npy.zz"
            )

        def zz_prod():
            return FileShardProducer(
                str(tmp_path / "s_*.npy.zz"), seed=0,
                backend=CodecBackend(), cache=False, warm=False,
            )

        def raw_prod():
            return FileShardProducer(
                str(tmp_path / "s_*.npy"), seed=0, cache=False,
                warm=False,
            )

        raw = self._stream(raw_prod, mode="thread", epochs=2)
        zz = self._stream(zz_prod, mode="process", epochs=2)
        assert np.array_equal(raw, zz)

    def test_tfrecord_thread(self, tmp_path):
        from datagen import encode_example_int64, write_tfrecord

        from ddl_tpu.cache import CodecBackend
        from ddl_tpu.readers import TFRecordTokenProducer

        payloads = [
            encode_example_int64(
                "input_ids", list(range(20 * i, 20 * i + 20))
            )
            for i in range(4)
        ]
        path = str(tmp_path / "toks.tfrecord")
        write_tfrecord(path, payloads)
        self._compress_file(path, path + ".zz")

        raw = self._stream(
            lambda: TFRecordTokenProducer(
                path, seq_len=8, window_rows=4, warm=False
            )
        )
        zz = self._stream(
            lambda: TFRecordTokenProducer(
                path + ".zz", seq_len=8, window_rows=4,
                backend=CodecBackend(), warm=False,
            )
        )
        assert np.array_equal(raw, zz)

    def test_webdataset_thread(self, tmp_path):
        pytest.importorskip("PIL")
        from datagen import write_image_shard

        from ddl_tpu.cache import CodecBackend
        from ddl_tpu.readers import WebDatasetProducer

        path = str(tmp_path / "imgs.tar")
        write_image_shard(
            path, [(f"s{i:03d}", i % 3) for i in range(4)], size=8
        )
        self._compress_file(path, path + ".zz")

        raw = self._stream(
            lambda: WebDatasetProducer(
                path, image_size=8, window_rows=4, warm=False
            )
        )
        zz = self._stream(
            lambda: WebDatasetProducer(
                path + ".zz", image_size=8, window_rows=4,
                backend=CodecBackend(), warm=False,
            )
        )
        assert np.array_equal(raw, zz)

    def test_codec_backend_decode_fail_rides_retry_ladder(self, tmp_path):
        """DECODE_FAIL at the backend's wire.decode raises the
        TRANSIENT BackendFetchError, so open_with_retry's existing
        bounded retry heals a one-shot failure."""
        from ddl_tpu.cache import CodecBackend, open_with_retry

        src = tmp_path / "x.npy"
        np.save(src, np.arange(8, dtype=np.float32))
        self._compress_file(src, tmp_path / "x.npy.zz")
        be = CodecBackend()
        plan = FaultPlan([
            FaultSpec("wire.decode", FaultKind.DECODE_FAIL, at=1)
        ])
        m = Metrics()
        with faults.armed(plan):
            f = open_with_retry(
                be, str(tmp_path / "x.npy.zz"), retries=2,
                backoff_s=0.001, metrics=m,
            )
        assert np.array_equal(np.load(f), np.arange(8, dtype=np.float32))
        assert plan.fired and m.counter("cache.backend_retries") == 1

    def test_truly_corrupt_compressed_file_fails_decode(self, tmp_path):
        from ddl_tpu.cache import CodecBackend
        from ddl_tpu.exceptions import BackendFetchError

        (tmp_path / "bad.npy.zz").write_bytes(b"not a zlib stream")
        with pytest.raises(BackendFetchError):
            CodecBackend().open(str(tmp_path / "bad.npy.zz"))


class TestCompressedCacheEntries:
    def test_spill_entries_compressed_and_identical(self, tmp_path, rng):
        from ddl_tpu.cache import CacheStore

        arr = (rng.integers(0, 8, (64, 64))).astype(np.float32)
        store = CacheStore(
            spill_dir=str(tmp_path / "spill"), codec="zlib",
            codec_level=6,
        )
        digest = "ab" * 32
        store._spill(digest, arr)
        size = os.path.getsize(store._spill_path(digest))
        assert size < arr.nbytes  # under the SAME byte budget
        got = store._disk_get(digest)
        assert np.array_equal(got, arr)

    def test_corrupt_compressed_entry_quarantines(self, tmp_path, rng):
        from ddl_tpu.cache import CacheStore

        arr = (rng.integers(0, 8, (32, 32))).astype(np.float32)
        store = CacheStore(
            spill_dir=str(tmp_path / "spill"), codec="zlib"
        )
        digest = "cd" * 32
        store._spill(digest, arr)
        path = store._spill_path(digest)
        blob = np.fromfile(path, np.uint8)
        blob[len(blob) // 2] ^= 0xFF
        blob.tofile(path)
        assert store._disk_get(digest) is None  # quarantined, not served
        assert store.metrics.counter("cache.quarantined") >= 1

    def test_bad_codec_name_fails_at_construction(self, tmp_path):
        from ddl_tpu.cache import CacheStore

        with pytest.raises(ValueError):
            CacheStore(spill_dir=str(tmp_path), codec="brotli")


# -- ICI wire: accounting hand-checks + virtual-mesh transport ---------------


class TestIciWireAccounting:
    def _sharding(self, shape, names, spec):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(
            np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape),
            names,
        )
        return NamedSharding(mesh, P(*spec))

    def test_replicate_wire_bytes_hand_check(self):
        """int8 replicate over x8, window (64, 512) f32: encoded rows
        are 512 + 4*2 = 520 bytes wide, so every wire figure is the raw
        formula evaluated at 64*520 bytes instead of 64*2048."""
        from ddl_tpu.ops import ici_fanout
        from ddl_tpu.parallel.ici import plan_distribution

        sh = self._sharding((8,), ("dp",), (None, None))
        raw = plan_distribution((64, 512), np.float32, sh)
        p = plan_distribution(
            (64, 512), np.float32, sh, wire_dtype="int8"
        )
        enc = 64 * (512 + 4 * 2)
        assert p.encoded_bytes == enc
        assert p.wire_bytes == ici_fanout.wire_bytes("replicate", enc, 8)
        assert p.wire_bytes < raw.wire_bytes
        assert p.payload_bytes == raw.payload_bytes  # logical delivery
        assert p.legs[0].wire_dtype == "int8"
        assert raw.legs[0].wire_dtype == "raw"

    def test_shard_wire_bytes_hand_check(self):
        from ddl_tpu.ops import ici_fanout
        from ddl_tpu.parallel.ici import plan_distribution

        sh = self._sharding((4, 2), ("dp", "fsdp"), ("dp", None))
        raw = plan_distribution((64, 512), np.float32, sh)
        p = plan_distribution(
            (64, 512), np.float32, sh, wire_dtype="bf16"
        )
        enc = 64 * 512 * 2
        assert p.encoded_bytes == enc
        scatter = ici_fanout.wire_bytes("shard", enc, 8)
        gather = 8 * (2 - 1) * (enc // 8)  # m=2 replicas per dp group
        assert p.wire_bytes == scatter + gather
        assert p.wire_bytes == raw.wire_bytes // 2
        assert all(leg.wire_dtype == "bf16" for leg in p.legs[:2])

    def test_wire_ordering_int8_lt_bf16_lt_raw(self):
        from ddl_tpu.parallel.ici import plan_distribution

        sh = self._sharding((8,), ("dp",), ("dp", None))
        sizes = {
            wd: plan_distribution(
                (64, 512), np.float32, sh, wire_dtype=wd
            ).wire_bytes
            for wd in ("raw", "bf16", "int8")
        }
        assert sizes["int8"] < sizes["bf16"] < sizes["raw"]

    def test_int_window_plans_raw_silently(self):
        from ddl_tpu.parallel.ici import plan_distribution

        sh = self._sharding((8,), ("dp",), ("dp", None))
        p = plan_distribution(
            (64, 512), np.int32, sh, wire_dtype="int8"
        )
        assert p.wire_dtype == "raw"


class TestIciWireTransport:
    @pytest.mark.parametrize("wd", ["int8", "bf16"])
    @pytest.mark.parametrize(
        "axes,spec",
        [
            (((8,), ("dp",)), ("dp", None)),
            (((8,), ("dp",)), (None, None)),
            (((4, 2), ("dp", "fsdp")), ("dp", None)),
        ],
    )
    def test_distributed_values_drift_bounded(self, wd, axes, spec):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from ddl_tpu.parallel.ici import IciDistributor

        shape, names = axes
        mesh = Mesh(
            np.array(jax.devices()[: int(np.prod(shape))]).reshape(shape),
            names,
        )
        sh = NamedSharding(mesh, P(*spec))
        win = np.random.default_rng(0).standard_normal(
            (64, 48)
        ).astype(np.float32)
        m = Metrics()
        dist = IciDistributor(
            sh, metrics=m, interpret=True, wire_dtype=wd
        )
        out = dist.put(win, __import__("jax").device_put)
        ref = jax.device_put(win, sh)
        assert out.sharding == ref.sharding
        d = np.abs(np.asarray(out) - np.asarray(ref)).max() / np.abs(
            win
        ).max()
        assert 0.0 < d < 0.02 if wd == "int8" else d < 0.01
        assert m.counter("ici.fallbacks") == 0
        assert 0 < m.counter("wire.encoded_bytes") < m.counter(
            "wire.payload_bytes"
        )
        plan = dist.plan(win.shape, win.dtype)
        assert m.counter("ici.bytes") == plan.wire_bytes

    def test_raw_stays_byte_identical(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from ddl_tpu.parallel.ici import IciDistributor

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
        sh = NamedSharding(mesh, P("dp", None))
        win = np.random.default_rng(1).standard_normal(
            (64, 48)
        ).astype(np.float32)
        dist = IciDistributor(sh, interpret=True, wire_dtype="raw")
        out = dist.put(win, jax.device_put)
        assert np.array_equal(np.asarray(out), win)


# -- report keys -------------------------------------------------------------


class TestWireReport:
    def test_north_star_report_carries_wire_keys(self):
        from ddl_tpu.ingest import north_star_report

        m = Metrics()
        m.incr("wire.encoded_bytes", 100.0)
        m.incr("wire.payload_bytes", 400.0)
        m.incr("wire.decoded_windows", 2.0)
        report = north_star_report(m)
        assert report["wire_encoded_bytes"] == 100.0
        assert report["wire_payload_bytes"] == 400.0
        assert report["wire_decoded_windows"] == 2.0
        assert report["wire_decode_fails"] == 0.0
        assert report["wire_fallbacks"] == 0.0

    def test_wire_report_helper(self):
        m = Metrics()
        m.incr("wire.fallbacks")
        rep = wire.wire_report(m)
        assert rep["fallbacks"] == 1.0 and rep["encoded_bytes"] == 0.0

"""Wire format for the data plane: quantized and compressed window bytes.

Every byte the pipeline moves — producer→consumer ring slots, the DCN
shuffle exchange, the ICI fan-out, shard fetches — has so far traveled
at the window's storage dtype.  PR 8 gave *gradients* a wire discipline
(EQuARX blockwise int8, ``parallel/collectives.py``); this module gives
the same discipline to the data plane itself (ROADMAP item 3):

- **Lossy tier** (``wire_dtype``): ``"bf16"`` halves and ``"int8"``
  quarters the wire bytes of float windows with blockwise fp32 scales
  (one per :data:`QUANT_BLOCK` values, the EQuARX granularity — the
  NUMERICS intentionally match ``parallel.collectives.quantize_blockwise``
  so the loss-parity story is one story).  Opt-in per reader
  (``ProducerFunctionSkeleton.wire_dtype``) and licensed by the same
  ``loss_parity`` gate the int8 optimizer wire is
  (``parallel.optimizer.loss_parity``): a lossy wire may never silently
  change training.
- **Lossless tier** (``codec``): general-purpose compression for
  token/image shards where quantization is wrong — ``zlib`` (stdlib,
  always available) plus ``zstd``/``lz4`` seams that engage only when
  the host has the libraries (the container may not; missing codecs are
  *named* in the error, never silently swapped).  Every codec call is
  bounded: encode takes an explicit ``level``, decode an explicit
  ``max_output`` (a corrupt length header must never balloon the
  decoder — ddl-lint DDL021 enforces both at configured wire paths).

Chaos sites ``wire.encode`` / ``wire.decode`` (``ddl_tpu.faults``):
``WIRE_CORRUPTION`` flips bytes in an encoded payload (integrity
verifies the *encoded* bytes, so the quarantine-and-replay ladder
catches it exactly like raw-slot corruption); ``DECODE_FAIL`` raises
the real :class:`~ddl_tpu.exceptions.DecodeError` so the production
retry/fallback ladders are what chaos exercises.

Accounting: encoders report ``wire.encoded_bytes`` (what actually moved)
next to ``wire.payload_bytes`` (the logical raw bytes) so every
bytes-per-second headline divides honest numerators —
``north_star_report`` surfaces both as ``wire_*`` keys.
"""

from __future__ import annotations

import os
import struct
import time
import zlib
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ddl_tpu import envspec
from ddl_tpu.exceptions import DecodeError
from ddl_tpu.faults import fault_point

#: Valid wire dtypes for the lossy tier.  "raw" is the identity (the
#: window's own storage dtype travels).
WIRE_DTYPES = ("raw", "bf16", "int8")

#: Header wire-code values (stable on-the-wire enum: the integrity
#: trailer extension and the pack_rows header both carry these).
WIRE_CODES = {"raw": 0, "bf16": 1, "int8": 2}
_CODE_TO_DTYPE = {v: k for k, v in WIRE_CODES.items()}

#: Quantization granularity (values per fp32 scale) — deliberately the
#: optimizer wire's ``parallel.collectives.QUANT_BLOCK`` so the data
#: plane and the gradient plane share one error model.
QUANT_BLOCK = 256

#: Decode output bound default: no window/exchange payload in this repo
#: exceeds it, and a corrupt compressed stream claiming more dies here
#: instead of in the allocator.
DEFAULT_MAX_OUTPUT = 1 << 31


def check_wire_dtype(wire_dtype: Optional[str]) -> str:
    """Normalise/validate a wire dtype (None → "raw")."""
    wd = wire_dtype or "raw"
    if wd not in WIRE_DTYPES:
        raise ValueError(
            f"wire_dtype must be one of {WIRE_DTYPES}, got {wire_dtype!r}"
        )
    return wd


def resolve_wire_dtype(requested: Optional[str]) -> str:
    """The effective wire dtype: ``DDL_TPU_WIRE_DTYPE`` (the operator's
    override — ``raw`` is the kill switch, a lossy value forces the
    tier on for A/B runs) wins over the per-reader capability
    (``ProducerFunctionSkeleton.wire_dtype``)."""
    env = envspec.raw("DDL_TPU_WIRE_DTYPE")
    if env is not None and env != "":
        return check_wire_dtype(env)
    return check_wire_dtype(requested)


def resolve_wire_codec(requested: Optional[str] = None) -> Optional[str]:
    """The effective lossless codec name: ``DDL_TPU_WIRE_CODEC`` wins
    when SET AND NON-EMPTY (``"none"`` is the explicit kill switch; an
    empty string states no opinion, exactly like the sibling
    :func:`resolve_wire_dtype` knob), else the requested name.
    Validated against the registry but NOT constructed — callers
    construct at use sites so a gated library fails where the bytes
    are, with the available set named."""
    env = envspec.raw("DDL_TPU_WIRE_CODEC")
    name = env if env is not None and env != "" else requested
    if not name or name == "none":
        return None
    if name not in _CODECS:
        raise ValueError(
            f"unknown codec {name!r}; known: {tuple(_CODECS)}"
        )
    return name


def lossy_supported(dtype: Any) -> bool:
    """The lossy tier only makes sense on float windows: quantizing an
    int8 token stream would corrupt ids for zero wire win (use the
    lossless codec tier there)."""
    return np.dtype(dtype).kind == "f"


# -- blockwise quantization (host-side numpy twin of collectives) ------------


def _nblocks(cols: int, block: int = QUANT_BLOCK) -> int:
    return -(-cols // block)


def scale_bytes_for(shape: Tuple[int, ...], wire_dtype: str,
                    block: int = QUANT_BLOCK) -> int:
    """Trailer-extension bytes the scales of one encoded window occupy
    (0 for raw/bf16 — only int8 carries per-block fp32 scales)."""
    if wire_dtype != "int8":
        return 0
    rows = int(shape[0])
    cols = int(np.prod(shape[1:])) if len(shape) > 1 else 1
    return 4 * rows * _nblocks(cols, block)


def encoded_nbytes(shape: Tuple[int, ...], dtype: Any, wire_dtype: str) -> int:
    """Payload bytes of one window after lossy encoding (scales are
    priced separately — :func:`scale_bytes_for`)."""
    n = int(np.prod(shape))
    itemsize = np.dtype(dtype).itemsize
    if wire_dtype == "raw":
        return n * itemsize
    if wire_dtype == "bf16":
        return n * 2
    return n  # int8: one byte per value


def quantize_rows(arr: np.ndarray, block: int = QUANT_BLOCK
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Blockwise int8 quantize over the last axis of a 2D row view.

    ``arr`` is reshaped to ``(rows, cols)`` (rows = ``shape[0]``);
    returns ``(q int8 (rows, cols), scales fp32 (rows, nblocks))`` with
    ``scale = max(|x|)/127`` per block (zero blocks get scale 1 so the
    round trip is exact there) — the numerics of
    ``parallel.collectives.quantize_blockwise``, round-to-nearest.
    """
    rows = arr.shape[0]
    flat = np.ascontiguousarray(arr, dtype=np.float32).reshape(rows, -1)
    cols = flat.shape[1]
    pad = (-cols) % block
    padded = np.pad(np.abs(flat), ((0, 0), (0, pad))) if pad else np.abs(flat)
    s = padded.reshape(rows, -1, block).max(axis=-1) / 127.0
    s = np.where(s == 0.0, 1.0, s).astype(np.float32)
    expand = np.repeat(s, block, axis=1)[:, :cols]
    q = np.clip(np.rint(flat / expand), -127.0, 127.0).astype(np.int8)
    return q, s


def dequantize_rows(q: np.ndarray, scales: np.ndarray,
                    block: int = QUANT_BLOCK) -> np.ndarray:
    """Inverse of :func:`quantize_rows` (fp32, up to rounding error)."""
    cols = q.shape[1]
    expand = np.repeat(scales.astype(np.float32), block, axis=1)[:, :cols]
    return q.astype(np.float32) * expand


def encode_window(arr: np.ndarray, wire_dtype: str,
                  block: int = QUANT_BLOCK
                  ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Encode a window into its wire payload.

    Returns ``(payload uint8 1-D, scales fp32 | None)``.  Raw is a
    zero-copy byte view; bf16/int8 require a float window
    (:func:`lossy_supported`).
    """
    wire_dtype = check_wire_dtype(wire_dtype)
    if wire_dtype == "raw":
        return np.ascontiguousarray(arr).view(np.uint8).reshape(-1), None
    if not lossy_supported(arr.dtype):
        raise ValueError(
            f"lossy wire_dtype {wire_dtype!r} needs a float window, got "
            f"{np.dtype(arr.dtype).name} (use the lossless codec tier)"
        )
    if wire_dtype == "bf16":
        import ml_dtypes

        enc = np.ascontiguousarray(arr, dtype=np.float32).astype(
            ml_dtypes.bfloat16
        )
        return enc.view(np.uint8).reshape(-1), None
    q, s = quantize_rows(arr.reshape(arr.shape[0], -1), block)
    return q.view(np.uint8).reshape(-1), s


def decode_window(payload: np.ndarray, scales: Optional[np.ndarray],
                  shape: Tuple[int, ...], dtype: Any, wire_dtype: str,
                  block: int = QUANT_BLOCK, out: Optional[np.ndarray] = None
                  ) -> np.ndarray:
    """Decode a wire payload back to window shape/dtype.

    ``out`` (optional, shape/dtype-matched) receives the decode in
    place — the consumer edge's write-once discipline (DDL015: decode
    straight into the serving buffer, no extra temp copy-out).
    """
    wire_dtype = check_wire_dtype(wire_dtype)
    dtype = np.dtype(dtype)
    n = int(np.prod(shape))
    if wire_dtype == "raw":
        dec = payload[: n * dtype.itemsize].view(dtype).reshape(shape)
    elif wire_dtype == "bf16":
        import ml_dtypes

        dec = (
            payload[: n * 2].view(ml_dtypes.bfloat16)
            .astype(dtype).reshape(shape)
        )
    else:
        if scales is None:
            raise DecodeError("int8 wire payload arrived without scales")
        rows = int(shape[0])
        q = payload[:n].view(np.int8).reshape(rows, -1)
        dec = dequantize_rows(q, scales.reshape(rows, -1), block).astype(
            dtype
        ).reshape(shape)
    if out is not None:
        np.copyto(out, dec)
        return out
    return dec


# -- lossless codec seam -----------------------------------------------------


class ZlibCodec:
    """stdlib zlib — the always-available codec (levels 1-9).

    Decode auto-detects zlib AND gzip framing (``wbits=47`` = 32+15):
    :class:`~ddl_tpu.cache.backends.CodecBackend` maps the ``.gz``
    shard suffix here, and a plain ``decompressobj()`` cannot read a
    gzip header — every ``.gz`` shard would fail persistently.
    """

    name = "zlib"

    def encode_bytes(self, data: bytes, level: int) -> bytes:
        return zlib.compress(data, min(max(int(level), 1), 9))

    def decode_bytes(self, data: bytes, max_output: int) -> bytes:
        d = zlib.decompressobj(47)  # auto-detect zlib/gzip headers
        try:
            out = d.decompress(data, max_output)
        except zlib.error as e:
            raise DecodeError(f"zlib decode failed: {e}") from e
        if d.unconsumed_tail:
            raise DecodeError(
                f"zlib decode exceeded max_output={max_output} bytes"
            )
        if not d.eof:
            # A truncated stream decompresses "successfully" to partial
            # output with no exception — the torn-partial-object case
            # the retry ladders exist for must FAIL here, not surface
            # later as a short np.load/tar read.
            raise DecodeError(
                f"zlib stream truncated ({len(data)} input bytes, "
                "no end-of-stream marker)"
            )
        return out


class ZstdCodec:
    """zstandard, engaged only when the library is importable."""

    name = "zstd"

    def __init__(self) -> None:
        import zstandard  # gated: raises ImportError where absent

        self._mod = zstandard

    def encode_bytes(self, data: bytes, level: int) -> bytes:
        return self._mod.ZstdCompressor(level=int(level)).compress(data)

    def decode_bytes(self, data: bytes, max_output: int) -> bytes:
        try:
            return self._mod.ZstdDecompressor().decompress(
                data, max_output_size=max_output
            )
        except self._mod.ZstdError as e:
            raise DecodeError(f"zstd decode failed: {e}") from e


class Lz4Codec:
    """lz4.frame, engaged only when the library is importable."""

    name = "lz4"

    def __init__(self) -> None:
        import lz4.frame  # gated: raises ImportError where absent

        self._mod = lz4.frame

    def encode_bytes(self, data: bytes, level: int) -> bytes:
        return self._mod.compress(data, compression_level=int(level))

    def decode_bytes(self, data: bytes, max_output: int) -> bytes:
        try:
            out = self._mod.decompress(data)
        except RuntimeError as e:
            raise DecodeError(f"lz4 decode failed: {e}") from e
        if len(out) > max_output:
            raise DecodeError(
                f"lz4 decode exceeded max_output={max_output} bytes"
            )
        return out


#: Codec registry: name → (constructor, on-the-wire code).  Code 0 is
#: "no codec"; the constructors for zstd/lz4 raise ImportError where the
#: container lacks them — :func:`get_codec` turns that into a named
#: error and :func:`available_codecs` reports what this host can run.
_CODECS = {"zlib": (ZlibCodec, 1), "zstd": (ZstdCodec, 2), "lz4": (Lz4Codec, 3)}
_CODEC_BY_CODE = {code: name for name, (_, code) in _CODECS.items()}


def available_codecs() -> Tuple[str, ...]:
    """Codec names this host can actually construct."""
    out = []
    for name, (ctor, _) in _CODECS.items():
        try:
            ctor()
        except ImportError:
            continue
        out.append(name)
    return tuple(out)


def get_codec(name: str) -> Any:
    """Construct a codec by name, or raise naming what IS available."""
    if name not in _CODECS:
        raise ValueError(
            f"unknown codec {name!r}; known: {tuple(_CODECS)}"
        )
    ctor, _ = _CODECS[name]
    try:
        return ctor()
    except ImportError as e:
        raise ValueError(
            f"codec {name!r} needs a library this host lacks ({e}); "
            f"available here: {available_codecs()}"
        ) from e


# -- self-describing exchange payloads (the shuffle/DCN wire) ----------------

#: pack_rows header: magic, version, wire_code, codec_code, ndim,
#: dtype-name length, scales nbytes, payload nbytes, raw nbytes.
_PACK_MAGIC = 0x44444C58  # "DDLX"
_PACK_FMT = "<IHBBBBQQQ"
_PACK_BYTES = struct.calcsize(_PACK_FMT)


def pack_rows(
    rows: np.ndarray,
    wire_dtype: str = "raw",
    codec: Optional[str] = None,
    level: int = 3,
    block: int = QUANT_BLOCK,
    metrics: Any = None,
) -> np.ndarray:
    """Encode an exchange payload into one self-describing uint8 array.

    The shuffle fabrics (:class:`~ddl_tpu.shuffle.Rendezvous` /
    :class:`~ddl_tpu.shuffle.ShmRendezvous`) move numpy arrays; this
    wraps the lane rows in a wire envelope — header, shape, optional
    scales, (optionally codec-compressed) payload — so the DECODER needs
    no out-of-band agreement: a peer that latched the raw fallback still
    interoperates with one that didn't.  The ``wire.encode`` chaos site
    fires against the encoded payload bytes.
    """
    wire_dtype = check_wire_dtype(wire_dtype)
    payload, scales = encode_window(rows, wire_dtype, block)
    raw_nbytes = int(rows.nbytes)
    codec_code = 0
    body = payload.tobytes()
    if codec:
        c = get_codec(codec)
        body = c.encode_bytes(body, level=level)
        codec_code = _CODECS[codec][1]
    scales_b = scales.tobytes() if scales is not None else b""
    dtype_name = np.dtype(rows.dtype).name.encode()
    hdr = struct.pack(
        _PACK_FMT, _PACK_MAGIC, 1, WIRE_CODES[wire_dtype], codec_code,
        rows.ndim, len(dtype_name), len(scales_b), len(body), raw_nbytes,
    )
    shape_b = struct.pack(f"<{rows.ndim}q", *rows.shape)
    buf = np.frombuffer(
        hdr + shape_b + dtype_name + scales_b + body, dtype=np.uint8
    ).copy()
    # Chaos: WIRE_CORRUPTION flips encoded bytes post-encode — the
    # partner's decode (or the integrity CRC on slot paths) must catch
    # them, exactly like real wire corruption.
    fault_point("wire.encode", view=buf[_PACK_BYTES:])
    if metrics is not None:
        metrics.incr("wire.encoded_bytes", float(buf.nbytes))
        metrics.incr("wire.payload_bytes", float(raw_nbytes))
    return buf


def unpack_rows(
    buf: np.ndarray,
    max_output: int = DEFAULT_MAX_OUTPUT,
    block: int = QUANT_BLOCK,
    metrics: Any = None,
) -> np.ndarray:
    """Decode a :func:`pack_rows` envelope back to its rows.

    Raises :class:`~ddl_tpu.exceptions.DecodeError` on any malformed
    field — callers run the bounded-retry-then-raw-fallback ladder
    (``wire.fallbacks``).  The ``wire.decode`` chaos site fires first,
    against the encoded bytes (``DECODE_FAIL`` raises the real type;
    ``WIRE_CORRUPTION`` flips payload bytes so the decode itself, or
    the value checks downstream, trip).
    """
    buf = np.ascontiguousarray(buf, dtype=np.uint8)
    fault_point("wire.decode", view=buf[_PACK_BYTES:])
    if buf.nbytes < _PACK_BYTES:
        raise DecodeError(f"wire envelope truncated ({buf.nbytes} bytes)")
    raw = buf.tobytes()
    magic, ver, wcode, ccode, ndim, dlen, slen, blen, raw_nbytes = (
        struct.unpack_from(_PACK_FMT, raw)
    )
    if magic != _PACK_MAGIC or ver != 1:
        raise DecodeError(
            f"bad wire envelope magic/version 0x{magic:08x}/{ver}"
        )
    if wcode not in _CODE_TO_DTYPE:
        raise DecodeError(f"unknown wire code {wcode}")
    off = _PACK_BYTES
    # Corruption landing in the shape/dtype region raises non-DDL types
    # (struct.error on a short buffer, UnicodeDecodeError/TypeError on a
    # mangled dtype name) — normalise to DecodeError so every decode
    # ladder (retry, raw fallback, backend refetch) actually catches it.
    try:
        shape = struct.unpack_from(f"<{ndim}q", raw, off)
        off += 8 * ndim
        dtype = np.dtype(raw[off : off + dlen].decode())
        off += dlen
    except (struct.error, UnicodeDecodeError, TypeError, ValueError) as e:
        raise DecodeError(f"malformed wire envelope header: {e}") from e
    scales_b = raw[off : off + slen]
    off += slen
    body = raw[off : off + blen]
    if len(body) != blen:
        raise DecodeError(
            f"wire envelope payload truncated ({len(body)} < {blen})"
        )
    if ccode:
        name = _CODEC_BY_CODE.get(ccode)
        if name is None:
            raise DecodeError(f"unknown codec code {ccode}")
        body = get_codec(name).decode_bytes(body, max_output=max_output)
    payload = np.frombuffer(body, dtype=np.uint8)
    wire_dtype = _CODE_TO_DTYPE[wcode]
    n = int(np.prod(shape))
    # Every region is length-checked against what the SHAPE implies
    # before any numpy view: exchange envelopes carry no CRC, so a
    # corrupt length field must die here as DecodeError — a truncated
    # scales buffer fed to frombuffer/reshape raises plain ValueError,
    # which every decode ladder would miss.
    if len(scales_b) != slen or slen != scale_bytes_for(
        tuple(shape), wire_dtype, block
    ):
        raise DecodeError(
            f"wire scales region {len(scales_b)}/{slen} bytes disagrees "
            f"with shape {shape}/{wire_dtype}"
        )
    scales = np.frombuffer(scales_b, dtype=np.float32) if slen else None
    if encoded_nbytes(tuple(shape), dtype, wire_dtype) != payload.nbytes:
        raise DecodeError(
            f"wire payload size {payload.nbytes} disagrees with "
            f"shape {shape}/{dtype.name}/{wire_dtype}"
        )
    if n * dtype.itemsize != raw_nbytes:
        raise DecodeError("wire envelope raw-size field disagrees with shape")
    try:
        rows = decode_window(payload, scales, tuple(shape), dtype,
                             wire_dtype, block)
    except ValueError as e:
        raise DecodeError(f"wire payload decode failed: {e}") from e
    if metrics is not None:
        metrics.incr("wire.decoded_windows")
    return rows


def wire_report(metrics: Any) -> Dict[str, float]:
    """The ``wire.*`` counters one registry accumulated (bench/report)."""
    return {
        "encoded_bytes": metrics.counter("wire.encoded_bytes"),
        "payload_bytes": metrics.counter("wire.payload_bytes"),
        "decoded_windows": metrics.counter("wire.decoded_windows"),
        "fallbacks": metrics.counter("wire.fallbacks"),
        "decode_fails": metrics.counter("wire.decode_fails"),
    }


# -- format economics ------------------------------------------------------
#
# The break-even model every wire decision in this repo prices against:
# moving one raw byte over a link of speed L costs 1/L seconds on the
# raw leg, and 1/enc + ratio/L + 1/dec on an encoded leg.  The encoded
# leg wins exactly when L < (1 - ratio) / (1/enc + 1/dec) — a 4x ratio
# is worthless behind a codec slower than the link.  One implementation,
# the one the boot-time Calibrator (``ddl_tpu.tune``) decides from.


def measure_wire_stats(
    sample: np.ndarray,
    wire_dtypes: Tuple[str, ...] = ("bf16", "int8"),
    codecs: Tuple[str, ...] = (),
    level: int = 1,
    deadline: Optional[float] = None,
) -> Dict[str, Dict[str, float]]:
    """Microbenchmark each wire format on ``sample``.

    Returns ``{fmt: {"ratio", "encode_bytes_per_s", "decode_bytes_per_s"}}``
    (lossy entries add ``max_rel_drift``) — the stats dict
    :func:`break_even_table` and :func:`pick_wire_format` consume.
    ``deadline`` is an absolute ``time.monotonic()`` bound: formats not
    reached before it are simply absent (the Calibrator's budget
    discipline — a partial table beats a stalled training start).
    """
    sample = np.ascontiguousarray(sample)
    out: Dict[str, Dict[str, float]] = {}
    for wd in wire_dtypes:
        if deadline is not None and time.monotonic() >= deadline:
            break
        if not lossy_supported(sample.dtype):
            break
        t0 = time.perf_counter()
        payload, scales = encode_window(sample, wd)
        t_enc = time.perf_counter() - t0
        enc_bytes = payload.nbytes + (
            scales.nbytes if scales is not None else 0
        )
        t0 = time.perf_counter()
        dec = decode_window(
            payload, scales, sample.shape, sample.dtype, wd
        )
        t_dec = time.perf_counter() - t0
        drift = float(
            np.abs(dec - sample).max()
            / max(float(np.abs(sample).max()), 1e-9)
        )
        out[wd] = {
            "ratio": round(enc_bytes / sample.nbytes, 4),
            "encode_bytes_per_s": round(
                sample.nbytes / max(t_enc, 1e-9), 1
            ),
            "decode_bytes_per_s": round(
                sample.nbytes / max(t_dec, 1e-9), 1
            ),
            "max_rel_drift": drift,
        }
    raw = sample.tobytes()
    for name in codecs:
        if deadline is not None and time.monotonic() >= deadline:
            break
        if name not in available_codecs():
            continue
        c = get_codec(name)
        t0 = time.perf_counter()
        enc = c.encode_bytes(raw, level=level)
        t_enc = time.perf_counter() - t0
        t0 = time.perf_counter()
        dec = c.decode_bytes(enc, max_output=2 * len(raw))
        t_dec = time.perf_counter() - t0
        if dec != raw:
            continue  # a corrupting codec never enters the table
        out[f"{name}-l{level}"] = {
            "ratio": round(len(enc) / len(raw), 4),
            "encode_bytes_per_s": round(len(raw) / max(t_enc, 1e-9), 1),
            "decode_bytes_per_s": round(len(raw) / max(t_dec, 1e-9), 1),
        }
    return out


def break_even_table(
    stats: Dict[str, Any],
    link_bytes_per_s: Optional[float] = None,
) -> Dict[str, float]:
    """Per-format break-even link speed (bytes/s) from measured stats.

    ``stats`` maps format name → a dict carrying at least ``ratio``,
    ``encode_bytes_per_s``, ``decode_bytes_per_s`` (non-dict or
    ratio-free entries are skipped, so a caller's own annotation such
    as a shard label passes through unfiltered).  A format appears only
    when it can win at all (``ratio < 1.0``); its value is the link speed below which paying
    the encode+decode CPU beats moving raw bytes.  When
    ``link_bytes_per_s`` is given, formats whose threshold the measured
    link already exceeds are dropped — what remains is exactly the set
    worth flipping on for that link.
    """
    table: Dict[str, float] = {}
    for fmt, st in stats.items():
        if not isinstance(st, dict) or "ratio" not in st:
            continue
        enc = float(st.get("encode_bytes_per_s", 0.0))
        dec = float(st.get("decode_bytes_per_s", 0.0))
        if enc <= 0 or dec <= 0:
            continue
        denom = 1.0 / enc + 1.0 / dec
        if st["ratio"] < 1.0 and denom > 0:
            threshold = (1.0 - float(st["ratio"])) / denom
            if link_bytes_per_s is None or link_bytes_per_s < threshold:
                table[fmt] = threshold
    return table


def pick_wire_format(
    stats: Dict[str, Any],
    link_bytes_per_s: float,
) -> str:
    """The cheapest format for a link, ``"raw"`` included as the floor.

    Prices one raw byte end to end (encode + wire + decode) per format
    at the measured link speed and returns the argmin — the Calibrator's
    wire_dtype decision, made from the same stats the break-even table
    reports to operators.
    """
    link = max(float(link_bytes_per_s), 1e-9)
    best, best_t = "raw", 1.0 / link
    for fmt, st in sorted(stats.items()):
        if not isinstance(st, dict) or "ratio" not in st:
            continue
        enc = float(st.get("encode_bytes_per_s", 0.0))
        dec = float(st.get("decode_bytes_per_s", 0.0))
        if enc <= 0 or dec <= 0:
            continue
        t = 1.0 / enc + float(st["ratio"]) / link + 1.0 / dec
        if t < best_t:
            best, best_t = fmt, t
    return best

"""Probe: what does each wire format actually cost and buy on this host?

Measures, on REAL shard data (token-like integer shards + gaussian
float shards), per wire dtype and per available codec:

- encode and decode throughput (bytes/s of RAW payload processed) —
  the CPU cost a wire format charges the producer/consumer edges;
- the wire ratio (encoded bytes / raw bytes, scales and envelope
  included) — what the link saves;
- the break-even link bandwidth: the link speed below which paying the
  encode+decode CPU beats moving raw bytes (ratio and codec speed
  together decide; a 4x ratio is worthless behind a codec slower than
  the link).

Plus the analytic ICI fan-out pricing: ``plan_distribution`` wire
bytes raw vs bf16 vs int8 for one canonical window geometry on the
8-device virtual mesh.  The mirror of ``tools/probe_ici.py`` /
``probe_opt.py`` for the wire tier: the numbers that decide which
format a deployment should pin before ever touching a chip.

Run anywhere (`make wire-dryrun`):

    python tools/probe_wire.py
"""
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def _bench_codec(data: bytes, codec_name: str, level: int) -> dict:
    from ddl_tpu import wire

    c = wire.get_codec(codec_name)
    t0 = time.perf_counter()
    enc = c.encode_bytes(data, level=level)
    t_enc = time.perf_counter() - t0
    t0 = time.perf_counter()
    dec = c.decode_bytes(enc, max_output=2 * len(data))
    t_dec = time.perf_counter() - t0
    assert dec == data, f"{codec_name} round trip corrupted data"
    return {
        "ratio": round(len(enc) / len(data), 4),
        "encode_bytes_per_s": round(len(data) / max(t_enc, 1e-9), 1),
        "decode_bytes_per_s": round(len(data) / max(t_dec, 1e-9), 1),
    }


def _bench_lossy(arr: np.ndarray, wire_dtype: str) -> dict:
    from ddl_tpu import wire

    t0 = time.perf_counter()
    payload, scales = wire.encode_window(arr, wire_dtype)
    t_enc = time.perf_counter() - t0
    enc_bytes = payload.nbytes + (scales.nbytes if scales is not None else 0)
    t0 = time.perf_counter()
    dec = wire.decode_window(
        payload, scales, arr.shape, arr.dtype, wire_dtype
    )
    t_dec = time.perf_counter() - t0
    drift = float(
        np.abs(dec - arr).max() / max(float(np.abs(arr).max()), 1e-9)
    )
    return {
        "ratio": round(enc_bytes / arr.nbytes, 4),
        "encode_bytes_per_s": round(arr.nbytes / max(t_enc, 1e-9), 1),
        "decode_bytes_per_s": round(arr.nbytes / max(t_dec, 1e-9), 1),
        "max_rel_drift": drift,
    }


def main():
    from ddl_tpu import wire

    rows = int(os.environ.get("DDL_PROBE_WIRE_ROWS", "2048"))
    cols = int(os.environ.get("DDL_PROBE_WIRE_COLS", "1024"))
    rng = np.random.default_rng(0)
    shards = {
        "tokens": rng.integers(0, 32000, (rows, cols)).astype(np.int32),
        "float_gauss": rng.standard_normal((rows, cols)).astype(np.float32),
        "float_tokens": rng.integers(0, 32, (rows, cols)).astype(np.float32),
    }
    out: dict = {"rows": rows, "cols": cols,
                 "codecs_available": list(wire.available_codecs())}
    for name, arr in shards.items():
        entry: dict = {"raw_bytes": arr.nbytes}
        for codec in wire.available_codecs():
            for level in (1, 3):
                entry[f"{codec}-l{level}"] = _bench_codec(
                    arr.tobytes(), codec, level
                )
        if wire.lossy_supported(arr.dtype):
            for wd in ("bf16", "int8"):
                entry[wd] = _bench_lossy(arr, wd)
        out[name] = entry
    # Break-even link speeds per format for the token-like float shard
    # (the bench's geometry).  One implementation, shared with the
    # boot-time Calibrator: wire.break_even_table (bytes/s; the CLI
    # reports MiB/s).
    out["break_even_link_mib_s"] = {
        fmt: round(v / (1 << 20), 1)
        for fmt, v in wire.break_even_table(out["float_tokens"]).items()
    }

    # Analytic ICI fan-out pricing on the virtual mesh (no kernels run).
    try:
        import bench

        platform = bench.bring_up(cpu_devices=8)
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from ddl_tpu.parallel.ici import plan_distribution

        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
        sh = NamedSharding(mesh, P("dp", None))
        win = (256, 1024)
        ici = {}
        for wd in ("raw", "bf16", "int8"):
            p = plan_distribution(win, np.float32, sh, wire_dtype=wd)
            ici[wd] = {
                "wire_bytes": p.wire_bytes,
                "payload_bytes": p.payload_bytes,
                "encoded_bytes": p.encoded_bytes,
                "peak_factor": round(p.peak_factor, 3),
            }
        out["ici_pricing"] = {
            "window": list(win), "dtype": "float32",
            "target": "P('dp', None) x8", **ici,
        }
    except Exception as e:  # noqa: BLE001 - the probe must print regardless
        out["ici_pricing"] = {"error": f"{type(e).__name__}: {e}"}
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()

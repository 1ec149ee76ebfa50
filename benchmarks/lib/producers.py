"""The benchmark's traffic generator: seeded PROCESS producers.

One general generator per row kind, parameterised by a traffic-mix file
(``benchmarks/jobs/<mix>.json``): a mix is data, never code.  Copied in
idiom from ``chip_smoke.py`` (``_SeededProducer``), which later PRs may
change; the benchmark imports none of it.

Every window is a function of ``(seed, producer, iteration)`` and
nothing else, so the consumer side can say what each window must hold
without ever seeing the producer.  A producer

- fully rewrites the ring slot it is handed (``inplace_fill``),
- appends one line per fill to ``fills_<idx>.txt`` in its status
  directory: iteration, start and end on ``time.monotonic`` (one clock
  for every process of the machine) — ``producer_fill_ms`` reads it,
- rewrites ``producer_<idx>.json`` after each fill: its pid, and whether
  this process has imported JAX or initialised a backend.  The consumer
  holds the chip; a producer must stay off it.

This module imports no JAX.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton

#: Rows summed per block when checksumming a pool (bounds the temporary).
_CHECKSUM_BLOCK = 64


def row_checksums(rows: np.ndarray) -> np.ndarray:
    """Per-row checksum of 32-bit rows, as the consumer computes it on
    the device: the row's uint32 view, each word times its odd position
    weight ``2 i + 1``, summed modulo 2**32.  The weight makes the sum
    see a permutation inside a row (a lane pack/unpack gone wrong), not
    only an altered word."""
    rows = np.ascontiguousarray(rows)
    if rows.dtype.itemsize != 4:
        raise TypeError(f"rows must be a 32-bit dtype, got {rows.dtype}")
    words = rows.view(np.uint32).reshape(rows.shape[0], -1)
    weights = np.arange(words.shape[1], dtype=np.uint32) * np.uint32(2) + np.uint32(1)
    out = np.empty(words.shape[0], np.uint32)
    for lo in range(0, words.shape[0], _CHECKSUM_BLOCK):
        block = words[lo : lo + _CHECKSUM_BLOCK]
        out[lo : lo + _CHECKSUM_BLOCK] = (block * weights).sum(
            axis=1, dtype=np.uint32
        )
    return out


# -- what a window holds: pure functions of (seed, producer, iteration) -------


def token_window(seed: int, producer_idx: int, iteration: int, vocab: int,
                 out: np.ndarray) -> None:
    """Uniform token ids, written in place."""
    rng = np.random.default_rng([seed, producer_idx, iteration])
    out[...] = rng.integers(0, vocab, out.shape, dtype=np.int32)


def image_pool_row(seed: int, producer_idx: int, row: int, n_values: int,
                   n_classes: int, out: np.ndarray) -> None:
    """Pool row ``row``: ``n_values - 1`` pixels in [0, 1) and the label
    as the last float32 (the repo's ``[pixels..., label]`` image row)."""
    rng = np.random.default_rng([seed, producer_idx, row])
    rng.random(n_values, dtype=np.float32, out=out)
    out[-1] = np.float32(rng.integers(0, n_classes))


def image_window_rows(seed: int, producer_idx: int, iteration: int,
                      pool_rows: int, window_rows: int) -> np.ndarray:
    """Pool indices of window ``iteration``: pass ``p`` walks one seeded
    permutation of the pool, ``window_rows`` at a time, so every row is
    served once per pass."""
    per_pass = pool_rows // window_rows
    p, k = divmod(iteration, per_pass)
    perm = np.random.default_rng([seed, producer_idx, 1 << 20, p]).permutation(
        pool_rows
    )
    return perm[k * window_rows : (k + 1) * window_rows]


def geometry(mix: dict, sizes: dict) -> DataProducerOnInitReturn:
    """A window's shape, column splits and dtype, from the mix alone."""
    rows = mix["window_rows"]
    if mix["rows"] == "tokens":
        seq = sizes["seq"]
        return DataProducerOnInitReturn(
            nData=rows, nValues=seq, shape=(rows, seq), splits=(seq,),
            dtype=np.int32,
        )
    n = sizes["row_values"]  # the repo's image row: [pixels..., label]
    return DataProducerOnInitReturn(
        nData=rows, nValues=n, shape=(rows, n), splits=(n - 1, 1),
    )


# -- the producers ------------------------------------------------------------


class SeededProducer(ProducerFunctionSkeleton):
    """Module-level (picklable) write-once producer.  ``fault`` names a
    deliberate defect for the correctness rehearsal and is ``None`` in
    every measured run."""

    inplace_fill = True

    def __init__(self, seed: int, status_dir: str, mix: dict, sizes: dict,
                 fault: str | None = None):
        self.seed = seed
        self.status_dir = status_dir
        self.mix = mix
        self.sizes = sizes
        self.fault = fault

    def on_init(self, producer_idx=0, **kw):
        self._idx = producer_idx
        self._fills = open(
            os.path.join(self.status_dir, f"fills_{producer_idx}.txt"), "a",
            buffering=1,
        )
        self.prepare()
        return geometry(self.mix, self.sizes)

    def prepare(self) -> None:
        """Once per producer process, before the first fill."""

    def execute_function(self, my_ary, iteration=0, **kw):
        t0 = time.monotonic()
        self.fill(my_ary, iteration)
        if self.fault == "alter-row" and self._idx == 1 and iteration == 1:
            flat = my_ary.reshape(my_ary.shape[0], -1)
            flat[0, 0] = flat[0, 0] + flat.dtype.type(1)
        if self.fault == "swap-rows" and self._idx == 1 and iteration == 1:
            my_ary[[0, 1]] = my_ary[[1, 0]]
        t1 = time.monotonic()
        self._fills.write(f"{iteration} {t0!r} {t1!r}\n")
        bridge = sys.modules.get("jax._src.xla_bridge")
        status = {
            "pid": os.getpid(),
            "windows": iteration + 1,
            "jax_imported": "jax" in sys.modules,
            "backend_initialised": bool(
                bridge is not None and getattr(bridge, "_backends", None)
            ),
        }
        path = os.path.join(self.status_dir, f"producer_{self._idx}.json")
        with open(path + ".tmp", "w") as f:
            json.dump(status, f)
        os.replace(path + ".tmp", path)


class TokenProducer(SeededProducer):
    """int32 token windows of ``window_rows`` x ``seq``."""

    def fill(self, my_ary, iteration):
        token_window(
            self.seed, self._idx, iteration, self.sizes["vocab"], my_ary
        )


class ImagePoolProducer(SeededProducer):
    """Decoded float32 image rows served from a resident pool — what the
    repo's warm shard cache serves from the second epoch on.  Builds its
    pool, and the pool's row checksums (``pool_<idx>.npy``), once."""

    def prepare(self):
        n_rows, n = self.mix["pool_rows"], self.sizes["row_values"]
        self._pool = np.empty((n_rows, n), np.float32)
        for r in range(n_rows):
            image_pool_row(
                self.seed, self._idx, r, n, self.sizes["n_classes"],
                self._pool[r],
            )
        path = os.path.join(self.status_dir, f"pool_{self._idx}.npy")
        np.save(path + ".tmp.npy", row_checksums(self._pool))
        os.replace(path + ".tmp.npy", path)

    def fill(self, my_ary, iteration):
        idx = image_window_rows(
            self.seed, self._idx, iteration, self.mix["pool_rows"],
            self.mix["window_rows"],
        )
        # mode="clip": the indices are a permutation's, and the default
        # mode="raise" buffers the whole gather (35x slower at this size).
        np.take(self._pool, idx, axis=0, out=my_ary, mode="clip")


PRODUCERS = {"tokens": TokenProducer, "image_pool": ImagePoolProducer}


def make_producer(mix: dict, sizes: dict, seed: int, status_dir: str,
                  fault: str | None = None) -> SeededProducer:
    try:
        cls = PRODUCERS[mix["rows"]]
    except KeyError:
        raise ValueError(
            f"traffic mix {mix.get('name')!r} asks for rows {mix.get('rows')!r}; "
            f"the generator knows {sorted(PRODUCERS)}"
        ) from None
    return cls(seed, status_dir, mix, sizes, fault)


# -- the consumer's side of the same functions --------------------------------


def producer_of_window(n: int, n_producers: int) -> tuple[int, int]:
    """The loader's rotation: window ``n`` of a run is window ``n //
    n_producers`` of producer ``n % n_producers + 1``."""
    return n % n_producers + 1, n // n_producers


def host_window(mix: dict, sizes: dict, seed: int, producer_idx: int,
                iteration: int) -> np.ndarray:
    """Regenerate one window on the host (the reference's first window;
    the token mixes' expected checksums)."""
    geom = geometry(mix, sizes)
    out = np.empty(geom.shape, geom.dtype)
    if mix["rows"] == "tokens":
        token_window(seed, producer_idx, iteration, sizes["vocab"], out)
        return out
    idx = image_window_rows(
        seed, producer_idx, iteration, mix["pool_rows"], geom.nData
    )
    for k, r in enumerate(idx):
        image_pool_row(
            seed, producer_idx, int(r), geom.nValues, sizes["n_classes"], out[k]
        )
    return out


def expected_checksums(mix: dict, sizes: dict, seed: int, n_producers: int,
                       n_windows: int, status_dir: str) -> np.ndarray:
    """Row checksums every window of a run must show, in order:
    ``(n_windows, window_rows)``.  Token windows are regenerated here;
    image windows look their rows up in the checksums the producer wrote
    when it built its pool (what is under test is the transport, not the
    generator), through the same seeded permutation."""
    out = np.empty((n_windows, mix["window_rows"]), np.uint32)
    pools: dict = {}
    for n in range(n_windows):
        p, it = producer_of_window(n, n_producers)
        if mix["rows"] == "tokens":
            out[n] = row_checksums(host_window(mix, sizes, seed, p, it))
            continue
        if p not in pools:
            pools[p] = np.load(os.path.join(status_dir, f"pool_{p}.npy"))
        out[n] = pools[p][
            image_window_rows(seed, p, it, mix["pool_rows"], mix["window_rows"])
        ]
    return out

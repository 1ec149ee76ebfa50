"""ICI ingest tier: device-side fan-out + loader→trainer redistribution.

The layer between the loader and every parallelism axis (ROADMAP item
1).  A committed window crosses H2D exactly once — onto one *anchor*
device — and every further hop rides ICI under this module's control:

1. **Fan-out** (:mod:`ddl_tpu.ops.ici_fanout`): a Pallas
   ``make_async_remote_copy`` ring replicates or shards the anchor's
   window across a flat device ring (double-buffered DMA pipeline).
2. **Redistribution**: the ring layout ("split n ways along one dim",
   ring-ordered) is moved to the trainer's ``dp×fsdp×tp``
   ``NamedSharding`` as a short sequence of portable, memory-bounded
   collectives — the ring order is chosen target-major so the only leg
   ever needed is a tiled ``all_gather`` over the replication axes
   (following *Memory-efficient array redistribution through portable
   collective communication*, arXiv:2112.01075: per-axis legs, never an
   unsharded intermediate).  Peak per-device live bytes — including the
   ring's window-sized SPMD landing block that every device must hold —
   are computed in the plan and asserted against ``max_memory_factor``
   × the window size.

Planning is geometry-cached; steady-state windows dispatch two compiled
programs (fan-out kernel + finish collective) and allocate nothing on
the host.  Two fallback rungs to the ``xla`` path — the pre-existing
``device_put`` scatter: an UNPLANNABLE geometry (ragged batch,
indivisible split) degrades that geometry only, while a DMA-leg failure
at run time (or the ``ici.fanout`` chaos site) latches the whole tier
off — so the degradation ladder covers the new tier (``ici.fallbacks``
counts both rungs).  A kernel that fails to BUILD or COMPILE is neither:
it is a broken program and propagates to the caller.

Observability (all flowing into ``north_star_report`` / the bench
``ici`` block): ``ici.bytes`` (wire bytes the fan-out moved),
``ici.windows``, ``ici.fallbacks``, ``ici.fanout`` / ``ici.redistribute``
dispatch timers, and the ``ici.peak_bytes`` gauge (the plan's asserted
per-device peak).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ddl_tpu import envspec
from ddl_tpu.exceptions import InjectedFault
from ddl_tpu.faults import fault_point
from ddl_tpu.observability import Metrics, metrics as default_metrics
from ddl_tpu.profiling import stage

logger = logging.getLogger("ddl_tpu")

#: Redistribution legs may not exceed this multiple of the WINDOW size
#: in per-device live bytes (the arXiv:2112.01075 discipline: a
#: bounded-memory plan or no plan).  The accounting includes the SPMD
#: ring's per-device landing block — shard_map needs an equal-shaped
#: input block on EVERY ring device, so each non-source device carries
#: one window-sized (cached, pinned) landing buffer through every leg —
#: plus the kernel's output (the kernels keep no transit or sink
#: buffer).  2.0 is the worst case the shipped legs can construct: a
#: raw replicate (landing + the window-sized output); every shard or
#: wire-encoded plan sits under it.
DEFAULT_MEMORY_FACTOR = 2.0


def fused_enabled(default: bool = True) -> bool:
    """The ``DDL_TPU_FUSED`` escape hatch (default ON).

    Gates both halves of the fused compute/ingest step: the
    distributor's two-slot (double-buffered landing) dispatch here and
    the trainer's fused stream loop (``Trainer._fused_stream_loop``).
    ``DDL_TPU_FUSED=0`` restores the synchronous discipline everywhere
    — the same path a latched DMA failure degrades to.
    """
    val = envspec.raw("DDL_TPU_FUSED")
    if val is None:
        return default
    return val != "0"


class PlanError(ValueError):
    """The target sharding has no bounded-memory ICI plan (caller falls
    back to the XLA path)."""


@dataclasses.dataclass(frozen=True)
class RedistLeg:
    """One plan step: what moves, over which axes, at what cost.

    ``asynchronous`` marks a leg emitted as a start/wait PAIR (the
    fused two-slot protocol): its start is the async dispatch of the
    slot's ring program and its wait is the consuming step's first use
    of the data.  Async legs are REMAT-COMPATIBLE by construction —
    they run outside the consuming step's trace, so a consumer wrapped
    in ``jax.checkpoint`` recomputes its own activations from the
    landed window (an input) without ever re-executing the DMA ring
    (asserted by tests/test_ici.py's remat row).
    """

    kind: str  #: "fanout.replicate" | "fanout.shard" | "all_gather" | "reshape"
    axes: Tuple[str, ...]  #: named mesh axes the leg communicates over
    ici_bytes: int  #: bytes this leg moves over ICI (wire, per window)
    peak_bytes: int  #: max per-device live bytes during the leg
    asynchronous: bool = False  #: emitted as a start/wait pair (fused)
    #: Wire dtype of the bytes THIS leg moves (``ddl_tpu.wire``): a
    #: quantized replicate leg reports the int8+scales bytes it
    #: actually moves, never the raw window size — ``ici_bytes`` above
    #: is already the encoded figure, this names the encoding so
    #: ``bandwidth_utilization``'s numerator cannot flatter itself.
    wire_dtype: str = "raw"


@dataclasses.dataclass(frozen=True)
class DistributionPlan:
    """A geometry's full route from anchor device to target sharding."""

    mode: str  #: "replicate" | "shard"
    shape: Tuple[int, ...]
    dtype: Any
    split_dim: Optional[int]  #: window dim the target shards (None = replicated)
    split_axes: Tuple[str, ...]  #: mesh axes sharding split_dim (target-major)
    rest_axes: Tuple[str, ...]  #: replication axes the finish leg gathers
    ring_devices: Tuple[Any, ...]  #: fan-out ring, target-major order
    legs: Tuple[RedistLeg, ...]
    wire_bytes: int  #: total ICI bytes per window
    payload_bytes: int  #: bytes usefully delivered per window
    peak_bytes: int  #: max per-device live bytes across legs (incl. landing)
    dst_shard_bytes: int  #: destination per-device shard size
    peak_factor: float  #: peak_bytes / window bytes (asserted bound)
    n_slots: int = 1  #: landing slots priced in flight (2 = fused)
    #: Wire format the fan-out ring carries (``ddl_tpu.wire``): "raw"
    #: moves the window's storage dtype; "bf16"/"int8" encode on the
    #: anchor (device-side, jitted — never a host round trip), the ring
    #: kernels move the uint8 payload (+ per-row scales), and the
    #: finish legs decode at the landing edge.  ``wire_bytes``/leg
    #: ``ici_bytes`` price the ENCODED bytes.
    wire_dtype: str = "raw"
    encoded_bytes: int = 0  #: 2D encoded bytes per window (== nbytes for raw)

    @property
    def anchor(self):
        """The device H2D lands on (ring source)."""
        return self.ring_devices[0]


def _split_layout(spec: Any, ndim: int) -> Tuple[Optional[int], Tuple[str, ...]]:
    """The single (dim, mesh-axes) pair a supported target spec shards,
    or (None, ()) for full replication.  Raises PlanError on specs the
    fan-out ring cannot source (more than one sharded dim)."""
    sharded = []
    entries = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    for dim, entry in enumerate(entries):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        if axes:
            sharded.append((dim, axes))
    if not sharded:
        return None, ()
    if len(sharded) > 1:
        raise PlanError(
            f"target spec {spec} shards {len(sharded)} dims; the ICI "
            "fan-out sources a single split dim"
        )
    return sharded[0]


def _ring_order(mesh: Any, split_axes: Tuple[str, ...],
                rest_axes: Tuple[str, ...]) -> Tuple[Any, ...]:
    """Mesh devices flattened target-major (split axes outermost, in
    spec order): ring block ``i`` then lands exactly where the target
    layout wants row-block ``i``, so the finish leg is a pure gather
    over ``rest_axes`` — never a permute."""
    names = list(mesh.axis_names)
    order = [names.index(a) for a in split_axes] + [
        names.index(a) for a in rest_axes
    ]
    return tuple(np.transpose(mesh.devices, order).reshape(-1))


def wire_cols(cols: int, dtype: Any, wire_dtype: str) -> int:
    """uint8 columns of one encoded 2D row: the payload bytes plus (for
    int8) the per-row fp32 block scales — scales travel WITH their rows
    so any row split carries its own decode state.  Delegates to THE
    size formulas in ``ddl_tpu.wire`` (one row = a (1, cols) window),
    so the plan's pricing can never drift from what the encode
    actually produces.  Public: the device-shuffle planner
    (``ops/device_shuffle.plan_exchange``) prices the host path's
    wire-encoded DCN legs with the same formula the distribution plan
    uses, so the two tiers' accounting cannot diverge."""
    from ddl_tpu import wire

    return wire.encoded_nbytes(
        (1, cols), dtype, wire_dtype
    ) + wire.scale_bytes_for((1, cols), wire_dtype)


#: Backwards-compatible private alias (pre-device-shuffle call sites).
_wire_cols = wire_cols


def plan_distribution(
    shape: Sequence[int],
    dtype: Any,
    sharding: Any,
    max_memory_factor: Optional[float] = None,
    n_slots: int = 1,
    wire_dtype: str = "raw",
) -> DistributionPlan:
    """Plan the anchor→``sharding`` route for one window geometry.

    ``n_slots`` prices the fused two-slot protocol: with 2 landing
    slots, window N+1's fan-out is live (its landing buffers and
    output) while window N's finish legs run, so every leg's peak
    carries one extra in-flight fan-out's worth of bytes and the
    fan-out legs themselves are emitted ``asynchronous`` — start/wait
    pairs whose wait is the consuming step's first use (and which
    therefore survive a ``jax.checkpoint`` around that step).
    ``max_memory_factor`` defaults to ``DEFAULT_MEMORY_FACTOR *
    n_slots`` — the single-slot worst case per in-flight slot.

    Raises :class:`PlanError` when no bounded plan exists (unsupported
    spec shape, split dim not divisible by the device count, or the
    computed peak exceeding ``max_memory_factor`` × the window) —
    callers fall back to the XLA path and count it.
    """
    from ddl_tpu import wire as wire_mod
    from ddl_tpu.ops import ici_fanout

    shape = tuple(int(s) for s in shape)
    dtype = np.dtype(dtype)
    nbytes = int(np.prod(shape)) * dtype.itemsize
    mesh = sharding.mesh
    spec = sharding.spec
    n_dev = int(np.prod(list(mesh.shape.values())))
    split_dim, split_axes = _split_layout(spec, len(shape))
    rest_axes = tuple(
        a for a in mesh.axis_names if a not in split_axes
    )
    n_slots = max(1, min(int(n_slots), ici_fanout.N_SLOTS))
    if max_memory_factor is None:
        max_memory_factor = DEFAULT_MEMORY_FACTOR * n_slots
    fused = n_slots > 1
    # Lossy wire only applies to float windows: an int/token geometry
    # silently plans raw (values would corrupt for zero win) — the
    # distributor's per-geometry plan cache makes this a per-geometry
    # decision, exactly like the xla fallback.
    wire_dtype = wire_mod.check_wire_dtype(wire_dtype)
    if wire_dtype != "raw" and not wire_mod.lossy_supported(dtype):
        wire_dtype = "raw"

    if split_dim is None:
        ring = _ring_order(mesh, (), rest_axes)
        rows = shape[0]
        enc = rows * _wire_cols(
            int(np.prod(shape)) // rows, dtype, wire_dtype
        )
        wire = ici_fanout.wire_bytes("replicate", enc, n_dev)
        payload = ici_fanout.wire_bytes("replicate", nbytes, n_dev)
        # Per-device live: the window-sized SPMD landing block (cached —
        # every ring device needs an equal-shaped input block) + the
        # kernel output (the full window, which IS the target).  Every
        # ADDITIONAL in-flight landing slot pins one more landing +
        # output set for its whole dispatch span.  Wire plans size the
        # ring pieces at the ENCODED bytes and add the decoded output
        # (raw size) the landing-edge decode materialises.
        slot_live = 2 * enc
        peak = n_slots * slot_live + (nbytes if wire_dtype != "raw" else 0)
        legs = (
            RedistLeg("fanout.replicate", ("x",), wire, peak,
                      asynchronous=fused, wire_dtype=wire_dtype),
        )
        dst = nbytes
        plan = DistributionPlan(
            mode="replicate", shape=shape, dtype=dtype, split_dim=None,
            split_axes=(), rest_axes=rest_axes, ring_devices=ring,
            legs=legs, wire_bytes=wire, payload_bytes=payload,
            peak_bytes=peak, dst_shard_bytes=dst,
            peak_factor=peak / nbytes, n_slots=n_slots,
            wire_dtype=wire_dtype, encoded_bytes=enc,
        )
    else:
        split = shape[split_dim]
        if split % n_dev:
            raise PlanError(
                f"split dim {split_dim} ({split} rows) not divisible by "
                f"the {n_dev}-device ring"
            )
        g = int(np.prod([mesh.shape[a] for a in split_axes]))
        ring = _ring_order(mesh, split_axes, rest_axes)
        enc = split * _wire_cols(
            int(np.prod(shape)) // split, dtype, wire_dtype
        )
        wire = ici_fanout.wire_bytes("shard", enc, n_dev)
        payload = ici_fanout.wire_bytes("shard", nbytes, n_dev)
        block = enc // n_dev
        dst = nbytes // g
        # Scatter slot-live: the window-sized SPMD landing block (cached
        # on every ring device) + the output block — at the ENCODED
        # size for wire plans.  With the fused two-slot protocol the
        # NEXT window's fan-out is live through every leg of this
        # window's plan, so each leg carries one extra slot-live span.
        slot_live = enc + block
        extra = (n_slots - 1) * slot_live
        legs: List[RedistLeg] = [
            RedistLeg("fanout.shard", ("x",), wire, slot_live + extra,
                      asynchronous=fused, wire_dtype=wire_dtype),
        ]
        dec_extra = nbytes // g if wire_dtype != "raw" else 0
        if rest_axes:
            m = n_dev // g
            # Tiled all_gather over the replication axes: each device
            # receives the m-1 sibling ENCODED blocks of its target
            # shard (decode runs after the gather, so this leg moves
            # wire bytes too); the pinned landing block + kernel output
            # stay live under it, and the decoded shard (raw dst size)
            # materialises at the landing edge.
            legs.append(
                RedistLeg(
                    "all_gather", rest_axes, n_dev * (m - 1) * block,
                    enc + block + enc // g + dec_extra + extra,
                    wire_dtype=wire_dtype,
                )
            )
        legs.append(
            RedistLeg("reshape", (), 0, enc + dst + dec_extra + extra)
        )
        peak = max(leg.peak_bytes for leg in legs)
        plan = DistributionPlan(
            mode="shard", shape=shape, dtype=dtype, split_dim=split_dim,
            split_axes=split_axes, rest_axes=rest_axes, ring_devices=ring,
            legs=tuple(legs), wire_bytes=wire + (
                legs[1].ici_bytes if rest_axes else 0
            ),
            payload_bytes=payload, peak_bytes=peak, dst_shard_bytes=dst,
            peak_factor=peak / nbytes, n_slots=n_slots,
            wire_dtype=wire_dtype, encoded_bytes=enc,
        )
    if plan.peak_factor > max_memory_factor:
        raise PlanError(
            f"plan peak {plan.peak_bytes}B is {plan.peak_factor:.2f}x the "
            f"window ({nbytes}B) — over the "
            f"{max_memory_factor}x memory bound"
        )
    return plan


# -- compiled execution pieces (geometry-cached) ------------------------------


# Hashable Mesh wrapper for lru_cache keys — the one definition lives
# with the other mesh-keyed compiled-call caches (importing it here is
# free: ddl_tpu.parallel.__init__ already loads collectives eagerly).
from ddl_tpu.parallel.collectives import _MeshKey  # noqa: E402


def _value_ready(value: Any) -> bool:
    """Non-blocking completion probe for the fused-step OBSERVABILITY
    paths (slots-in-flight gauge, the trainer's overlap accounting):
    one shared implementation (:func:`ddl_tpu.utils.value_ready`), with
    the ready-by-default fallback — gauges degrade to zero rather than
    the probe becoming a sync."""
    from ddl_tpu.utils import value_ready

    return value_ready(value, default=True)


@functools.lru_cache(maxsize=64)
def _to2d_call(device: Any, shape: Tuple[int, ...], dtype_name: str,
               split_dim: int):
    """Anchor-local (split, -1) view builder: moveaxis + reshape, one
    compiled program per geometry, stays on the anchor device."""
    import jax
    import jax.numpy as jnp

    sds = jax.sharding.SingleDeviceSharding(device)

    def body(x):
        return jnp.moveaxis(x, split_dim, 0).reshape(shape[split_dim], -1)

    return jax.jit(body, out_shardings=sds)


def _jx_encode2d(x: Any, wire_dtype: str) -> Any:
    """Device-side 2D wire encode (traced): float rows → uint8 rows.

    bf16 bitcasts to 2 bytes/value; int8 rides the SAME blockwise
    quantizer the optimizer wire uses
    (``parallel.collectives.quantize_blockwise``) with the per-row fp32
    scales bitcast and concatenated after the payload columns — scales
    travel WITH their rows, so any row split carries its decode state.
    Runs on the anchor inside a jitted call: the window is never
    materialised at fp32 between the encode and the ring send.
    """
    import jax.numpy as jnp
    from jax import lax

    rows = x.shape[0]
    if wire_dtype == "bf16":
        b = lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint8)
        return b.reshape(rows, -1)
    from ddl_tpu import wire
    from ddl_tpu.parallel.collectives import quantize_blockwise

    q, s = quantize_blockwise(x.astype(jnp.float32), wire.QUANT_BLOCK)
    qb = lax.bitcast_convert_type(q, jnp.uint8)
    sb = lax.bitcast_convert_type(s, jnp.uint8).reshape(rows, -1)
    return jnp.concatenate([qb, sb], axis=1)


def _jx_decode2d(w: Any, cols: int, dtype: Any, wire_dtype: str) -> Any:
    """Inverse of :func:`_jx_encode2d` (traced, landing-edge local)."""
    import jax.numpy as jnp
    from jax import lax

    rows = w.shape[0]
    if wire_dtype == "bf16":
        v = lax.bitcast_convert_type(
            w.reshape(rows, cols, 2), jnp.bfloat16
        )
        return v.astype(dtype)
    from ddl_tpu import wire
    from ddl_tpu.parallel.collectives import dequantize_blockwise

    nblk = -(-cols // wire.QUANT_BLOCK)
    q = lax.bitcast_convert_type(w[:, :cols], jnp.int8)
    s = lax.bitcast_convert_type(
        w[:, cols:].reshape(rows, nblk, 4), jnp.float32
    )
    return dequantize_blockwise(q, s, dtype, wire.QUANT_BLOCK)


@functools.lru_cache(maxsize=64)
def _encode2d_call(device: Any, rows: int, cols: int, dtype_name: str,
                   wire_dtype: str):
    """Anchor-local jitted wire encode: (rows, cols) dtype → (rows,
    wire_cols) uint8, pinned to the anchor device (one compiled program
    per geometry, like :func:`_to2d_call`)."""
    import jax

    sds = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(
        lambda x: _jx_encode2d(x, wire_dtype), out_shardings=sds
    )


@functools.lru_cache(maxsize=64)
def _finish_replicate_wire_call(mesh_key: _MeshKey, shape: Tuple[int, ...],
                                dtype_name: str, wire_dtype: str):
    """Replicated encoded 2D view → decoded window at the target mesh's
    fully-replicated sharding (decode is per-device local compute — the
    landing-edge dequantize)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh_key.mesh
    cols = int(np.prod(shape)) // shape[0]
    sharding = NamedSharding(mesh, P(*([None] * len(shape))))
    dtype = np.dtype(dtype_name)
    return jax.jit(
        lambda w: _jx_decode2d(w, cols, dtype, wire_dtype).reshape(shape),
        out_shardings=sharding,
    )


@functools.lru_cache(maxsize=64)
def _finish_shard_call(mesh_key: _MeshKey, shape: Tuple[int, ...],
                       dtype_name: str, split_dim: int,
                       split_axes: Tuple[str, ...],
                       rest_axes: Tuple[str, ...],
                       wire_dtype: str = "raw"):
    """The single finish collective for shard mode: gather the
    replication axes (tiled on the split dim), restore the window's dim
    order locally, land on the exact target spec.  Wire plans gather
    the ENCODED rows (the gather leg moves wire bytes too) and decode
    at the landing edge, after the collective."""
    import jax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jax import shard_map

    mesh = mesh_key.mesh
    other_dims = tuple(
        d for d in range(len(shape)) if d != split_dim
    )
    cols = int(np.prod(shape)) // shape[split_dim]
    dtype = np.dtype(dtype_name)

    def body(x):  # x: (split_local, flat_features | wire_cols)
        if rest_axes:
            x = lax.all_gather(
                x, rest_axes if len(rest_axes) > 1 else rest_axes[0],
                axis=0, tiled=True,
            )
        import jax.numpy as jnp

        if wire_dtype != "raw":
            x = _jx_decode2d(x, cols, dtype, wire_dtype)
        x = x.reshape((x.shape[0],) + tuple(shape[d] for d in other_dims))
        return jnp.moveaxis(x, 0, split_dim)

    in_spec = P(tuple(split_axes) + tuple(rest_axes), None)
    out_entries: List[Any] = [None] * len(shape)
    out_entries[split_dim] = tuple(split_axes)
    out_spec = P(*out_entries)
    fn = shard_map(
        body, mesh=mesh, in_specs=in_spec, out_specs=out_spec,
        check_vma=False,
    )
    return jax.jit(
        fn,
        in_shardings=NamedSharding(mesh, in_spec),
        out_shardings=NamedSharding(mesh, out_spec),
    )


@functools.lru_cache(maxsize=64)
def _finish_replicate_call(mesh_key: _MeshKey, shape: Tuple[int, ...],
                           dtype_name: str):
    """Replicated 2D view → the window's original shape, landed on the
    target mesh's fully-replicated sharding (local reshape per device)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh_key.mesh
    sharding = NamedSharding(mesh, P(*([None] * len(shape))))
    return jax.jit(
        lambda x: x.reshape(shape), out_shardings=sharding
    )


class IciDistributor:
    """Executes :func:`plan_distribution` routes for one target sharding.

    Geometry plans (and their compiled programs) are cached.  Two
    fallback rungs, scoped to match their causes:

    - **Per-geometry** — a shape with no bounded plan (ragged final
      batch, indivisible split) takes the XLA scatter for THAT geometry
      only, counted once at plan time; plannable geometries keep riding
      ICI.
    - **Tier-wide latch** — a failed DMA leg (or the ``ici.fanout``
      chaos site) sets ``faulted`` and every later window takes the XLA
      fallback — the chip keeps training while the bench/report shows
      ``ici.fallbacks`` ticking.  Only run-time faults latch: the
      kernels are compiled ahead of time, and one that does not build
      or compile raises to the caller (a broken program is not a
      degraded link).  The first window of each geometry is
      synchronized (``block_until_ready``) inside the ladder's
      try/except, because on real TPUs dispatch is async and a bring-up
      DMA failure would otherwise surface at the CONSUMER's sync point,
      outside the ladder; steady-state windows stay async.  A mid-stream
      link failure on already-validated geometry still surfaces
      downstream — that rung is the trainer's existing failure path, not
      this latch.

    **Fused two-slot dispatch** (default, ``DDL_TPU_FUSED=0`` off):
    consecutive windows alternate between :data:`~ddl_tpu.ops.
    ici_fanout.N_SLOTS` device-side landing slots — per-slot collective
    ids and landing buffers — so window N+1's ring program is dispatched
    (``fanout_start``) while window N's output is still being consumed,
    and the DMA semaphores are waited on only at the consuming step's
    first use of the data (``fanout_wait``'s data dependence).  The
    ``ici.slots_in_flight`` gauge tracks how many slots actually carry
    an unresolved window (high-water rides ``.max``); every fused
    window also ticks ``ici.fused_windows``.  A latch clears the
    in-flight tracking but never strands a started slot: already-
    dispatched ring programs resolve on their own device-side
    semaphores, independent of later windows taking the xla path.
    """

    def __init__(
        self,
        sharding: Any,
        metrics: Optional[Metrics] = None,
        interpret: Optional[bool] = None,
        max_memory_factor: Optional[float] = None,
        n_chunks: Optional[int] = None,
        n_slots: Optional[int] = None,
        wire_dtype: Optional[str] = None,
    ):
        from ddl_tpu import wire
        from ddl_tpu.ops import ici_fanout

        # Wire format the fan-out carries (ddl_tpu.wire): encode on the
        # anchor, move uint8 over the ring, decode at the landing edge.
        # None defers to DDL_TPU_WIRE_DTYPE (the one data-plane knob);
        # pass "raw" explicitly when the slot wire already encoded
        # upstream — re-quantizing a decoded window erases the win
        # (ddl-lint DDL021's decode-then-requantize finding).
        self.wire_dtype = wire.resolve_wire_dtype(wire_dtype)
        self.sharding = sharding
        self.metrics = metrics or default_metrics()
        self.interpret = interpret
        if n_slots is None:
            n_slots = ici_fanout.N_SLOTS if fused_enabled() else 1
        self.n_slots = max(1, min(int(n_slots), ici_fanout.N_SLOTS))
        # The plan's memory bound scales with the in-flight slot count
        # (each slot pins one landing + output set); an explicit factor
        # wins.
        if max_memory_factor is None:
            max_memory_factor = DEFAULT_MEMORY_FACTOR * self.n_slots
        self.max_memory_factor = max_memory_factor
        self.n_chunks = n_chunks
        self.faulted = False
        self._slot = 0  # next landing slot (cycled per fused window)
        # Recent async outputs, tracked ONLY for the slots_in_flight
        # gauge (bounded by n_slots; resolved entries are swept on the
        # next dispatch).  Dropping an entry never cancels its window.
        self._in_flight: "list" = []
        self._mesh_key = _MeshKey(sharding.mesh)
        # geometry -> DistributionPlan | PlanError; windows recur over a
        # handful of geometries, and a failed plan must not be re-derived
        # (nor re-logged, nor re-counted) per window.  Bounded: 8
        # geometries LRU.
        self._plans: "dict" = {}
        # Geometries whose FIRST window completed a synchronized
        # dispatch — later windows skip the block_until_ready.
        self._validated: set = set()
        # Unplannable geometries already logged + counted: the LRU can
        # evict and re-derive their PlanError, but ``ici.fallbacks``
        # must tick once per geometry, not once per re-derivation.
        self._counted_failures: set = set()

    def plan(self, shape: Sequence[int], dtype: Any) -> DistributionPlan:
        key = (tuple(int(s) for s in shape), np.dtype(dtype).name)
        # pop + re-insert marks recency (dict preserves insertion
        # order), so the hot per-window geometry is never the one
        # evicted by a burst of rare put_batch shapes.
        hit = self._plans.pop(key, None)
        if hit is None:
            try:
                hit = plan_distribution(
                    key[0], key[1], self.sharding,
                    max_memory_factor=self.max_memory_factor,
                    n_slots=self.n_slots,
                    wire_dtype=self.wire_dtype,
                )
            except PlanError as e:
                hit = e
                # Counted + logged ONCE per geometry for the
                # distributor's life (NOT per cache insert — the LRU
                # may evict and re-derive a PlanError): this geometry
                # rides the xla scatter, the tier stays up for
                # plannable ones.
                if key not in self._counted_failures:
                    self._counted_failures.add(key)
                    logger.warning(
                        "ddl_tpu: no bounded ICI plan for %s/%s (%s) — "
                        "this geometry takes the xla path",
                        key[0], key[1], e,
                    )
                    self.metrics.incr("ici.fallbacks")
            if len(self._plans) >= 8:
                self._plans.pop(next(iter(self._plans)))
        self._plans[key] = hit
        if isinstance(hit, PlanError):
            raise hit
        return hit

    def anchor(self, shape: Sequence[int], dtype: Any) -> Any:
        """The device H2D must land on for this geometry."""
        return self.plan(shape, dtype).anchor

    def put(self, arr: Any, device_put: Any) -> Any:
        """The ingest seam's one-call entry: H2D ``arr`` onto the plan's
        anchor device with ``device_put``, then distribute over ICI.  A
        geometry with no bounded plan takes one XLA-scattered put for
        that geometry instead — the seam sees exactly the exceptions the
        plain xla path would raise, never an ICI-specific one."""
        if not self.faulted:
            try:
                anchor = self.plan(arr.shape, arr.dtype).anchor
            except PlanError:
                pass  # counted+logged once in plan(); per-geometry xla
            else:
                return self.distribute(device_put(arr, anchor))
        return device_put(arr, self.sharding)

    def distribute(self, block: Any) -> Any:
        """Move an anchor-resident window to the target sharding over
        ICI.  An unplannable geometry re-routes through the XLA path
        (that geometry only); a fan-out that fails at RUN time — a
        device-side error surfacing as ``JaxRuntimeError``, or the
        ``ici.fanout`` chaos site — re-routes AND latches the fallback
        for the rest of the distributor's life.  Anything else (a
        kernel that does not build or compile, a shutdown) is not a
        link fault and propagates."""
        import jax

        if self.faulted:
            return self._xla_fallback(block)
        try:
            plan = self.plan(block.shape, block.dtype)
        except PlanError:
            return self._xla_fallback(block)
        try:
            return self._distribute_planned(block, plan)
        except (InjectedFault, jax.errors.JaxRuntimeError) as e:
            self._latch(f"{type(e).__name__}: {e}")
            return self._xla_fallback(block)

    def _distribute_planned(self, block: Any, plan: DistributionPlan) -> Any:
        import time

        from ddl_tpu.ops import ici_fanout

        fault_point("ici.fanout")
        m = self.metrics
        dtype_name = np.dtype(block.dtype).name
        slot = self._slot
        # The fan-out DISPATCH stage (lane pack, wire encode, ring-kernel
        # launch), keyed on the thread's current window: the ring
        # kernels are async — the stage is the host-side cost the fused
        # step must hide.
        if plan.mode == "replicate":
            with stage("ddl.ici_fanout", m):
                flat = _to2d_call(
                    plan.anchor, plan.shape, dtype_name, 0
                )(block)
                if plan.wire_dtype != "raw":
                    # Anchor-side device encode: the ring moves uint8
                    # wire rows; the window is never a host fp32 temp
                    # between the encode and the send (DDL021
                    # discipline).
                    flat = _encode2d_call(
                        plan.anchor, plan.shape[0],
                        int(np.prod(plan.shape)) // plan.shape[0],
                        dtype_name, plan.wire_dtype,
                    )(flat)
                ticket = ici_fanout.fanout_start(
                    "replicate", flat, plan.ring_devices, src=0,
                    slot=slot,
                    n_chunks=self.n_chunks or ici_fanout.DEFAULT_CHUNKS,
                    interpret=self.interpret,
                )
            t1 = time.perf_counter()
            rep = ici_fanout.replicated_view(
                ici_fanout.fanout_wait(ticket), plan.ring_devices
            )
            if plan.wire_dtype != "raw":
                result = _finish_replicate_wire_call(
                    self._mesh_key, plan.shape, dtype_name,
                    plan.wire_dtype,
                )(rep)
            else:
                result = _finish_replicate_call(
                    self._mesh_key, plan.shape, dtype_name
                )(rep)
            m.add_time("ici.redistribute", time.perf_counter() - t1)
        else:
            with stage("ddl.ici_fanout", m):
                flat = _to2d_call(
                    plan.anchor, plan.shape, dtype_name, plan.split_dim
                )(block)
                if plan.wire_dtype != "raw":
                    flat = _encode2d_call(
                        plan.anchor, plan.shape[plan.split_dim],
                        int(np.prod(plan.shape))
                        // plan.shape[plan.split_dim],
                        dtype_name, plan.wire_dtype,
                    )(flat)
                ticket = ici_fanout.fanout_start(
                    "shard", flat, plan.ring_devices, src=0, slot=slot,
                    interpret=self.interpret,
                )
            t1 = time.perf_counter()
            result = _finish_shard_call(
                self._mesh_key, plan.shape, dtype_name, plan.split_dim,
                plan.split_axes, plan.rest_axes, plan.wire_dtype,
            )(self._onto_mesh(ici_fanout.fanout_wait(ticket), plan))
            m.add_time("ici.redistribute", time.perf_counter() - t1)
        key = (plan.shape, np.dtype(plan.dtype).name)
        if key not in self._validated:
            # First window of a geometry: synchronize so that a
            # bring-up DMA failure — asynchronous on real TPUs, where
            # dispatch returns before the ring kernel runs — surfaces
            # HERE, inside distribute()'s try/except, and latches the
            # xla fallback instead of stranding the consumer's
            # block_until_ready.  Steady-state windows stay async (the
            # fused wait is the consuming step's first use of the data).
            import jax

            ici_fanout.fanout_wait(ticket, sync=True)  # ddl-lint: disable=DDL020 - bring-up validation, once per geometry
            jax.block_until_ready(result)  # ddl-lint: disable=DDL020 - bring-up validation, once per geometry
            self._validated.add(key)
        # Landing-slot bookkeeping: cycle the slot AFTER a successful
        # dispatch (an exception re-routes through the ladder without
        # burning the slot), count the fused window, and refresh the
        # slots-in-flight gauge from a non-blocking readiness probe.
        if plan.n_slots > 1:
            self._slot = (slot + 1) % plan.n_slots
            m.incr("ici.fused_windows")
        self._track_in_flight(result)
        m.incr("ici.bytes", float(plan.wire_bytes))
        if plan.wire_dtype != "raw":
            # Wire accounting (ddl_tpu.wire): what the ring actually
            # moved per window vs the logical raw bytes it delivered.
            m.incr("wire.encoded_bytes", float(plan.encoded_bytes))
            m.incr(
                "wire.payload_bytes",
                float(int(np.prod(plan.shape)) * plan.dtype.itemsize),
            )
        m.incr("ici.windows")
        m.set_gauge("ici.peak_bytes", float(plan.peak_bytes))
        return result

    def _track_in_flight(self, result: Any) -> None:
        """Sweep resolved windows, record ``result``, refresh the
        ``ici.slots_in_flight`` gauge (high-water on ``.max``) — all
        non-blocking; tracking is observability, never a wait.

        Entries are WEAK references: after the stream's last window
        there is no next dispatch to sweep on, and a strong reference
        would pin up to ``n_slots`` window-sized device buffers for the
        distributor's remaining life.  The consumer dropping the window
        releases the tracking with it."""
        import weakref

        self._in_flight = [
            r for r in self._in_flight
            if r() is not None and not _value_ready(r())
        ]
        # Every survivor of the sweep is by construction alive and
        # unresolved, so occupancy is the survivor count plus one probe
        # of the new result — no second pass over the tracked set.
        occupied = len(self._in_flight) + (
            0 if _value_ready(result) else 1
        )
        try:
            self._in_flight.append(weakref.ref(result))
        except TypeError:
            pass  # non-weakrefable value: skip tracking, never pin
        del self._in_flight[: -max(1, self.n_slots)]  # bounded
        occupied = min(occupied, self.n_slots)
        self.metrics.set_gauge("ici.slots_in_flight", float(occupied))

    def _onto_mesh(self, ring_out: Any, plan: DistributionPlan) -> Any:
        """Zero-copy reinterpretation of the ring's block-per-device
        output as a trainer-mesh global array (split dim sharded over
        every axis, target-major) — the finish collective's input."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = P(tuple(plan.split_axes) + tuple(plan.rest_axes), None)
        sharding = NamedSharding(self.sharding.mesh, spec)
        by_device = {s.device: s.data for s in ring_out.addressable_shards}
        order = sharding.addressable_devices_indices_map(ring_out.shape)
        return jax.make_array_from_single_device_arrays(
            ring_out.shape, sharding,
            [by_device[d] for d in order],
        )

    def _latch(self, why: str) -> None:
        if not self.faulted:
            logger.error(
                "ddl_tpu: ICI distribution failed (%s) — latched "
                "fallback to the xla path", why,
            )
        self.faulted = True
        # Drop the in-flight tracking but never the windows themselves:
        # an already-dispatched slot resolves on its own device-side
        # semaphores — the latch only re-routes FUTURE windows, so a
        # mid-fused-step failure cannot strand a started slot.
        self._in_flight = []
        self.metrics.set_gauge("ici.slots_in_flight", 0.0)
        self.metrics.incr("ici.fallbacks")

    def _xla_fallback(self, block: Any) -> Any:
        """The pre-ICI behavior: let XLA scatter from the anchor."""
        import jax

        return jax.device_put(block, self.sharding)


#: The loader→trainer sharding pairs the dryrun/property tests cover on
#: the 8-device virtual mesh: every trainer layout the repo's examples
#: use, from pure dp to dp×fsdp×tp, batch-dim and leading-dim splits,
#: plus full replication.  (mesh axes, target spec entries) — specs are
#: built per-test so the module stays importable without jax devices.
DRYRUN_MATRIX: Tuple[Tuple[Tuple[Tuple[str, int], ...], Tuple[Any, ...]], ...] = (
    ((("dp", 8),), ("dp", None)),
    ((("dp", 8),), (None, "dp")),
    ((("dp", 4), ("fsdp", 2)), (None, "dp")),
    ((("dp", 4), ("fsdp", 2)), (("dp", "fsdp"), None)),
    ((("dp", 2), ("fsdp", 2), ("tp", 2)), (None, "dp")),
    ((("dp", 2), ("fsdp", 2), ("tp", 2)), (("dp", "fsdp"), None)),
    ((("dp", 2), ("fsdp", 4)), (None, None)),
    ((("dp", 8),), (None, "dp", None)),
)

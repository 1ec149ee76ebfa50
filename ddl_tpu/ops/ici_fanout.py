"""Pallas ICI fan-out kernels: device-side window distribution.

One source device's committed window is replicated (pipelined ring
broadcast) or sharded (direct scatter) across a 1-axis device ring
entirely over ICI with ``pltpu.make_async_remote_copy`` DMAs.  The
kernels are written for the hardware — every device runs at its own
pace — and the synchronisation argument is in the code, not in the
test harness:

- **Entry barrier.**  A remote DMA writes into the *destination's*
  output buffer, which exists only once that device has entered the
  kernel.  Every device that will be written to therefore signals its
  writer on the kernel's barrier semaphore
  (``pltpu.get_barrier_semaphore``, keyed by the per-slot
  ``collective_id``) and every writer waits for those signals before
  its first send.  Each signal is consumed by exactly one wait, so the
  semaphore is back at zero when the kernel exits.
- **Write-once destinations.**  No byte of any output buffer is written
  twice and no buffer is reused inside a kernel: the broadcast forwards
  chunk ``c`` only after ITS receive semaphore fired and nobody writes
  chunk ``c`` of that device again; the scatter sends each block
  straight from the source's window into its owner's output.  There is
  no transit buffer to overwrite and no semaphore shared between two
  in-flight transfers (one DMA semaphore per chunk / per block), so no
  ordering between different DMAs is ever assumed.
- **Exit.**  A device leaves only after every DMA it sent has drained
  (``wait_send``) and every DMA aimed at it has landed (``wait_recv``).

**Landing slots (fused step).**  The fused compute/ingest step keeps
TWO windows' fan-outs dispatched: window N+1's program is enqueued
while the step computing window N runs.  Devices skew, so a fast
device can enter window N+1's kernel while a neighbour is still in
window N's; the two must not share a barrier semaphore.  Every wrapper
takes a ``slot`` (< ``N_SLOTS``) selecting a per-slot ``collective_id``
AND a per-slot set of cached landing buffers.  The split start/wait
surface is :func:`fanout_start` / :func:`fanout_wait`: start IS the
async dispatch of the slot's program and the wait is the consumer's
first use of the returned value.

**Tile alignment.**  Mosaic only slices an HBM array along its tiling
(128 lanes by 8 sublanes of 32 bits; 16 sublanes at 16 bits, 32 at 8),
and a training job's windows do not oblige: a dp-sharded token window
hands each device ONE row.  Since a row block is just a contiguous run
of elements, the wrappers move any block whose shape is off the tiling
through its LANE VIEW — the same elements as ``(R, 128)`` rows, ``R``
padded up to whole tiles — packed and unpacked by two small jitted
programs around the kernel.  Blocks already on the tiling (the 64 MiB
stream windows) go through as they are, with no extra copy.

Off-TPU the wrappers run the same kernels under Pallas' TPU interpret
mode (``pltpu.InterpretParams``), which simulates per-device progress,
remote DMAs and semaphores — the barrier is executed there too, and the
race detector can be turned on (tests/test_ici.py).  **Interpret mode
has a size limit**: the interpreter hands each kernel operand to a
Python callback, the CPU client copies operands past ~100 KiB on its
own thread pool, and the ring's device threads — blocked in their
callbacks on semaphores — already occupy that pool when the ring is as
wide as the host has cores: the run deadlocks.  Keep interpreted
windows under 64 KiB (tier-1 does, by a wide margin); real sizes are
for the chip.  The compiled
programs are built ahead of time (``.lower().compile()``) so that a
kernel Mosaic refuses surfaces as :class:`KernelBuildError` at build,
never as a "link fault" in a caller's fallback ladder.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import lax
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.exceptions import KernelBuildError
from ddl_tpu.ops.naming import named_pallas_call

#: The fan-out ring's private mesh axis (always 1-axis: the
#: redistribution planner owns the mapping onto dp x fsdp x tp).
AXIS = "x"

#: Default chunk count for the broadcast pipeline.  More chunks deepen
#: the pipeline (a relay forwards chunk c while chunk c+1 is still
#: arriving) at one DMA semaphore pair per chunk; 4 is a reasonable
#: floor for the window sizes the loader moves (>= 8 MiB).
DEFAULT_CHUNKS = 4

#: Device-side landing slots the fused step may keep in flight at once.
#: Two is the double-buffer: window N+1's ring runs while window N's
#: output is being consumed; a third slot would buy nothing (the step
#: consuming window N-1 has already waited its data) and cost one more
#: pinned landing-buffer set per geometry.
N_SLOTS = 2

#: Mosaic collective ids, indexed by landing slot: the id names the
#: barrier semaphore a kernel's entry handshake runs on, and two
#: kernels that can be entered out of step by different devices (the
#: two landing slots) must not share one.
_BCAST_COLLECTIVE_IDS = (11, 13)
_SCATTER_COLLECTIVE_IDS = (12, 14)

_LOGICAL = pltpu.DeviceIdType.LOGICAL


def _bcast_kernel(in_ref, out_ref, send_sem, recv_sem, copy_sem, *,
                  src: int, n_dev: int, chunks: Tuple[Tuple[int, int], ...]):
    """Pipelined ring broadcast: the source's ``in_ref`` lands in every
    device's ``out_ref``.  Ring position 0 (the source) sends each chunk
    to its right neighbour straight from the window; positions
    1..n-2 forward chunk ``c`` as soon as it has landed; the tail only
    receives.  ``chunks`` is the static ((row_start, n_rows), ...)
    split."""
    me = lax.axis_index(AXIS)
    pos = lax.rem(me - src + n_dev, n_dev)
    right = lax.rem(me + 1, n_dev)
    left = lax.rem(me + n_dev - 1, n_dev)
    barrier = pltpu.get_barrier_semaphore()

    def hop(c, from_ref):
        start, size = chunks[c]
        return pltpu.make_async_remote_copy(
            src_ref=from_ref.at[pl.ds(start, size)],
            dst_ref=out_ref.at[pl.ds(start, size)],
            send_sem=send_sem.at[c],
            recv_sem=recv_sem.at[c],
            device_id=right,
            device_id_type=_LOGICAL,
        )

    # Entry barrier: my LEFT neighbour is the one device that writes
    # into me — tell it my out_ref exists; before my own first send,
    # wait for the same word from my right.
    @pl.when(pos > 0)
    def _announce():
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=left, device_id_type=_LOGICAL
        )

    @pl.when(pos < n_dev - 1)
    def _await_right():
        pltpu.semaphore_wait(barrier, 1)

    @pl.when(pos == 0)
    def _source():
        own = pltpu.make_async_copy(in_ref, out_ref, copy_sem.at[0])
        own.start()
        for c in range(len(chunks)):
            hop(c, in_ref).start()
        own.wait()  # ddl-lint: disable=DDL012 - device-side DMA semaphore, not a host wait
        for c in range(len(chunks)):
            hop(c, in_ref).wait_send()

    @pl.when((pos > 0) & (pos < n_dev - 1))
    def _relay():
        for c in range(len(chunks)):
            hop(c, out_ref).wait_recv()
            hop(c, out_ref).start()
        for c in range(len(chunks)):
            hop(c, out_ref).wait_send()

    @pl.when(pos == n_dev - 1)
    def _tail():
        for c in range(len(chunks)):
            hop(c, out_ref).wait_recv()


def _scatter_kernel(in_ref, out_ref, send_sem, recv_sem, copy_sem, *,
                    src: int, n_dev: int, rows: int):
    """Direct scatter: row-block ``d`` of the source's window lands in
    device ``d``'s ``out_ref``, one remote DMA per destination, all in
    flight together (the source's egress is the bound either way, and
    nothing is forwarded, so there is no transit buffer)."""
    me = lax.axis_index(AXIS)
    barrier = pltpu.get_barrier_semaphore()

    def block_to(dst: int):
        return pltpu.make_async_remote_copy(
            src_ref=in_ref.at[pl.ds(dst * rows, rows)],
            dst_ref=out_ref,
            send_sem=send_sem.at[dst],
            recv_sem=recv_sem.at[0],
            device_id=dst,
            device_id_type=_LOGICAL,
        )

    others = [d for d in range(n_dev) if d != src]

    # Entry barrier: only the source writes remotely, so every other
    # device tells the source its out_ref exists and the source waits
    # for all of them before its first send.
    @pl.when(me != src)
    def _dest():
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=src, device_id_type=_LOGICAL
        )
        block_to(others[0]).wait_recv()

    @pl.when(me == src)
    def _source():
        pltpu.semaphore_wait(barrier, n_dev - 1)
        own = pltpu.make_async_copy(
            in_ref.at[pl.ds(src * rows, rows)], out_ref, copy_sem.at[0]
        )
        own.start()
        for d in others:
            block_to(d).start()
        own.wait()  # ddl-lint: disable=DDL012 - device-side DMA semaphore, not a host wait
        for d in others:
            block_to(d).wait_send()


def interpret_default(devices: Sequence[Any]) -> bool:
    """Interpret (CPU-simulate) unless every ring device is a real TPU."""
    return any(getattr(d, "platform", "cpu") != "tpu" for d in devices)


def interpret_arg(interpret: bool) -> Any:
    """The ``pallas_call(interpret=...)`` value: Pallas' TPU interpret
    mode (simulated per-device progress, remote DMAs, semaphores and
    the barrier) off-chip, Mosaic on it."""
    return pltpu.InterpretParams() if interpret else False


def compile_kernel(jitted: Any, *args: Any) -> Any:
    """Build a jitted kernel program ahead of time.  Whatever the
    compiler refuses (Mosaic: a slice off the tiling, a fast-memory
    overrun, a misused semaphore; XLA: a program that does not fit) is
    a broken program, so it is re-typed as :class:`KernelBuildError` —
    the runtime fault ladders catch ``JaxRuntimeError`` for genuine
    link faults and must not see this one.  Python-level build errors
    (tracing, lowering) propagate as they are."""
    lowered = jitted.lower(*args)
    try:
        return lowered.compile()
    except Exception as e:  # noqa: BLE001 - every failure of compile() is one
        raise KernelBuildError(f"kernel failed to compile: {e}") from e


# -- geometry helpers ---------------------------------------------------------


#: Lane width of the TPU's HBM/VMEM tiling.
LANES = 128


def sublanes(dtype: Any) -> int:
    """Rows of one tile: 8 at 32 bits, 16 at 16, 32 at 8 — 4 KiB a
    tile whatever the dtype."""
    return max(8, 32 // np.dtype(dtype).itemsize)


def tile_aligned(rows: int, cols: int, dtype: Any) -> bool:
    """Can Mosaic slice whole ``rows``-row blocks out of a (k * rows,
    cols) array of this dtype?"""
    return cols % LANES == 0 and rows % sublanes(dtype) == 0


def lane_rows(n_elems: int, dtype: Any) -> int:
    """Rows ``R`` of the ``(R, 128)`` lane view that holds ``n_elems``
    elements, padded up to whole tiles."""
    sub = sublanes(dtype)
    return -(-n_elems // (LANES * sub)) * sub


def kernel_view(n_blocks: int, block_rows: int, cols: int,
                dtype: Any) -> Tuple[int, int]:
    """The (rows, cols) array a kernel moves for ``n_blocks`` blocks of
    (block_rows, cols): the blocks themselves where Mosaic can slice
    them, else their lane views stacked."""
    if tile_aligned(block_rows, cols, dtype):
        return n_blocks * block_rows, cols
    return n_blocks * lane_rows(block_rows * cols, dtype), LANES


def chunk_rows(rows: int, n_chunks: int,
               align: int = 1) -> Tuple[Tuple[int, int], ...]:
    """Static ((row_start, n_rows), ...) split of ``rows`` into at most
    ``n_chunks`` contiguous chunks of a multiple of ``align`` rows (the
    last one may be short)."""
    n_chunks = max(1, min(n_chunks, rows))
    size = -(-rows // n_chunks)
    size = -(-size // align) * align
    return tuple(
        (start, min(size, rows - start)) for start in range(0, rows, size)
    )


def wire_bytes(mode: str, nbytes: int, n_dev: int) -> int:
    """Bytes the fan-out's DMAs move over ICI — the numerator for
    link-utilization math.  Neither kernel sends a byte that is not
    delivered: the broadcast forwards the window once per non-tail
    ring position, the scatter sends each off-source block once."""
    if mode not in ("replicate", "shard"):
        raise ValueError(f"mode must be replicate|shard, got {mode!r}")
    if n_dev <= 1:
        return 0
    if mode == "replicate":
        return (n_dev - 1) * nbytes
    return nbytes - nbytes // n_dev


# -- compiled-call cache ------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _ring_mesh(devices: Tuple[Any, ...]):
    from jax.sharding import Mesh

    return Mesh(np.array(devices), (AXIS,))


def _ring_program(call: Any, devices: Tuple[Any, ...], in_rows: int,
                  cols: int, dtype: Any) -> Any:
    """shard_map + jit + ahead-of-time compile of one ring kernel whose
    single input is the global (n * in_rows, cols) P(x) array."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _ring_mesh(devices)
    fn = shard_map(
        call, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS),
        check_vma=False,
    )
    spec = NamedSharding(mesh, P(AXIS))
    return compile_kernel(
        jax.jit(fn, in_shardings=spec, out_shardings=spec),
        jax.ShapeDtypeStruct(
            (len(devices) * in_rows, cols), dtype, sharding=spec
        ),
    )


@functools.lru_cache(maxsize=64)
def _bcast_call(devices: Tuple[Any, ...], rows: int, cols: int,
                dtype_name: str, src: int, n_chunks: int, interpret: bool,
                slot: int = 0):
    """Compiled broadcast over ``devices``: input global (n * rows,
    cols) P(x) [only the source's block is real], output the same
    global shape, every block the source's."""
    dtype = np.dtype(dtype_name)
    chunks = chunk_rows(rows, n_chunks, align=sublanes(dtype))
    call = named_pallas_call(
        "ddl_ici_bcast",
        functools.partial(
            _bcast_kernel, src=src, n_dev=len(devices), chunks=chunks
        ),
        out_shape=jax.ShapeDtypeStruct((rows, cols), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((len(chunks),)),
            pltpu.SemaphoreType.DMA((len(chunks),)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        interpret=interpret_arg(interpret),
        compiler_params=pltpu.CompilerParams(
            collective_id=_BCAST_COLLECTIVE_IDS[slot]
        ),
    )
    return _ring_program(call, devices, rows, cols, dtype)


@functools.lru_cache(maxsize=64)
def _scatter_call(devices: Tuple[Any, ...], rows: int, cols: int,
                  dtype_name: str, src: int, interpret: bool,
                  slot: int = 0):
    """Compiled scatter: input global (n * rows, cols) P(x) [source
    block real], output global (rows, cols) P(x) — row-block i on
    device i."""
    n_dev = len(devices)
    dtype = np.dtype(dtype_name)
    block_rows = rows // n_dev
    call = named_pallas_call(
        "ddl_ici_scatter",
        functools.partial(
            _scatter_kernel, src=src, n_dev=n_dev, rows=block_rows
        ),
        out_shape=jax.ShapeDtypeStruct((block_rows, cols), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((n_dev,)),
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        interpret=interpret_arg(interpret),
        compiler_params=pltpu.CompilerParams(
            collective_id=_SCATTER_COLLECTIVE_IDS[slot]
        ),
    )
    return _ring_program(call, devices, rows, cols, dtype)


@functools.lru_cache(maxsize=64)
def _pack_call(device: Any, n_blocks: int, block_elems: int,
               dtype_name: str):
    """Source-local lane view: ``n_blocks`` blocks of ``block_elems``
    contiguous elements each → ``(n_blocks * R, 128)``, every block
    padded to whole tiles."""
    from jax import numpy as jnp

    lane_elems = lane_rows(block_elems, dtype_name) * LANES

    def body(x):
        x = x.reshape(n_blocks, block_elems)
        x = jnp.pad(x, ((0, 0), (0, lane_elems - block_elems)))
        return x.reshape(-1, LANES)

    return jax.jit(
        body, out_shardings=jax.sharding.SingleDeviceSharding(device)
    )


@functools.lru_cache(maxsize=64)
def _unpack_call(devices: Tuple[Any, ...], rows: int, cols: int,
                 dtype_name: str):
    """Per-device inverse of :func:`_pack_call` for ONE block: global
    (n * R, 128) P(x) → global (n * rows, cols) P(x)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _ring_mesh(devices)
    spec = NamedSharding(mesh, P(AXIS))

    def body(x):
        return x.reshape(-1)[: rows * cols].reshape(rows, cols)

    fn = shard_map(
        body, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS),
        check_vma=False,
    )
    return jax.jit(fn, in_shardings=spec, out_shardings=spec)


@functools.lru_cache(maxsize=8)
def _landing_buffers(devices: Tuple[Any, ...], rows: int, cols: int,
                     dtype_name: str, skip: int, slot: int = 0):
    """Per-device landing buffers for the non-source ring positions (the
    SPMD input needs a block on every device; only the source's carries
    data).  Cached per (geometry, landing slot) so steady-state windows
    allocate nothing — each entry PINS one window-sized block per
    non-source device in HBM for the cache's life, which is why (a) the
    cache is small (a loader cycles a handful of window geometries ×
    ``N_SLOTS`` landing slots, not 64) and (b) the redistribution plan
    prices the landing blocks — one set per IN-FLIGHT slot — into its
    asserted per-device peak.  Keying by ``slot`` keeps two in-flight
    ring programs off each other's input buffers, so XLA sees no shared
    operand ordering the dispatches."""
    zeros = np.zeros((rows, cols), np.dtype(dtype_name))
    return tuple(
        None if i == skip else jax.device_put(zeros, d)
        for i, d in enumerate(devices)
    )


def _as_ring_input(block: Any, devices: Tuple[Any, ...], rows: int,
                   cols: int, src: int, slot: int = 0):
    """Assemble the SPMD global input (n * rows, cols) P(x): the source
    block plus cached landing buffers — zero host traffic after the
    first call per (geometry, slot)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = len(devices)
    dtype_name = np.dtype(block.dtype).name
    landing = _landing_buffers(devices, rows, cols, dtype_name, src, slot)
    shards = [landing[i] if i != src else block for i in range(n_dev)]
    return jax.make_array_from_single_device_arrays(
        (n_dev * rows, cols),
        NamedSharding(_ring_mesh(devices), P(AXIS)),
        shards,
    )


# -- public wrappers ----------------------------------------------------------


def _check_slot(slot: int) -> int:
    slot = int(slot)
    if not 0 <= slot < N_SLOTS:
        raise ValueError(
            f"landing slot must be in [0, {N_SLOTS}), got {slot}"
        )
    return slot


def _through_kernel(build: Any, block: Any, devices: Tuple[Any, ...],
                    src: int, slot: int, n_blocks: int, block_rows: int,
                    cols: int) -> Any:
    """Run ``build(kernel_rows, kernel_cols, dtype_name)``'s program over
    ``block`` = ``n_blocks`` blocks of (block_rows, cols): as it is where
    the blocks sit on Mosaic's tiling, else packed into lane views
    before and unpacked after."""
    dtype_name = np.dtype(block.dtype).name
    krows, kcols = kernel_view(n_blocks, block_rows, cols, dtype_name)
    packed = (krows, kcols) != (n_blocks * block_rows, cols)
    if packed:
        block = _pack_call(
            devices[src], n_blocks, block_rows * cols, dtype_name
        )(block)
    out = build(krows, kcols, dtype_name)(
        _as_ring_input(block, devices, krows, kcols, src, slot)
    )
    if packed:
        return _unpack_call(devices, block_rows, cols, dtype_name)(out)
    return out


def fanout_replicate(block: Any, devices: Sequence[Any], src: int = 0,
                     n_chunks: int = DEFAULT_CHUNKS,
                     interpret: Optional[bool] = None,
                     slot: int = 0) -> Any:
    """Broadcast a (rows, cols) device block to every ring device.

    ``block`` must live on ``devices[src]``.  Returns a global
    ``(n * rows, cols)`` array sharded one block per device, every block
    byte-identical to the source (callers reinterpret the shards — see
    :func:`replicated_view`).  ``slot`` selects the landing slot
    (collective id + cached landing buffers); callers keeping two
    fan-outs in flight must alternate slots.
    """
    devices = tuple(devices)
    # Validate BEFORE the single-device passthrough: a bad slot must
    # fail on the 1-device dev box, not first on a real ring.
    slot = _check_slot(slot)
    if len(devices) == 1:
        return block
    if interpret is None:
        interpret = interpret_default(devices)
    rows, cols = block.shape
    return _through_kernel(
        lambda krows, kcols, dtype_name: _bcast_call(
            devices, krows, kcols, dtype_name, src, n_chunks, interpret, slot
        ),
        block, devices, src, slot, 1, rows, cols,
    )


def fanout_shard(block: Any, devices: Sequence[Any], src: int = 0,
                 interpret: Optional[bool] = None, slot: int = 0) -> Any:
    """Scatter a (rows, cols) device block: row-block ``i`` lands on
    device ``i``.

    ``rows`` must divide evenly by the ring size (the planner guarantees
    this or falls back).  Returns a global (rows, cols) array sharded
    P(x) over the ring.  ``slot`` selects the landing slot, as in
    :func:`fanout_replicate`.
    """
    devices = tuple(devices)
    n_dev = len(devices)
    slot = _check_slot(slot)  # before the passthrough, as in replicate
    if n_dev == 1:
        return block
    if interpret is None:
        interpret = interpret_default(devices)
    rows, cols = block.shape
    if rows % n_dev:
        raise ValueError(
            f"shard fan-out needs rows ({rows}) divisible by the ring "
            f"size ({n_dev})"
        )
    return _through_kernel(
        lambda krows, kcols, dtype_name: _scatter_call(
            devices, krows, kcols, dtype_name, src, interpret, slot
        ),
        block, devices, src, slot, n_dev, rows // n_dev, cols,
    )


@dataclasses.dataclass(frozen=True)
class FanoutTicket:
    """A started (dispatched, possibly still in flight) fan-out.

    ``value`` is the kernel's output as an ASYNC device value: the ring
    program is enqueued device-side at :func:`fanout_start` and its DMA
    semaphores are waited on by the hardware, not the host — the host
    thread returns immediately and the consuming step's first use of
    ``value`` is the wait leg.  The ticket records which landing slot
    the window occupies so callers can assert the double-buffer
    discipline (at most one in-flight window per slot).
    """

    value: Any
    mode: str  #: "replicate" | "shard"
    slot: int


def fanout_start(mode: str, block: Any, devices: Sequence[Any],
                 src: int = 0, *, slot: int = 0,
                 n_chunks: int = DEFAULT_CHUNKS,
                 interpret: Optional[bool] = None) -> FanoutTicket:
    """Start a fan-out into landing slot ``slot``; never waits.

    The start half of the fused step's split start/wait surface: the
    ring program for window N+1 is dispatched here — at the entry of
    the step computing window N — and runs under that step.  Pair with
    :func:`fanout_wait`.
    """
    slot = _check_slot(slot)  # fail BEFORE dispatching side effects
    if mode == "replicate":
        out = fanout_replicate(
            block, devices, src=src, n_chunks=n_chunks,
            interpret=interpret, slot=slot,
        )
    elif mode == "shard":
        out = fanout_shard(
            block, devices, src=src, interpret=interpret, slot=slot
        )
    else:
        raise ValueError(f"mode must be replicate|shard, got {mode!r}")
    return FanoutTicket(value=out, mode=mode, slot=slot)


def fanout_wait(ticket: FanoutTicket, sync: bool = False) -> Any:
    """The wait half: hand the started fan-out's value to its consumer.

    The real wait is the DATA DEPENDENCE — the consuming step's first
    use of the returned value drains the slot's DMA semaphores on
    device, with the host never blocking.  ``sync=True`` forces a host
    ``block_until_ready`` and is reserved for the bring-up validation
    path (the first window of a geometry, where an async DMA failure
    must surface inside the distributor's fallback ladder rather than
    at the consumer's sync point).
    """
    if sync:
        jax.block_until_ready(ticket.value)
    return ticket.value


def replicated_view(out: Any, devices: Sequence[Any]) -> Any:
    """Reinterpret a block-per-device broadcast result (n * rows, cols)
    as ONE logically-replicated (rows, cols) array — zero-copy: the
    per-device shards become the replicas."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = tuple(devices)
    n_dev = len(devices)
    if n_dev == 1:
        return out
    rows = out.shape[0] // n_dev
    shards = sorted(
        out.addressable_shards, key=lambda s: s.index[0].start or 0
    )
    return jax.make_array_from_single_device_arrays(
        (rows, out.shape[1]),
        NamedSharding(_ring_mesh(devices), P(None, None)),
        [s.data for s in shards],
    )

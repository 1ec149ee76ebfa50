"""Pallas device-side global shuffle: the epoch exchange on the mesh.

``ThreadExchangeShuffler`` moves the two exchange lanes peer-to-peer on
the HOST — host memcpys and DCN hops on data that is about to be H2D'd
anyway (ROADMAP item 2).  These kernels run the same permutation
exchange ON-DEVICE: each instance's exchange block (lane A + lane B,
``2 * half`` rows) lands once on its ring device, and two remote-DMA
steps move lane A forward along the shared permutation (``i -> p[i]``)
and lane B backward (``i -> pinv[i]``) — byte-identical to the host
rendezvous exchange because both sides derive the permutation from
``exchange_permutation(n, seed, round)`` (ddl_tpu.shuffle).

Kernel shape (the ``ops/ici_fanout.py`` discipline — written for
devices that each run at their own pace):

- **Routes are data.**  The permutation changes every round; baking it
  into the kernel would recompile per round.  The routes array
  ``[p, pinv]`` (2, n) int32 is an SMEM input instead, so one compiled
  program serves every round of a geometry and ``device_id`` is read
  from SMEM per lane.
- **Entry barrier.**  Lane A of device ``i`` lands in ``p[i]``'s output
  buffer and lane B in ``pinv[i]``'s, and a buffer exists only once its
  device has entered the kernel.  The two devices that write into ``i``
  are ``pinv[i]`` and ``p[i]`` — exactly the two it writes into — so
  every device signals both on the barrier semaphore
  (``pltpu.get_barrier_semaphore``, keyed by the per-slot
  ``collective_id``) and waits for two signals before its first send.
  The wait consumes what was signalled, so the semaphore exits at zero.
- **Write-once destinations.**  Each lane slice of every output is
  written by exactly one DMA, on its own semaphore pair; both lanes are
  in flight together and a device leaves only after both of its sends
  drained and both of its lanes landed.
- **Landing slots.**  Round r+1's program can be entered by a fast
  device while a peer is still in round r's, so consecutive rounds
  alternate ``slot`` (a per-slot ``collective_id``, distinct from the
  fan-out's 11-14), exactly like ``fanout_start``/``fanout_wait``.  The
  split surface is :func:`exchange_start` / :func:`exchange_wait`:
  start dispatches the program device-side and returns immediately; the
  wait is the consumer's first use of the value.

Off-TPU the wrappers run the same kernel under Pallas' TPU interpret
mode (simulated devices, remote DMAs, semaphores, barrier — how tier-1
proves byte identity against the host path on the CPU virtual mesh); on
a pod it compiles through Mosaic, ahead of time, so a compiler refusal
is a :class:`~ddl_tpu.exceptions.KernelBuildError` at build.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import lax
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddl_tpu.ops.ici_fanout import (
    AXIS,
    N_SLOTS,
    _check_slot,
    _ring_mesh,
    compile_kernel,
    interpret_arg,
    interpret_default,
    kernel_view,
)
from ddl_tpu.ops.naming import named_pallas_call

#: Mosaic collective ids for the exchange kernel, indexed by landing
#: slot — the id names the barrier semaphore the entry handshake runs
#: on, and must differ from every other collective kernel a device can
#: be ahead of or behind in (the fan-out holds 11-14).
_EXCHANGE_COLLECTIVE_IDS = (15, 16)

#: The two lanes of one exchange round: lane 0 (A) moves along ``p``,
#: lane 1 (B) along ``pinv``.
_N_LANES = 2

_LOGICAL = pltpu.DeviceIdType.LOGICAL


def _exchange_kernel(routes_ref, in_ref, out_ref, send_sem, recv_sem, *,
                     half: int):
    """One exchange round: this device's rows ``[t*half, (t+1)*half)``
    go to device ``routes[t, me]`` and the same lane slice arrives from
    its inverse, for both lanes at once."""
    me = lax.axis_index(AXIS)
    barrier = pltpu.get_barrier_semaphore()

    def lane(t):
        return pltpu.make_async_remote_copy(
            src_ref=in_ref.at[pl.ds(t * half, half)],
            dst_ref=out_ref.at[pl.ds(t * half, half)],
            send_sem=send_sem.at[t],
            recv_sem=recv_sem.at[t],
            device_id=routes_ref[t, me],
            device_id_type=_LOGICAL,
        )

    # Entry barrier: my two targets are also my two writers (see the
    # module docstring) — tell both I am in, wait for both.
    for t in range(_N_LANES):
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=routes_ref[t, me],
            device_id_type=_LOGICAL,
        )
    pltpu.semaphore_wait(barrier, _N_LANES)
    for t in range(_N_LANES):
        lane(t).start()
    for t in range(_N_LANES):
        lane(t).wait_recv()
    for t in range(_N_LANES):
        lane(t).wait_send()


@functools.lru_cache(maxsize=64)
def _exchange_call(devices: Tuple[Any, ...], half: int, cols: int,
                   dtype_name: str, interpret: bool, slot: int = 0):
    """Compiled shard_map'ed exchange over ``devices``: inputs are the
    (2, n) int32 routes (replicated) and the global (n * 2 * half, cols)
    P(x) lane blocks; output has the same global shape with both lanes
    exchanged.  Cached per geometry — the routes are DATA, so every
    round of a geometry reuses one program."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = len(devices)
    mesh = _ring_mesh(devices)
    dtype = np.dtype(dtype_name)
    call = named_pallas_call(
        "ddl_shuffle_exchange",
        functools.partial(_exchange_kernel, half=half),
        out_shape=jax.ShapeDtypeStruct((_N_LANES * half, cols), dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((_N_LANES,))] * 2,
        interpret=interpret_arg(interpret),
        compiler_params=pltpu.CompilerParams(
            collective_id=_EXCHANGE_COLLECTIVE_IDS[slot]
        ),
    )
    fn = shard_map(
        call, mesh=mesh, in_specs=(P(None, None), P(AXIS)),
        out_specs=P(AXIS), check_vma=False,
    )
    spec = NamedSharding(mesh, P(AXIS))
    rspec = NamedSharding(mesh, P(None, None))
    return compile_kernel(
        jax.jit(fn, in_shardings=(rspec, spec), out_shardings=spec),
        jax.ShapeDtypeStruct((_N_LANES, n_dev), np.int32, sharding=rspec),
        jax.ShapeDtypeStruct(
            (n_dev * _N_LANES * half, cols), dtype, sharding=spec
        ),
    )


@functools.lru_cache(maxsize=64)
def _exchange_xla_call(devices: Tuple[Any, ...], half: int, cols: int,
                       dtype_name: str, perm: Tuple[int, ...]):
    """XLA reference variant: two ``lax.ppermute`` lanes over the ring
    mesh (the ``parallel.collectives._build_sendrecv_step`` idiom on the
    producer-side block layout).  Cached per permutation — the A/B
    baseline and the non-Pallas fallback impl."""
    from jax import numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddl_tpu.shuffle import inverse_permutation

    mesh = _ring_mesh(devices)
    p = np.array(perm)
    pinv = inverse_permutation(p)
    fwd = tuple((int(i), int(pi)) for i, pi in enumerate(p))
    bwd = tuple((int(i), int(pi)) for i, pi in enumerate(pinv))

    def shard_fn(block):
        # block: (2 * half, cols) — this instance's lane A + lane B.
        a = lax.ppermute(block[:half], AXIS, fwd)
        b = lax.ppermute(block[half:], AXIS, bwd)
        return jnp.concatenate([a, b], axis=0)

    fn = shard_map(
        shard_fn, mesh=mesh, in_specs=P(AXIS), out_specs=P(AXIS),
        check_vma=False,
    )
    spec = NamedSharding(mesh, P(AXIS))
    return jax.jit(fn, in_shardings=spec, out_shardings=spec)


def as_exchange_input(blocks: Sequence[np.ndarray],
                      devices: Sequence[Any]) -> Any:
    """Land per-instance lane blocks on their ring devices and assemble
    the SPMD global (n * 2 * half, cols) P(x) input — the H2D landing
    edge of the exchange (the host touches the rows exactly once; every
    subsequent hop rides ICI).

    Mosaic only slices HBM along its tiling, so a block whose lanes are
    off it (``ici_fanout.kernel_view``) is landed as its LANE VIEW —
    each exchange lane's elements as ``(R, 128)`` rows padded to whole
    tiles, lane A over lane B — and :func:`exchange_output_blocks`
    restores the shape.  The packing is host-side numpy at an edge the
    host touches anyway; tile-aligned blocks pass through untouched."""
    devices = tuple(devices)
    n_dev = len(devices)
    if len(blocks) != n_dev:
        raise ValueError(
            f"need one lane block per ring device ({n_dev}), got "
            f"{len(blocks)}"
        )
    from jax.sharding import NamedSharding, PartitionSpec as P

    rows, cols = blocks[0].shape
    half = rows // _N_LANES
    krows, kcols = kernel_view(_N_LANES, half, cols, blocks[0].dtype)
    if (krows, kcols) != (rows, cols):
        blocks = [
            np.pad(
                b.reshape(_N_LANES, half * cols),
                ((0, 0), (0, krows // _N_LANES * kcols - half * cols)),
            ).reshape(krows, kcols)
            for b in blocks
        ]
        rows, cols = krows, kcols
    shards = [jax.device_put(b, d) for b, d in zip(blocks, devices)]
    return jax.make_array_from_single_device_arrays(
        (n_dev * rows, cols),
        NamedSharding(_ring_mesh(devices), P(AXIS)),
        shards,
    )


def exchange_output_blocks(out: Any, devices: Sequence[Any],
                           shape: Tuple[int, int]) -> List[np.ndarray]:
    """Fetch the exchanged lane blocks back to the host, one per ring
    position and each of the posted block ``shape`` (undoing
    :func:`as_exchange_input`'s lane view where it applied) — the D2H
    edge where the fabric hands rows back to each producer's private
    pool (the exchange's only other host touch)."""
    devices = tuple(devices)
    n_dev = len(devices)
    rows = out.shape[0] // n_dev
    by_start: Dict[int, Any] = {
        (s.index[0].start or 0): s.data for s in out.addressable_shards
    }
    blocks = [np.asarray(by_start[i * rows]) for i in range(n_dev)]
    if blocks[0].shape != tuple(shape):
        half_elems = shape[0] // _N_LANES * shape[1]
        blocks = [
            b.reshape(_N_LANES, -1)[:, :half_elems].reshape(shape)
            for b in blocks
        ]
    return blocks


def exchange_ring(gin: Any, devices: Sequence[Any], routes: np.ndarray,
                  interpret: Optional[bool] = None, slot: int = 0) -> Any:
    """Run one Pallas ring exchange round over the assembled global
    input.  ``routes`` is the (2, n) int32 ``[p, pinv]`` for this round
    (data, not code — no per-round recompile).  ``slot`` selects the
    landing slot (collective-id pair), as in ``fanout_replicate``."""
    devices = tuple(devices)
    slot = _check_slot(slot)
    n_dev = len(devices)
    if n_dev == 1:
        return gin
    if interpret is None:
        interpret = interpret_default(devices)
    rows = gin.shape[0] // n_dev
    half = rows // _N_LANES
    routes = np.ascontiguousarray(routes, dtype=np.int32)
    if routes.shape != (_N_LANES, n_dev):
        raise ValueError(
            f"routes must be (2, {n_dev}) [p, pinv], got {routes.shape}"
        )
    call = _exchange_call(
        devices, half, gin.shape[1], np.dtype(gin.dtype).name, interpret,
        slot,
    )
    return call(routes, gin)


def exchange_xla(gin: Any, devices: Sequence[Any],
                 perm: Sequence[int]) -> Any:
    """Run one XLA ``ppermute`` exchange round (the A/B baseline and
    the ``shuffle_impl=xla`` path) over the assembled global input."""
    devices = tuple(devices)
    n_dev = len(devices)
    if n_dev == 1:
        return gin
    rows = gin.shape[0] // n_dev
    half = rows // _N_LANES
    call = _exchange_xla_call(
        devices, half, gin.shape[1], np.dtype(gin.dtype).name,
        tuple(int(x) for x in perm),
    )
    return call(gin)


@dataclasses.dataclass(frozen=True)
class ExchangeTicket:
    """A started (dispatched, possibly still in flight) exchange round.

    ``value`` is the kernel output as an ASYNC device value — the ring
    program is enqueued at :func:`exchange_start` and its DMA
    semaphores are hardware-waited, so the exchange hides under
    whatever step is running (the ``FanoutTicket`` discipline: at most
    one in-flight round per ``slot``)."""

    value: Any
    impl: str  #: "ring" | "xla"
    slot: int


def exchange_start(impl: str, gin: Any, devices: Sequence[Any],
                   perm: Sequence[int], *, slot: int = 0,
                   interpret: Optional[bool] = None) -> ExchangeTicket:
    """Start an exchange round into landing slot ``slot``; never waits.

    The start half of the split start/wait surface (the PR-12
    ``fanout_start``/``fanout_wait`` + ``gate_release_on`` protocol):
    the round's ring program is dispatched here and runs under the
    in-flight train step — a shuffle the trainer never waits for.
    Pair with :func:`exchange_wait`."""
    slot = _check_slot(slot)  # fail BEFORE dispatching side effects
    if impl == "ring":
        from ddl_tpu.shuffle import inverse_permutation

        p = np.asarray(perm)  # ddl-lint: disable=DDL016 - scalar-prefetch route table (host metadata), not window rows
        routes = np.stack([p, inverse_permutation(p)]).astype(np.int32)
        out = exchange_ring(
            gin, devices, routes, interpret=interpret, slot=slot
        )
    elif impl == "xla":
        out = exchange_xla(gin, devices, perm)
    else:
        raise ValueError(f"impl must be ring|xla, got {impl!r}")
    return ExchangeTicket(value=out, impl=impl, slot=slot)


def exchange_wait(ticket: ExchangeTicket, sync: bool = False) -> Any:
    """The wait half: the real wait is the DATA DEPENDENCE — the first
    use of the returned value drains the slot's DMA semaphores on
    device.  ``sync=True`` forces a host ``block_until_ready`` (the
    fabric's bring-up/fallback boundary, where an async DMA failure
    must surface inside the degradation ladder rather than at a remote
    consumer's sync point)."""
    if sync:
        jax.block_until_ready(ticket.value)
    return ticket.value


def exchange_wire_bytes(n: int, half: int, cols: int, dtype: Any) -> int:
    """Raw bytes one device round moves over ICI links: two lanes of
    ``half`` rows per device, every device sending each step — the
    honest numerator for per-leg utilization math."""
    if n <= 1 or half < 1:
        return 0
    row = cols * np.dtype(dtype).itemsize
    return _N_LANES * n * half * row


def plan_exchange(n: int, num_exchange: int, cols: int, dtype: Any,
                  wire_dtype: Optional[str] = None,
                  n_devices: Optional[int] = None) -> Dict[str, Any]:
    """Price one exchange round, per leg, device vs host.

    The host path's DCN-tier legs may ride the PR-13 wire
    (``plan_distribution(wire_dtype=)`` composition): its per-row cost
    is the ENCODED row + its scale stripe (``parallel.ici.wire_cols``),
    while the device legs move raw rows over ICI (on-device lossy
    re-quantization would break the exchange's exact byte identity, so
    the device tier only engages on the raw wire).  ``plannable`` is
    the geometry gate the shuffler consults before its first round —
    an unplannable geometry latches the host fallback for the
    shuffler's life (``shuffle.device_fallbacks``)."""
    from ddl_tpu import wire as _wire
    from ddl_tpu.parallel.ici import wire_cols

    dtype = np.dtype(dtype)
    half = num_exchange // 2
    wd = _wire.resolve_wire_dtype(wire_dtype)
    if wd != "raw" and not _wire.lossy_supported(dtype):
        wd = "raw"
    raw_row = cols * dtype.itemsize
    host_row = wire_cols(cols, dtype, wd)
    legs = []
    for lane in ("lane_a", "lane_b"):
        legs.append({
            "leg": lane,
            "rows": n * half,
            "ici_bytes": n * half * raw_row,
            "host_bytes_raw": n * half * raw_row,
            "host_bytes_wire": n * half * host_row,
        })
    plannable = n >= 2 and half >= 1
    why = None
    if n < 2:
        why = "single instance: nothing to exchange"
    elif half < 1:
        why = f"num_exchange {num_exchange} leaves no lane rows"
    if plannable and n_devices is not None and n_devices < n:
        plannable = False
        why = (
            f"ring needs {n} devices for {n} instances, have {n_devices}"
        )
    return {
        "plannable": plannable,
        "why_not": why,
        "n": n,
        "half": half,
        "cols": cols,
        "dtype": dtype.name,
        "wire_dtype": wd,
        "legs": legs,
        "ici_bytes": sum(leg["ici_bytes"] for leg in legs),
        "host_bytes_raw": sum(leg["host_bytes_raw"] for leg in legs),
        "host_bytes_wire": sum(leg["host_bytes_wire"] for leg in legs),
    }

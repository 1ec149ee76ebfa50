"""Sharded training steps: the consumer-side compute fed by the loader.

The reference delegated gradient data-parallelism to user-initialised
``torch.distributed`` DDP outside the library (reference
``tests/run_ddl.py:199-200``, SURVEY §2.3); the TPU-native equivalent is a
jitted train step with NamedSharding annotations — GSPMD inserts the psum
for dp-replicated gradients, the all-gathers for fsdp-sharded params, and
the tp collectives, all riding ICI.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)


def _named(mesh: Any, spec_tree: Any) -> Any:
    """Map a PartitionSpec pytree to NamedShardings, dropping axes the mesh
    doesn't have (so one spec tree serves dp-only and dp×fsdp×tp meshes)."""

    def fix(spec: P) -> NamedSharding:
        parts = []
        for entry in spec:
            if entry is None:
                parts.append(None)
            elif isinstance(entry, (tuple, list)):
                kept = tuple(a for a in entry if a in mesh.axis_names)
                parts.append(kept if kept else None)
            else:
                parts.append(entry if entry in mesh.axis_names else None)
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(
        fix, spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


def _prune_indivisible(sh: NamedSharding, x: Any) -> NamedSharding:
    """Drop spec axes whose mesh size doesn't divide the array dimension
    (e.g. 2 experts on an ep=8 mesh) — the leaf degrades to replicated on
    that dimension instead of failing sharding validation."""
    mesh = sh.mesh
    if len(tuple(sh.spec)) > np.ndim(x):
        raise ValueError(
            f"param spec {sh.spec} has more entries than array rank "
            f"{np.ndim(x)} (shape {np.shape(x)})"
        )
    parts = []
    for dim_size, entry in zip(
        np.shape(x), tuple(sh.spec) + (None,) * len(np.shape(x))
    ):
        axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
        n = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        keep = n > 1 and dim_size % n == 0
        if axes and n > 1 and not keep:
            logger.warning(
                "param spec axis %r (size %d) does not divide dim %d of "
                "shape %s — that dimension degrades to REPLICATED (memory "
                "cost: full copy per device group)",
                entry, n, dim_size, np.shape(x),
            )
        parts.append(entry if keep else None)
    return NamedSharding(mesh, P(*parts))


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: int = 0


#: Valid ``optimizer_sharding`` values for the step factories.
OPTIMIZER_SHARDING = ("none", "zero1")


def _maybe_shard_optimizer(
    optimizer: Any,
    mesh: Any,
    param_spec_tree: Any,
    optimizer_sharding: str,
    grad_comm: str,
    stochastic_rounding: bool,
    grad_comm_block: int,
) -> Any:
    """Wrap ``optimizer`` in the distributed-optimizer subsystem when the
    config asks for it (``ddl_tpu.parallel.optimizer``): ``"zero1"``
    shards state + weight update over dp; ``grad_comm="int8"`` alone
    applies only the quantized wire format.  An already-wrapped
    ShardedOptimizer passes through untouched (make_multistep wraps once
    and reuses the instance for its inner make_train_step)."""
    from ddl_tpu.parallel.optimizer import ShardedOptimizer

    if optimizer_sharding not in OPTIMIZER_SHARDING:
        raise ValueError(
            f"optimizer_sharding must be one of {OPTIMIZER_SHARDING}, "
            f"got {optimizer_sharding!r}"
        )
    if isinstance(optimizer, ShardedOptimizer):
        return optimizer
    if optimizer_sharding == "none" and grad_comm == "fp32":
        return optimizer
    return ShardedOptimizer(
        optimizer,
        mesh,
        param_spec_tree,
        axis="dp" if optimizer_sharding == "zero1" else None,
        grad_comm=grad_comm,
        stochastic_rounding=stochastic_rounding,
        block=grad_comm_block or None,
    )


def _lead_extent(mesh: Any, batch_spec: P) -> int:
    """Mesh extent sharding the batch's LEADING axis (1 if unsharded)."""
    entry = tuple(batch_spec)[0] if tuple(batch_spec) else None
    axes = (entry,) if isinstance(entry, str) else tuple(entry or ())
    ext = 1
    for a in axes:
        if a in mesh.axis_names:
            ext *= mesh.shape[a]
    return ext


def _make_apply_step(loss_fn: Callable[..., jax.Array], optimizer: Any,
                     accum_steps: int = 1, lead_divisor: int = 1):
    """One loss/grad/update/apply step — shared by the single-step and
    multi-step (scan) factories so the update rule cannot diverge.

    ``accum_steps > 1``: gradient accumulation — the batch splits into
    ``accum_steps`` equal microbatches along the leading axis, grads
    average over a ``lax.scan``, and ONE optimizer update applies.  For
    a mean-reduction loss this is mathematically the full-batch step at
    1/``accum_steps`` of the activation memory (the standard trade when
    the global batch does not fit).
    """

    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    def _micro(b: Any) -> Any:
        if b.shape[0] % accum_steps:
            raise ValueError(
                f"batch leading dim {b.shape[0]} is not divisible by "
                f"accum_steps={accum_steps} (microbatches must be equal "
                "for exact accumulation)"
            )
        return b.reshape(
            (accum_steps, b.shape[0] // accum_steps) + b.shape[1:]
        )

    def _grads(params: Any, batch: Any):
        if accum_steps == 1:
            return jax.value_and_grad(loss_fn)(params, batch)

        lead = jax.tree.leaves(batch)[0].shape[0]
        if lead % accum_steps == 0 and (lead // accum_steps) % lead_divisor:
            # Not incorrect, but the dp split silently degrades: GSPMD
            # pads/reshards each microbatch inside the scan.  (Checked once
            # per trace, not per batch leaf.)
            logger.warning(
                "gradient accumulation: microbatch size %d is not "
                "divisible by the batch-sharding extent %d — per-"
                "microbatch data parallelism degrades to padding/"
                "resharding", lead // accum_steps, lead_divisor,
            )
        micro = jax.tree.map(_micro, batch)

        # Accumulate in fp32 regardless of the params dtype: with bf16
        # params, summing accum_steps bf16 grads rounds at every add and
        # the "mathematically the full-batch step" equivalence degrades.
        # Grads cast back to the param dtype after the 1/accum_steps scale
        # so the optimizer sees the same dtypes as the unaccumulated path.
        def acc_dtype(p: Any) -> Any:
            d = jnp.result_type(p)
            return jnp.float32 if jnp.issubdtype(d, jnp.inexact) else d

        def body(carry, mb):
            loss_acc, grads_acc = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, mb)
            return (
                loss_acc + loss,
                jax.tree.map(
                    lambda a, g: a + g.astype(a.dtype), grads_acc, grads
                ),
            ), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, acc_dtype(p)), params
        )
        (loss_sum, grads_sum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro
        )
        inv = 1.0 / accum_steps
        return loss_sum * inv, jax.tree.map(
            lambda g, p: (g * inv).astype(jnp.result_type(p)), grads_sum,
            params,
        )

    def apply_step(params: Any, opt_state: Any, batch: Any):
        import optax

        from ddl_tpu.ops.naming import scope

        loss, grads = _grads(params, batch)
        with scope("ddl.optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return apply_step


def _reshard(batch: Any, sh: Any) -> Any:
    # device_put reshards device-resident arrays on-device and uploads
    # host arrays — no host round trip in either case.
    return jax.tree.map(
        lambda b: b
        if isinstance(b, jax.Array) and b.sharding == sh
        else jax.device_put(b, sh),
        batch,
    )


def make_train_step(
    loss_fn: Callable[..., jax.Array],
    optimizer: Any,
    mesh: Any,
    param_spec_tree: Any,
    batch_spec: P = P(("dp",)),
    donate: bool = True,
    accum_steps: int = 1,
    optimizer_sharding: str = "none",
    grad_comm: str = "fp32",
    stochastic_rounding: bool = False,
    grad_comm_block: int = 0,
) -> Tuple[Callable[..., Any], Callable[..., TrainState]]:
    """Build (init_fn, step_fn) for a sharded training loop.

    - ``loss_fn(params, batch) -> scalar`` — pure; model/config closed over.
    - ``optimizer`` — an optax GradientTransformation.
    - ``param_spec_tree`` — PartitionSpecs matching the params pytree
      (axes absent from ``mesh`` are dropped, see :func:`_named`).
    - ``batch_spec`` — sharding of each batch leaf (default: dp over the
      leading axis; pass ``P(("dp",), "sp")`` for sequence-parallel token
      batches).
    - ``accum_steps`` — gradient accumulation: grads average over this
      many microbatches (leading-axis split) before ONE optimizer update
      (see :func:`_make_apply_step`); mathematically the full-batch step
      at a fraction of the activation memory.
    - ``optimizer_sharding`` — ``"zero1"`` shards the optimizer state and
      weight update over the dp axis (ZeRO-1;
      :class:`ddl_tpu.parallel.optimizer.ShardedOptimizer` — bit-exact
      vs replicated at fp32, ~dp× less state HBM); ``grad_comm="int8"``
      opts the gradient/update communication into the quantized wire
      format (gate with the loss-parity check; ``stochastic_rounding`` /
      ``grad_comm_block`` tune it).  All four mirror
      :class:`ddl_tpu.config.TrainConfig` fields.

    GSPMD derives every collective from these annotations; there is no
    hand-written psum anywhere.
    """
    optimizer = _maybe_shard_optimizer(
        optimizer, mesh, param_spec_tree, optimizer_sharding, grad_comm,
        stochastic_rounding, grad_comm_block,
    )
    param_sh = _named(mesh, param_spec_tree)
    batch_sh = _named(mesh, batch_spec)
    apply_step = _make_apply_step(
        loss_fn, optimizer, accum_steps, _lead_extent(mesh, batch_spec)
    )

    def init_fn(params: Any) -> TrainState:
        # Jitted identity, NOT device_put: device_put aliases buffers that
        # already live on a target device (e.g. replicated specs), and the
        # donated train step would then delete the caller's input tree.
        # A compiled copy guarantees fresh buffers the step may donate.
        sh = jax.tree.map(_prune_indivisible, param_sh, params)
        params = jax.jit(lambda t: t, out_shardings=sh)(params)
        # optax states are built leaf-wise from params (zeros_like etc.), so
        # moments inherit the param shardings — fsdp shards the optimizer
        # state for free (the ZeRO property).  Leaves NOT derived from
        # params (adam's scalar step count) come out pinned to one device;
        # reshard those to mesh-replicated so the whole state lives on one
        # device set (mixed sets break jit after checkpoint restore).
        opt_state = optimizer.init(params)
        replicated = NamedSharding(mesh, P())

        def on_mesh(x: Any) -> Any:
            sh = getattr(x, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh == mesh:
                return x
            return jax.device_put(x, replicated)

        opt_state = jax.tree.map(on_mesh, opt_state)
        return TrainState(params=params, opt_state=opt_state, step=0)

    donate_argnums = (0, 1) if donate else ()

    _step = functools.partial(jax.jit, donate_argnums=donate_argnums)(
        apply_step
    )

    def step_fn(state: TrainState, batch: Any) -> Tuple[TrainState, jax.Array]:
        batch = _reshard(batch, batch_sh)
        params, opt_state, loss = _step(state.params, state.opt_state, batch)
        return TrainState(params, opt_state, state.step + 1), loss

    return init_fn, step_fn


def make_multistep(
    loss_fn: Callable[..., jax.Array],
    optimizer: Any,
    mesh: Any,
    param_spec_tree: Any,
    batch_spec: P = P(("dp",)),
    n_steps: int = 8,
    donate: bool = True,
    accum_steps: int = 1,
    optimizer_sharding: str = "none",
    grad_comm: str = "fp32",
    stochastic_rounding: bool = False,
    grad_comm_block: int = 0,
) -> Tuple[Callable[..., Any], Callable[..., Tuple[TrainState, jax.Array]]]:
    """Like :func:`make_train_step`, but each call runs ``n_steps``
    optimizer steps chained in ONE jitted program (``lax.scan``).
    ``accum_steps`` applies per optimizer step, as in
    :func:`make_train_step`; the distributed-optimizer knobs
    (``optimizer_sharding`` / ``grad_comm`` / ``stochastic_rounding`` /
    ``grad_comm_block``) wrap the optimizer ONCE here and the wrapped
    instance serves both the init path and every scanned step.

    One dispatch per ``n_steps`` steps: the per-call host cost
    amortises away, and the steps are serialized by the params data
    dependence — so wall time per step is the true device time, which is
    also why the benchmark uses this for its timing (a python-loop
    measurement can under-report arbitrarily when ``block_until_ready``
    fails to cover the full async chain).

    ``multi_step_fn(state, batch, per_step=False) -> (state,
    losses[n_steps])``; with ``per_step=True`` every batch leaf carries a
    leading ``n_steps`` axis (one batch per step), otherwise the single
    batch is reused by every step.
    """
    optimizer = _maybe_shard_optimizer(
        optimizer, mesh, param_spec_tree, optimizer_sharding, grad_comm,
        stochastic_rounding, grad_comm_block,
    )
    init_fn, _ = make_train_step(
        loss_fn, optimizer, mesh, param_spec_tree, batch_spec=batch_spec
    )
    apply_step = _make_apply_step(
        loss_fn, optimizer, accum_steps, _lead_extent(mesh, batch_spec)
    )
    batch_sh = _named(mesh, batch_spec)
    per_step_sh = _named(mesh, P(*((None,) + tuple(batch_spec))))

    @functools.partial(
        jax.jit,
        donate_argnums=(0, 1) if donate else (),
        static_argnums=(3,),
    )
    def _run(params: Any, opt_state: Any, batch: Any, per_step: bool):
        def body(carry, xs):
            params, opt_state = carry
            params, opt_state, loss = apply_step(
                params, opt_state, xs if per_step else batch
            )
            return (params, opt_state), loss

        xs = batch if per_step else None
        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), xs, length=n_steps
        )
        return params, opt_state, losses

    def multi_step_fn(state: TrainState, batch: Any, per_step: bool = False):
        batch = _reshard(batch, per_step_sh if per_step else batch_sh)
        params, opt_state, losses = _run(
            state.params, state.opt_state, batch, per_step
        )
        return TrainState(params, opt_state, state.step + n_steps), losses

    return init_fn, multi_step_fn

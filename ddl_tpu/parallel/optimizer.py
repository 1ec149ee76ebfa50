"""Distributed optimizer: ZeRO-1 cross-replica weight-update sharding.

The train step's optimizer state was fully replicated across the ``dp``
axis — at adamw that is 2× the params in moments PER REPLICA, the single
biggest HBM waste left in the training hot path (the 1.39B bench model:
params+moments in bf16 = 7.8 GiB replicated per chip).  This
module is the cross-replica sharding of the weight update from
*Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training* (arXiv:2004.13336), realised the GSPMD-native way:

- **state sharding**: :meth:`ShardedOptimizer.init` pins a ``dp``-sharded
  view of the params inside the init program, so every param-derived
  state leaf (adam moments) comes out sharded ``dp`` × whatever the
  param spec already shards (fsdp/tp/pp compose for free — the zero1
  spec only ADDS the dp axis to a dividing dimension).
- **reduce-scatter**: :meth:`update` constrains the (GSPMD-reduced)
  grads to the same dp-sharded layout; XLA's SPMD partitioner
  canonicalises all-reduce + slice into a reduce-scatter, which is
  exactly the compiler transformation the paper describes.
- **shard-local update**: the inner optax transformation runs on 1/dp of
  every leaf.
- **all-gather**: the updates are constrained back to the param layout
  (gathering the UPDATE rather than the updated params is the
  optax-shaped equivalent — ``apply_updates`` adds the gathered update
  to the dp-replicated params).  With ``grad_comm="int8"`` the gather
  moves the EQuARX wire format for real: the update shard quantizes to
  int8 + fp32 block scales (``parallel.collectives``), the sharding
  constraint gathers the INT8 payload (visible as an s8 all-gather in
  the compiled HLO), and the dequantize runs replica-local — a ~3.9×
  cut of the gather leg's bytes.  The reduce leg's quantization applies
  the same wire numerics to the sharded grads (the explicit-collective
  form is :func:`~ddl_tpu.parallel.collectives.quantized_all_reduce`,
  for shard_map contexts); the loss-curve-parity gate
  (:func:`loss_parity`) is what licenses the int8 path.

Observability (``opt.*`` family → ``north_star_report`` → the bench
``opt`` block): ``opt.state_bytes_per_replica`` /
``opt.state_bytes_total`` gauges (set at init from the REAL placed
state), ``opt.grad_comm_bytes_raw`` / ``opt.grad_comm_bytes_quantized``
per-step gauges (set at trace time, the pp.bubble pattern), and the
``opt.gather`` / ``opt.scatter`` timers (:meth:`measure_legs`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

#: Relative loss-drift tolerance of the int8 grad-comm parity gate: the
#: quantized run's loss curve must stay within this of the fp32 curve
#: at every compared step.  2e-2 is ~4× the drift measured on the bench
#: geometry (tests pin the measured margin), so a real numerics
#: regression trips it while rounding noise does not.
PARITY_REL_TOL = 2e-2

_VALID_GRAD_COMM = ("fp32", "int8")


def _axes_of(entry: Any) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def zero1_sharding(named_sh: Any, shape: Any, axis: str = "dp") -> Any:
    """The dp-extended NamedSharding of one param leaf.

    Adds ``axis`` to the first dimension it divides (on top of whatever
    the spec already shards there); leaves already sharded over ``axis``
    pass through, and a leaf no dimension of which divides stays
    replicated over ``axis`` (scalars, odd-shaped norms on huge meshes)
    — correctness never depends on the extension, only the memory win
    does.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = named_sh.mesh
    if axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return named_sh
    n_axis = mesh.shape[axis]
    spec = tuple(named_sh.spec)
    parts = list(spec) + [None] * (len(shape) - len(spec))
    if any(axis in _axes_of(e) for e in parts):
        return named_sh
    for i, dim in enumerate(shape):
        axes = _axes_of(parts[i])
        n = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if dim % (n * n_axis) == 0:
            parts[i] = axes + (axis,)
            return NamedSharding(mesh, P(*parts))
    return named_sh


def _tree_bytes(tree: Any) -> int:
    import jax

    return sum(
        int(np.prod(np.shape(x)) or 1) * np.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(tree)
    )


def _spec_extent(sh: Any, shape: Any, axis: Optional[str] = None) -> int:
    """Devices a leaf is split over (all spec axes, or just ``axis``)."""
    mesh = sh.mesh
    ext = 1
    for i, entry in enumerate(tuple(sh.spec)[: len(shape)]):
        for a in _axes_of(entry):
            if axis is None or a == axis:
                ext *= mesh.shape[a]
    return ext


def state_bytes_per_replica(state: Any, axis: str = "dp") -> int:
    """Optimizer-state bytes STORED per data-parallel replica: each
    leaf's bytes divided by the extent of ``axis`` in its placed
    sharding (1 where the leaf is dp-replicated).  Under zero1 the
    param-derived leaves carry ``axis``, so this shrinks ~dp×."""
    import jax
    from jax.sharding import NamedSharding

    total = 0
    for leaf in jax.tree.leaves(state):
        nbytes = int(np.prod(np.shape(leaf)) or 1) * np.dtype(
            leaf.dtype
        ).itemsize
        sh = getattr(leaf, "sharding", None)
        ext = (
            _spec_extent(sh, np.shape(leaf), axis)
            if isinstance(sh, NamedSharding)
            else 1
        )
        total += nbytes // ext
    return total


class ShardedOptimizer:
    """optax-compatible wrapper: ZeRO-1 state/update sharding over dp.

    ``ShardedOptimizer(inner, mesh, param_spec_tree)`` exposes the optax
    ``init``/``update`` interface, so it drops into
    :func:`ddl_tpu.parallel.train.make_train_step` /
    :func:`~ddl_tpu.parallel.train.make_multistep` (which wrap
    automatically from ``optimizer_sharding="zero1"``) and anything else
    that speaks GradientTransformation.  ``update`` MUST run inside the
    caller's jit (the constraints are trace-time annotations).

    - ``axis``: the replica axis to shard over (default ``"dp"``); a
      mesh without it (or extent 1) makes the wrapper an exact pass-
      through (modulo ``grad_comm``).
    - ``grad_comm``: ``"fp32"`` (exact) or ``"int8"`` (EQuARX wire
      format on the grad reduce + the update gather; gate with
      :func:`loss_parity`).
    - ``stochastic_rounding``: the int8 path rounds stochastically —
      unbiased in expectation, deterministic per step (each leaf's key
      folds ``seed`` ⊕ phase ⊕ leaf index ⊕ the bits of the leaf's
      first element, so successive steps draw fresh randomness without
      an extra key leaf changing the checkpoint tree).
    """

    def __init__(
        self,
        inner: Any,
        mesh: Any,
        param_spec_tree: Any,
        axis: Optional[str] = "dp",
        grad_comm: str = "fp32",
        stochastic_rounding: bool = False,
        block: Optional[int] = None,
        seed: int = 0,
    ):
        from ddl_tpu.parallel.collectives import QUANT_BLOCK

        if grad_comm not in _VALID_GRAD_COMM:
            raise ValueError(
                f"grad_comm must be one of {_VALID_GRAD_COMM}, "
                f"got {grad_comm!r}"
            )
        self._inner = inner
        self.mesh = mesh
        self.spec_tree = param_spec_tree
        self.axis = axis
        self.grad_comm = grad_comm
        self.stochastic_rounding = bool(stochastic_rounding)
        self.block = int(block or QUANT_BLOCK)
        self.seed = int(seed)
        # axis=None: the wrapper applies ONLY the grad_comm wire format
        # (the optimizer_sharding="none", grad_comm="int8" combination).
        self.active = (
            axis is not None
            and axis in mesh.axis_names
            and mesh.shape[axis] > 1
        )
        self.n_replicas = mesh.shape[axis] if self.active else 1

    # -- sharding resolution ------------------------------------------------

    def _shardings(self, tree: Any) -> Tuple[Any, Any]:
        """(param shardings, zero1 shardings) for a params-shaped tree —
        resolved from the spec tree + the tree's (possibly traced)
        shapes, so concrete init and traced update agree exactly."""
        import jax

        from ddl_tpu.parallel.train import _named, _prune_indivisible

        param_sh = jax.tree.map(
            _prune_indivisible, _named(self.mesh, self.spec_tree), tree
        )
        z1_sh = jax.tree.map(
            lambda sh, x: zero1_sharding(sh, np.shape(x), self.axis),
            param_sh,
            tree,
        )
        return param_sh, z1_sh

    @staticmethod
    def _constrain(tree: Any, sh_tree: Any) -> Any:
        import jax

        return jax.tree.map(
            jax.lax.with_sharding_constraint, tree, sh_tree
        )

    # -- optax interface ----------------------------------------------------

    def _state_out_shardings(self, params: Any, z1_sh: Any) -> Any:
        """zero1 shardings for the whole optimizer-state tree, matched
        by KEY PATH: optax states embed param-shaped subtrees (adam's
        ``mu``/``nu`` are ``tree.map``s over params), so a state leaf
        whose path ends with a param's path (longest suffix wins, shape
        must agree) IS that param's moment and takes its zero1 sharding;
        everything else (adam's scalar count) pins mesh-replicated.

        Explicit out_shardings rather than GSPMD propagation from a
        constrained input: the moments are ``zeros_like`` CONSTANTS with
        no data dependence on the params, so propagation into them is
        shape-dependent luck (observed: one geometry sharded, another
        fully replicated).
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax.tree_util import (
            tree_flatten_with_path,
            tree_unflatten,
        )

        p_flat, _ = tree_flatten_with_path(params)
        sh_leaves = jax.tree.leaves(
            z1_sh, is_leaf=lambda x: isinstance(x, NamedSharding)
        )
        by_path = {
            tuple(path): (np.shape(leaf), sh)
            for (path, leaf), sh in zip(p_flat, sh_leaves)
        }
        replicated = NamedSharding(self.mesh, P())
        state_shapes = jax.eval_shape(self._inner.init, params)
        s_flat, treedef = tree_flatten_with_path(state_shapes)
        out = []
        for path, leaf in s_flat:
            path = tuple(path)
            sh = replicated
            for start in range(len(path)):  # longest suffix first
                hit = by_path.get(path[start:])
                if hit is not None and hit[0] == tuple(leaf.shape):
                    sh = hit[1]
                    break
            out.append(sh)
        return tree_unflatten(treedef, out)

    def init(self, params: Any) -> Any:
        """Inner init compiled with explicit zero1 out_shardings
        (:meth:`_state_out_shardings`) — every param-derived state leaf
        (adam moments) lands dp-sharded on the mesh, scalars land
        mesh-replicated, so the whole state lives on one device set
        (mixed sets break donation/restore)."""
        import jax

        if not self.active:
            return self._inner.init(params)
        _, z1_sh = self._shardings(params)
        state = jax.jit(
            self._inner.init,
            out_shardings=self._state_out_shardings(params, z1_sh),
        )(params)
        self._record_state_bytes(state)
        return state

    def update(
        self, grads: Any, state: Any, params: Optional[Any] = None
    ) -> Tuple[Any, Any]:
        """reduce-scatter → shard-local inner update → all-gather.

        Runs under the caller's jit: the constraints are annotations
        GSPMD lowers to the collectives (all-reduce+slice fuses to
        reduce-scatter; the update constraint is the gather).  Traced
        once per compile, which is when the comm-bytes gauges record.
        """
        if not self.active:
            if self.grad_comm == "int8":
                grads = self._quantize_tree(grads, phase=0)
            return self._inner.update(grads, state, params)
        like = params if params is not None else grads
        param_sh, z1_sh = self._shardings(like)
        self._record_comm_bytes(grads)
        grads = self._constrain(grads, z1_sh)  # all-reduce -> reduce-scatter
        if self.grad_comm == "int8":
            # The reduce leg's wire numerics, applied to the shard each
            # replica owns (explicit-collective form: quantized_all_reduce).
            grads = self._quantize_tree(grads, phase=0)
        if params is not None:
            # Weight decay etc. read params: the dp-shard view is a
            # free slice of the replicated leaves.
            params = self._constrain(params, z1_sh)
        updates, state = self._inner.update(grads, state, params)
        if self.grad_comm == "int8":
            updates = self._gather_quantized(updates, param_sh)
        else:
            updates = self._constrain(updates, param_sh)  # all-gather
        return updates, state

    # -- int8 wire format ---------------------------------------------------

    def _leaf_keys(self, tree: Any, phase: int) -> Any:
        """Per-leaf stochastic-rounding keys: seed ⊕ phase ⊕ leaf index
        ⊕ a data-derived fold (the first element's bits) so successive
        steps draw fresh randomness without carrying key state."""
        import jax
        import jax.numpy as jnp

        from jax.tree_util import tree_flatten, tree_unflatten

        leaves, treedef = tree_flatten(tree)
        base = jax.random.PRNGKey(self.seed + 7919 * phase)
        keys = []
        for i, leaf in enumerate(leaves):
            first = jax.lax.bitcast_convert_type(
                jnp.ravel(leaf.astype(jnp.float32))[0], jnp.int32
            ).astype(jnp.uint32)
            keys.append(jax.random.fold_in(jax.random.fold_in(base, i), first))
        return tree_unflatten(treedef, keys)

    def _quantize_tree(self, tree: Any, phase: int) -> Any:
        import jax

        from ddl_tpu.parallel.collectives import quantize_dequantize

        keys = (
            self._leaf_keys(tree, phase)
            if self.stochastic_rounding
            else jax.tree.map(lambda _: None, tree)
        )
        return jax.tree.map(
            lambda x, k: x
            if np.ndim(x) == 0
            else quantize_dequantize(
                x, self.block, stochastic=self.stochastic_rounding, key=k
            ),
            tree,
            keys,
        )

    def _gather_quantized(self, updates: Any, param_sh: Any) -> Any:
        """All-gather the update in the int8 wire format: quantize the
        dp-shard, constrain the INT8 payload (and the tiny fp32 scales)
        to the gathered layout, dequantize replica-local."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ddl_tpu.parallel.collectives import (
            dequantize_blockwise,
            quantize_blockwise,
        )

        replicated = NamedSharding(self.mesh, P())
        keys = (
            self._leaf_keys(updates, phase=1)
            if self.stochastic_rounding
            else jax.tree.map(lambda _: None, updates)
        )

        def one(u: Any, sh: Any, k: Any) -> Any:
            if np.ndim(u) == 0:
                return jax.lax.with_sharding_constraint(u, replicated)
            q, s = quantize_blockwise(
                u, self.block, stochastic=self.stochastic_rounding, key=k
            )
            # q keeps u's shape: the param sharding applies verbatim and
            # the gather moves s8 elements.  The barrier pins the int8
            # materialization — the values are round+clip exact, so the
            # algebraic simplifier would otherwise cancel the
            # f32->s8->f32 convert pair and the all-gather would silently
            # ride fp32 again (observed on the CPU backend).  Scales are
            # 1/block of the payload; gather them replicated.
            q = jax.lax.optimization_barrier(q)
            q = jax.lax.with_sharding_constraint(q, sh)
            s = jax.lax.with_sharding_constraint(s, replicated)
            return dequantize_blockwise(q, s, u.dtype, self.block)

        return jax.tree.map(one, updates, param_sh, keys)

    # -- observability ------------------------------------------------------

    def _record_state_bytes(self, state: Any) -> None:
        from ddl_tpu.observability import metrics as default_metrics

        m = default_metrics()
        m.set_gauge("opt.state_bytes_total", float(_tree_bytes(state)))
        m.set_gauge(
            "opt.state_bytes_per_replica",
            float(state_bytes_per_replica(state, self.axis)),
        )

    def _record_comm_bytes(self, grads: Any) -> None:
        # Trace-time (once per compile), like pipeline_apply's pp.*
        # gauges: per-step LOGICAL payload of the two collective legs
        # (reduce-scatter of grads + all-gather of updates).  Shapes are
        # static under trace, so these are plain Python ints.
        import jax

        from ddl_tpu.observability import metrics as default_metrics
        from ddl_tpu.parallel.collectives import quantized_bytes

        raw = 2 * _tree_bytes(grads)
        quant = 2 * sum(
            quantized_bytes(np.shape(g), self.block)
            if np.ndim(g) > 0
            else int(np.dtype(g.dtype).itemsize)
            for g in jax.tree.leaves(grads)
        )
        m = default_metrics()
        m.set_gauge("opt.grad_comm_bytes_raw", float(raw))
        m.set_gauge(
            "opt.grad_comm_bytes_quantized",
            float(quant if self.grad_comm == "int8" else raw),
        )

    def measure_legs(
        self, params: Any, metrics: Optional[Any] = None, trials: int = 3
    ) -> Dict[str, float]:
        """Measured wall time of the two collective legs on a params-
        sized tree: ``gather`` (dp-shard → param layout — the all-gather
        the update pays every step) and ``scatter`` (param layout →
        dp-shard — the slice half of the fused reduce-scatter).  Runs
        its own tiny jitted programs outside the train step (per-leg
        timers cannot be read out of one fused jit); records into the
        ``opt.gather`` / ``opt.scatter`` timers.
        """
        import time

        import jax

        from ddl_tpu.observability import metrics as default_metrics

        m = metrics or default_metrics()
        if not self.active:
            return {"gather_s": 0.0, "scatter_s": 0.0}
        param_sh, z1_sh = self._shardings(params)
        shard = jax.jit(lambda t: t, out_shardings=z1_sh)(params)
        gather = jax.jit(lambda t: t, out_shardings=param_sh)
        scatter = jax.jit(lambda t: t, out_shardings=z1_sh)
        full = jax.block_until_ready(gather(shard))  # compile
        jax.block_until_ready(scatter(full))
        out = {}
        for name, fn, arg in (
            ("gather", gather, shard),
            ("scatter", scatter, full),
        ):
            best = float("inf")
            for _ in range(trials):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(arg))
                best = min(best, time.perf_counter() - t0)
            m.add_time(f"opt.{name}", best)
            out[f"{name}_s"] = best
        return out


# -- HBM accounting ----------------------------------------------------------


@dataclasses.dataclass
class HbmAccount:
    """Per-device HBM bytes of the persistent training residents."""

    param_bytes: int
    grad_bytes: int
    opt_state_bytes: int

    @property
    def total_bytes(self) -> int:
        return self.param_bytes + self.grad_bytes + self.opt_state_bytes


def hbm_accounting(
    shape_tree: Any,
    spec_tree: Any,
    mesh_axes: Dict[str, int],
    optimizer_sharding: str = "none",
    axis: str = "dp",
    moments_per_param: int = 2,
) -> HbmAccount:
    """Analytic per-device bytes for params + grads + optimizer state.

    Pure shape/spec arithmetic over an ``eval_shape`` tree (e.g. a
    model's ``param_shapes(cfg)``) and a mesh-shape dict — NO devices
    needed, so a v5e-32 layout prices on a laptop (the
    fits-only-with-zero1 test).  Mirrors ``_prune_indivisible``: a spec
    axis only shards a dimension it divides.  ``moments_per_param``:
    adam keeps 2 param-shaped fp-moment leaves (adamw too); SGD+momentum
    is 1.  Moments price at each leaf's own dtype (optax zeros_like).

    Transient peaks (activations, collective scratch) are deliberately
    out of scope — this accounts the residents whose footprint the
    optimizer-sharding decision controls.
    """

    def shard_extent(spec: Any, shape: Any, extra_axis: bool) -> int:
        parts = list(tuple(spec)) + [None] * (len(shape) - len(tuple(spec)))
        ext = 1
        extra_placed = not extra_axis
        for i, dim in enumerate(shape):
            axes = tuple(
                a for a in _axes_of(parts[i]) if mesh_axes.get(a, 1) > 1
            )
            n = int(np.prod([mesh_axes[a] for a in axes])) if axes else 1
            if n > 1 and dim % n == 0:
                ext *= n
            else:
                n = 1  # degrades replicated, as _prune_indivisible would
            if not extra_placed and dim % (n * mesh_axes.get(axis, 1)) == 0:
                ext *= mesh_axes.get(axis, 1)
                extra_placed = True
        return ext

    import jax
    from jax.sharding import PartitionSpec as P

    leaves = jax.tree.leaves(shape_tree)
    specs = [
        s if isinstance(s, P) else P()
        for s in jax.tree.leaves(
            spec_tree, is_leaf=lambda x: x is None or isinstance(x, P)
        )
    ]
    if len(leaves) != len(specs):
        raise ValueError(
            f"shape tree has {len(leaves)} leaves but spec tree {len(specs)}"
        )
    zero1 = optimizer_sharding == "zero1"
    if optimizer_sharding not in ("none", "zero1"):
        raise ValueError(
            f"optimizer_sharding must be 'none' or 'zero1', "
            f"got {optimizer_sharding!r}"
        )
    p_bytes = g_bytes = o_bytes = 0
    for leaf, spec in zip(leaves, specs):
        shape = tuple(leaf.shape)
        nbytes = int(np.prod(shape) or 1) * np.dtype(leaf.dtype).itemsize
        base = shard_extent(spec, shape, extra_axis=False)
        p_bytes += nbytes // base
        g_bytes += nbytes // base
        z1 = shard_extent(spec, shape, extra_axis=True) if zero1 else base
        o_bytes += moments_per_param * (nbytes // z1)
    return HbmAccount(p_bytes, g_bytes, o_bytes)


# -- the parity gate ---------------------------------------------------------


def loss_parity(
    ref_losses: Any, test_losses: Any, rel_tol: float = PARITY_REL_TOL
) -> Dict[str, Any]:
    """THE loss-curve-parity gate the int8 path is licensed by.

    Compares two per-step loss sequences (same init, same batches) and
    returns ``{"parity": bool, "max_rel_drift": float, "rel_tol": ...}``
    — parity holds when every step's relative drift stays under
    ``rel_tol``.  ``tests/test_optimizer.py::TestZero1Parity`` pins the
    fp32 zero1 path to max_rel_drift == 0.0 (bit-exact) and holds the
    int8 path inside this gate with non-zero drift.
    """
    ref = np.asarray(ref_losses, dtype=np.float64)
    test = np.asarray(test_losses, dtype=np.float64)
    if ref.shape != test.shape:
        raise ValueError(
            f"loss curves differ in length: {ref.shape} vs {test.shape}"
        )
    denom = np.maximum(np.abs(ref), 1e-12)
    drift = float(np.max(np.abs(test - ref) / denom)) if ref.size else 0.0
    return {
        "parity": bool(drift <= rel_tol),
        "max_rel_drift": drift,
        "rel_tol": float(rel_tol),
    }

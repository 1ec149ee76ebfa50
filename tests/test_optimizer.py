"""Distributed optimizer (ISSUE 8): ZeRO-1 state/update sharding parity,
the quantized wire format, and the fits-only-with-zero1 HBM accounting.

The load-bearing claims, each pinned here on the 8-device virtual mesh:

- fp32 zero1 is BIT-EXACT vs the replicated optimizer (the update is
  elementwise, so reduce-scatter → shard-local update → all-gather
  computes the same bits), composed with fsdp AND with the pp pipeline;
- the placed optimizer state really shrinks ~dp× per replica;
- int8 grad comm stays inside the loss-parity gate and its all-gather
  genuinely moves s8 elements (asserted in compiled HLO);
- the quantized all-reduce collective matches psum-mean within the
  blockwise-rounding bound, and stochastic rounding is unbiased;
- a ≥4B llama config fits a v5e-32 chip's HBM ONLY with zero1 (pure
  eval_shape/spec arithmetic — no chip, no weights materialised).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map
from ddl_tpu.models import llama
from ddl_tpu.parallel.collectives import (
    QUANT_BLOCK,
    dequantize_blockwise,
    quantize_blockwise,
    quantize_dequantize,
    quantized_all_reduce,
    quantized_bytes,
)
from ddl_tpu.parallel.mesh import make_mesh
from ddl_tpu.parallel.optimizer import (
    PARITY_REL_TOL,
    ShardedOptimizer,
    hbm_accounting,
    loss_parity,
    state_bytes_per_replica,
    zero1_sharding,
)
from ddl_tpu.parallel.train import make_multistep, make_train_step

TINY = dict(
    vocab=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq=64,
)


def _tokens(rng, cfg, batch=8, seq=32):
    return (rng.integers(0, cfg.vocab, (batch, seq)).astype(np.int32),)


def _loss_fn(cfg):
    return lambda p, b: llama.next_token_loss(p, b[0], cfg)


def _run_steps(loss_fn, opt, mesh, specs, params, batch, n=8, **kw):
    init_fn, step_fn = make_train_step(
        loss_fn, opt, mesh, specs, batch_spec=P(("dp",)), **kw
    )
    state = init_fn(params)
    losses = []
    for _ in range(n):
        state, loss = step_fn(state, batch)
        losses.append(float(loss))
    return state, losses


# -- the quantized wire format ------------------------------------------------


class TestQuantize:
    def test_roundtrip_error_bound(self, rng):
        x = jnp.asarray(rng.normal(size=(7, 500)).astype(np.float32))
        out = quantize_dequantize(x)
        # Per-block max-abs scaling: error <= scale/2 = max|block|/254.
        err = np.abs(np.asarray(out) - np.asarray(x))
        assert err.max() <= float(jnp.abs(x).max()) / 254 + 1e-7

    def test_scales_shape_and_zero_blocks_exact(self):
        x = jnp.zeros((4, 2 * QUANT_BLOCK + 3))
        q, s = quantize_blockwise(x)
        assert q.shape == x.shape and q.dtype == jnp.int8
        assert s.shape == (4, 3)  # ceil((2B+3)/B)
        out = dequantize_blockwise(q, s, x.dtype)
        assert np.array_equal(np.asarray(out), np.zeros_like(x))

    def test_preserves_dtype(self, rng):
        x = jnp.asarray(rng.normal(size=(32,)), dtype=jnp.bfloat16)
        assert quantize_dequantize(x).dtype == jnp.bfloat16

    def test_stochastic_requires_key_and_is_deterministic_per_key(
        self, rng
    ):
        x = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
        with pytest.raises(ValueError, match="key"):
            quantize_blockwise(x, stochastic=True)
        k = jax.random.PRNGKey(7)
        a = quantize_dequantize(x, stochastic=True, key=k)
        b = quantize_dequantize(x, stochastic=True, key=k)
        assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_stochastic_rounding_is_unbiased(self, rng):
        # E over keys approaches x much closer than any single rounded
        # draw: the averaged error must collapse vs the deterministic
        # one (the property that keeps long accumulations drift-free).
        x = jnp.asarray(
            (rng.normal(size=(2048,)) * 0.01).astype(np.float32)
        )
        det_err = np.abs(
            np.asarray(quantize_dequantize(x)) - np.asarray(x)
        ).mean()
        draws = np.mean(
            [
                np.asarray(
                    quantize_dequantize(
                        x, stochastic=True, key=jax.random.PRNGKey(i)
                    )
                )
                for i in range(64)
            ],
            axis=0,
        )
        sto_err = np.abs(draws - np.asarray(x)).mean()
        assert sto_err < det_err / 3

    def test_quantized_bytes_accounting(self):
        # int8 payload + one fp32 scale per block per row.
        shape = (4, 2 * QUANT_BLOCK + 1)
        assert quantized_bytes(shape) == 4 * (2 * QUANT_BLOCK + 1) + 4 * 4 * 3
        assert quantized_bytes(shape) < 4 * int(np.prod(shape))  # < fp32


class TestQuantizedAllReduce:
    def test_matches_psum_mean_within_bound(self, rng, eight_devices):
        mesh = make_mesh({"dp": 8})
        n = 8
        x = rng.normal(size=(n, 1000)).astype(np.float32)

        def f(xl):
            return quantized_all_reduce(xl[0], "dp", n)[None]

        fn = shard_map(
            f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False,
        )
        out = np.asarray(jax.jit(fn)(x))
        ref = x.mean(0)
        # Every device holds the SAME reduced vector (the all-gather
        # completed), and it matches the exact mean within the two-phase
        # quantization bound (quantize -> sum -> re-quantize).
        for i in range(n):
            assert np.array_equal(out[i], out[0])
        peak = np.abs(ref).max()
        assert np.abs(out[0] - ref).max() <= 0.02 * peak

    def test_unpadded_sizes_and_sum_mode(self, rng, eight_devices):
        mesh = make_mesh({"dp": 8})
        n = 8
        # size not divisible by n*block: the pad/unpad path.
        x = rng.normal(size=(n, 37)).astype(np.float32)

        def f(xl):
            return quantized_all_reduce(xl[0], "dp", n, mean=False)[None]

        fn = shard_map(
            f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
            check_vma=False,
        )
        out = np.asarray(jax.jit(fn)(x))
        ref = x.sum(0)
        assert out.shape == x.shape
        assert np.abs(out[0] - ref).max() <= 0.02 * np.abs(ref).max()

    def test_axis_size_one_is_local_roundtrip(self, rng):
        x = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
        out = quantized_all_reduce(x, "dp", 1)
        # No collective at n=1: just the wire-format numerics.
        assert np.abs(np.asarray(out) - np.asarray(x)).max() <= float(
            jnp.abs(x).max()
        ) / 60
        with pytest.raises(ValueError, match="axis_size"):
            quantized_all_reduce(x, "dp", 0)


# -- zero1 spec derivation ----------------------------------------------------


class TestZero1Sharding:
    def _mesh(self):
        return make_mesh({"dp": 4, "fsdp": 2})

    def test_adds_dp_to_first_dividing_dim(self, eight_devices):
        mesh = self._mesh()
        sh = NamedSharding(mesh, P("fsdp", None))
        out = zero1_sharding(sh, (64, 64))
        assert tuple(out.spec) == (("fsdp", "dp"), None)

    def test_skips_nondividing_dims(self, eight_devices):
        mesh = self._mesh()
        # dim0 (6) not divisible by dp=4; dim1 (64) is.
        out = zero1_sharding(NamedSharding(mesh, P()), (6, 64))
        # Compared as specs: JAX spells a one-axis entry ("dp",) as "dp".
        assert out.spec == P(None, ("dp",))

    def test_nothing_divides_stays_replicated(self, eight_devices):
        mesh = self._mesh()
        out = zero1_sharding(NamedSharding(mesh, P()), (3, 5))
        assert tuple(out.spec) == ()
        out = zero1_sharding(NamedSharding(mesh, P()), ())  # scalar
        assert tuple(out.spec) == ()

    def test_already_dp_sharded_passes_through(self, eight_devices):
        mesh = self._mesh()
        sh = NamedSharding(mesh, P("dp", None))
        assert zero1_sharding(sh, (64, 64)) is sh

    def test_no_dp_axis_is_identity(self, eight_devices):
        mesh = make_mesh({"fsdp": 8})
        sh = NamedSharding(mesh, P("fsdp"))
        assert zero1_sharding(sh, (64,)) is sh


# -- the sharded optimizer on the virtual mesh --------------------------------


class TestZero1Parity:
    """The acceptance matrix: bit-exact fp32 parity zero1↔replicated on
    dp×fsdp AND dp×pp, state shrink ~dp×, bounded int8 drift."""

    def _setup(self, rng):
        cfg = llama.LlamaConfig(**TINY)
        mesh = make_mesh({"dp": 4, "fsdp": 2})
        specs = llama.param_specs(cfg)
        params = llama.init_params(cfg, jax.random.key(0))
        return cfg, mesh, specs, params, _tokens(rng, cfg)

    def test_fp32_bit_exact_on_dp_fsdp(self, rng, eight_devices):
        cfg, mesh, specs, params, batch = self._setup(rng)
        st_r, l_r = _run_steps(
            _loss_fn(cfg), optax.adamw(1e-2), mesh, specs, params, batch
        )
        st_z, l_z = _run_steps(
            _loss_fn(cfg),
            ShardedOptimizer(optax.adamw(1e-2), mesh, specs),
            mesh, specs, params, batch,
        )
        assert l_r == l_z  # bit-exact loss curve, all 8 steps
        gate = loss_parity(l_r, l_z)
        assert gate["parity"] and gate["max_rel_drift"] == 0.0
        for a, b in zip(
            jax.tree.leaves(st_r.params), jax.tree.leaves(st_z.params)
        ):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_state_shards_and_shrinks(self, rng, eight_devices):
        cfg, mesh, specs, params, batch = self._setup(rng)
        opt = ShardedOptimizer(optax.adamw(1e-2), mesh, specs)
        init_fn, _ = make_train_step(
            _loss_fn(cfg), opt, mesh, specs, batch_spec=P(("dp",))
        )
        st_z = init_fn(params)
        init_fn_r, _ = make_train_step(
            _loss_fn(cfg), optax.adamw(1e-2), mesh, specs,
            batch_spec=P(("dp",)),
        )
        st_r = init_fn_r(params)
        # Moment leaves carry dp in their PLACED sharding.
        dp_leaves = [
            leaf
            for leaf in jax.tree.leaves(st_z.opt_state)
            if isinstance(getattr(leaf, "sharding", None), NamedSharding)
            and any(
                "dp" in ((e,) if isinstance(e, str) else tuple(e or ()))
                for e in tuple(leaf.sharding.spec)
            )
        ]
        assert len(dp_leaves) > 0
        per_r = state_bytes_per_replica(st_r.opt_state)
        per_z = state_bytes_per_replica(st_z.opt_state)
        # ~dp× shrink (scalar count + any non-divisible leaf excepted).
        assert per_r / per_z >= 0.7 * mesh.shape["dp"]
        # The trace-time gauge reflects the same measurement.
        from ddl_tpu.observability import metrics as default_metrics

        assert default_metrics().gauge("opt.state_bytes_per_replica") == (
            float(per_z)
        )

    def test_fp32_bit_exact_on_dp_pp(self, rng, eight_devices):
        cfg = llama.LlamaConfig(**{**TINY, "n_layers": 4})
        mesh = make_mesh({"dp": 2, "pp": 4})
        specs = llama.pp_param_specs(cfg)
        params = llama.stage_params(
            llama.init_params(cfg, jax.random.key(0)), 4
        )
        loss = lambda p, b: llama.next_token_loss_pp(  # noqa: E731
            p, b[0], cfg, mesh, n_microbatches=2
        )
        batch = _tokens(rng, cfg)
        st_r, l_r = _run_steps(
            loss, optax.adamw(1e-2), mesh, specs, params, batch
        )
        st_z, l_z = _run_steps(
            loss, ShardedOptimizer(optax.adamw(1e-2), mesh, specs),
            mesh, specs, params, batch,
        )
        assert l_r == l_z
        assert state_bytes_per_replica(
            st_r.opt_state
        ) >= 2 * state_bytes_per_replica(st_z.opt_state) * 0.9
        # The stage-stacked leaves keep pp AND gain dp.
        stage_specs = {
            tuple(leaf.sharding.spec)
            for leaf in jax.tree.leaves(st_z.opt_state)
            if isinstance(getattr(leaf, "sharding", None), NamedSharding)
            and np.ndim(leaf) >= 3
        }
        assert any(
            "pp" in spec and any("dp" in ((e,) if isinstance(e, str)
                                          else tuple(e or ()))
                                 for e in spec)
            for spec in stage_specs
        )

    def test_int8_drift_bounded_and_nonzero(self, rng, eight_devices):
        cfg, mesh, specs, params, batch = self._setup(rng)
        _, l_r = _run_steps(
            _loss_fn(cfg), optax.adamw(1e-2), mesh, specs, params, batch
        )
        _, l_q = _run_steps(
            _loss_fn(cfg),
            ShardedOptimizer(
                optax.adamw(1e-2), mesh, specs, grad_comm="int8"
            ),
            mesh, specs, params, batch,
        )
        gate = loss_parity(l_r, l_q)
        assert gate["parity"], gate  # inside the gate's tolerance...
        assert gate["rel_tol"] == PARITY_REL_TOL
        assert gate["max_rel_drift"] > 0.0  # ...but the path IS engaged

    def test_int8_stochastic_rounding_trains(self, rng, eight_devices):
        cfg, mesh, specs, params, batch = self._setup(rng)
        _, l_r = _run_steps(
            _loss_fn(cfg), optax.adamw(1e-2), mesh, specs, params, batch
        )
        _, l_s = _run_steps(
            _loss_fn(cfg),
            ShardedOptimizer(
                optax.adamw(1e-2), mesh, specs, grad_comm="int8",
                stochastic_rounding=True,
            ),
            mesh, specs, params, batch,
        )
        assert loss_parity(l_r, l_s)["parity"]
        assert l_s[-1] < l_s[0]

    def test_multistep_matches_single_step_zero1(self, rng, eight_devices):
        cfg, mesh, specs, params, batch = self._setup(rng)
        opt = ShardedOptimizer(optax.adamw(1e-2), mesh, specs)
        _, l_single = _run_steps(
            _loss_fn(cfg), opt, mesh, specs, params, batch, n=4
        )
        init_fn, multi_fn = make_multistep(
            _loss_fn(cfg), optax.adamw(1e-2), mesh, specs,
            batch_spec=P(("dp",)), n_steps=4,
            optimizer_sharding="zero1",
        )
        state, losses = multi_fn(init_fn(params), batch)
        assert [float(x) for x in losses] == l_single

    def test_int8_gather_moves_s8_in_compiled_hlo(self, rng, eight_devices):
        """The update all-gather genuinely rides the int8 wire format:
        the compiled program contains s8 all-gathers (the barrier in
        _gather_quantized pins them — without it XLA cancels the
        f32→s8→f32 converts and gathers fp32 again)."""
        cfg = llama.LlamaConfig(**{**TINY, "n_layers": 1})
        mesh = make_mesh({"dp": 8})
        specs = llama.param_specs(cfg)
        params = llama.init_params(cfg, jax.random.key(0))
        opt = ShardedOptimizer(
            optax.adamw(1e-2), mesh, specs, grad_comm="int8"
        )
        init_fn, _ = make_train_step(
            _loss_fn(cfg), opt, mesh, specs, batch_spec=P(("dp",))
        )
        state = init_fn(params)
        batch = _tokens(rng, cfg)

        def step(p, s, b):
            loss, grads = jax.value_and_grad(
                lambda pp: llama.next_token_loss(pp, b[0], cfg)
            )(p)
            updates, s = opt.update(grads, s, p)
            return optax.apply_updates(p, updates), s, loss

        txt = (
            jax.jit(step)
            .lower(state.params, state.opt_state, batch)
            .compile()
            .as_text()
        )
        s8_gathers = [
            ln for ln in txt.splitlines()
            if "all-gather" in ln and "s8[" in ln
        ]
        assert len(s8_gathers) > 0

    def test_measure_legs_records_timers(self, rng, eight_devices):
        from ddl_tpu.observability import Metrics

        cfg, mesh, specs, params, _ = self._setup(rng)
        opt = ShardedOptimizer(optax.adamw(1e-2), mesh, specs)
        m = Metrics()
        legs = opt.measure_legs(params, metrics=m)
        assert legs["gather_s"] > 0 and legs["scatter_s"] > 0
        assert m.timer("opt.gather").count == 1
        assert m.timer("opt.scatter").count == 1

    def test_inactive_on_dp1_mesh(self, rng):
        cfg = llama.LlamaConfig(**TINY)
        mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
        specs = llama.param_specs(cfg)
        opt = ShardedOptimizer(optax.adamw(1e-2), mesh, specs)
        assert not opt.active
        params = llama.init_params(cfg, jax.random.key(0))
        _, losses = _run_steps(
            _loss_fn(cfg), opt, mesh, specs, params,
            _tokens(np.random.default_rng(0), cfg), n=2,
        )
        assert np.isfinite(losses).all()

    def test_validation(self, eight_devices):
        cfg = llama.LlamaConfig(**TINY)
        mesh = make_mesh({"dp": 8})
        specs = llama.param_specs(cfg)
        with pytest.raises(ValueError, match="grad_comm"):
            ShardedOptimizer(
                optax.adamw(1e-2), mesh, specs, grad_comm="fp16"
            )
        with pytest.raises(ValueError, match="optimizer_sharding"):
            make_train_step(
                _loss_fn(cfg), optax.adamw(1e-2), mesh, specs,
                optimizer_sharding="zero3",
            )


# -- HBM accounting -----------------------------------------------------------


class TestHbmAccounting:
    #: v5e per-chip HBM and the v5e-32 layout the accounting prices.
    V5E_HBM = 16 * 2**30
    POD = {"dp": 8, "fsdp": 4}

    def test_4b_fits_only_with_zero1(self):
        """THE acceptance claim: ~4.6B params (fp32 master weights) on
        the v5e-32 layout — persistent residents bust 16 GiB/chip with
        the optimizer state replicated over dp, fit with zero1.  Pure
        eval_shape/spec arithmetic; no weights materialised."""
        cfg = llama.LlamaConfig.llama_4b()
        shapes = llama.param_shapes(cfg)
        specs = llama.param_specs(cfg)
        n_params = sum(
            int(np.prod(l.shape)) for l in jax.tree.leaves(shapes)
        )
        assert n_params >= 4e9
        replicated = hbm_accounting(
            shapes, specs, self.POD, optimizer_sharding="none"
        )
        zero1 = hbm_accounting(
            shapes, specs, self.POD, optimizer_sharding="zero1"
        )
        assert replicated.total_bytes > self.V5E_HBM
        assert zero1.total_bytes < self.V5E_HBM
        # The delta is exactly the moments' dp-sharding win: params and
        # grads price identically under both.
        assert replicated.param_bytes == zero1.param_bytes
        assert replicated.grad_bytes == zero1.grad_bytes
        assert replicated.opt_state_bytes > (
            zero1.opt_state_bytes * (self.POD["dp"] * 0.7)
        )

    def test_accounting_arithmetic_known_case(self):
        """Hand-checkable case: one (64, 64) fp32 leaf sharded
        P('fsdp', None) on dp=4 × fsdp=2."""
        leaf = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        spec = P("fsdp", None)
        mesh_axes = {"dp": 4, "fsdp": 2}
        rep = hbm_accounting([leaf], [spec], mesh_axes, "none")
        z1 = hbm_accounting([leaf], [spec], mesh_axes, "zero1")
        nbytes = 64 * 64 * 4
        assert rep.param_bytes == nbytes // 2  # fsdp only
        assert rep.opt_state_bytes == 2 * nbytes // 2  # 2 moments
        assert z1.opt_state_bytes == 2 * nbytes // 8  # fsdp × dp
        assert z1.param_bytes == rep.param_bytes

    def test_indivisible_axis_degrades_replicated(self):
        # A (6, 5) leaf: fsdp=2 divides dim0, dp=4 divides neither ->
        # zero1 changes nothing (mirrors _prune_indivisible).
        leaf = jax.ShapeDtypeStruct((6, 5), jnp.float32)
        rep = hbm_accounting([leaf], [P("fsdp", None)],
                             {"dp": 4, "fsdp": 2}, "none")
        z1 = hbm_accounting([leaf], [P("fsdp", None)],
                            {"dp": 4, "fsdp": 2}, "zero1")
        assert rep.opt_state_bytes == z1.opt_state_bytes

    def test_rejects_unknown_sharding(self):
        leaf = jax.ShapeDtypeStruct((8,), jnp.float32)
        with pytest.raises(ValueError, match="optimizer_sharding"):
            hbm_accounting([leaf], [P()], {"dp": 2}, "zero2")


# -- the parity gate ----------------------------------------------------------


class TestLossParity:
    def test_exact_curves_pass_with_zero_drift(self):
        out = loss_parity([1.0, 0.5], [1.0, 0.5])
        assert out == {
            "parity": True, "max_rel_drift": 0.0,
            "rel_tol": PARITY_REL_TOL,
        }

    def test_drift_over_tolerance_fails(self):
        out = loss_parity([1.0, 1.0], [1.0, 1.05], rel_tol=0.02)
        assert not out["parity"]
        assert out["max_rel_drift"] == pytest.approx(0.05)

    def test_length_mismatch_raises(self):
        with pytest.raises(ValueError, match="length"):
            loss_parity([1.0], [1.0, 2.0])


# -- config + trainer plumbing ------------------------------------------------


class TestConfigPlumbing:
    def test_train_config_validates_and_splats(self):
        from ddl_tpu.config import TrainConfig

        tc = TrainConfig.load(
            optimizer_sharding="zero1", grad_comm="int8"
        )
        assert tc.optimizer_kwargs() == {
            "optimizer_sharding": "zero1",
            "grad_comm": "int8",
            "grad_comm_block": 0,
            "stochastic_rounding": False,
        }
        with pytest.raises(ValueError, match="optimizer_sharding"):
            TrainConfig.load(optimizer_sharding="zero3")
        with pytest.raises(ValueError, match="grad_comm"):
            TrainConfig.load(grad_comm="fp8")

    def test_env_override(self, monkeypatch):
        from ddl_tpu.config import TrainConfig

        monkeypatch.setenv("DDL_TPU_TRAIN_OPTIMIZER_SHARDING", "zero1")
        monkeypatch.setenv("DDL_TPU_TRAIN_GRAD_COMM", "int8")
        tc = TrainConfig.load()
        assert tc.optimizer_sharding == "zero1"
        assert tc.grad_comm == "int8"

    def test_trainer_zero1_matches_replicated(self, rng, eight_devices):
        """End-to-end plumbing proof: a Trainer built from
        TrainConfig(optimizer_sharding='zero1') trains BIT-IDENTICALLY
        to the replicated Trainer on the same producer stream."""
        from ddl_tpu.config import TrainConfig
        from ddl_tpu.models import pointnet
        from ddl_tpu.readers import ArrayProducer
        from ddl_tpu.trainer import Trainer

        cfg = pointnet.PointNetConfig(n_inputs=3, n_outputs=2)
        data = rng.random((256, 6)).astype(np.float32)

        def fit(train_config):
            return Trainer(
                loss_fn=lambda p, b: pointnet.weighted_mse_loss(
                    p, b, cfg
                ),
                optimizer=optax.adam(1e-2),
                mesh=make_mesh({"dp": 8}),
                param_specs=pointnet.param_specs(cfg),
                init_params=pointnet.init_params(cfg, jax.random.key(0)),
                batch_spec=P(("dp",)),
                train_config=train_config,
            ).fit(
                ArrayProducer(data, window_size=64, splits=(3, 2, 1)),
                batch_size=16, n_epochs=2, n_producers=2,
                mode="thread", output="numpy",
            )

        r_rep = fit(None)
        r_z1 = fit(TrainConfig(optimizer_sharding="zero1"))
        assert r_z1.losses == r_rep.losses
        assert r_z1.losses[-1] < r_z1.losses[0]

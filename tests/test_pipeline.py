"""Pipeline-parallel schedule tests (virtual 8-device mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from ddl_tpu.parallel.mesh import make_mesh
from ddl_tpu.parallel.pipeline import (
    pipeline_apply,
    pipeline_spec,
    stack_stage_params,
)
from ddl_tpu.parallel.train import make_train_step

D = 16


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _stages(rng, n):
    return [
        {
            "w": jnp.asarray(rng.standard_normal((D, D)) / 4, jnp.float32),
            "b": jnp.asarray(rng.standard_normal((D,)) / 4, jnp.float32),
        }
        for _ in range(n)
    ]


def _sequential(stages, x):
    for p in stages:
        x = _stage_fn(p, x)
    return x


def test_pipeline_matches_sequential(rng):
    """pp=4 pipelined output == applying the 4 stages in sequence."""
    stages = _stages(rng, 4)
    stacked = stack_stage_params(stages)
    mesh = make_mesh({"pp": 4, "dp": 2})
    x = jnp.asarray(rng.standard_normal((8, D)), jnp.float32)
    out = pipeline_apply(stacked, x, _stage_fn, mesh, n_microbatches=4)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_sequential(stages, x)), atol=1e-5
    )


def test_pipeline_fallback_no_pp_axis(rng):
    stages = _stages(rng, 3)
    stacked = stack_stage_params(stages)
    mesh = make_mesh({"dp": 8})
    x = jnp.asarray(rng.standard_normal((4, D)), jnp.float32)
    out = pipeline_apply(stacked, x, _stage_fn, mesh, n_microbatches=2)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_sequential(stages, x)), atol=1e-5
    )


def test_pipeline_spec_prepends_pp():
    spec = pipeline_spec({"w": P("fsdp", "tp"), "b": P(None)})
    assert spec["w"] == P("pp", "fsdp", "tp")
    assert spec["b"] == P("pp", None)


def test_bubble_fraction():
    from ddl_tpu.parallel import bubble_fraction

    assert bubble_fraction(1, 4) == 0.0  # no pipe, no bubble
    assert bubble_fraction(4, 4) == 3 / 7
    assert bubble_fraction(4, 28) == 3 / 31  # deep microbatching amortizes
    # 1f1b interleaving divides the per-chunk ramp cost: the ISSUE 5
    # acceptance point — strictly below gpipe's 0.429 at pp=4/M=4.
    assert bubble_fraction(4, 4, schedule="1f1b") == 3 / 11
    assert bubble_fraction(4, 4, schedule="1f1b", n_chunks=4) == 3 / 19
    assert bubble_fraction(4, 4, schedule="1f1b") < bubble_fraction(4, 4)
    import pytest

    with pytest.raises(ValueError):
        bubble_fraction(0, 4)
    with pytest.raises(ValueError):
        bubble_fraction(4, 4, schedule="pipedream")
    with pytest.raises(ValueError):
        bubble_fraction(4, 4, schedule="gpipe", n_chunks=2)


class Test1F1BSchedule:
    """The interleaved (1f1b) schedule: chunk layout, forward AND grad
    equivalence with gpipe/sequential at identical total stages, and
    the microbatch-divisibility contract."""

    def _layers(self, rng, n):
        return [
            {
                "w": jnp.asarray(
                    rng.standard_normal((D, D)) / 4, jnp.float32
                ),
                "b": jnp.asarray(
                    rng.standard_normal((D,)) / 4, jnp.float32
                ),
            }
            for _ in range(n)
        ]

    @staticmethod
    def _layer_fn(layer, x):
        return jnp.tanh(x @ layer["w"] + layer["b"])

    def _stage_fn(self, stage, x):
        out, _ = jax.lax.scan(
            lambda c, lyr: (self._layer_fn(lyr, c), None), x, stage
        )
        return out

    def _sequential(self, layers, x):
        for layer in layers:
            x = self._layer_fn(layer, x)
        return x

    def test_chunk_layout(self, rng):
        """Device d chunk c holds global stage c*S+d (the Megatron
        virtual-pipeline assignment)."""
        from ddl_tpu.parallel.pipeline import stack_layer_stages

        layers = self._layers(rng, 8)
        st = stack_layer_stages(layers, 4, n_chunks=2)
        assert st["w"].shape == (4, 2, 1, D, D)
        for d in range(4):
            for c in range(2):
                np.testing.assert_array_equal(
                    np.asarray(st["w"][d, c, 0]),
                    np.asarray(layers[c * 4 + d]["w"]),
                )
        import pytest

        with pytest.raises(ValueError):
            stack_layer_stages(layers, 4, n_chunks=3)  # 8 % 12 != 0

    def test_1f1b_matches_sequential_and_gpipe(self, rng):
        from ddl_tpu.parallel.pipeline import stack_layer_stages

        layers = self._layers(rng, 8)
        x = jnp.asarray(rng.standard_normal((8, D)), jnp.float32)
        mesh = make_mesh({"pp": 4, "dp": 2})
        ref = np.asarray(self._sequential(layers, x))
        gp = pipeline_apply(
            stack_layer_stages(layers, 4), x, self._stage_fn, mesh, 4
        )
        f1 = pipeline_apply(
            stack_layer_stages(layers, 4, n_chunks=2), x,
            self._stage_fn, mesh, 4, schedule="1f1b", n_chunks=2,
        )
        np.testing.assert_allclose(np.asarray(gp), ref, atol=1e-5)
        np.testing.assert_allclose(np.asarray(f1), ref, atol=1e-5)
        # M = 8 (multiple of S) exercises the two-group packing.
        f2 = pipeline_apply(
            stack_layer_stages(layers, 4, n_chunks=2), x,
            self._stage_fn, mesh, 8, schedule="1f1b", n_chunks=2,
        )
        np.testing.assert_allclose(np.asarray(f2), ref, atol=1e-5)

    def test_1f1b_fallback_no_pp_axis(self, rng):
        from ddl_tpu.parallel.pipeline import stack_layer_stages

        layers = self._layers(rng, 8)
        x = jnp.asarray(rng.standard_normal((8, D)), jnp.float32)
        out = pipeline_apply(
            stack_layer_stages(layers, 4, n_chunks=2), x,
            self._stage_fn, make_mesh({"dp": 8}), 4,
            schedule="1f1b", n_chunks=2,
        )
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(self._sequential(layers, x)), atol=1e-5,
        )

    def test_1f1b_grads_match_gpipe(self, rng):
        """Loss AND per-layer grads identical between the schedules at
        the same (total stages, M) — only device placement and tick
        order differ (ISSUE 5 acceptance)."""
        from ddl_tpu.parallel.pipeline import stack_layer_stages

        layers = self._layers(rng, 8)
        x = jnp.asarray(rng.standard_normal((8, D)), jnp.float32)
        mesh = make_mesh({"pp": 4, "dp": 2})

        def loss(stacked, schedule, n_chunks):
            out = pipeline_apply(
                stacked, x, self._stage_fn, mesh, 4,
                schedule=schedule, n_chunks=n_chunks,
            )
            return jnp.sum(out**2)

        lg, gg = jax.value_and_grad(
            lambda p: loss(p, "gpipe", None)
        )(stack_layer_stages(layers, 4))
        lf, gf = jax.value_and_grad(
            lambda p: loss(p, "1f1b", 2)
        )(stack_layer_stages(layers, 4, n_chunks=2))
        np.testing.assert_allclose(float(lg), float(lf), rtol=1e-5)
        # Map both grad layouts back to the original layer order:
        # gpipe [s, i] = layer 2s+i; 1f1b [d, c, 0] = layer c*4+d.
        for li in range(8):
            for k in ("w", "b"):
                np.testing.assert_allclose(
                    np.asarray(gg[k][li // 2, li % 2]),
                    np.asarray(gf[k][li % 4, li // 4, 0]),
                    atol=2e-5, err_msg=f"layer {li} {k}",
                )

    def test_1f1b_requires_divisible_microbatches(self, rng):
        import pytest

        from ddl_tpu.parallel.pipeline import stack_layer_stages

        layers = self._layers(rng, 8)
        x = jnp.asarray(rng.standard_normal((6, D)), jnp.float32)
        mesh = make_mesh({"pp": 4, "dp": 2})
        st = stack_layer_stages(layers, 4, n_chunks=2)
        with pytest.raises(ValueError, match="divisible by n_stages"):
            pipeline_apply(
                st, x, self._stage_fn, mesh, 6,
                schedule="1f1b", n_chunks=2,
            )
        # Params stacked without the expected chunk axis are rejected
        # up front (here: a 4-layer gpipe stack, whose (4, 1, D, D)
        # leaves cannot carry n_chunks=2).  NB a gpipe stack with
        # L/S == n_chunks is shape-indistinguishable from a chunked
        # stack — the layout contract is the caller's.
        with pytest.raises(ValueError, match="stack_layer_stages"):
            pipeline_apply(
                stack_layer_stages(layers[:4], 4),
                jnp.asarray(rng.standard_normal((8, D)), jnp.float32),
                self._stage_fn, mesh, 4, schedule="1f1b", n_chunks=2,
            )


class TestLlamaPipeline:
    """The FLAGSHIP model through the pipe (VERDICT r4 item 4): llama
    blocks stacked into stages, equivalence vs the plain forward, and a
    full sharded train step on a pp×dp mesh."""

    def _cfg(self, n_layers=4):
        from ddl_tpu.models.llama import LlamaConfig

        # fp32 + dense attention so pp-vs-plain comparisons are tight.
        return LlamaConfig(
            vocab=64, d_model=32, n_layers=n_layers, n_heads=4,
            n_kv_heads=2, d_ff=64, dtype=jnp.float32, attn_impl="dense",
        )

    def test_stage_params_layout(self, rng):
        from ddl_tpu.models import llama

        cfg = self._cfg(4)
        params = llama.init_params(cfg, jax.random.key(0))
        pp = llama.stage_params(params, 2)
        # (S, L/S, ...) leaves; stage 1 layer 0 is original layer 2.
        assert pp["stages"]["wq"].shape == (2, 2, 32, 32)
        np.testing.assert_array_equal(
            np.asarray(pp["stages"]["wq"][1, 0]),
            np.asarray(params["layers"][2]["wq"]),
        )
        import pytest

        with pytest.raises(ValueError):
            llama.stage_params(params, 3)  # 4 layers don't split in 3

    def test_forward_pp_matches_forward(self, rng):
        """Pipelined llama logits == plain llama logits for every stage
        count that divides the layers (pp=4 and pp=2 over the 8-device
        mesh), microbatched or not."""
        from ddl_tpu.models import llama

        cfg = self._cfg(4)
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab, (8, 16)), jnp.int32
        )
        ref = np.asarray(llama.forward(params, tokens, cfg))
        for S, dp, M in ((4, 2, 4), (2, 4, 2), (4, 2, 8)):
            mesh = make_mesh({"pp": S, "dp": dp})
            got = llama.forward_pp(
                llama.stage_params(params, S), tokens, cfg, mesh,
                n_microbatches=M,
            )
            np.testing.assert_allclose(
                np.asarray(got), ref, atol=2e-5, rtol=2e-5,
                err_msg=f"pp={S} dp={dp} M={M}",
            )

    def test_train_step_pp_llama(self, rng):
        """Full sharded train step (loss+grad+adamw) of the pipelined
        llama on a pp=4 × dp=2 mesh: loss starts near ln(vocab) and
        decreases — the reverse schedule works through jax.grad."""
        from ddl_tpu.models import llama

        cfg = self._cfg(4)
        mesh = make_mesh({"pp": 4, "dp": 2})
        flat_params = llama.init_params(cfg, jax.random.key(0))
        params = llama.stage_params(flat_params, 4)
        tokens = np.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (8, 16)),
            np.int32,
        )
        init_fn, step_fn = make_train_step(
            lambda p, b: llama.next_token_loss_pp(
                p, b, cfg, mesh, n_microbatches=4
            ),
            optax.adamw(1e-2), mesh, llama.pp_param_specs(cfg),
            batch_spec=P(("dp",)),
        )
        state = init_fn(params)
        losses = []
        for _ in range(8):
            state, loss = step_fn(state, tokens)
            losses.append(float(loss))
        # Step-1 loss must match the UNPIPELINED loss on identical
        # params — an invariant of the schedule, unlike the absolute
        # ln(vocab) proximity of the old assert, which floats with the
        # jax version's init-draw stream.
        ref = float(
            llama.next_token_loss(flat_params, jnp.asarray(tokens), cfg)
        )
        assert abs(losses[0] - ref) < 0.05, (losses[0], ref)
        assert losses[-1] < losses[0] - 0.3, losses

    def test_forward_pp_tp_resident_matches(self, rng):
        """pp × tp: stages run on LOCAL Megatron weight shards with
        explicit psums — logits must equal the plain forward exactly
        (the tp-resident path changes memory and collectives, not
        math)."""
        from ddl_tpu.models import llama

        cfg = self._cfg(4)
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab, (8, 16)), jnp.int32
        )
        ref = np.asarray(llama.forward(params, tokens, cfg))
        mesh = make_mesh({"pp": 2, "tp": 2, "dp": 2})
        got = llama.forward_pp(
            llama.stage_params(params, 2), tokens, cfg, mesh,
            n_microbatches=4,
        )
        np.testing.assert_allclose(
            np.asarray(got), ref, atol=2e-5, rtol=2e-5
        )

    def test_forward_pp_degenerate_pp1_with_tp(self, rng):
        """pp=1 with a tp axis present takes the sequential fallback on
        FULL weights (tp-resident stages need a real pp axis for their
        psums) — must run, not raise, and match the plain forward."""
        from ddl_tpu.models import llama

        cfg = self._cfg(4)
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab, (4, 16)), jnp.int32
        )
        mesh = make_mesh({"pp": 1, "tp": 2, "dp": 4})
        got = llama.forward_pp(
            llama.stage_params(params, 1), tokens, cfg, mesh,
            n_microbatches=2,
        )
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(llama.forward(params, tokens, cfg)),
            atol=2e-5, rtol=2e-5,
        )

    def test_train_step_pp_tp_llama(self, rng):
        """Full sharded train step of the tp-resident pipelined llama on
        pp=2 × tp=2 × dp=2 — grads flow through the psums and the
        ppermute schedule together."""
        from ddl_tpu.models import llama

        cfg = self._cfg(4)
        mesh = make_mesh({"pp": 2, "tp": 2, "dp": 2})
        init_fn, step_fn = make_train_step(
            lambda p, b: llama.next_token_loss_pp(
                p, b, cfg, mesh, n_microbatches=4
            ),
            optax.adamw(1e-2), mesh, llama.pp_param_specs(cfg),
            batch_spec=P(("dp",)),
        )
        flat_params = llama.init_params(cfg, jax.random.key(0))
        state = init_fn(llama.stage_params(flat_params, 2))
        tokens = np.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (8, 16)),
            np.int32,
        )
        losses = []
        for _ in range(6):
            state, loss = step_fn(state, tokens)
            losses.append(float(loss))
        # Same-params unpipelined reference (see test_train_step_pp_llama).
        ref = float(
            llama.next_token_loss(flat_params, jnp.asarray(tokens), cfg)
        )
        assert abs(losses[0] - ref) < 0.05, (losses[0], ref)
        assert losses[-1] < losses[0] - 0.3, losses

    def test_remat_pp_matches(self, rng):
        """Per-layer remat inside a pipeline stage changes memory, not
        math — for EVERY named policy (none/full/selective/dots)."""
        from ddl_tpu.models import llama

        cfg = self._cfg(4)
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab, (4, 16)), jnp.int32
        )
        mesh = make_mesh({"pp": 4, "dp": 2})
        pp = llama.stage_params(params, 4)
        a = llama.forward_pp(pp, tokens, cfg, mesh, n_microbatches=4)
        for policy in (True, "full", "selective", "dots"):
            cfg_r = type(cfg)(**{**cfg.__dict__, "remat": policy})
            b = llama.forward_pp(
                pp, tokens, cfg_r, mesh, n_microbatches=4
            )
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-6,
                err_msg=f"remat={policy}",
            )

    def test_forward_pp_1f1b_matches_forward(self, rng):
        """The interleaved 1f1b schedule on the FLAGSHIP model: logits
        equal the plain forward (8 layers, pp=4 x 2 chunks)."""
        from ddl_tpu.models import llama

        cfg = self._cfg(8)
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab, (8, 16)), jnp.int32
        )
        ref = np.asarray(llama.forward(params, tokens, cfg))
        mesh = make_mesh({"pp": 4, "dp": 2})
        got = llama.forward_pp(
            llama.stage_params(params, 4, n_chunks=2), tokens, cfg,
            mesh, n_microbatches=4, schedule="1f1b", n_chunks=2,
        )
        np.testing.assert_allclose(
            np.asarray(got), ref, atol=2e-5, rtol=2e-5
        )

    def test_train_step_1f1b_matches_gpipe(self, rng):
        """Loss/grad equivalence of the 1f1b schedule with gpipe on the
        flagship model (ISSUE 5 acceptance): identical step-1 loss and
        per-layer gradients from identical params at the same (total
        stages, M)."""
        from ddl_tpu.models import llama

        cfg = self._cfg(8)
        flat = llama.init_params(cfg, jax.random.key(0))
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (8, 16)),
            jnp.int32,
        )
        mesh = make_mesh({"pp": 4, "dp": 2})
        lg, gg = jax.value_and_grad(
            lambda p: llama.next_token_loss_pp(
                p, tokens, cfg, mesh, n_microbatches=4
            )
        )(llama.stage_params(flat, 4))
        lf, gf = jax.value_and_grad(
            lambda p: llama.next_token_loss_pp(
                p, tokens, cfg, mesh, n_microbatches=4,
                schedule="1f1b", n_chunks=2,
            )
        )(llama.stage_params(flat, 4, n_chunks=2))
        ref = float(llama.next_token_loss(flat, tokens, cfg))
        assert abs(float(lg) - ref) < 0.05
        np.testing.assert_allclose(float(lg), float(lf), rtol=1e-5)
        # Grad layouts map back to original layer order: gpipe [s, i]
        # = layer 2s+i; 1f1b [d, c, 0] = layer c*4+d.  Compare a
        # representative leaf per layer plus the shared non-staged
        # leaves.
        for li in range(8):
            np.testing.assert_allclose(
                np.asarray(gg["stages"]["wq"][li // 2, li % 2]),
                np.asarray(gf["stages"]["wq"][li % 4, li // 4, 0]),
                atol=2e-5, err_msg=f"layer {li}",
            )
        for leaf in ("embed", "lm_head", "final_norm"):
            np.testing.assert_allclose(
                np.asarray(gg[leaf]), np.asarray(gf[leaf]), atol=2e-5
            )


class TestMoePipeline:
    """MoE through the pipe: the activation pytree carries the router
    aux accumulator alongside the residual stream."""

    def _cfg(self, **kw):
        from ddl_tpu.models.moe import MoeConfig

        base = dict(
            vocab=64, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2,
            d_ff=64, n_experts=4, dtype=jnp.float32, attn_impl="dense",
            capacity_factor=8.0,  # unbound capacity -> exact logits
            moe_impl="einsum",  # the dispatch these tests were written for
        )
        base.update(kw)
        return MoeConfig(**base)

    def test_forward_pp_matches_forward(self, rng):
        """With capacity unbound, routing is per-token, so pipelined
        logits equal the plain forward exactly; the aux differs only by
        its granularity (mean of per-microbatch aux) and stays the same
        order of magnitude."""
        from ddl_tpu.models import moe

        cfg = self._cfg()
        params = moe.init_params(cfg, jax.random.key(0))
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab, (8, 16)), jnp.int32
        )
        ref_logits, ref_aux = moe.forward(params, tokens, cfg)
        mesh = make_mesh({"pp": 4, "dp": 2})
        got_logits, got_aux = moe.forward_pp(
            moe.stage_params(params, 4), tokens, cfg, mesh,
            n_microbatches=4,
        )
        np.testing.assert_allclose(
            np.asarray(got_logits), np.asarray(ref_logits),
            atol=2e-5, rtol=2e-5,
        )
        assert np.isfinite(float(got_aux)) and float(got_aux) > 0
        # Same load-balance pressure at different granularity.
        assert abs(float(got_aux) - float(ref_aux)) < 0.5 * float(ref_aux)

    def test_train_step_pp_moe(self, rng):
        """Full sharded train step of the pipelined MoE on pp=4 × dp=2 —
        grads flow through the routed experts, the aux accumulator, and
        the ppermute schedule."""
        from ddl_tpu.models import moe

        cfg = self._cfg(capacity_factor=2.0)
        mesh = make_mesh({"pp": 4, "dp": 2})
        init_fn, step_fn = make_train_step(
            lambda p, b: moe.next_token_loss_pp(
                p, b, cfg, mesh, n_microbatches=4
            ),
            optax.adamw(1e-2), mesh, moe.pp_param_specs(cfg),
            batch_spec=P(("dp",)),
        )
        state = init_fn(
            moe.stage_params(moe.init_params(cfg, jax.random.key(0)), 4)
        )
        tokens = np.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (8, 16)),
            np.int32,
        )
        losses = []
        for _ in range(8):
            state, loss = step_fn(state, tokens)
            losses.append(float(loss))
        assert abs(losses[0] - np.log(cfg.vocab)) < 1.0, losses[0]
        assert losses[-1] < losses[0] - 0.3, losses


class TestViTPipeline:
    """The image family through the pipe: same stage layout and schedule
    as llama (shared stack_layer_stages), non-causal attention."""

    def _cfg(self):
        from ddl_tpu.models.vit import ViTConfig

        return ViTConfig(
            image_size=16, patch_size=4, d_model=32, n_layers=4,
            n_heads=4, d_ff=64, n_classes=8, dtype=jnp.float32,
            attn_impl="dense",
        )

    def test_forward_pp_matches_forward(self, rng):
        from ddl_tpu.models import vit

        cfg = self._cfg()
        params = vit.init_params(cfg, jax.random.key(0))
        images = jnp.asarray(
            rng.random((8, 16 * 16 * 3)), jnp.float32
        )
        ref = np.asarray(vit.forward(params, images, cfg))
        mesh = make_mesh({"pp": 4, "dp": 2})
        got = vit.forward_pp(
            vit.stage_params(params, 4), images, cfg, mesh,
            n_microbatches=4,
        )
        np.testing.assert_allclose(
            np.asarray(got), ref, atol=2e-5, rtol=2e-5
        )

    def test_train_step_pp_vit(self, rng):
        from ddl_tpu.models import vit

        cfg = self._cfg()
        mesh = make_mesh({"pp": 4, "dp": 2})
        init_fn, step_fn = make_train_step(
            lambda p, b: vit.classification_loss_pp(
                p, b, cfg, mesh, n_microbatches=4
            ),
            optax.adam(1e-2), mesh, vit.pp_param_specs(cfg),
            batch_spec=P(("dp",)),
        )
        flat_params = vit.init_params(cfg, jax.random.key(0))
        state = init_fn(vit.stage_params(flat_params, 4))
        g = np.random.default_rng(0)
        pixels = g.random((8, 16 * 16 * 3)).astype(np.float32)
        labels = g.integers(0, 8, (8, 1)).astype(np.float32)
        losses = []
        for _ in range(8):
            state, loss = step_fn(state, (pixels, labels))
            losses.append(float(loss))
        # Same-params unpipelined reference (see test_train_step_pp_llama).
        ref = float(
            vit.classification_loss(flat_params, (pixels, labels), cfg)
        )
        assert abs(losses[0] - ref) < 0.05, (losses[0], ref)
        assert losses[-1] < losses[0] - 0.3, losses


def test_pipeline_gradients_train(rng):
    """A pipelined regression model trains end-to-end on a pp×dp mesh —
    grads flow backwards through the ppermute schedule."""
    mesh = make_mesh({"pp": 4, "dp": 2})
    stacked = stack_stage_params(_stages(rng, 4))
    x = jnp.asarray(rng.standard_normal((16, D)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((16, D)) * 0.1, jnp.float32)

    def loss_fn(params, batch):
        xb, yb = batch
        pred = pipeline_apply(params, xb, _stage_fn, mesh, n_microbatches=4)
        return jnp.mean((pred - yb) ** 2)

    init_fn, step_fn = make_train_step(
        loss_fn, optax.adam(1e-2), mesh,
        pipeline_spec({"w": P(None, None), "b": P(None)}),
        batch_spec=P(),
    )
    state = init_fn(stacked)
    losses = []
    for _ in range(30):
        state, loss = step_fn(state, (x, y))
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])

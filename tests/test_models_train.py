"""Model + sharded train-step tests on the virtual 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddl_tpu.models import llama, pointnet
from ddl_tpu.parallel.mesh import make_mesh
from ddl_tpu.parallel.train import make_train_step
from jax.sharding import PartitionSpec as P


class TestLlamaModel:
    def test_forward_shapes_and_finite(self):
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
        logits = llama.forward(params, tokens, cfg)
        assert logits.shape == (2, 16, cfg.vocab)
        assert logits.dtype == jnp.float32
        assert bool(jnp.all(jnp.isfinite(logits)))

    def test_causality(self):
        """Changing a future token must not change earlier logits."""
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        t1 = jnp.zeros((1, 8), jnp.int32)
        t2 = t1.at[0, 7].set(5)
        l1 = llama.forward(params, t1, cfg)
        l2 = llama.forward(params, t2, cfg)
        np.testing.assert_allclose(
            np.asarray(l1[0, :7]), np.asarray(l2[0, :7]), rtol=1e-5
        )

    def test_flash_attn_impl_matches_dense(self):
        """forward(attn_impl="flash") == forward(attn_impl="dense")."""
        cfg = llama.LlamaConfig(dtype=jnp.float32, attn_impl="dense")
        cfg_flash = llama.LlamaConfig(dtype=jnp.float32, attn_impl="flash")
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 48), 0, cfg.vocab)
        dense = llama.forward(params, tokens, cfg)
        flash = llama.forward(params, tokens, cfg_flash)
        np.testing.assert_allclose(
            np.asarray(dense), np.asarray(flash), atol=1e-4, rtol=1e-4
        )

    def test_packed_segments_isolation(self):
        """Packed batches: perturbing document 0's tokens must not change
        document 1's logits (flash and dense agree, both isolated)."""
        seg = jnp.asarray(
            np.concatenate([np.zeros(8, np.int32), np.ones(8, np.int32)])
        )[None]
        for impl in ("dense", "flash"):
            cfg = llama.LlamaConfig(dtype=jnp.float32, attn_impl=impl)
            # Identical params/tokens per impl ON PURPOSE: the loop
            # compares implementations, not random draws.
            params = llama.init_params(cfg, jax.random.key(0))  # ddl-lint: disable=DDL003
            t1 = jax.random.randint(jax.random.key(1), (1, 16), 0, cfg.vocab)  # ddl-lint: disable=DDL003
            t2 = t1.at[0, :8].set(0)  # rewrite doc 0 entirely
            l1 = llama.forward(params, t1, cfg, segment_ids=seg)
            l2 = llama.forward(params, t2, cfg, segment_ids=seg)
            np.testing.assert_allclose(
                np.asarray(l1[0, 8:]), np.asarray(l2[0, 8:]),
                rtol=1e-5, atol=1e-6,
            )
            assert not np.allclose(
                np.asarray(l1[0, :8]), np.asarray(l2[0, :8])
            )

    def test_packed_loss_masks_boundaries(self):
        """The boundary position's next-token (first token of the NEXT
        document) is excluded from the packed loss."""
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab)
        seg = jnp.asarray(
            np.concatenate([np.zeros(8, np.int32), np.ones(8, np.int32)])
        )[None].repeat(2, axis=0)
        loss = llama.next_token_loss(params, tokens, cfg, segment_ids=seg)
        assert np.isfinite(float(loss))
        # Perturb ONLY the boundary target (first token of doc 1): packed
        # loss must be invariant (position 7's prediction is masked and
        # position 8's own target is position 9's token).
        logits = llama.forward(params, tokens, cfg, segment_ids=seg)
        from ddl_tpu.models.losses import next_token_cross_entropy

        boundary = seg != jnp.roll(seg, -1, axis=1)
        m1 = next_token_cross_entropy(logits, tokens, extra_mask=boundary)
        t_mut = tokens.at[:, 8].set((tokens[:, 8] + 1) % cfg.vocab)
        m2 = next_token_cross_entropy(logits, t_mut, extra_mask=boundary)
        # Changing token 8 changes target at position 7 (masked) and
        # target at position 8 stays tokens[9] — but token 8 is itself
        # target of nothing else, so the masked loss shifts only through
        # position 8's INPUT in logits; with fixed logits it is invariant
        # except where token 8 is a target: position 7 (masked). Equal.
        np.testing.assert_allclose(float(m1), float(m2), rtol=1e-6)

    def test_attn_impl_validated(self):
        with pytest.raises(ValueError, match="attn_impl"):
            llama.LlamaConfig(attn_impl="Flash")

    def test_flash_on_dp_tp_mesh_matches_dense(self):
        """attn_impl='flash' engages (shard_mapped) on a no-sp mesh."""
        cfg = llama.LlamaConfig(dtype=jnp.float32, attn_impl="flash")
        params = llama.init_params(cfg, jax.random.key(0))
        mesh = make_mesh({"dp": 4, "tp": 2})
        tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab)
        sharded = llama.forward(params, tokens, cfg, mesh=mesh)
        dense = llama.forward(
            params, tokens,
            llama.LlamaConfig(dtype=jnp.float32, attn_impl="dense"),
        )
        np.testing.assert_allclose(
            np.asarray(sharded), np.asarray(dense), atol=1e-4, rtol=1e-4
        )

    def test_loss_decreases_under_training(self):
        cfg = llama.LlamaConfig(
            vocab=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=1,
            d_ff=64, dtype=jnp.float32,
        )
        params = llama.init_params(cfg, jax.random.key(0))
        mesh = make_mesh({"dp": 8})
        opt = optax.adam(1e-2)
        init_fn, step_fn = make_train_step(
            lambda p, b: llama.next_token_loss(p, b, cfg),
            opt, mesh, llama.param_specs(cfg), batch_spec=P(("dp",)),
        )
        state = init_fn(params)
        tokens = np.tile(np.arange(16, dtype=np.int32) % 7, (8, 1))
        losses = []
        for _ in range(20):
            state, loss = step_fn(state, tokens)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.7, losses

    def test_sp_forward_matches_dense(self):
        """Ring-attention (sp) forward == dense forward."""
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab)
        mesh = make_mesh({"dp": 2, "sp": 4})
        dense = llama.forward(params, tokens, cfg, mesh=None)
        sp = llama.forward(params, tokens, cfg, mesh=mesh)
        np.testing.assert_allclose(
            np.asarray(dense), np.asarray(sp), rtol=2e-4, atol=2e-4
        )

    def test_sp_packed_forward_matches_dense(self):
        """Packed batches on the sp mesh: segment ids ride the ring and
        the model forward matches the single-device packed forward."""
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab)
        seg = jnp.asarray(
            np.repeat(np.arange(4, dtype=np.int32), 8)
        )[None].repeat(2, axis=0)
        mesh = make_mesh({"dp": 2, "sp": 4})
        dense = llama.forward(params, tokens, cfg, mesh=None,
                              segment_ids=seg)
        sp = llama.forward(params, tokens, cfg, mesh=mesh,
                           segment_ids=seg)
        np.testing.assert_allclose(
            np.asarray(dense), np.asarray(sp), rtol=2e-4, atol=2e-4
        )


class TestGradAccumulation:
    def test_accum_matches_full_batch_step(self):
        """accum_steps=4 produces the same params and loss as the
        full-batch step (mean-reduction losses make accumulation exact,
        up to fp summation order)."""
        import optax

        cfg = pointnet.PointNetConfig(n_inputs=3, n_outputs=2)
        mesh = make_mesh({"dp": 8})
        rng = np.random.default_rng(0)
        batch = tuple(
            np.asarray(a, np.float32)
            for a in (rng.random((32, 3)), rng.random((32, 2)),
                      rng.random((32, 1)))
        )
        results = {}
        for accum in (1, 4):
            init_fn, step_fn = make_train_step(
                lambda p, b: pointnet.weighted_mse_loss(p, b, cfg),
                optax.adam(1e-2), mesh, pointnet.param_specs(cfg),
                batch_spec=P(("dp",)), accum_steps=accum,
            )
            # Same init per accum value ON PURPOSE: the loop compares
            # accumulation settings over identical starting params.
            state = init_fn(pointnet.init_params(cfg, jax.random.key(0)))  # ddl-lint: disable=DDL003
            state, loss = step_fn(state, batch)
            results[accum] = (state, float(loss))
        np.testing.assert_allclose(
            results[1][1], results[4][1], rtol=1e-6
        )
        for a, b in zip(
            jax.tree.leaves(results[1][0].params),
            jax.tree.leaves(results[4][0].params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
            )

    def test_accum_validation(self):
        import optax
        import pytest

        cfg = pointnet.PointNetConfig(n_inputs=3, n_outputs=2)
        mesh = make_mesh({"dp": 8})
        with pytest.raises(ValueError, match="accum_steps"):
            make_train_step(
                lambda p, b: pointnet.weighted_mse_loss(p, b, cfg),
                optax.adam(1e-2), mesh, pointnet.param_specs(cfg),
                accum_steps=0,
            )
        # dp=2 so a 6-row batch passes sharding but not accum_steps=4.
        mesh2 = make_mesh({"dp": 2}, jax.devices()[:2])
        init_fn, step_fn = make_train_step(
            lambda p, b: pointnet.weighted_mse_loss(p, b, cfg),
            optax.adam(1e-2), mesh2, pointnet.param_specs(cfg),
            batch_spec=P(("dp",)), accum_steps=4,
        )
        state = init_fn(pointnet.init_params(cfg, jax.random.key(0)))
        bad = tuple(np.zeros((6, w), np.float32) for w in (3, 2, 1))
        with pytest.raises(ValueError, match="not divisible"):
            step_fn(state, bad)


class TestLlamaDecode:
    def test_cached_prefill_matches_forward(self):
        """forward_with_cache over a whole prompt == plain forward."""
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 12), 0, cfg.vocab)
        full = llama.forward(params, tokens, cfg)
        cache = llama.init_cache(cfg, 2, 12)
        cached, _ = llama.forward_with_cache(
            params, tokens, cfg, cache, jnp.int32(0)
        )
        np.testing.assert_allclose(
            np.asarray(full), np.asarray(cached), rtol=2e-5, atol=2e-5
        )

    def test_stepwise_decode_matches_teacher_forcing(self):
        """One-token cached steps reproduce the full forward's logits at
        every position (the KV cache is exact, not approximate)."""
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(2), (1, 10), 0, cfg.vocab)
        full = llama.forward(params, tokens, cfg)
        cache = llama.init_cache(cfg, 1, 10)
        for t in range(10):
            lt, cache = llama.forward_with_cache(
                params, tokens[:, t : t + 1], cfg, cache, jnp.int32(t)
            )
            np.testing.assert_allclose(
                np.asarray(full[:, t]), np.asarray(lt[:, 0]),
                rtol=2e-5, atol=2e-5,
            )

    def test_generate_with_tp_sharded_params(self):
        """Multi-chip serving: the decode path with params laid out
        tensor-parallel on a tp mesh (GSPMD shards the decode matmuls;
        no code changes needed — the sharding rides the params).
        Logits must match the single-device computation to float
        tolerance (sharded all-reduce order differs by ULPs, so tokens
        are not compared bitwise — a near-tied argmax could flip), and
        generate must run end to end on the sharded layout."""
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(3), (2, 5), 0, cfg.vocab)

        mesh = make_mesh({"tp": 8})
        init_fn, _ = make_train_step(
            lambda p, b: llama.next_token_loss(p, b, cfg),
            optax.adamw(1e-3), mesh, llama.param_specs(cfg),
        )
        sharded = init_fn(params).params
        # Weights really are distributed, not replicated.
        assert "tp" in str(
            sharded["layers"][0]["wq"].sharding.spec
        ), sharded["layers"][0]["wq"].sharding

        # Cached-prefill logits: sharded serving == single-device math.
        cache_1 = llama.init_cache(cfg, 2, 5)
        logits_1, _ = llama.forward_with_cache(
            params, prompt, cfg, cache_1, jnp.int32(0)
        )
        cache_tp = llama.init_cache(cfg, 2, 5)
        logits_tp, _ = llama.forward_with_cache(
            sharded, prompt, cfg, cache_tp, jnp.int32(0)
        )
        np.testing.assert_allclose(
            np.asarray(logits_1), np.asarray(logits_tp),
            rtol=2e-5, atol=2e-5,
        )

        out_tp = llama.generate(sharded, prompt, cfg, max_new_tokens=6)
        arr = np.asarray(out_tp)
        assert arr.shape == (2, 11)
        np.testing.assert_array_equal(arr[:, :5], np.asarray(prompt))
        assert ((arr >= 0) & (arr < cfg.vocab)).all()

    def test_greedy_generate(self):
        """Greedy generation is deterministic, returns the prompt prefix,
        and each emitted token is the argmax continuation."""
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(3), (2, 5), 0, cfg.vocab)
        out = llama.generate(params, prompt, cfg, max_new_tokens=4)
        assert out.shape == (2, 9)
        np.testing.assert_array_equal(np.asarray(out[:, :5]),
                                      np.asarray(prompt))
        out2 = llama.generate(params, prompt, cfg, max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
        # Teacher-forced check of the first generated token.
        full = llama.forward(params, prompt, cfg)
        np.testing.assert_array_equal(
            np.asarray(out[:, 5]),
            np.asarray(jnp.argmax(full[:, -1], axis=-1)),
        )

    def test_sampled_generate_finite(self):
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(4), (1, 4), 0, cfg.vocab)
        out = llama.generate(
            params, prompt, cfg, max_new_tokens=6, temperature=1.0,
            key=jax.random.key(7),
        )
        assert out.shape == (1, 10)
        assert int(out.max()) < cfg.vocab and int(out.min()) >= 0

    def test_remat_matches_plain_forward_and_grad(self):
        """cfg.remat changes memory, NOT math: loss and grads must match
        the plain path (it recomputes the same layer internals)."""
        base = llama.LlamaConfig(dtype=jnp.float32)
        rcfg = llama.LlamaConfig(dtype=jnp.float32, remat=True)
        params = llama.init_params(base, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, base.vocab)

        def loss(cfg):
            return jax.value_and_grad(
                lambda p: llama.next_token_loss(p, tokens, cfg)
            )(params)

        l0, g0 = loss(base)
        l1, g1 = loss(rcfg)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            g0, g1,
        )

    def test_param_dtype_bf16_storage(self):
        cfg = llama.LlamaConfig(param_dtype=jnp.bfloat16)
        params = llama.init_params(cfg, jax.random.key(0))
        assert all(
            x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params)
        )
        # Forward still runs and produces fp32 logits.
        tokens = jax.random.randint(jax.random.key(1), (1, 8), 0, cfg.vocab)
        out = llama.forward(params, tokens, cfg)
        assert out.dtype == jnp.float32

    def test_sampled_generate_requires_key(self):
        """Sampling without an explicit key raises — a silent default
        would make every 'sampled' call deterministically identical."""
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(4), (1, 4), 0, cfg.vocab)
        with pytest.raises(ValueError, match="explicit PRNG key"):
            llama.generate(
                params, prompt, cfg, max_new_tokens=2, temperature=0.7
            )

    def test_sample_filter_top_k(self):
        """top-k masks everything but the k best logits; k=1 makes
        sampling deterministic-greedy at any temperature."""
        logits = jnp.asarray([[3.0, 1.0, 2.0, 0.0], [0.0, 5.0, 4.0, 1.0]])
        f = llama._sample_filter(logits, top_k=2, top_p=None)
        np.testing.assert_array_equal(
            np.isfinite(np.asarray(f)),
            [[True, False, True, False], [False, True, True, False]],
        )
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(4), (2, 4), 0, cfg.vocab)
        greedy = llama.generate(params, prompt, cfg, max_new_tokens=4)
        k1 = llama.generate(
            params, prompt, cfg, max_new_tokens=4, temperature=1.3,
            key=jax.random.key(9), top_k=1,
        )
        np.testing.assert_array_equal(np.asarray(greedy), np.asarray(k1))

    def test_sample_filter_top_p(self):
        """Nucleus filter keeps the smallest prefix reaching mass p;
        the best token always survives, and p=1.0 keeps everything."""
        # Probabilities ~ [0.643, 0.236, 0.087, 0.032] for these logits.
        logits = jnp.asarray([[4.0, 3.0, 2.0, 1.0]])
        f = llama._sample_filter(logits, top_k=None, top_p=0.7)
        np.testing.assert_array_equal(
            np.isfinite(np.asarray(f)), [[True, True, False, False]]
        )
        f_tiny = llama._sample_filter(logits, top_k=None, top_p=0.01)
        np.testing.assert_array_equal(
            np.isfinite(np.asarray(f_tiny)), [[True, False, False, False]]
        )
        f_all = llama._sample_filter(logits, top_k=None, top_p=1.0)
        assert np.isfinite(np.asarray(f_all)).all()

    def test_sampled_tokens_stay_in_filtered_support(self):
        """End to end: every token sampled with top_k=3 lies in that
        step's top-3 set (checked via teacher forcing on the output)."""
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(4), (2, 5), 0, cfg.vocab)
        out = llama.generate(
            params, prompt, cfg, max_new_tokens=6, temperature=1.0,
            key=jax.random.key(11), top_k=3,
        )
        logits = llama.forward(params, out, cfg)
        for t in range(5, 11):
            top3 = np.asarray(
                jax.lax.top_k(logits[:, t - 1], 3)[1]
            )
            tok = np.asarray(out[:, t])
            for b in range(2):
                assert tok[b] in top3[b], (t, b, tok[b], top3[b])

    def test_eos_masks_rest_of_row(self):
        """Once a row emits eos_id, every later position is eos_id; up
        to (and including) the first EOS the output matches the run
        without EOS handling."""
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(4), (2, 5), 0, cfg.vocab)
        free = np.asarray(
            llama.generate(params, prompt, cfg, max_new_tokens=8)
        )
        # Choose the token row 0 emits at its second decode step as EOS.
        eos = int(free[0, 6])
        out = np.asarray(
            llama.generate(
                params, prompt, cfg, max_new_tokens=8, eos_id=eos
            )
        )
        for b in range(2):
            hits = np.where(out[b, 5:] == eos)[0]
            if hits.size:
                first = 5 + hits[0]
                # Prefix (through the first EOS) is unchanged...
                np.testing.assert_array_equal(
                    out[b, : first + 1], free[b, : first + 1]
                )
                # ...and everything after it is EOS.
                assert (out[b, first:] == eos).all(), out[b]
            else:
                np.testing.assert_array_equal(out[b], free[b])
        # Row 0 definitely hit it at position 6.
        assert (out[0, 6:] == eos).all(), out[0]
        with pytest.raises(ValueError, match="outside the model vocab"):
            llama.generate(
                params, prompt, cfg, max_new_tokens=2, eos_id=cfg.vocab
            )

    def test_filters_require_sampling(self):
        cfg = llama.LlamaConfig(dtype=jnp.float32)
        params = llama.init_params(cfg, jax.random.key(0))
        prompt = jnp.zeros((1, 4), jnp.int32)
        with pytest.raises(ValueError, match="temperature > 0"):
            llama.generate(params, prompt, cfg, max_new_tokens=2, top_k=5)
        with pytest.raises(ValueError, match="top_k must be"):
            llama.generate(
                params, prompt, cfg, max_new_tokens=2, temperature=1.0,
                key=jax.random.key(0), top_k=0,
            )
        with pytest.raises(ValueError, match="top_p must be"):
            llama.generate(
                params, prompt, cfg, max_new_tokens=2, temperature=1.0,
                key=jax.random.key(0), top_p=1.5,
            )


class TestShardedTrainStep:
    @pytest.mark.parametrize(
        "axes,batch_spec",
        [
            ({"dp": 8}, P(("dp",))),
            ({"dp": 2, "fsdp": 2, "tp": 2}, P(("dp",))),
            ({"dp": 2, "sp": 4}, P("dp", "sp")),
            ({"dp": 2, "fsdp": 2, "sp": 2}, P("dp", "sp")),
        ],
    )
    def test_llama_step_on_mesh(self, axes, batch_spec):
        cfg = llama.LlamaConfig(
            vocab=64, d_model=32, n_layers=1, n_heads=4, n_kv_heads=2,
            d_ff=64, dtype=jnp.float32,
        )
        mesh = make_mesh(dict(axes))
        params = llama.init_params(cfg, jax.random.key(0))
        init_fn, step_fn = make_train_step(
            lambda p, b: llama.next_token_loss(p, b, cfg, mesh=mesh),
            optax.adamw(1e-3), mesh, llama.param_specs(cfg),
            batch_spec=batch_spec,
        )
        state = init_fn(params)
        tokens = np.random.default_rng(0).integers(
            0, 64, (8, 16), dtype=np.int32
        )
        state, loss = step_fn(state, tokens)
        state, loss2 = step_fn(state, tokens)
        assert np.isfinite(float(loss)) and np.isfinite(float(loss2))
        assert float(loss2) < float(loss)  # it learns the repeated batch
        assert state.step == 2

    def test_param_shardings_respected(self):
        cfg = llama.LlamaConfig(
            vocab=64, d_model=32, n_layers=1, n_heads=4, n_kv_heads=2,
            d_ff=64, dtype=jnp.float32,
        )
        mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
        params = llama.init_params(cfg, jax.random.key(0))
        init_fn, _ = make_train_step(
            lambda p, b: llama.next_token_loss(p, b, cfg),
            optax.adam(1e-3), mesh, llama.param_specs(cfg),
        )
        state = init_fn(params)
        wq = state.params["layers"][0]["wq"]
        assert wq.sharding.spec == P("fsdp", "tp")
        # fsdp shards the optimizer moments too (ZeRO property).
        mu_wq = state.opt_state[0].mu["layers"][0]["wq"]
        assert mu_wq.sharding.spec == P("fsdp", "tp")


class TestPointNet:
    def test_train_on_loader_batches(self):
        """Close the reference's loop: pointwise model trained from the
        actual DistributedDataLoader output tuple."""
        from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
        import sys, os

        sys.path.insert(
            0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "examples")
        )
        from run_ddl import DataProducer, Params

        cfg = pointnet.PointNetConfig(n_inputs=3, n_outputs=6)
        mesh = make_mesh({"dp": 8})
        init_fn, step_fn = make_train_step(
            lambda p, b: pointnet.weighted_mse_loss(p, b, cfg),
            optax.adam(1e-2), mesh, pointnet.param_specs(cfg),
        )
        state = init_fn(pointnet.init_params(cfg, jax.random.key(0)))

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(params, env):
            nonlocal state
            loader = DistributedDataLoader(
                DataProducer(params), batch_size=64,
                connection=env.connection, n_epochs=2, output="numpy",
            )
            losses = []
            for _ in range(2):
                for batch in loader:
                    state, loss = step_fn(state, batch)
                    losses.append(float(loss))
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            return losses

        losses = main(Params(n_data=256, batch_size=64))
        assert len(losses) == 2 * (256 // 64)
        assert losses[-1] < losses[0]
        assert all(np.isfinite(l) for l in losses)


class TestInitFnDonationSafety:
    def test_same_host_params_reusable_across_train_steps(self):
        """Regression: init_fn must copy (not alias) so the donated step
        cannot delete the caller's params tree (bit dryrun n=2/6)."""
        cfg = llama.LlamaConfig(
            vocab=32, d_model=16, n_layers=1, n_heads=2, n_kv_heads=1,
            d_ff=32, dtype=jnp.float32,
        )
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = np.zeros((2, 8), np.int32)
        for axes in ({"dp": 2}, {"sp": 2}):
            mesh = make_mesh(axes, jax.devices()[:2])
            init_fn, step_fn = make_train_step(
                lambda p, b, _m=mesh: llama.next_token_loss(p, b, cfg, mesh=_m),
                optax.adam(1e-3), mesh, llama.param_specs(cfg),
                batch_spec=P("dp", "sp") if "sp" in axes else P(("dp",)),
            )
            state = init_fn(params)  # same host tree every plan
            _, loss = step_fn(state, tokens)
            assert np.isfinite(float(loss))


class TestMultistep:
    """make_multistep: n_steps chained in one jitted scan."""

    def _setup(self, n_steps, donate=True):
        from ddl_tpu.parallel.train import make_multistep

        cfg = llama.LlamaConfig(
            vocab=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=1,
            d_ff=64, dtype=jnp.float32,
        )
        mesh = make_mesh({"dp": 8})
        loss_fn = lambda p, b: llama.next_token_loss(p, b, cfg)  # noqa: E731
        opt = optax.adam(1e-2)
        init_m, multi = make_multistep(
            loss_fn, opt, mesh, llama.param_specs(cfg), n_steps=n_steps,
            donate=donate,
        )
        init_s, single = make_train_step(
            loss_fn, opt, mesh, llama.param_specs(cfg)
        )
        params = llama.init_params(cfg, jax.random.key(0))
        return init_m, multi, init_s, single, params

    def test_matches_single_step_trajectory(self):
        K = 4
        init_m, multi, init_s, single, params = self._setup(K)
        tokens = np.tile(np.arange(16, dtype=np.int32) % 7, (8, 1))
        sm, losses = multi(init_m(params), tokens)
        assert losses.shape == (K,) and sm.step == K
        ss = init_s(params)
        ref = []
        for _ in range(K):
            ss, l = single(ss, tokens)
            ref.append(float(l))
        np.testing.assert_allclose(
            np.asarray(losses, np.float32), np.asarray(ref, np.float32),
            rtol=1e-5,
        )

    def test_per_step_batches(self):
        K = 3
        init_m, multi, *_, params = self._setup(K)
        toks = np.random.default_rng(0).integers(
            0, 64, (K, 8, 16), dtype=np.int32
        )
        state, losses = multi(init_m(params), toks, per_step=True)
        assert losses.shape == (K,)
        assert np.isfinite(np.asarray(losses)).all()
        # per-step batches differ -> per-step losses differ
        assert len({round(float(x), 6) for x in losses}) == K

    def test_donate_false_keeps_state_alive(self):
        K = 2
        init_m, multi, *_, params = self._setup(K, donate=False)
        s0 = init_m(params)
        _, losses1 = multi(s0, np.zeros((8, 16), np.int32))
        # s0 must still be usable (no donated-buffer deletion)
        _, losses2 = multi(s0, np.zeros((8, 16), np.int32))
        np.testing.assert_allclose(
            np.asarray(losses1, np.float32), np.asarray(losses2, np.float32)
        )


class TestLlama3_8BScale:
    """BASELINE.json's pod-scale config (Llama-3-8B pretrain feed): the
    sharded train step must trace and lower at full model scale.  Lowering
    (not compiling) validates shapes, shardings, and GSPMD constraints
    without materialising the 8B-parameter pytree."""

    @pytest.mark.slow
    def test_8b_train_step_lowers_on_fsdp_tp_mesh(self):
        import optax

        from ddl_tpu.parallel.train import _named, _prune_indivisible

        cfg = llama.LlamaConfig.llama3_8b()
        mesh = make_mesh({"dp": 1, "fsdp": 4, "tp": 2})
        opt = optax.adamw(1e-4)

        params_shape = jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.key(0))
        )
        opt_state_shape = jax.eval_shape(opt.init, params_shape)
        batch = jax.ShapeDtypeStruct((4, 8192), jnp.int32)

        def step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: llama.next_token_loss(p, tokens, cfg, mesh)
            )(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        param_sh = jax.tree.map(
            _prune_indivisible,
            _named(mesh, llama.param_specs(cfg)),
            params_shape,
        )
        lowered = jax.jit(
            step, in_shardings=(param_sh, None, None)
        ).lower(params_shape, opt_state_shape, batch)
        text = lowered.as_text()
        # 8B params really are in the traced program: the vocab dimension
        # (128256) appears, and the program contains real matmuls.
        assert "128256" in text
        assert "stablehlo.dot_general" in text


class TestRematPolicies:
    """Named remat policies (ddl_tpu.models.remat): every policy is a
    pure memory/FLOPs trade — loss and grads must match the no-remat
    path exactly (the ISSUE 5 selective-remat equivalence test)."""

    def _cfg(self, **kw):
        base = dict(
            vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=64, dtype=jnp.float32, attn_impl="dense",
        )
        base.update(kw)
        return llama.LlamaConfig(**base)

    def test_resolve_names_and_bools(self):
        from ddl_tpu.models import remat

        assert remat.resolve(False) == "none"
        assert remat.resolve(None) == "none"
        assert remat.resolve(True) == "full"
        for name in remat.POLICIES:
            assert remat.resolve(name) == name
        with pytest.raises(ValueError):
            remat.resolve("everything")
        with pytest.raises(ValueError):
            self._cfg(remat="everything")  # config validates at build

    @pytest.mark.parametrize("policy", ["full", "selective", "dots"])
    def test_llama_loss_and_grads_match_no_remat(self, policy):
        cfg = self._cfg()
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (2, 16)),
            jnp.int32,
        )
        ln, gn = jax.value_and_grad(
            lambda p: llama.next_token_loss(p, tokens, cfg)
        )(params)
        lr, gr = jax.value_and_grad(
            lambda p: llama.next_token_loss(
                p, tokens, self._cfg(remat=policy)
            )
        )(params)
        np.testing.assert_allclose(float(ln), float(lr), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(gn), jax.tree.leaves(gr)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5
            )

    def test_selective_saves_attention_outputs(self):
        """The attention-output tag must be LIVE in the traced forward:
        with the name stripped (or the tag site dropped), "selective"
        would silently degrade to "full" and re-run the attention
        kernel in every backward pass."""
        from ddl_tpu.models import remat

        cfg = self._cfg(remat="selective")
        params = llama.init_params(cfg, jax.random.key(0))
        tokens = jnp.zeros((2, 16), jnp.int32)
        tagged = jax.make_jaxpr(
            lambda p: llama.forward(p, tokens, cfg)
        )(params)
        assert remat.ATTN_OUT_NAME in str(tagged)
        # ... and with the flash kernels what is saved is what their
        # backward reads: the train step lowered for the TPU holds one
        # forward kernel a layer under "selective", two under "full", and
        # one backward kernel a layer (the dK/dV grid carrying dQ: no
        # ``ddl_flash_bwd_dq`` family).
        assert self._flash_kernels("selective") == {
            "ddl_flash_fwd": 2, "ddl_flash_bwd_dkv": 2}
        assert self._flash_kernels("full")["ddl_flash_fwd"] == 4

    def _flash_kernels(self, policy):
        import collections
        import re
        from unittest import mock

        cfg = self._cfg(
            remat=policy, attn_impl="flash", d_model=256, n_heads=2,
            n_kv_heads=1, max_seq=1024, dtype=jnp.bfloat16,
        )
        params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            text = jax.jit(jax.value_and_grad(
                lambda p, t: llama.next_token_loss(p, t, cfg)
            )).trace(params, jax.ShapeDtypeStruct((1, 1024), jnp.int32)).lower(
                lowering_platforms=("tpu",)).as_text()
        return dict(collections.Counter(
            re.findall(r'kernel_name = "(ddl_flash_\w+)"', text)))

    @staticmethod
    def _flash_stack(family, T, policy):
        from ddl_tpu.models import afmoe, deepseek_v3, moe

        common = dict(vocab=64, max_seq=T, dtype=jnp.float32,
                      attn_impl="flash", remat=policy)
        if family == "llama":
            return llama, llama.LlamaConfig(
                d_model=32, n_layers=2, n_heads=2, n_kv_heads=1, d_ff=64,
                **common)
        if family == "moe":
            return moe, moe.MoeConfig(
                d_model=32, n_layers=2, n_heads=2, n_kv_heads=1, d_ff=64,
                n_experts=4, topk=2, qk_norm=True, **common)
        if family == "afmoe":
            return afmoe, afmoe.AfmoeConfig(
                d_model=32, n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
                d_expert=16, n_experts=8, topk=2,
                layer_types=(afmoe.SLIDING, afmoe.FULL), n_dense_layers=1,
                sliding_window=8, route_scale=2.0, **common)
        return deepseek_v3, deepseek_v3.DeepseekV3Config(
            d_model=32, n_layers=2, n_heads=2, qk_nope_dim=16, qk_rope_dim=8,
            v_head_dim=16, kv_lora_rank=16, d_ff=64, d_expert=16, n_experts=8,
            topk=2, n_shared_experts=1, n_dense_layers=1, route_scale=2.0,
            **common)

    @pytest.mark.parametrize("T", [16, 21], ids=["on_the_block", "off_the_block"])
    @pytest.mark.parametrize("family", ["llama", "moe", "afmoe", "deepseek_v3"])
    def test_selective_is_bit_equal_to_no_remat_through_the_flash_kernels(
            self, family, T):
        """What "selective" saves of a blockwise call — the output and the
        compact logsumexp — is what a second forward call would compute
        again: loss and every gradient leaf equal the no-remat step's to
        the last bit, with the interpreted kernels, at a T that fills its
        (8-row) tile and one whose rows are padded.  Op by op
        (``disable_jit``): under ``jit`` XLA fuses the recomputed norms
        and projections another way and the last bit moves with it, with
        or without kernels."""
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (1, T)), jnp.int32)
        got, key = {}, jax.random.key(0)  # the same weights under both
        for policy in ("none", "selective"):
            mod, cfg = self._flash_stack(family, T, policy)
            params = mod.init_params(cfg, key)
            with jax.disable_jit():
                got[policy] = jax.value_and_grad(
                    lambda p: mod.next_token_loss(p, tokens, cfg))(params)
        (loss_n, grads_n), (loss_s, grads_s) = got["none"], got["selective"]
        assert float(loss_n) == float(loss_s)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads_n),
                                jax.tree.leaves(grads_s)):
            assert float(jnp.max(jnp.abs(a))) > 0 or "bias" in str(path), path
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), jax.tree_util.keystr(path))

    @pytest.mark.parametrize("impl", ["einsum", "ragged"])
    def test_moe_selective_matches_no_remat(self, impl):
        from ddl_tpu.models import moe

        base = dict(
            vocab=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=64, n_experts=4, dtype=jnp.float32, attn_impl="dense",
            capacity_factor=8.0, moe_impl=impl,
        )
        cfg = moe.MoeConfig(**base)
        cfg_r = moe.MoeConfig(**base, remat="selective")
        params = moe.init_params(cfg, jax.random.key(0))
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (2, 16)),
            jnp.int32,
        )
        ln, gn = jax.value_and_grad(
            lambda p: moe.next_token_loss(p, tokens, cfg)
        )(params)
        lr, gr = jax.value_and_grad(
            lambda p: moe.next_token_loss(p, tokens, cfg_r)
        )(params)
        np.testing.assert_allclose(float(ln), float(lr), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(gn), jax.tree.leaves(gr)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-5
            )


class TestMixtral8x7bScale:
    """The pod-scale MoE config (VERDICT r5 weak #8): the sharded MoE
    train step must trace and lower at Mixtral-8x7B scale on an
    fsdp x tp mesh — mirroring llama's 8B lowering test.  Lowering (not
    compiling) validates shapes, shardings, and GSPMD constraints
    without materialising the 47B-parameter pytree."""

    @pytest.mark.slow
    def test_mixtral_train_step_lowers_on_fsdp_tp_mesh(self):
        import optax

        from ddl_tpu.models import moe
        from ddl_tpu.parallel.train import _named, _prune_indivisible

        cfg = moe.MoeConfig.mixtral_8x7b()
        mesh = make_mesh({"dp": 1, "fsdp": 4, "tp": 2})
        opt = optax.adamw(1e-4)

        params_shape = jax.eval_shape(
            lambda: moe.init_params(cfg, jax.random.key(0))
        )
        opt_state_shape = jax.eval_shape(opt.init, params_shape)
        batch = jax.ShapeDtypeStruct((2, 8192), jnp.int32)

        def step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: moe.next_token_loss(p, tokens, cfg, mesh)
            )(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        param_sh = jax.tree.map(
            _prune_indivisible,
            _named(mesh, moe.param_specs(cfg)),
            params_shape,
        )
        lowered = jax.jit(
            step, in_shardings=(param_sh, None, None)
        ).lower(params_shape, opt_state_shape, batch)
        text = lowered.as_text()
        # Mixtral's params really are in the traced program: its vocab
        # (32000) and per-expert hidden (14336) appear, with real
        # matmuls.
        assert "32000" in text
        assert "14336" in text
        assert "stablehlo.dot_general" in text


class TestViT:
    """Vision transformer: the image-pipeline model family."""

    def _cfg(self, **kw):
        from ddl_tpu.models import vit

        base = dict(
            image_size=16, patch_size=4, d_model=32, n_layers=2, n_heads=2,
            d_ff=64, n_classes=5, dtype=jnp.float32,
        )
        base.update(kw)
        return vit.ViTConfig(**base)

    def test_forward_shapes_and_finite(self):
        from ddl_tpu.models import vit

        cfg = self._cfg()
        params = vit.init_params(cfg, jax.random.key(0))
        imgs = jax.random.uniform(jax.random.key(1), (3, 16, 16, 3))
        logits = vit.forward(params, imgs, cfg)
        assert logits.shape == (3, 5)
        assert np.isfinite(np.asarray(logits)).all()
        # Flat pixel rows (the loader layout) give identical results.
        flat = vit.forward(params, imgs.reshape(3, -1), cfg)
        np.testing.assert_allclose(np.asarray(flat), np.asarray(logits))

    def test_flash_matches_dense(self):
        from ddl_tpu.models import vit

        params = vit.init_params(self._cfg(), jax.random.key(0))
        imgs = jax.random.uniform(jax.random.key(1), (2, 16, 16, 3))
        dense = vit.forward(params, imgs, self._cfg(attn_impl="dense"))
        flash = vit.forward(params, imgs, self._cfg(attn_impl="flash"))
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(dense), atol=2e-4, rtol=2e-4
        )

    def test_learns_on_mesh(self):
        from ddl_tpu.models import vit

        cfg = self._cfg()
        mesh = make_mesh({"dp": 4, "tp": 2})
        init_fn, step_fn = make_train_step(
            lambda p, b: vit.classification_loss(p, b, cfg),
            optax.adam(3e-3), mesh, vit.param_specs(cfg),
            batch_spec=P(("dp",)),
        )
        state = init_fn(vit.init_params(cfg, jax.random.key(0)))
        rng = np.random.default_rng(0)
        labels = rng.integers(0, 5, (8, 1)).astype(np.float32)
        # Label-dependent pixels: learnable signal.
        pixels = (
            labels[:, :, None] / 5.0
            + 0.05 * rng.standard_normal((8, 1, 16 * 16 * 3))
        ).reshape(8, -1).astype(np.float32)
        losses = []
        for _ in range(25):
            state, loss = step_fn(state, (pixels, labels))
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, losses

    def test_trains_from_webdataset_loader(self, tmp_path):
        """The full ImageNet-config story: tar image shards -> loader ->
        ViT train step (BASELINE configs[1-2])."""
        from ddl_tpu import DistributedDataLoader, Marker, distributed_dataloader
        from ddl_tpu.models import vit
        from ddl_tpu.readers import WebDatasetProducer
        from datagen import write_image_shard

        for s in range(2):
            write_image_shard(
                str(tmp_path / f"train-{s}.tar"),
                [(f"s{s}k{i}", i % 3) for i in range(8)],
                size=16,
            )
        cfg = self._cfg(n_classes=3)
        mesh = make_mesh({"dp": 8})
        init_fn, step_fn = make_train_step(
            lambda p, b: vit.classification_loss(p, b, cfg),
            optax.adam(1e-3), mesh, vit.param_specs(cfg),
            batch_spec=P(("dp",)),
        )

        @distributed_dataloader(n_producers=2, mode="thread")
        def main(env):
            loader = DistributedDataLoader(
                WebDatasetProducer(
                    str(tmp_path / "train-*.tar"), image_size=16,
                    window_rows=8,
                ),
                batch_size=8, connection=env.connection, n_epochs=2,
                output="numpy",
            )
            state = init_fn(vit.init_params(cfg, jax.random.key(0)))
            losses = []
            for _ in range(2):
                for batch in loader:
                    state, loss = step_fn(state, batch)
                    losses.append(float(loss))
                    loader.mark(Marker.END_OF_BATCH)
                loader.mark(Marker.END_OF_EPOCH)
            return losses

        losses = main()
        assert losses and all(np.isfinite(l) for l in losses)

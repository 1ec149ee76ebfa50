"""OLMoE through ``models/moe.py`` against its plain float32 reference
(``tests/reference_olmoe.py``), at a tiny size on the CPU: hidden 64,
4 heads x 16, 8 experts top-3 of width 32, 2 layers, vocab 256, T 32.

Seeded weights (norm weights moved off 1 so that they count) and tokens.
"""

import dataclasses
import hashlib
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import reference_olmoe as ref
from ddl_tpu.models import decoder, llama, moe

B, T = 2, 32

#: float32 system against float32 reference, as a share of the largest
#: magnitude in the compared array.  Both run the same float32 arithmetic
#: on the CPU and differ in summation order alone (expert rows sorted and
#: summed over 3 slots against a masked sum over 8 experts; attention in
#: one block against the reference's): measured 2e-7 .. 1.5e-6 here.
#: bf16 compute misses it by three orders (the last test of this group).
F32_TOL = 1e-5

#: bf16 system against the float32 reference on the tokens whose top-3
#: sets agree in both layers, logits, as a share of the reference logits'
#: root mean square, in units of bf16's roundoff u = 2^-9 (8 bits of
#: mantissa).  A layer rounds the residual stream and its matmul operands
#: about ten times, so two layers and the head leave a few u times
#: sqrt(20) in a logit.  Measured over four seeds: rms 7.3-8.3 u, the
#: worst logit 57-73 u (one in 16,384).  The reference computed in
#: float8_e4m3fn (3 bits, the next precision down) reads rms 107-129 u and
#: worst 690-830 u, and is refused (last test of the bf16 group).
U_BF16 = 2.0**-9
BF16_RMS_TOL = 16 * U_BF16
BF16_MAX_TOL = 128 * U_BF16


def tiny(**kw) -> moe.MoeConfig:
    base = dict(
        vocab=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=32,
        n_experts=8, topk=3, max_seq=T, rope_theta=10000.0, norm_eps=1e-5,
        dtype=jnp.float32, param_dtype=jnp.float32, qk_norm=True,
        norm_topk_prob=False, router_aux_weight=0.01,
        router_aux_all_slots=True, router_z_weight=0.001,
    )
    base.update(kw)
    return moe.MoeConfig(**base)


def ref_config(cfg: moe.MoeConfig) -> ref.Config:
    return ref.Config(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_experts=cfg.n_experts, topk=cfg.topk, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, norm_topk_prob=False,
        router_aux_weight=0.01, router_z_weight=0.001, query_block=8,
    )


@pytest.fixture(scope="module")
def case():
    """Seeded parameters (float32) and tokens.  The router's weights are
    scaled up so that routing is decided, not nearly uniform."""
    cfg = tiny()
    params = moe.init_params(cfg, jax.random.key(26))
    keys = iter(jax.random.split(jax.random.key(27), 64))

    def off_one(x):
        return x + 0.2 * jax.random.normal(next(keys), x.shape, x.dtype)

    for layer in params["layers"]:
        for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
            layer[name] = off_one(layer[name])
        layer["w_router"] = 4.0 * layer["w_router"]
    params["final_norm"] = off_one(params["final_norm"])
    tokens = jnp.asarray(
        np.random.default_rng(26).integers(0, cfg.vocab, (B, T)), jnp.int32
    )
    return cfg, params, tokens


def close(got, want, tol, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    err = float(np.max(np.abs(got - want))) / scale
    assert err <= tol, f"{what}: {err:.3g} of the largest magnitude, limit {tol}"


def assert_matches_reference(cfg, params, tokens, tol=F32_TOL):
    """Logits, loss and every gradient leaf of the system against the
    reference's."""
    c = ref_config(cfg)
    want_logits, _, _, want_picks = ref.forward(params, tokens, c)
    want_loss, want_grads = ref.loss_and_grads(params, tokens, c)
    got_logits, got_picks = moe.forward_with_choices(params, tokens, cfg)
    got_loss, got_grads = jax.value_and_grad(
        lambda p: moe.next_token_loss(p, tokens, cfg)
    )(params)
    if cfg.qk_norm:  # else there is no routing to speak of: the hidden states differ
        np.testing.assert_array_equal(
            np.sort(np.asarray(got_picks), -1),
            np.sort(np.asarray(want_picks), -1),
        )
    close(got_logits, want_logits, tol, "logits")
    close(got_loss, want_loss, tol, "loss")
    got_leaves = dict(jax.tree_util.tree_leaves_with_path(got_grads))
    for path, want in jax.tree_util.tree_leaves_with_path(want_grads):
        close(got_leaves[path], want, tol, "d" + jax.tree_util.keystr(path))


# -- float32: the system is the reference --------------------------------------


def test_float32_system_matches_the_reference(case):
    assert_matches_reference(*case)


def test_the_einsum_dispatch_matches_it_too_where_nothing_is_dropped(case):
    cfg, params, tokens = case
    roomy = dataclasses.replace(cfg, moe_impl="einsum", capacity_factor=8.0)
    assert_matches_reference(roomy, params, tokens)


def _per_head_qkv(layer, h, cfg, positions, n_heads=None, n_kv_heads=None):
    """``llama._attn_qkv`` with the norm taken per head, as Qwen3 or
    OLMo-2's per-head variants have it: not what OLMoE does."""
    B_, T_ = h.shape[:2]

    def project(w, norm, heads):
        y = (h @ layer[w]).reshape(B_, T_, heads, cfg.head_dim)
        if norm:
            y = decoder.rms_norm(
                y, layer[norm].reshape(heads, cfg.head_dim), cfg.norm_eps
            )
        return y

    q = project("wq", "q_norm", cfg.n_heads)
    k = project("wk", "k_norm", cfg.n_kv_heads)
    return (
        decoder.rope(q, positions, cfg.rope_theta),
        decoder.rope(k, positions, cfg.rope_theta),
        project("wv", None, cfg.n_kv_heads),
    )


@pytest.mark.parametrize("left_out", [
    "renormalised_gates", "slot0_only_aux", "no_qk_norm", "norm_per_head",
    "no_z_loss",
])
def test_leaving_out_part_of_the_mathematics_fails(case, left_out, monkeypatch):
    cfg, params, tokens = case
    if left_out == "norm_per_head":
        monkeypatch.setattr(llama, "_attn_qkv", _per_head_qkv)
    else:
        cfg = dataclasses.replace(cfg, **{
            "renormalised_gates": {"norm_topk_prob": True},
            "slot0_only_aux": {"router_aux_all_slots": False},
            "no_qk_norm": {"qk_norm": False},
            "no_z_loss": {"router_z_weight": 0.0},
        }[left_out])
    with pytest.raises(AssertionError):
        assert_matches_reference(cfg, params, tokens)


def test_a_float32_configuration_run_in_bf16_fails_the_float32_tolerance(case):
    cfg, params, tokens = case
    with pytest.raises(AssertionError):
        assert_matches_reference(
            dataclasses.replace(cfg, dtype=jnp.bfloat16), params, tokens
        )


# -- bf16 compute: compared where the routers agree -----------------------------


def bf16_errors(got_logits, got_picks, want_logits, want_picks):
    """(share of tokens whose top-k sets agree in every layer, rms and
    worst logit error on those tokens as shares of the reference's rms)."""
    same = np.all(
        np.sort(np.asarray(got_picks), -1) == np.sort(np.asarray(want_picks), -1),
        axis=(0, -1),
    )  # (B, T)
    want = np.asarray(want_logits, np.float64)[same]
    diff = np.asarray(got_logits, np.float64)[same] - want
    rms = float(np.sqrt(np.mean(want**2)))
    return (
        float(same.mean()),
        float(np.sqrt(np.mean(diff**2))) / rms,
        float(np.max(np.abs(diff))) / rms,
    )


@pytest.fixture(scope="module")
def bf16_case(case):
    """The same weights as bf16 can hold them, on both sides."""
    cfg, params, tokens = case
    stored = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    exact = jax.tree.map(lambda x: x.astype(jnp.float32), stored)
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    want = ref.forward(exact, tokens, ref_config(cfg))
    return cfg, stored, exact, tokens, want


def test_bf16_system_routes_and_scores_as_the_reference(bf16_case):
    cfg, stored, exact, tokens, (want_logits, _, _, want_picks) = bf16_case
    got_logits, got_picks = moe.forward_with_choices(stored, tokens, cfg)
    agree, rms, worst = bf16_errors(got_logits, got_picks, want_logits, want_picks)
    # Token by token: a rounding may flip a last choice, not many.
    assert agree >= 0.75, agree
    assert rms <= BF16_RMS_TOL and worst <= BF16_MAX_TOL, (agree, rms, worst)
    got_loss = moe.next_token_loss(stored, tokens, cfg)
    want_loss = ref.loss(exact, tokens, ref_config(cfg))
    assert abs(float(got_loss) - float(want_loss)) <= 2e-3 * float(want_loss)


def test_the_next_precision_down_is_outside_the_bf16_tolerance(bf16_case):
    cfg, _, exact, tokens, (want_logits, _, _, want_picks) = bf16_case
    low_logits, _, _, low_picks = ref.forward(
        exact, tokens, ref_config(cfg), compute_dtype=jnp.float8_e4m3fn
    )
    _, rms, worst = bf16_errors(low_logits, low_picks, want_logits, want_picks)
    assert rms > 4 * BF16_RMS_TOL and worst > 4 * BF16_MAX_TOL, (rms, worst)


# -- what the shared attention block may not do to the other models ------------

#: ``llama.forward`` on the commit before QK-norm existed (69ae942):
#: LlamaConfig(dtype=float32), init key 7, tokens default_rng(7) (2, 16).
PARENT_JAX = "0.9.0"
PARENT_PROGRAM_SHA256 = (
    "5614e2756b5715ec2231bb5d26aca1550e10b8f974f30ae342ba31c2ce209c14"
)
PARENT_PARAMS_CRC = 2583417954
PARENT_LOGITS_CRC = 2430373789
PARENT_LOGITS_SUM = 290.27875421143835
PARENT_TREE = (
    "PyTreeDef({'embed': *, 'final_norm': *, 'layers': [{'attn_norm': *, "
    "'mlp_norm': *, 'w_down': *, 'w_gate': *, 'w_up': *, 'wk': *, 'wo': *, "
    "'wq': *, 'wv': *}, {'attn_norm': *, 'mlp_norm': *, 'w_down': *, "
    "'w_gate': *, 'w_up': *, 'wk': *, 'wo': *, 'wq': *, 'wv': *}], "
    "'lm_head': *})"
)


def test_qk_norm_off_leaves_llama_as_the_parent_had_it():
    cfg = llama.LlamaConfig(dtype=jnp.float32)
    assert cfg.qk_norm is False
    params = llama.init_params(cfg, jax.random.key(7))
    assert str(jax.tree_util.tree_structure(params)) == PARENT_TREE
    assert jax.tree_util.tree_structure(
        llama.param_specs(cfg), is_leaf=lambda x: isinstance(x, P)
    ) == jax.tree_util.tree_structure(params)
    leaves = b"".join(np.asarray(x).tobytes() for x in jax.tree.leaves(params))
    assert zlib.crc32(leaves) == PARENT_PARAMS_CRC
    tokens = jnp.asarray(
        np.random.default_rng(7).integers(0, cfg.vocab, (2, 16)), jnp.int32
    )
    fwd = jax.jit(lambda p, t: llama.forward(p, t, cfg))
    if jax.__version__ == PARENT_JAX:  # the text is this JAX's
        program = fwd.lower(params, tokens).as_text()
        assert hashlib.sha256(program.encode()).hexdigest() == PARENT_PROGRAM_SHA256
    logits = np.asarray(fwd(params, tokens))
    np.testing.assert_allclose(
        float(logits.astype(np.float64).sum()), PARENT_LOGITS_SUM, rtol=1e-9
    )
    assert zlib.crc32(logits.tobytes()) == PARENT_LOGITS_CRC


def test_qk_norm_on_adds_two_vectors_a_layer_and_decode_takes_them(case):
    cfg, params, tokens = case
    assert params["layers"][0]["q_norm"].shape == (cfg.n_heads * cfg.head_dim,)
    assert params["layers"][0]["k_norm"].shape == (cfg.n_kv_heads * cfg.head_dim,)
    assert jax.tree_util.tree_structure(
        moe.param_specs(cfg), is_leaf=lambda x: isinstance(x, P)
    ) == jax.tree_util.tree_structure(params)
    lcfg = llama.LlamaConfig(qk_norm=True)
    assert "q_norm" in llama.init_params(lcfg, jax.random.key(0))["layers"][0]
    assert "k_norm" in llama.param_specs(lcfg)["layers"][0]
    # Train and decode share _attn_qkv: prefill through the cache is the
    # plain forward.
    full, _ = moe.forward(params, tokens, cfg)
    cached, _ = moe.forward_with_cache(
        params, tokens, cfg, moe.init_cache(cfg, B, T), jnp.int32(0)
    )
    np.testing.assert_allclose(
        np.asarray(cached), np.asarray(full), rtol=2e-5, atol=2e-5
    )


def test_a_tp_resident_stage_refuses_qk_norm(case):
    cfg, params, tokens = case
    h = jnp.zeros((B, T, cfg.d_model), jnp.float32)
    with pytest.raises(NotImplementedError, match="whole projection"):
        llama._attn_qkv(
            params["layers"][0], h, cfg, jnp.arange(T), n_heads=2, n_kv_heads=2
        )


# -- the dispatch is chosen from what the mesh shows ---------------------------


def test_auto_dispatch_is_dropless_unless_an_ep_axis_shards_the_experts():
    from ddl_tpu.parallel.mesh import make_mesh

    cfg = moe.MoeConfig()
    assert cfg.moe_impl == "auto"
    assert moe._resolve_impl(cfg, None).moe_impl == "ragged"
    assert moe._resolve_impl(cfg, make_mesh({"dp": 8})).moe_impl == "ragged"
    assert moe._resolve_impl(
        cfg, make_mesh({"dp": 2, "ep": 4})
    ).moe_impl == "einsum"
    forced = dataclasses.replace(cfg, moe_impl="ragged")
    with pytest.raises(ValueError, match="ep>1"):
        moe._resolve_impl(forced, make_mesh({"ep": 8}))


def test_the_default_config_keeps_its_router_as_it_was():
    """``MoeConfig()`` still renormalises, scores slot 0 and has no
    z-loss: ``tests/test_moe.py`` keeps its meaning."""
    cfg = moe.MoeConfig()
    assert (cfg.norm_topk_prob, cfg.router_aux_all_slots, cfg.router_z_weight,
            cfg.qk_norm) == (True, False, 0.0, False)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((64, 128)), jnp.float32)
    layer = moe.init_params(
        dataclasses.replace(cfg, dtype=jnp.float32), jax.random.key(0)
    )["layers"][0]
    probs, top_p, top_e, z = moe._router_topk(x, layer, cfg)
    np.testing.assert_allclose(np.asarray(top_p.sum(-1)), 1.0, rtol=1e-6)
    balance, z_loss = moe._router_losses(probs, top_e, z, cfg)
    slot0 = np.bincount(np.asarray(top_e[:, 0]), minlength=cfg.n_experts) / 64
    np.testing.assert_allclose(
        float(balance),
        cfg.n_experts * float(np.sum(slot0 * np.asarray(probs).mean(0))),
        rtol=1e-6,
    )
    raw = moe._router_topk(
        x, layer, dataclasses.replace(cfg, norm_topk_prob=False)
    )[1]
    assert float(raw.sum(-1).max()) < 1.0


def test_the_preset_states_the_published_architecture():
    cfg = moe.MoeConfig.olmoe_1b_7b()
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.n_experts, cfg.topk, cfg.vocab,
            cfg.max_seq) == (2048, 16, 16, 16, 128, 1024, 64, 8, 50304, 4096)
    assert cfg.qk_norm and not cfg.norm_topk_prob and cfg.router_aux_all_slots
    shapes = moe.param_shapes(cfg)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 6.9e9 < n < 6.93e9  # "1B-7B": 6.9 B parameters in all


# -- the benchmark's copies cannot drift ----------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _body(path):
    """A module's source from its first import on (its docstring may say
    where the file lives)."""
    with open(path) as f:
        text = f.read()
    return text[text.index("\nfrom __future__"):]


def test_the_benchmarks_reference_is_this_one():
    assert _body(os.path.join(ROOT, "tests", "reference_olmoe.py")) == _body(
        os.path.join(ROOT, "benchmarks", "lib", "olmoe_reference.py")
    )


def test_the_preset_is_what_the_benchmark_builds_at_full_depth():
    import json
    import sys

    sys.path.insert(0, ROOT)
    from benchmarks.families import olmoe as family

    with open(os.path.join(ROOT, "benchmarks/configs/olmoe-1b-7b.json")) as f:
        c = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/jobs/tokens-4k.json")) as f:
        mix = json.load(f)
    c["num_hidden_layers"] = c["published"]["num_hidden_layers"]
    built = family.model_config(c, mix)
    preset = moe.MoeConfig.olmoe_1b_7b()
    # remat is the training section's choice, not the architecture's.
    assert dataclasses.replace(built, remat=preset.remat) == preset


# -- through the Trainer, loader live --------------------------------------------


def test_trainer_fit_reproduces_the_plain_loops_first_window():
    from ddl_tpu import DataProducerOnInitReturn, ProducerFunctionSkeleton
    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.trainer import Trainer

    cfg = tiny(remat="selective")
    rows, batch = 8, 4

    def window(iteration):
        return np.random.default_rng([26, iteration]).integers(
            0, cfg.vocab, (rows, T), dtype=np.int32
        )

    class TokenWindows(ProducerFunctionSkeleton):
        inplace_fill = True  # every fill rewrites the whole slot

        def on_init(self, producer_idx=0, **kw):
            return DataProducerOnInitReturn(
                nData=rows, nValues=T, shape=(rows, T), splits=(T,),
                dtype=np.int32,
            )

        def execute_function(self, my_ary, iteration=0, **kw):
            my_ary[:] = window(iteration)

    params = moe.init_params(cfg, jax.random.key(3))
    optimizer = optax.adamw(1e-3)

    def loss_fn(p, b):
        return moe.next_token_loss(p, b[0], cfg)

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        loss_fn=loss_fn, optimizer=optimizer, mesh=mesh,
        param_specs=moe.param_specs(cfg), init_params=params,
        batch_spec=P(("dp",)), watchdog=False,
    )
    res = trainer.fit(
        TokenWindows(), batch_size=batch, n_epochs=2, n_producers=1,
        mode="thread", output="jax", window_stream=True,
    )
    assert len(res.losses) == 2 and all(np.isfinite(v) for v in res.losses)

    @jax.jit
    def step(p, o, b):
        loss, grads = jax.value_and_grad(loss_fn)(p, b)
        updates, o = optimizer.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    p, o, losses = params, optimizer.init(params), []
    for lo in range(0, rows, batch):
        p, o, loss = step(p, o, (jnp.asarray(window(0)[lo : lo + batch]),))
        losses.append(float(loss))
    np.testing.assert_allclose(res.losses[0], np.mean(losses), rtol=1e-5)
    # ... and the loss is the reference's on the same first batch.
    want = ref.loss(params, jnp.asarray(window(0)[:batch]), ref_config(cfg))
    np.testing.assert_allclose(losses[0], float(want), rtol=F32_TOL)

"""DeepSeek-V3's architecture at Kanana-2-30B-A3B's shape through
``models/deepseek_v3.py`` against its plain float32 reference
(``tests/reference_deepseek_v3.py``), at a tiny size on the CPU: hidden 64,
4 heads of 16 + 8 score and 16 value width over a 32-wide latent, one
dense layer of width 96 then two expert layers of 16 experts x 32, top-3,
plus 2 shared; vocab 256, T 40.

Seeded weights (norm weights moved off 1 and the router scaled up, so
that both count) and tokens.
"""

import dataclasses
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import reference_deepseek_v3 as ref
from ddl_tpu.models import afmoe, decoder, deepseek_v3, llama, moe
from ddl_tpu.ops import flash_attention, flash_tile
from ddl_tpu.parallel import ring_attention
from ddl_tpu.parallel.ring_attention import attention, attention_reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, T = 2, 40

#: float32 system against float32 reference, as a share of the largest
#: magnitude in the compared array: the same float32 arithmetic on the CPU,
#: differing in summation order alone (two score products against one
#: 24-wide one; expert rows sorted and summed over 3 slots against a
#: masked sum over the held experts).  Measured up to 2e-6.
F32_TOL = 1e-5


def tiny(**kw) -> deepseek_v3.DeepseekV3Config:
    base = dict(
        vocab=256, d_model=64, n_layers=3, n_heads=4, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, kv_lora_rank=32, d_ff=96, d_expert=32,
        n_experts=16, topk=3, n_shared_experts=2, n_dense_layers=1,
        route_scale=2.448, max_seq=T, dtype=jnp.float32,
        param_dtype=jnp.float32,
    )
    base.update(kw)
    return deepseek_v3.DeepseekV3Config(**base)


def ref_config(cfg, **kw) -> ref.Config:
    return ref.Config(
        n_heads=cfg.n_heads, qk_nope_dim=cfg.qk_nope_dim,
        qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, n_experts=cfg.n_experts, topk=cfg.topk,
        n_dense_layers=cfg.n_dense_layers, held=cfg.held,
        route_norm=cfg.route_norm, route_scale=cfg.route_scale,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps, query_block=8,
    )._replace(**kw)


def seeded(cfg):
    """Parameters with every norm weight moved off 1, the selection bias
    off 0 and the router scaled up so that routing is decided."""
    params = deepseek_v3.init_params(cfg, jax.random.key(32))
    keys = iter(jax.random.split(jax.random.key(33), 128))

    def off(x, by=0.2):
        return x + by * jax.random.normal(next(keys), x.shape, x.dtype)

    for layer in params["layers"]:
        for name in ("attn_norm", "mlp_norm", "kv_a_norm"):
            layer[name] = off(layer[name])
        if "w_router" in layer:
            layer["w_router"] = 4.0 * layer["w_router"]
            layer["expert_bias"] = off(layer["expert_bias"], 0.05)
    params["final_norm"] = off(params["final_norm"])
    return params


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(
        np.random.default_rng(32).integers(0, 256, (B, T)), jnp.int32
    )


def close(got, want, tol, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    err = float(np.max(np.abs(got - want))) / scale
    assert err <= tol, f"{what}: {err:.3g} of the largest magnitude, limit {tol}"


def assert_matches_reference(cfg, params, tokens, c=None, tol=F32_TOL):
    """Logits, loss, the routers' picks and every gradient leaf."""
    c = c or ref_config(cfg)
    want_logits, want_picks = jax.jit(lambda p: ref.forward(p, tokens, c))(params)
    want_loss, want_grads = jax.jit(
        lambda p: ref.loss_and_grads(p, tokens, c)
    )(params)
    got_logits, got_picks = jax.jit(
        lambda p: deepseek_v3.forward_with_choices(p, tokens, cfg)
    )(params)
    got_loss, got_grads = jax.jit(jax.value_and_grad(
        lambda p: deepseek_v3.next_token_loss(p, tokens, cfg)
    ))(params)
    assert got_picks.shape == (cfg.n_layers - cfg.n_dense_layers, B, T, cfg.topk)
    np.testing.assert_array_equal(
        np.sort(np.asarray(got_picks), -1), np.sort(np.asarray(want_picks), -1)
    )
    close(got_logits, want_logits, tol, "logits")
    close(got_loss, want_loss, tol, "loss")
    got_leaves = dict(jax.tree_util.tree_leaves_with_path(got_grads))
    assert set(got_leaves) == set(dict(jax.tree_util.tree_leaves_with_path(want_grads)))
    for path, want in jax.tree_util.tree_leaves_with_path(want_grads):
        close(got_leaves[path], want, tol, "d" + jax.tree_util.keystr(path))
    return got_grads


# -- float32: the system is the reference ----------------------------------------


@pytest.mark.parametrize("held,remat", [
    (None, "none"), (None, "selective"), ((4, 4), "selective"),
    ((12, 4), "full"), ((0, 1), "none"),
], ids=["uncut", "uncut_selective", "share_4_7_selective", "share_12_15_full",
        "one_expert"])
def test_float32_system_matches_the_reference(tokens, held, remat):
    cfg = tiny(held_experts=held, remat=remat)
    params = seeded(cfg)
    assert params["layers"][1]["experts"]["w_gate"].shape[0] == cfg.held[1]
    grads = assert_matches_reference(cfg, params, tokens)
    # The bias enters the selection only.
    assert float(jnp.max(jnp.abs(grads["layers"][1]["expert_bias"]))) == 0.0
    # A share does not train its router; the uncut model does.
    router = float(jnp.max(jnp.abs(grads["layers"][1]["w_router"])))
    assert (router == 0.0) == (held is not None)


def test_the_interpreted_mla_kernels_match_it_too(tokens):
    """The interpreted Pallas kernels in place of the dense scores."""
    cfg = tiny(attn_impl="flash", held_experts=(4, 4))
    assert_matches_reference(cfg, seeded(cfg), tokens)


@pytest.mark.parametrize("left_out", [
    "rope_product_omitted", "scale_of_the_nope_width", "kv_a_norm_missing",
    "rope_on_the_nope_part", "half_split_rope_in_the_reference",
    "bias_in_the_weights", "unnormalised_routes", "unscaled_routes",
    "one_shared_expert", "no_shared_expert", "another_share",
])
def test_leaving_out_part_of_the_mathematics_fails(tokens, left_out, monkeypatch):
    cfg = tiny(held_experts=(4, 4))
    params = seeded(cfg)
    c = None
    real_attention = ring_attention.attention
    if left_out == "rope_product_omitted":
        monkeypatch.setattr(
            ring_attention, "attention",
            lambda q, k, v, q_rope, k_rope, **kw: real_attention(
                q, k, v, q_rope=0 * q_rope, k_rope=k_rope, **kw),
        )
    elif left_out == "scale_of_the_nope_width":
        wrong = (24 / 16) ** 0.5  # 1/sqrt(16) where 1/sqrt(16 + 8) belongs
        monkeypatch.setattr(
            ring_attention, "attention",
            lambda q, k, v, q_rope, k_rope, **kw: real_attention(
                wrong * q, k, v, q_rope=wrong * q_rope, k_rope=k_rope, **kw),
        )
    elif left_out == "kv_a_norm_missing":
        real_norm = decoder.rms_norm
        monkeypatch.setattr(
            decoder, "rms_norm",
            lambda x, gain, eps: x if x.shape[-1] == 32 else real_norm(x, gain, eps),
        )
    elif left_out == "rope_on_the_nope_part":
        monkeypatch.setattr(
            ring_attention, "attention",
            lambda q, k, v, q_rope, k_rope, **kw: real_attention(
                decoder.rope(q, jnp.arange(T), 1e6), decoder.rope(k, jnp.arange(T), 1e6),
                v, q_rope=q_rope, k_rope=k_rope, **kw),
        )
    elif left_out == "half_split_rope_in_the_reference":
        # Without the de-interleave the program's rotation pairs (x[i],
        # x[i + R/2]): another function of the same weights.
        monkeypatch.setattr(
            deepseek_v3, "_rope_pairs", lambda x, pos, theta: decoder.rope(x, pos, theta)
        )
    elif left_out == "bias_in_the_weights":
        monkeypatch.setattr(jax.lax, "stop_gradient", lambda x: x)
        real = moe.sigmoid_route

        def biased(h, layer, cfg):
            top_w, top_e = real(h, layer, cfg)
            return top_w + 0.1 * layer["expert_bias"][top_e], top_e

        monkeypatch.setattr(moe, "sigmoid_route", biased)
    elif left_out == "unnormalised_routes":
        cfg = dataclasses.replace(cfg, route_norm=False)
        c = ref_config(cfg, route_norm=True)
    elif left_out == "unscaled_routes":
        cfg = dataclasses.replace(cfg, route_scale=1.0)
        c = ref_config(cfg, route_scale=2.448)
    elif left_out == "one_shared_expert":
        # One shared expert of d_expert where the config states two: the
        # first half of the shared SwiGLU's width alone.
        def half(w):
            return {k: x[:32] if k == "w_down" else x[:, :32] for k, x in w.items()}

        monkeypatch.setattr(
            decoder, "swiglu",
            lambda layer, h, real=decoder.swiglu: (
                real(layer, h) if layer["w_gate"].shape[-1] != 64
                else real(half(layer), h)
            ),
        )
    elif left_out == "no_shared_expert":
        monkeypatch.setattr(
            decoder, "swiglu",
            lambda layer, h, real=decoder.swiglu: (
                real(layer, h) if layer["w_gate"].shape[-1] != 64
                else jnp.zeros_like(h)
            ),
        )
    elif left_out == "another_share":
        c = ref_config(cfg, held=(8, 4))
    with pytest.raises(AssertionError):
        assert_matches_reference(cfg, params, tokens, c)


def test_a_float32_configuration_run_in_bf16_fails_the_float32_tolerance(tokens):
    cfg = tiny(held_experts=(4, 4))
    with pytest.raises(AssertionError):
        assert_matches_reference(
            dataclasses.replace(cfg, dtype=jnp.bfloat16), seeded(cfg), tokens
        )


def test_a_bf16_reference_fails_the_float32_tolerance(tokens):
    cfg = tiny(held_experts=(4, 4))
    params = seeded(cfg)
    c = ref_config(cfg)
    want, _ = ref.forward(params, tokens, c)
    lower, _ = ref.forward(params, tokens, c, jnp.bfloat16)
    with pytest.raises(AssertionError):
        close(lower, want, F32_TOL, "logits")


# -- the kernels -----------------------------------------------------------------


def _mla_operands(T, dtype=jnp.float32, H=3, D=32, R=16):
    ks = jax.random.split(jax.random.key(T), 6)
    q, k, v, w = (jax.random.normal(kk, (B, T, H, D), dtype) for kk in ks[:4])
    q_rope = jax.random.normal(ks[4], (B, T, H, R), dtype)
    k_rope = jax.random.normal(ks[5], (B, T, 1, R), dtype)
    return (q, k, v, q_rope, k_rope), w


@pytest.mark.parametrize("T_,block_q,block_k", [
    (64, 16, 16), (64, 32, 16), (50, 16, 32), (24, 512, 512), (33, 16, 16),
], ids=["on_the_block", "bq_twice_bk", "off_the_block", "one_block", "ragged"])
def test_the_mla_kernels_are_the_dense_scores(T_, block_q, block_k):
    """Forward and all five gradients - ``dk_rope`` the sum over the heads
    of the one shared key's - in interpret mode against the dense oracle,
    at T on and off the block size."""
    operands, w = _mla_operands(T_)

    def flash(q, k, v, q_rope, k_rope):
        return flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                               q_rope=q_rope, k_rope=k_rope)

    def dense(q, k, v, q_rope, k_rope):
        return attention_reference(q, k, v, q_rope=q_rope, k_rope=k_rope)

    got, got_grads = jax.value_and_grad(
        lambda *a: jnp.sum(flash(*a) * w), argnums=range(5))(*operands)
    want, want_grads = jax.value_and_grad(
        lambda *a: jnp.sum(dense(*a) * w), argnums=range(5))(*operands)
    close(flash(*operands), dense(*operands), 2e-6, "output")
    for name, g, wg in zip(("dq", "dk", "dv", "dq_rope", "dk_rope"),
                           got_grads, want_grads):
        assert g.shape == wg.shape
        close(g, wg, 5e-6, name)
    # The oracle's scores are the materialised 48-wide product's.
    q, k, v, q_rope, k_rope = operands
    wide_q = jnp.concatenate([q, q_rope], -1)
    wide_k = jnp.concatenate([k, jnp.broadcast_to(k_rope, q_rope.shape)], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", wide_q, wide_k) / np.sqrt(48)
    s = jnp.where(np.tril(np.ones((T_, T_), bool)), s, -jnp.inf)
    close(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v),
          dense(*operands), 2e-6, "materialised")


def test_the_dispatcher_takes_the_latent_form_on_every_local_strategy():
    from ddl_tpu.parallel.mesh import make_mesh

    (q, k, v, q_rope, k_rope), _ = _mla_operands(32, H=4)
    want = attention_reference(q, k, v, q_rope=q_rope, k_rope=k_rope)
    rope = dict(q_rope=q_rope, k_rope=k_rope)
    close(attention(q, k, v, impl="dense", **rope), want, 1e-6, "dense")
    close(attention(q, k, v, impl="flash", **rope), want, 2e-6, "flash")
    mesh = make_mesh({"dp": 2, "tp": 2}, jax.devices()[:4])
    got = jax.jit(lambda *a: attention(*a[:3], mesh=mesh, impl="flash",
                                       q_rope=a[3], k_rope=a[4]))(
        q, k, v, q_rope, k_rope)
    close(got, want, 2e-6, "dp x tp shards")


def test_what_has_no_latent_form_refuses_it_by_name():
    from ddl_tpu.parallel.mesh import make_mesh

    (q, k, v, q_rope, k_rope), _ = _mla_operands(32, H=4)
    rope = dict(q_rope=q_rope, k_rope=k_rope)
    with pytest.raises(NotImplementedError, match="no latent form"):
        attention(q, k, v, mesh=make_mesh({"sp": 2}, jax.devices()[:2]), **rope)
    for bad in (dict(window=8), dict(causal=False), dict(kv_repeat=2),
                dict(segment_ids=jnp.zeros((B, 32), jnp.int32))):
        with pytest.raises(NotImplementedError, match="latent form"):
            flash_attention(q, k, v, **rope, **bad)
    with pytest.raises(ValueError, match="come together"):
        flash_attention(q, k, v, q_rope=q_rope)
    with pytest.raises(ValueError, match="shared k_rope"):
        flash_attention(q, k, v, q_rope=q_rope,
                        k_rope=jnp.broadcast_to(k_rope, q_rope.shape))
    # The one-block kernels have one product: they refuse the form.
    assert flash_tile.fits(q, k, v, 1, 512, 512, None)
    assert not flash_tile.fits(q, k, v, 1, 512, 512, None, q_rope=q_rope)


# -- the share ---------------------------------------------------------------------


@pytest.mark.parametrize("n_tokens,favoured", [(B * T, ()), (512, (0, 1, 9))],
                         ids=["no_bound_at_this_size", "shares_past_their_bound"])
def test_the_shares_add_up_to_the_uncut_layer(n_tokens, favoured):
    """One expert layer's MLP on the same hidden states: the routed parts
    that all 8 shares of 2 experts give, plus the shared experts - which
    every chip computes alike - counted once, are what the uncut reference
    gives for the whole layer."""
    whole = tiny()
    layer = seeded(whole)["layers"][2]
    # Every token picks the favoured experts: their shares get more rows
    # than the static bound of held rows (``moe.held_row_bound``: twice the
    # balanced share, 512 rows here) and run at full width, the others
    # under the bound - and the parts still add up, nothing dropped.
    bias = np.zeros(whole.n_experts, np.float32)
    bias[list(favoured)] = 10.0
    layer = {**layer, "expert_bias": layer["expert_bias"] + bias}
    bound = moe.held_row_bound(n_tokens * whole.topk, 2, whole.n_experts)
    h = jax.random.normal(jax.random.key(5), (n_tokens, whole.d_model), jnp.float32)
    want, want_picks = ref.expert_mlp(h, layer, ref_config(whole))
    shared = decoder.swiglu(layer["shared"], h)

    routed = jnp.zeros_like(h)
    held_choices, past_the_bound = 0, []
    for first in range(0, whole.n_experts, 2):
        cfg = tiny(held_experts=(first, 2))
        mine = {**layer, "experts": jax.tree.map(
            lambda w: w[first : first + 2], layer["experts"]
        )}
        out, picks = moe.sigmoid_expert_tokens(h, mine, cfg)
        np.testing.assert_array_equal(np.asarray(picks), np.asarray(want_picks))
        # ... and the share is the reference's share.
        share_want, _ = ref.expert_mlp(h, mine, ref_config(cfg))
        close(out, share_want, F32_TOL, f"share {first}")
        routed = routed + (out - shared)
        mine_held = int(np.sum((picks >= first) & (picks < first + 2)))
        past_the_bound.append(mine_held > bound)
        held_choices += mine_held
    assert held_choices == n_tokens * whole.topk  # every choice is held once
    if favoured:  # both branches ran: share 0 (two favoured experts) at full width
        assert bound == 512 < n_tokens * whole.topk
        assert past_the_bound[0] and not all(past_the_bound), past_the_bound
    else:
        assert bound == n_tokens * whole.topk  # no bound below a row tile
    close(shared + routed, want, F32_TOL, "sum of the shares")
    # The uncut system layer is the same thing in one piece.
    close(moe.sigmoid_expert_tokens(h, layer, whole)[0], want, F32_TOL, "uncut")


def test_both_sigmoid_routed_families_run_one_expert_routine():
    """Trinity-Mini's family and this one call ``moe.sigmoid_expert_mlp``:
    no copy of the routine in either module."""
    import inspect

    assert afmoe._moe_mlp is moe.sigmoid_expert_mlp
    assert afmoe._moe_tokens is moe.sigmoid_expert_tokens
    for module in (afmoe, deepseek_v3):  # no router of their own
        assert not hasattr(module, "_route") and "lax.top_k" not in inspect.getsource(module)
    assert "_moe.sigmoid_expert_mlp(" in inspect.getsource(deepseek_v3._layer_apply)


def test_a_token_sharded_mesh_routes_per_shard_to_the_same_result(tokens):
    from ddl_tpu.parallel.mesh import make_mesh

    cfg = tiny(held_experts=(4, 4))
    params = seeded(cfg)
    want, want_picks = deepseek_v3.forward_with_choices(params, tokens, cfg)
    mesh = make_mesh({"dp": 2}, jax.devices()[:2])
    got, picks = jax.jit(
        lambda p, t: deepseek_v3.forward_with_choices(p, t, cfg, mesh)
    )(params, tokens)
    np.testing.assert_array_equal(np.asarray(picks), np.asarray(want_picks))
    close(got, want, F32_TOL, "logits on dp=2")


# -- the config states the architecture ---------------------------------------------


def test_the_preset_states_the_published_architecture():
    cfg = deepseek_v3.DeepseekV3Config.kanana_2_30b_a3b()
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank, cfg.d_ff,
            cfg.d_expert, cfg.n_experts, cfg.topk, cfg.n_shared_experts,
            cfg.vocab) == (
        2048, 48, 32, 128, 64, 128, 512, 6144, 768, 128, 6, 2, 128256)
    assert cfg.n_dense_layers == 1 and cfg.route_scale == 2.448
    assert cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-6 and cfg.held == (0, 128)
    shapes = jax.eval_shape(lambda: deepseek_v3.init_params(cfg, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 30.0e9 < n < 30.8e9  # "30B total": 30.67 B
    layer = shapes["layers"][2]
    attn = sum(int(np.prod(layer[k].shape)) for k in ("wq", "wkv_a", "wkv_b", "wo"))
    assert attn == 26_345_472  # ISSUE 32's 26.35 M
    outside = attn + sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(layer["shared"])
    ) + int(np.prod(layer["w_router"].shape))
    assert round(outside / 1e6, 2) == 36.04  # ISSUE 32's 36.05 M, norms aside
    assert jax.tree_util.tree_structure(
        deepseek_v3.param_specs(cfg), is_leaf=lambda x: isinstance(x, P)
    ) == jax.tree_util.tree_structure(shapes)


def test_the_preset_is_what_the_benchmark_builds_uncut():
    import sys

    sys.path.insert(0, ROOT)
    from benchmarks.families import deepseek_v3 as family

    with open(os.path.join(ROOT, "benchmarks/configs/kanana-2-30b-a3b.json")) as f:
        c = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/jobs/tokens-8k.json")) as f:
        mix = json.load(f)
    cut = family.model_config(c, mix)
    assert cut.n_layers == 7 and cut.n_dense_layers == 1
    assert cut.held == (0, 16) and cut.n_experts == 128 and cut.vocab == 16032
    uncut = family.model_config(
        {**c, **c["published"]}, {**mix, "seq": c["max_position_embeddings"]}
    )
    preset = deepseek_v3.DeepseekV3Config.kanana_2_30b_a3b()
    # remat is the training section's choice, not the architecture's.
    assert dataclasses.replace(uncut, remat=preset.remat) == preset


@pytest.mark.parametrize("bad", [
    dict(n_dense_layers=4), dict(held_experts=(12, 8)), dict(qk_rope_dim=7),
    dict(n_layers=0), dict(remat="sometimes"),
])
def test_the_config_refuses_what_is_not_an_architecture(bad):
    with pytest.raises(ValueError):
        tiny(**bad)


@pytest.mark.parametrize("entry", ["forward_with_cache", "generate"])
def test_serving_is_refused_by_name(entry):
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        getattr(deepseek_v3, entry)()


def test_the_head_sizes_are_stated_not_derived():
    cfg = tiny()
    layer = deepseek_v3.init_params(cfg, jax.random.key(0))["layers"][1]
    assert layer["wq"].shape == (64, 4 * 24) and layer["wkv_a"].shape == (64, 40)
    assert layer["kv_a_norm"].shape == (32,)
    assert layer["wkv_b"].shape == (32, 4 * 32) and layer["wo"].shape == (64, 64)
    assert layer["shared"]["w_gate"].shape == (64, 64)  # 2 shared x 32, one SwiGLU
    assert layer["experts"]["w_gate"].shape == (16, 64, 32)


def test_the_programs_rope_is_the_interleaved_one_permuted():
    """De-interleave, then half-split rotation = the adjacent-pair rotation
    with its output de-interleaved: the same scores from q and k."""
    x = jax.random.normal(jax.random.key(1), (1, 9, 2, 8), jnp.float32)
    got = deepseek_v3._rope_pairs(x, jnp.arange(9), 1e6)
    want = ref._rope(x, 1e6)
    close(got, jnp.concatenate([want[..., 0::2], want[..., 1::2]], -1), 1e-6, "rope")


@pytest.mark.parametrize("checkpoint_layers", [False, True])
def test_the_references_layer_hook_changes_nothing_and_sees_every_layer(
    tokens, checkpoint_layers
):
    cfg = tiny(held_experts=(4, 4))
    params = seeded(cfg)
    c = ref_config(cfg, checkpoint_layers=checkpoint_layers)
    seen = []

    def layer_fn(x, layer, c, r, dense):
        seen.append((dense, sorted(layer)))
        return ref._layer(x, layer, c, r, dense)

    want_loss, want = ref.loss_and_grads(params, tokens, c)
    got_loss, got = jax.value_and_grad(ref.loss)(params, tokens, c, None, layer_fn)
    if checkpoint_layers:  # traced once a kind, not once a layer
        assert {kind[0] for kind in seen} == {True, False}
    else:
        assert [kind[0] for kind in seen[:3]] == [True, False, False]
    assert seen[1][1] == sorted(params["layers"][1])
    assert float(got_loss) == float(want_loss)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- the benchmark's copy cannot drift ------------------------------------------------


def _body(path):
    with open(path) as f:
        text = f.read()
    return text[text.index("\nfrom __future__"):]


def test_the_benchmarks_reference_is_this_one():
    assert _body(os.path.join(ROOT, "tests", "reference_deepseek_v3.py")) == _body(
        os.path.join(ROOT, "benchmarks", "lib", "deepseek_v3_reference.py")
    )


# -- what the latent path and the lifted expert routine may not do to the others ------

#: sha256 of the 2-step window programs below (``parallel.train.
#: make_multistep``, adamw, selective remat, bf16 storage, T = 2048) on
#: PR 45's tree (the child of 90a08e5), which changed all eight on purpose:
#: a blockwise flash call's backward pass is ONE kernel, the dK/dV grid
#: carrying dQ (``ddl_flash_bwd_dkv`` / ``ddl_flash_swa_bwd_dkv``; no
#: ``*_bwd_dq`` call is left), and nothing else of a program moved.  What
#: the earlier recordings held each family to still reads off these:
#: PR 33's tree (selective remat saves the blockwise cores' residuals, so
#: the backward holds no second forward kernel), PR 35's for ``olmoe`` (the
#: routed layer's backward pass gathers where it scatter-added).
#: ``trinity`` was PR 40's tree (the child of 2b9d22c), changed on purpose: a
#: share of 4 of 16 experts runs its row passes over a static bound of held
#: rows, one ``cond`` a pass with the full-width code as the other branch
#: (``moe._held_rows``); ``trinity_half`` is the same stack holding 8 of the
#: 16 - half the router, so no bound - recorded on PR 40's PARENT: with
#: ``olmoe`` (``held=None``) it holds PR 40 to "no bound, the parent's
#: program":
#: ``tpu``: lowered for the TPU with each Mosaic kernel's serialised body
#: taken out (it carries the file and line of every operation);
#: ``interpreted``: with the kernels' bodies as the interpreter's HLO, line
#: for line what the kernels compute.  ``trinity`` is an AFMoE stack (two
#: sliding layers and a full one, a share of 4 of 16 experts) through the
#: expert routine PR 32 lifted into ``models/moe.py``.
PARENT_JAX = "0.9.0"
PARENT_WINDOW_PROGRAM_SHA256 = {
    ("mistral", "tpu"):
        "2df52d1bc6afd15b8ea6da5a28951bea61ff49523e0a10a71a8921f4591330da",
    ("mistral", "interpreted"):
        "9d9d9710868eef06ed644a761c10d6f899cfa2af15cbf21569d3f287e2f4d33b",
    ("olmoe", "tpu"):
        "fc2f8019e0a81562dae20e21a3e8a9b9e94d604d6076debf70c760599aedb8c5",
    ("olmoe", "interpreted"):
        "51e2b0eae69e4d526b8ae435a85f280cd9742a93d4877b83c45ab9dbb7bb03e5",
    ("trinity", "tpu"):
        "d4d6d4931f660a365b6dce8399679dbbb29cf5067eebf74d1da5615b5ed80c27",
    ("trinity", "interpreted"):
        "716266e0900c52e90c29b3a93b1084dd4799ddec5b8f8417838bae7a3bc4ef2d",
    ("trinity_half", "tpu"):
        "7f4a9c9df70845d67793571579176ac5b5f2b803138e490fa6bf7c540c58af71",
    ("trinity_half", "interpreted"):
        "996b4a6e441ee5dbf616cd0982f68ae20c2d139a91eae2142c4eee151f06b413",
}


@pytest.mark.parametrize("how", ["tpu", "interpreted"])
@pytest.mark.parametrize("model", ["mistral", "olmoe", "trinity", "trinity_half"])
def test_the_three_decoder_cells_window_programs_are_the_parents(model, how,
                                                                monkeypatch):
    import optax
    from jax.sharding import Mesh

    from ddl_tpu.parallel.train import make_multistep

    common = dict(
        vocab=512, d_model=256, n_heads=2, max_seq=2048,
        param_dtype=jnp.bfloat16, remat="selective",
        attn_impl="auto" if how == "tpu" else "flash",
    )
    if model == "mistral":
        mod, cfg = llama, llama.LlamaConfig(
            n_layers=2, n_kv_heads=1, d_ff=512, rope_theta=1e6, **common)
    elif model == "olmoe":
        mod, cfg = moe, moe.MoeConfig(
            n_layers=2, n_kv_heads=2, d_ff=128, n_experts=8, topk=2,
            rope_theta=1e4, qk_norm=True, norm_topk_prob=False,
            router_aux_all_slots=True, router_z_weight=0.001, **common)
    else:
        mod, cfg = afmoe, afmoe.AfmoeConfig(
            n_kv_heads=1, head_dim=128, d_ff=512, d_expert=128, n_experts=16,
            topk=4, layer_types=(afmoe.SLIDING, afmoe.SLIDING, afmoe.FULL),
            n_dense_layers=1, sliding_window=512, route_scale=2.826,
            held_experts=(0, 8 if model == "trinity_half" else 4), **common)
    optimizer = optax.adamw(3e-4)

    def traced():  # anew each time: a jitted function keeps its first trace
        _, multi = make_multistep(
            lambda p, b: mod.next_token_loss(p, b[0], cfg), optimizer,
            Mesh(np.array(jax.devices()[:1]), ("dp",)), mod.param_specs(cfg),
            batch_spec=P(("dp",)), n_steps=2,
        )
        run = next(c.cell_contents for c in multi.__closure__
                   if hasattr(c.cell_contents, "lower"))
        return run.trace(*args)

    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    args = (params, jax.eval_shape(optimizer.init, params),
            (jax.ShapeDtypeStruct((2, 2, 2048), jnp.int32),), True)
    # The parent's kernel families and no other, each kernel once a layer
    # in the scanned step: the forward not a second time for the backward
    # pass, and ONE backward kernel (the dK/dV grid carrying dQ, PR 45: no
    # ``*_bwd_dq`` family).
    with monkeypatch.context() as on_tpu:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        text = traced().lower(lowering_platforms=("tpu",)).as_text()
    kinds = ["swa_", "swa_", ""] if model.startswith("trinity") else ["", ""]
    calls = re.findall(r'kernel_name = "(ddl_flash_\w+)"', text)
    assert sorted(calls) == sorted(
        f"ddl_flash_{kind}{kernel}" for kind in kinds
        for kernel in ("fwd", "bwd_dkv")), calls
    if how == "tpu":
        assert text.count("tpu_custom_call") == len(calls)
        text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", text)
    else:
        text = traced().lower().as_text()
    assert "ddl_flash_mla" not in text
    if jax.__version__ == PARENT_JAX:  # the text is this JAX's
        assert hashlib.sha256(text.encode()).hexdigest() == (
            PARENT_WINDOW_PROGRAM_SHA256[model, how]
        )


# -- the benchmark's cell, rehearsed ---------------------------------------------------


def test_the_benchmarks_kernel_call_counts_are_the_lowered_steps(monkeypatch):
    """The program's own train step under the cell's remat policy, lowered
    for the TPU: three layers, the forward kernel once a layer — since
    PR 33, its output and logsumexp being what ``selective`` saves — and
    ONE backward kernel a layer, the dK/dV grid carrying dQ and dQ_rope
    (PR 45): no ``ddl_flash_mla_bwd_dq`` family.
    ``benchmarks/lib/mla_flops.MLA_CALLS_PER_LAYER`` (what
    ``mla_roofline_share`` multiplies by) still says ``fwd: 2`` there,
    counts a ``bwd_dq`` family no cell runs and gives ``bwd_dkv`` the four
    products it had: a ``benchmark`` PR's to follow (ROADMAP M9); until
    then that share misreads, and this test states the counts the table
    has to come to."""
    import collections

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = deepseek_v3.DeepseekV3Config(
        vocab=256, d_model=256, n_layers=3, n_heads=2, qk_nope_dim=128,
        qk_rope_dim=64, v_head_dim=128, kv_lora_rank=128, d_ff=128,
        d_expert=128, n_experts=8, topk=2, held_experts=(0, 2), max_seq=2048,
        param_dtype=jnp.bfloat16, remat="selective",
    )
    params = jax.eval_shape(lambda: deepseek_v3.init_params(cfg, jax.random.key(0)))
    text = jax.jit(jax.value_and_grad(
        lambda p, t: deepseek_v3.next_token_loss(p, t, cfg)
    )).trace(params, jax.ShapeDtypeStruct((1, 2048), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    got = collections.Counter(re.findall(r'kernel_name = "(ddl_flash_\w+)"', text))
    assert dict(got) == {
        "ddl_flash_mla_" + kernel: 3 * calls
        for kernel, calls in {"fwd": 1, "bwd_dkv": 1}.items()
    }


def test_the_cell_rehearses_through_trainer_fit_on_the_cpu():
    """``benchmarks/run.py --rehearsal cpu``: the cell's control flow at its
    tiny size - the reference check, then ``Trainer.fit(window_stream=True,
    mode="process")`` fed by two spawned producers - ends ``correct``."""
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "kanana-2-30b-a3b.tokens-8k", "--seed", "2147483659",
         "--seconds", "0.5", "--trace", "0", "--rehearsal", "cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    check = next(ln for ln in lines if ln.get("line") == "reference_check")
    assert check["problems"] == [] and check["layers"] == 3
    steady = next(ln for ln in lines if ln.get("line") == "steady")
    assert steady["windows"] >= 1 and steady["problems"] == []
    assert lines[-1]["correct"] is True and lines[-1]["failed"] == 0


# -- the query's low-rank step and YaRN (PR 46) ---------------------------------------


def _low_rank_case(**kw):
    from ddl_tpu.models.deepseek_v3 import Yarn

    base = dict(
        vocab=64, d_model=32, n_layers=2, n_heads=2, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, kv_lora_rank=16, q_lora_rank=24, d_ff=48, d_expert=16,
        n_experts=8, topk=2, n_shared_experts=1, n_dense_layers=1, max_seq=24,
        rope_theta=1e4, rope_scaling=Yarn(8.0, 8, 4.0, 1.0, 1.0, 1.0),
        dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="dense")
    base.update(kw)
    return deepseek_v3.DeepseekV3Config(**base)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_the_query_low_rank_step_and_yarn_are_the_references(impl):
    """``q = RMSNorm(h Wq_a) Wq_b``, YaRN's frequencies and the score's scale
    times ``m^2``: the attention sub-block against the plain float32 stages of
    ``tests/reference_xing4.py`` (the dispatcher's dense form, and the latent
    kernels under ``score_scale``)."""
    import reference_xing4 as ref4

    cfg = _low_rank_case(attn_impl=impl)
    params = deepseek_v3.init_params(cfg, jax.random.key(3))
    layer = params["layers"][1]
    assert {"wq_a", "q_a_norm", "wq_b"} <= set(layer) and "wq" not in layer
    assert layer["wq_a"].shape == (32, 24) and layer["wq_b"].shape == (24, 2 * 24)
    layer["q_a_norm"] = layer["q_a_norm"] + 0.3 * jax.random.normal(
        jax.random.key(4), layer["q_a_norm"].shape)
    x = jax.random.normal(jax.random.key(5), (2, 24, 32))
    got = deepseek_v3.attn(layer, x, cfg, jnp.arange(24), None, residual=False)
    c = ref4.Config(
        n_heads=2, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, kv_lora_rank=16,
        n_experts=8, topk=2, n_dense_layers=1, held=(0, 8),
        yarn=tuple(cfg.rope_scaling), rope_theta=1e4, query_block=8)
    with jax.default_matmul_precision("highest"):
        h = ref4._norm(x, layer["attn_norm"], cfg.norm_eps)
        want = ref4._attn_out(ref4._attention(
            *ref4.latent_qkv(h, layer, c), ref4.score_scale(c), 8), layer, ref4._same)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    with_residual = deepseek_v3.attn(layer, x, cfg, jnp.arange(24), None)
    np.testing.assert_allclose(np.asarray(with_residual), np.asarray(x + got), atol=1e-6)
    # ... and each of the three is felt
    for without in (dict(rope_scaling=None),
                    dict(rope_scaling=cfg.rope_scaling._replace(mscale_all_dim=0.0))):
        other = deepseek_v3.attn(
            layer, x, dataclasses.replace(cfg, **without), jnp.arange(24), None,
            residual=False)
        assert float(jnp.max(jnp.abs(other - got))) > 1e-3, without


def test_without_the_new_fields_a_layer_is_kananas():
    """``q_lora_rank`` and ``rope_scaling`` absent: the parameters, the keys
    drawn and the dispatcher's call are what they were (the pinned program
    hashes of ``tests/test_ops.py`` hold the traced step)."""
    cfg = _low_rank_case(q_lora_rank=None, rope_scaling=None)
    assert cfg.score_scale == 1.0
    layer = deepseek_v3.init_params(cfg, jax.random.key(3))["layers"][1]
    assert "wq" in layer and "wq_a" not in layer
    text = str(jax.make_jaxpr(
        lambda l, x: deepseek_v3.attn(l, x, cfg, jnp.arange(24), None))(
            layer, jnp.zeros((1, 24, 32))))
    assert "score_scale" not in text
    full = deepseek_v3.DeepseekV3Config.kanana_2_30b_a3b()
    assert full.q_lora_rank is None and full.rope_scaling is None
    # a low-rank layer draws one key more: its table's, not Kanana's
    assert deepseek_v3._table(cfg).n_keys == (2, 11)
    assert deepseek_v3._table(_low_rank_case()).n_keys == (2, 12)


# -- the weights are cut by head, the activations never (PR 48) ------------------


@pytest.mark.parametrize("H,width,at", [(4, 24, 16), (32, 192, 128), (2, 256, 128)])
def test_head_columns_are_each_heads_columns_side_by_side(H, width, at):
    """``_head_columns``: the two products against its parts are, head for
    head, the two slices of the one product against the whole weight."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((8, H * width)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((3, 8)), jnp.float32)
    first, rest = deepseek_v3._head_columns(w, H, at)
    assert first.shape == (8, H * at) and rest.shape == (8, H * (width - at))
    whole = (x @ w).reshape(3, H, width)
    for part, cut in ((first, slice(None, at)), (rest, slice(at, None))):
        np.testing.assert_array_equal(  # the same numbers, moved
            part.reshape(8, H, -1), w.reshape(8, H, width)[..., cut])
        np.testing.assert_allclose(  # (a CPU matmul's sums move with its width)
            (x @ part).reshape(3, H, -1), whole[..., cut], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("q_lora_rank", [None, 24], ids=["kanana", "xing4"])
def test_no_activation_is_split_or_joined_by_head_around_the_latent_kernels(
        q_lora_rank):
    """The traced train step of a layer: no ``slice``, ``pad`` or
    ``concatenate`` of a ``(B, T, H, nope + rope)`` or ``(B, T, H, nope +
    v)`` activation or cotangent - the strided copies of 134-201 MB arrays a
    layer that XLA made of them, forward and backward (PERF.md section 6, PR
    48) - is left: q_nope, q_rope, k_nope and v come out of products against
    the weights' own columns and their cotangents go back into them."""
    cfg = tiny(n_layers=1, n_dense_layers=1, q_lora_rank=q_lora_rank)
    layer = deepseek_v3.init_params(cfg, jax.random.key(0))["layers"][0]
    x = jnp.zeros((B, T, cfg.d_model))
    text = str(jax.make_jaxpr(jax.grad(
        lambda l, x: deepseek_v3.attn(l, x, cfg, jnp.arange(T), None).sum(),
        (0, 1)))(layer, x))
    H, nope = cfg.n_heads, cfg.qk_nope_dim
    whole = {f"f32[{B},{T},{H},{nope + w}]" for w in (cfg.qk_rope_dim, cfg.v_head_dim)}
    assert not any(shape in text for shape in whole), text
    # the parts are there, as the products' own reshapes
    assert f"f32[{B},{T},{H * nope}]" in text and f"f32[{B},{T},{H},{nope}]" in text

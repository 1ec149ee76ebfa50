"""LFM2-MoE's architecture at LFM2-24B-A2B's shape through
``models/lfm2_moe.py`` against its plain float32 reference
(``tests/reference_lfm2_moe.py``), at a tiny size on the CPU: hidden 64, 4
query heads over 2 key heads of 16, 3 taps, ``(conv, full_attention, conv,
conv)`` with one dense layer of width 96 then three expert layers of 16
experts x 32, top-4, no shared expert; vocab 256 tied, T 40.

Seeded weights (norm weights moved off 1 and the router scaled up, so
that both count) and tokens.
"""

import collections
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import reference_lfm2_moe as ref
from ddl_tpu.models import afmoe, decoder, deepseek_v3, lfm2_moe, moe, olmo_hybrid
from ddl_tpu.models.losses import next_token_cross_entropy

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, T = 2, 40
C, F = lfm2_moe.CONV, lfm2_moe.FULL

#: float32 system against float32 reference, as a share of the largest
#: magnitude in the compared array: the same float32 arithmetic on the CPU,
#: differing in summation order alone (the taps summed over a padded row
#: against shifted adds; expert rows sorted and summed over 4 slots against
#: a masked sum over the held experts).  Measured up to 4e-6.
F32_TOL = 2e-5


def tiny(**kw) -> lfm2_moe.Lfm2MoeConfig:
    base = dict(
        vocab=256, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96, d_expert=32,
        n_experts=16, topk=4, layer_types=(C, F, C, C), n_dense_layers=1,
        max_seq=T, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(kw)
    return lfm2_moe.Lfm2MoeConfig(**base)


def ref_config(cfg, **kw) -> ref.Config:
    return ref.Config(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, n_experts=cfg.n_experts,
        topk=cfg.topk, conv_layers=tuple(k == C for k in cfg.layer_types),
        n_dense_layers=cfg.n_dense_layers, held=cfg.held,
        route_norm=cfg.route_norm, route_scale=cfg.route_scale,
        route_eps=cfg.route_eps, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, query_block=8,
    )._replace(**kw)


def seeded(cfg):
    """Parameters with every norm weight moved off 1, the selection bias
    off 0 and the router scaled up so that routing is decided."""
    params = lfm2_moe.init_params(cfg, jax.random.key(43))
    keys = iter(jax.random.split(jax.random.key(44), 128))

    def off(x, by=0.2):
        return x + by * jax.random.normal(next(keys), x.shape, x.dtype)

    for layer in params["layers"]:
        for name in ("operator_norm", "ffn_norm", "q_norm", "k_norm"):
            if name in layer:
                layer[name] = off(layer[name])
        if "w_router" in layer:
            layer["w_router"] = 4.0 * layer["w_router"]
            layer["expert_bias"] = off(layer["expert_bias"], 0.05)
    params["final_norm"] = off(params["final_norm"])
    return params


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(
        np.random.default_rng(43).integers(0, 256, (B, T)), jnp.int32
    )


def close(got, want, tol, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    err = float(np.max(np.abs(got - want))) / scale
    assert err <= tol, f"{what}: {err:.3g} of the largest magnitude, limit {tol}"


def assert_matches_reference(cfg, params, tokens, c=None, tol=F32_TOL):
    """Logits, loss, the routers' picks and every gradient leaf: one
    program a side (the train loss is ``decoder.loss_of``'s: the
    cross-entropy of the family's forward)."""
    c = c or ref_config(cfg)

    def plain(p):
        logits, picks = ref.forward(p, tokens, c)
        return ref.cross_entropy(logits, tokens), (logits, picks)

    def system(p):
        logits, picks = lfm2_moe.forward_with_choices(p, tokens, cfg)
        return next_token_cross_entropy(logits, tokens), (logits, picks)

    (want_loss, (want_logits, want_picks)), want_grads = jax.jit(
        jax.value_and_grad(plain, has_aux=True))(params)
    (got_loss, (got_logits, got_picks)), got_grads = jax.jit(
        jax.value_and_grad(system, has_aux=True))(params)
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
    (None, "none"), ((4, 4), "selective"), ((4, 4), "full"), ((12, 4), "none"),
], ids=["uncut", "share_selective", "share_full", "last_share"])
def test_float32_system_matches_the_reference(tokens, held, remat):
    cfg = tiny(held_experts=held, remat=remat)
    grads = assert_matches_reference(cfg, seeded(cfg), tokens)
    router = float(jnp.linalg.norm(grads["layers"][1]["w_router"]))
    # The uncut model trains its router; a share does not.
    assert (router > 0) == (held is None)
    assert float(jnp.linalg.norm(grads["layers"][1]["expert_bias"])) == 0.0


@pytest.mark.parametrize("left_out", ["taps_shifted", "no_c_gate", "silu_on_conv",
                                      "b_c_swapped", "untied_head", "no_qk_norm",
                                      "shared_expert_eps"])
def test_leaving_out_part_of_the_mathematics_fails(tokens, left_out, monkeypatch):
    """The comparison has the power to see each of the assumed equations:
    a system that departs from one is outside the float32 tolerance."""
    small = dict(held_experts=(4, 4), layer_types=(C, F))  # every mechanism once
    cfg = tiny(**small)
    params = seeded(cfg)
    real = lfm2_moe.gated_short_conv

    def conv_with(change):
        def faulty(bcx, taps):
            d = bcx.shape[-1] // 3
            b, c, u = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
            return real(jnp.concatenate(change(b, c, u), axis=-1), taps)
        monkeypatch.setattr(lfm2_moe, "gated_short_conv", faulty)

    if left_out == "taps_shifted":
        early = lambda x: jnp.pad(x[:, 1:], ((0, 0), (0, 1), (0, 0)))
        conv_with(lambda b, c, u: (early(b), c, early(u)))
    elif left_out == "no_c_gate":
        conv_with(lambda b, c, u: (b, jnp.ones_like(c), u))
    elif left_out == "b_c_swapped":
        conv_with(lambda b, c, u: (c, b, u))
    elif left_out == "silu_on_conv":
        monkeypatch.setattr(
            lfm2_moe, "gated_short_conv",
            lambda bcx, taps: jax.nn.silu(real(bcx, taps)))
    elif left_out == "untied_head":
        head = decoder.lm_head
        monkeypatch.setattr(decoder, "lm_head", lambda p, x, c, s=None: head(
            {**p, "lm_head": jnp.roll(p["embed"], 1, axis=0).T}, x, c, s))
    elif left_out == "no_qk_norm":
        norm = decoder.rms_norm
        monkeypatch.setattr(decoder, "rms_norm", lambda x, g, eps: (
            x if g.shape == (cfg.head_dim,) else norm(x, g, eps)))
    else:  # the other two families' 1e-20 in this one's place: seen at 1e-2
        cfg = dataclasses.replace(cfg, route_eps=1e-2)
    with pytest.raises(AssertionError):
        assert_matches_reference(cfg, params, tokens, c=ref_config(tiny(**small)))


def test_a_float32_configuration_run_in_bf16_fails_the_float32_tolerance(tokens):
    cfg = tiny(held_experts=(4, 4), layer_types=(C, F))
    with pytest.raises(AssertionError):
        assert_matches_reference(
            dataclasses.replace(cfg, dtype=jnp.bfloat16), seeded(cfg), tokens
        )


def test_a_bf16_reference_fails_the_float32_tolerance(tokens):
    cfg = tiny(held_experts=(4, 4))
    params, c = seeded(cfg), ref_config(cfg)
    want, _ = ref.forward(params, tokens, c)
    lower, _ = ref.forward(params, tokens, c, jnp.bfloat16)
    with pytest.raises(AssertionError):
        close(lower, want, F32_TOL, "logits")


# -- the gated short convolution --------------------------------------------------


def _conv_operands(T_=37, d=24, K=3, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(T_ + K), 3)
    bcx = jax.random.normal(ks[0], (B, T_, 3 * d), dtype)
    taps = jax.random.normal(ks[1], (K, d), dtype)
    return bcx, taps, jax.random.normal(ks[2], (B, T_, d), jnp.float32)


def _plain_conv(bcx, taps):
    """``C * taps(B * u)`` as the reference writes it: shifted adds, autodiff."""
    d = bcx.shape[-1] // 3
    return bcx[..., d:2 * d] * ref._conv(bcx[..., :d] * bcx[..., 2 * d:], taps)


@pytest.mark.parametrize("K", [1, 3, 4])
def test_the_convolutions_own_backward_is_autodiffs_of_the_plain_form(K):
    bcx, taps, w = _conv_operands(K=K)
    got, got_grads = jax.value_and_grad(
        lambda *a: jnp.sum(lfm2_moe.gated_short_conv(*a) * w), argnums=(0, 1))(bcx, taps)
    want, want_grads = jax.value_and_grad(
        lambda *a: jnp.sum(_plain_conv(*a) * w), argnums=(0, 1))(bcx, taps)
    close(lfm2_moe.gated_short_conv(bcx, taps), _plain_conv(bcx, taps), 2e-6, "y")
    close(got, want, 2e-6, "sum")
    for name, g, wg in zip(("d_bcx", "d_taps"), got_grads, want_grads):
        assert g.shape == wg.shape and g.dtype == wg.dtype
        close(g, wg, 5e-6, name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_convolution_is_causal(dtype):
    """Changing position t leaves every output before t BIT-equal, and
    moves the outputs from t to t + K - 1 (the taps reach no further)."""
    bcx, taps, _ = _conv_operands(dtype=dtype)
    t, K = 20, taps.shape[0]
    moved = bcx.at[:, t].add(jnp.ones((), dtype))
    before, after = (np.asarray(lfm2_moe.gated_short_conv(x, taps), np.float32)
                     for x in (bcx, moved))
    np.testing.assert_array_equal(before[:, :t], after[:, :t])
    np.testing.assert_array_equal(before[:, t + K:], after[:, t + K:])
    assert np.all(np.any(before[:, t:t + K] != after[:, t:t + K], axis=-1))
    # ... and the backward pass is the transpose: the cotangent at t reaches
    # inputs t - K + 1 .. t and no other.
    pull = jax.vjp(lambda x: lfm2_moe.gated_short_conv(x, taps), bcx)[1]
    dy = jnp.zeros(before.shape, dtype).at[:, t].set(1)
    (d_bcx,) = pull(dy)
    reached = np.any(np.asarray(d_bcx, np.float32) != 0, axis=(0, 2))
    assert list(np.flatnonzero(reached)) == list(range(t - K + 1, t + 1))


def test_both_convolution_families_read_one_taps_sum():
    import inspect

    assert not hasattr(olmo_hybrid, "_taps_sum")
    for module in (olmo_hybrid, lfm2_moe):
        assert "_decoder.taps_sum(" in inspect.getsource(module)


# -- the tied head -----------------------------------------------------------------


def test_the_tied_heads_embedding_gradient_is_the_sum_of_the_two_untied_ones(tokens):
    """No ``lm_head`` among the parameters; with the same matrix as an
    untied head, the tied embedding's gradient is the untied embedding's
    plus the untied head's, transposed."""
    cfg = tiny(held_experts=(4, 4), layer_types=(C, F), remat="selective")
    params = seeded(cfg)
    assert "lm_head" not in params and "lm_head" not in lfm2_moe.param_specs(cfg)
    grad = jax.jit(jax.grad(lambda p: lfm2_moe.next_token_loss(p, tokens, cfg)))
    tied, untied = grad(params), grad({**params, "lm_head": params["embed"].T})
    assert float(jnp.linalg.norm(untied["lm_head"])) > 0
    assert float(jnp.linalg.norm(untied["embed"])) > 0
    close(tied["embed"], untied["embed"] + untied["lm_head"].T, 2e-6, "d embed")
    # The six untied families' tables keep their row.
    for module in (afmoe, deepseek_v3, moe, olmo_hybrid):
        assert not module._TABLE.tied


# -- the share ---------------------------------------------------------------------


@pytest.mark.parametrize("n_tokens,favoured", [(B * T, ()), (512, (0, 1, 9))],
                         ids=["no_bound_at_this_size", "shares_past_their_bound"])
def test_the_shares_add_up_to_the_uncut_layer(n_tokens, favoured):
    """One expert layer's FFN on the same hidden states: the routed parts
    that all 8 shares of 2 experts give - there is no shared expert, the
    router counted once - are what the uncut reference gives for the whole
    layer."""
    whole = tiny()
    layer = seeded(whole)["layers"][2]
    assert "shared" not in layer
    bias = np.zeros(whole.n_experts, np.float32)
    bias[list(favoured)] = 10.0
    layer = {**layer, "expert_bias": layer["expert_bias"] + bias}
    bound = moe.held_row_bound(n_tokens * whole.topk, 2, whole.n_experts)
    h = jax.random.normal(jax.random.key(5), (n_tokens, whole.d_model), jnp.float32)
    want, want_picks = ref.expert_mlp(h, layer, ref_config(whole))

    routed = jnp.zeros_like(h)
    held_choices, past_the_bound = 0, []
    for first in range(0, whole.n_experts, 2):
        cfg = tiny(held_experts=(first, 2))
        mine = {**layer, "experts": jax.tree.map(
            lambda w: w[first : first + 2], layer["experts"]
        )}
        out, picks = moe.sigmoid_expert_tokens(h, mine, cfg)
        np.testing.assert_array_equal(np.asarray(picks), np.asarray(want_picks))
        share_want, _ = ref.expert_mlp(h, mine, ref_config(cfg))
        close(out, share_want, F32_TOL, f"share {first}")
        routed = routed + out
        mine_held = int(np.sum((picks >= first) & (picks < first + 2)))
        past_the_bound.append(mine_held > bound)
        held_choices += mine_held
    assert held_choices == n_tokens * whole.topk  # every choice is held once
    if favoured:  # both branches ran: share 0 (two favoured experts) at full width
        assert past_the_bound[0] and not all(past_the_bound), past_the_bound
    close(routed, want, F32_TOL, "sum of the shares")
    close(moe.sigmoid_expert_tokens(h, layer, whole)[0], want, F32_TOL, "uncut")


def test_without_a_shared_expert_there_is_no_shared_op(tokens):
    """``n_shared_experts = 0``: no ``shared`` rows, and the lowered step
    holds no op under ``ddl.moe_shared``; Kanana-2's family, through the
    same routine, keeps both."""
    cfg = tiny(held_experts=(4, 4), remat="selective")
    assert cfg.n_shared_experts == 0
    assert not any("shared" in r.name for r in moe.sigmoid_expert_rows(cfg))

    def lowered(mod, cfg):
        params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
        return jax.jit(jax.value_and_grad(
            lambda p, t: mod.next_token_loss(p, t, cfg)
        )).lower(params, tokens).as_text(debug_info=True)

    text = lowered(lfm2_moe, cfg)
    assert "ddl.moe_shared" not in text and "ddl.moe_experts" in text
    # The three scopes of the new mixer stand in the lowered step.
    for name in ("ddl.shortconv_proj", "ddl.shortconv", "ddl.shortconv_out"):
        assert f"{name}/" in text or f"{name})" in text, name
    kanana = deepseek_v3.DeepseekV3Config(held_experts=(0, 2))
    assert any("shared" in r.name for r in moe.sigmoid_expert_rows(kanana))
    assert "ddl.moe_shared" in lowered(deepseek_v3, kanana)
    assert afmoe.AfmoeConfig().route_eps == kanana.route_eps == 1e-20
    assert cfg.route_eps == 1e-6


@pytest.mark.parametrize("remat", ["none", "selective", "full", "dots"])
def test_the_benchmarks_pass_counts_are_the_traced_steps(remat):
    """``benchmarks/lib/lfm2_flops.py:CONV_CALLS_PER_LAYER`` - what the
    convolution's bandwidth floor multiplies - is the number of forward and
    backward passes in the program's own train step, a conv layer."""
    import sys

    sys.path.insert(0, ROOT)
    from benchmarks.lib import lfm2_flops

    cfg = tiny(held_experts=(4, 4), remat=remat)
    params = jax.eval_shape(lambda: lfm2_moe.init_params(cfg, jax.random.key(0)))
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda p, t: lfm2_moe.next_token_loss(p, t, cfg)
    ))(params, jax.ShapeDtypeStruct((B, T), jnp.int32)))
    calls = collections.Counter(re.findall(r"name=_shortconv_(fwd|bwd)", text))
    n_conv = sum(k == C for k in cfg.layer_types)
    assert {k: v / n_conv for k, v in calls.items()} == (
        lfm2_flops.CONV_CALLS_PER_LAYER[remat])


def test_selective_saves_bcx_and_nothing_else_of_a_conv_layer(tokens):
    """Under ``selective`` a conv layer keeps its input and ``BCx``: the
    residuals of the step's forward pass hold one (B, T, 3 d) bfloat16 array
    a conv layer - the one the rule tags - and nothing float32, padded or
    K-fold of the row's size."""
    from jax._src.ad_checkpoint import saved_residuals

    cfg = tiny(layer_types=(C, C), n_dense_layers=2, remat="selective",
               dtype=jnp.bfloat16)
    res = saved_residuals(
        lambda p: lfm2_moe.next_token_loss(p, tokens, cfg), seeded(cfg))
    # A jitted function that hands an input on ("output of jitted function")
    # names the same buffer again: ``BCx`` through ``jnp.pad``'s wrapper.
    kept = [(tuple(s.shape), str(s.dtype)) for s, why in res
            if "jitted function" not in why]
    wide = [k for k in kept if len(k[0]) == 3 and k[0][-1] == 3 * cfg.d_model]
    assert wide == [((B, T, 3 * cfg.d_model), "bfloat16")] * 2, wide
    assert not [k for k in kept if len(k[0]) == 3 and k[0][1] > T], kept


# -- the config states the architecture ---------------------------------------------


def test_the_preset_states_the_published_architecture():
    cfg = lfm2_moe.Lfm2MoeConfig.lfm2_24b_a2b()
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.d_expert, cfg.n_experts, cfg.topk, cfg.conv_kernel,
            cfg.vocab) == (2048, 40, 32, 8, 64, 11776, 1536, 64, 4, 3, 65536)
    assert cfg.layer_types == (C, C, F, C) * 10 and cfg.n_dense_layers == 2
    assert cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-5 and cfg.held == (0, 64)
    assert cfg.route_scale == 1.0 and cfg.route_eps == 1e-6
    shapes = jax.eval_shape(lambda: lfm2_moe.init_params(cfg, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 23.5e9 < n < 24.5e9  # "24B": 23.84 B with the head tied
    count = lambda layer, keys: sum(int(np.prod(layer[k].shape)) for k in keys)
    # ISSUE 43's counts a layer, norms aside
    assert count(shapes["layers"][0], ("w_in", "w_out", "conv")) == 16_783_360
    assert count(shapes["layers"][2], ("wq", "wk", "wv", "wo")) == 10_485_760
    assert count(shapes["layers"][0], ("w_gate", "w_up", "w_down")) == 72_351_744
    assert sum(int(np.prod(x.shape))
               for x in jax.tree.leaves(shapes["layers"][2]["experts"])) == 603_979_776
    assert jax.tree_util.tree_structure(
        lfm2_moe.param_specs(cfg), is_leaf=lambda x: isinstance(x, P)
    ) == jax.tree_util.tree_structure(shapes)


def test_the_preset_is_what_the_benchmark_builds_uncut():
    import sys

    sys.path.insert(0, ROOT)
    from benchmarks.families import lfm2_moe as family

    with open(os.path.join(ROOT, "benchmarks/configs/lfm2-24b-a2b.json")) as f:
        c = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/jobs/tokens-8k.json")) as f:
        mix = json.load(f)
    cut = family.model_config(c, mix)
    assert cut.n_layers == 9 and cut.n_dense_layers == 1
    assert cut.layer_types == (C, F, C, C, C, F, C, C, C)
    assert cut.held == (0, 8) and cut.n_experts == 64 and cut.vocab == 8192
    shapes = jax.eval_shape(lambda: family.init_params(cut, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == 832_652_032  # ISSUE 43's 832.6 M; benchmarks/aot.py's count
    # ... and the bound of held rows at the cell's shape (ISSUE 43's 16,384
    # of the 65,536 sorted rows).
    assert moe.held_row_bound(2 * 8192 * cut.topk, 8, 64) == 16384
    uncut = family.model_config(
        {**c, **c["published"]}, {**mix, "seq": c["max_position_embeddings"]}
    )
    preset = lfm2_moe.Lfm2MoeConfig.lfm2_24b_a2b()
    # remat is the training section's choice, not the architecture's.
    assert dataclasses.replace(uncut, remat=preset.remat) == preset
    # The check's rows are the mix's window, a step's at a time.
    assert (family.CHECK_ROWS, family.PAIR_ROWS) == (
        mix["window_rows"], mix["batch_rows"])


@pytest.mark.parametrize("bad", [
    dict(n_dense_layers=5), dict(held_experts=(12, 8)), dict(layer_types=()),
    dict(layer_types=(C, "linear_attention")), dict(n_kv_heads=3),
    dict(conv_kernel=0), dict(remat="sometimes"),
])
def test_the_config_refuses_what_is_not_an_architecture(bad):
    with pytest.raises(ValueError):
        tiny(**bad)


@pytest.mark.parametrize("entry", ["forward_with_cache", "generate"])
def test_serving_is_refused_by_name(entry):
    with pytest.raises(NotImplementedError, match=f"lfm2_moe.{entry}.*cache"):
        getattr(lfm2_moe, entry)()


@pytest.mark.parametrize("kinds", [(C,), (F, C)])
def test_the_layer_kinds_follow_layer_types(tokens, kinds):
    cfg = tiny(layer_types=kinds, n_dense_layers=min(1, len(kinds) - 1))
    params = seeded(cfg)
    for kind, layer in zip(kinds, params["layers"]):
        assert ("w_in" in layer) == (kind == C) and ("wq" in layer) == (kind == F)
    assert_matches_reference(cfg, params, tokens)


def test_a_token_sharded_mesh_routes_per_shard_to_the_same_result(tokens):
    from ddl_tpu.parallel.mesh import make_mesh

    cfg = tiny(held_experts=(4, 4))
    params = seeded(cfg)
    want, want_picks = lfm2_moe.forward_with_choices(params, tokens, cfg)
    mesh = make_mesh({"dp": 2}, jax.devices()[:2])
    got, picks = jax.jit(
        lambda p, t: lfm2_moe.forward_with_choices(p, t, cfg, mesh)
    )(params, tokens)
    np.testing.assert_array_equal(np.asarray(picks), np.asarray(want_picks))
    close(got, want, F32_TOL, "logits on dp=2")


# -- the reference -------------------------------------------------------------------


@pytest.mark.parametrize("checkpoint_layers", [False, True])
def test_the_references_layer_hook_changes_nothing_and_sees_every_layer(
        tokens, checkpoint_layers):
    cfg = tiny(held_experts=(4, 4))
    params = seeded(cfg)
    c = ref_config(cfg, checkpoint_layers=checkpoint_layers)
    seen = []

    def hooked(x, layer, c, r, conv, dense):
        seen.append((conv, dense))
        return ref._layer(x, layer, c, r, conv, dense)

    want, want_grads = ref.loss_and_grads(params, tokens, ref_config(cfg))
    got, got_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, c, None, hooked))(params)
    kinds = [(True, True), (False, False), (True, False), (True, False)]
    # jax.checkpoint keeps a body's trace by its static arguments: the two
    # conv expert layers are traced once under it
    assert seen[:4] == kinds if not checkpoint_layers else set(seen) == set(kinds)
    close(got, want, 1e-6, "loss")
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads)):
        close(g, w, 1e-5, jax.tree_util.keystr(path))


def test_the_benchmarks_reference_is_this_one():
    with open(os.path.join(ROOT, "tests", "reference_lfm2_moe.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmarks", "lib", "lfm2_moe_reference.py"), "rb") as f:
        assert f.read() == mine
    # plain jax.numpy: nothing of the program, no kernel, no custom_vjp
    text = mine.decode()
    body = text[text.index("from __future__"):]
    assert "ddl_tpu" not in body and "custom_vjp" not in body and "pallas" not in body
    assert 'default_matmul_precision("highest")' in body

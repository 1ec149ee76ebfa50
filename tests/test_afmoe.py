"""AFMoE (Trinity-Mini's architecture) through ``models/afmoe.py`` against
its plain float32 reference (``tests/reference_afmoe.py``), at a tiny size
on the CPU: hidden 64, 4 query / 2 key-value heads x 32 (an attention
width of 128, twice the hidden size, as the model has it), one dense
layer of width 96 then four expert layers of 16 experts x 32, top-4, plus
a shared expert; attention kinds S | S S S F with a window of 8; vocab
256, T 32.

Seeded weights (norm weights moved off 1 and the router scaled up, so
that both count) and tokens.
"""

import collections
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

import reference_afmoe as ref
from ddl_tpu.models import afmoe, decoder, llama, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, T = 2, 32
S, F = afmoe.SLIDING, afmoe.FULL

#: float32 system against float32 reference, as a share of the largest
#: magnitude in the compared array: the same float32 arithmetic on the CPU,
#: differing in summation order alone (expert rows sorted and summed over
#: 4 slots against a masked sum over the held experts; attention in one
#: block against the reference's 8-query blocks).  Measured 1e-6 .. 4e-6.
F32_TOL = 2e-5


def tiny(**kw) -> afmoe.AfmoeConfig:
    base = dict(
        vocab=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=96,
        d_expert=32, n_experts=16, topk=4, layer_types=(S, S, S, S, F),
        n_dense_layers=1, sliding_window=8, route_scale=2.826, max_seq=T,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(kw)
    return afmoe.AfmoeConfig(**base)


def ref_config(cfg: afmoe.AfmoeConfig, **kw) -> ref.Config:
    return ref.Config(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        n_experts=cfg.n_experts, topk=cfg.topk, layer_types=cfg.layer_types,
        n_dense_layers=cfg.n_dense_layers, sliding_window=cfg.sliding_window,
        held=cfg.held, route_norm=cfg.route_norm, route_scale=cfg.route_scale,
        mup_enabled=cfg.mup_enabled, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, query_block=8,
    )._replace(**kw)


def seeded(cfg):
    """Parameters with every norm weight moved off 1, the selection bias
    off 0 and the router scaled up so that routing is decided."""
    params = afmoe.init_params(cfg, jax.random.key(30))
    keys = iter(jax.random.split(jax.random.key(31), 128))

    def off(x, by=0.2):
        return x + by * jax.random.normal(next(keys), x.shape, x.dtype)

    for layer in params["layers"]:
        for name in ("input_norm", "post_attn_norm", "pre_mlp_norm",
                     "post_mlp_norm", "q_norm", "k_norm"):
            layer[name] = off(layer[name])
        if "w_router" in layer:
            layer["w_router"] = 4.0 * layer["w_router"]
            layer["expert_bias"] = off(layer["expert_bias"], 0.05)
    params["final_norm"] = off(params["final_norm"])
    return params


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(
        np.random.default_rng(30).integers(0, 256, (B, T)), jnp.int32
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
        lambda p: afmoe.forward_with_choices(p, tokens, cfg)
    )(params)
    got_loss, got_grads = jax.jit(jax.value_and_grad(
        lambda p: afmoe.next_token_loss(p, tokens, cfg)
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
    # A share does not train its router (the absent experts add nothing, so
    # its part of the gradient only says "route to them"); the uncut model does.
    router = float(jnp.max(jnp.abs(grads["layers"][1]["w_router"])))
    assert (router == 0.0) == (held is not None)


def test_flash_kernels_in_both_attention_kinds_match_it_too(tokens):
    """The interpreted Pallas kernels in place of the dense oracle: the
    windowed ones in the sliding layers, the causal-full ones (T past one
    block: GQA bypasses the one-block path anyway) in the full layer."""
    cfg = tiny(attn_impl="flash", held_experts=(4, 4))
    assert_matches_reference(cfg, seeded(cfg), tokens)


@pytest.mark.parametrize("left_out", [
    "window_ignored", "rope_in_full_layers", "no_rope_in_sliding_layers",
    "no_embedding_scale", "unscaled_routes", "unnormalised_routes",
    "bias_in_the_weights", "no_shared_expert", "another_share",
])
def test_leaving_out_part_of_the_mathematics_fails(tokens, left_out, monkeypatch):
    cfg = tiny(held_experts=(4, 4))
    params = seeded(cfg)
    c = None
    if left_out == "window_ignored":
        cfg = dataclasses.replace(cfg, sliding_window=T)
        c = ref_config(tiny(held_experts=(4, 4)))
    elif left_out == "rope_in_full_layers":
        c = ref_config(cfg, layer_types=(S,) * 5, sliding_window=T)
        cfg = dataclasses.replace(cfg, layer_types=(S,) * 5, sliding_window=T)
        c = c._replace(layer_types=(S, S, S, S, F))
    elif left_out == "no_rope_in_sliding_layers":
        monkeypatch.setattr(decoder, "rope", lambda x, positions, theta: x)
    elif left_out == "no_embedding_scale":
        cfg = dataclasses.replace(cfg, mup_enabled=False)
        c = ref_config(cfg, mup_enabled=True)
    elif left_out == "unscaled_routes":
        cfg = dataclasses.replace(cfg, route_scale=1.0)
        c = ref_config(cfg, route_scale=2.826)
    elif left_out == "unnormalised_routes":
        cfg = dataclasses.replace(cfg, route_norm=False)
        c = ref_config(cfg, route_norm=True)
    elif left_out == "bias_in_the_weights":
        monkeypatch.setattr(jax.lax, "stop_gradient", lambda x: x)
        real = moe.sigmoid_route  # the routine afmoe shares (models/moe.py)

        def biased(h, layer, cfg):
            top_w, top_e = real(h, layer, cfg)
            return top_w + 0.1 * layer["expert_bias"][top_e], top_e

        monkeypatch.setattr(moe, "sigmoid_route", biased)
    elif left_out == "no_shared_expert":
        monkeypatch.setattr(
            decoder, "swiglu",
            lambda layer, h, real=decoder.swiglu: (
                real(layer, h) if layer["w_gate"].shape[-1] != 32
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


# -- the share ---------------------------------------------------------------------


@pytest.mark.parametrize("n_tokens,favoured", [(B * T, ()), (512, (0, 1, 9))],
                         ids=["no_bound_at_this_size", "shares_past_their_bound"])
def test_the_shares_add_up_to_the_uncut_layer(n_tokens, favoured):
    """One expert layer's MLP on the same hidden states: the routed parts
    that all 8 shares of 2 experts give, plus the shared expert - which
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
        out, picks = afmoe._moe_tokens(h, mine, cfg)
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
    close(afmoe._moe_tokens(h, layer, whole)[0], want, F32_TOL, "uncut")


def test_unheld_rows_are_left_out_not_multiplied_by_zero():
    """Rows past the last group are not defined by ragged_dot's contract:
    whatever the grouped matmul leaves there may not reach the result or
    a gradient.  A grouped matmul that leaves NaN there changes nothing."""
    cfg = tiny(held_experts=(4, 4))
    layer = seeded(cfg)["layers"][1]
    h = jax.random.normal(jax.random.key(6), (B * T, cfg.d_model), jnp.float32)

    def poisoned(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        past = jnp.arange(out.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    def run():
        return jax.value_and_grad(
            lambda h, lyr: jnp.sum(afmoe._moe_tokens(h, lyr, cfg)[0] ** 2),
            argnums=(0, 1),
        )(h, layer)

    want = run()
    real = jax.lax.ragged_dot
    try:
        jax.lax.ragged_dot = poisoned
        got = run()
    finally:
        jax.lax.ragged_dot = real
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-6)


def test_a_token_sharded_mesh_routes_per_shard_to_the_same_result(tokens):
    from ddl_tpu.parallel.mesh import make_mesh

    cfg = tiny(held_experts=(4, 4))
    params = seeded(cfg)
    want, want_picks = afmoe.forward_with_choices(params, tokens, cfg)
    mesh = make_mesh({"dp": 2}, jax.devices()[:2])
    got, picks = jax.jit(
        lambda p, t: afmoe.forward_with_choices(p, t, cfg, mesh)
    )(params, tokens)
    np.testing.assert_array_equal(np.asarray(picks), np.asarray(want_picks))
    close(got, want, F32_TOL, "logits on dp=2")


# -- the config states the architecture ---------------------------------------------


def test_the_preset_states_the_published_architecture():
    cfg = afmoe.AfmoeConfig.trinity_mini()
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.d_expert, cfg.n_experts, cfg.topk, cfg.vocab) == (
        2048, 32, 32, 4, 128, 6144, 1024, 128, 8, 200192)
    assert cfg.layer_types == (S, S, S, F) * 8 and cfg.n_dense_layers == 2
    assert cfg.sliding_window == 2048 and cfg.route_scale == 2.826
    assert cfg.held == (0, 128)
    shapes = jax.eval_shape(lambda: afmoe.init_params(cfg, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 26.0e9 < n < 26.3e9  # "26B total"
    layer = shapes["layers"][2]
    attn = sum(int(np.prod(layer[k].shape)) for k in ("wq", "wk", "wv", "wg", "wo"))
    assert attn == 27_262_976  # ISSUE 30's 27.26 M, the gate counted
    assert jax.tree_util.tree_structure(
        afmoe.param_specs(cfg), is_leaf=lambda x: isinstance(x, P)
    ) == jax.tree_util.tree_structure(shapes)


def test_the_preset_is_what_the_benchmark_builds_uncut():
    import sys

    sys.path.insert(0, ROOT)
    from benchmarks.families import afmoe as family

    with open(os.path.join(ROOT, "benchmarks/configs/trinity-mini.json")) as f:
        c = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/jobs/tokens-8k.json")) as f:
        mix = json.load(f)
    cut = family.model_config(c, mix)
    assert cut.layer_types == (S, S, S, S, F) and cut.n_dense_layers == 1
    assert cut.held == (0, 16) and cut.n_experts == 128 and cut.vocab == 25024
    uncut = family.model_config({**c, **c["published"]}, mix)
    preset = afmoe.AfmoeConfig.trinity_mini()
    # remat is the training section's choice, not the architecture's.
    assert dataclasses.replace(uncut, remat=preset.remat) == preset


@pytest.mark.parametrize("bad", [
    dict(layer_types=("windowed",)), dict(layer_types=()),
    dict(n_dense_layers=6), dict(held_experts=(12, 8)), dict(n_kv_heads=3),
])
def test_the_config_refuses_what_is_not_an_architecture(bad):
    with pytest.raises(ValueError):
        tiny(**bad)


@pytest.mark.parametrize("entry", ["forward_with_cache", "generate"])
def test_serving_is_refused_by_name(entry):
    with pytest.raises(NotImplementedError, match="sliding_attention|windowed KV"):
        getattr(afmoe, entry)()


def test_the_head_size_is_stated_not_derived():
    cfg = tiny()
    assert cfg.head_dim * cfg.n_heads == 2 * cfg.d_model
    layer = afmoe.init_params(cfg, jax.random.key(0))["layers"][0]
    assert layer["wq"].shape == (64, 128) and layer["wg"].shape == (64, 128)
    assert layer["wo"].shape == (128, 64) and layer["q_norm"].shape == (32,)


@pytest.mark.parametrize("checkpoint_layers", [False, True])
def test_the_references_layer_hook_changes_nothing_and_sees_every_layer(
    tokens, checkpoint_layers
):
    """``forward(layer_fn=)``: what stands in for ``_layer`` is called once
    a layer with that layer's parameters as they were given, under the
    layers' ``jax.checkpoint`` too; loss and gradients are the plain ones."""
    cfg = tiny(held_experts=(4, 4))
    params = seeded(cfg)
    c = ref_config(cfg, checkpoint_layers=checkpoint_layers)
    seen = []

    def layer_fn(x, layer, c, r, sliding, dense):
        seen.append((sliding, dense, sorted(layer)))
        return ref._layer(x, layer, c, r, sliding, dense)

    want_loss, want = ref.loss_and_grads(params, tokens, c)
    got_loss, got = jax.value_and_grad(ref.loss)(params, tokens, c, None, layer_fn)
    kinds = [
        (True, True), (True, False), (True, False), (True, False), (False, False)
    ]
    if checkpoint_layers:  # traced once a kind, not once a layer
        assert {kind[:2] for kind in seen} == set(kinds)
    else:
        assert [kind[:2] for kind in seen[:5]] == kinds
    assert seen[1][2] == sorted(params["layers"][1])
    assert float(got_loss) == float(want_loss)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- the benchmark's copy cannot drift ------------------------------------------------


def _body(path):
    with open(path) as f:
        text = f.read()
    return text[text.index("\nfrom __future__"):]


def test_the_benchmarks_reference_is_this_one():
    assert _body(os.path.join(ROOT, "tests", "reference_afmoe.py")) == _body(
        os.path.join(ROOT, "benchmarks", "lib", "afmoe_reference.py")
    )


# -- what the shared kernels and the shared expert core may not do to the others -----

#: sha256 of the 2-step window programs below (``parallel.train.
#: make_multistep``, adamw, selective remat, bf16 storage, T = 2048) on
#: PR 45's tree (the child of 90a08e5), which changed all four on purpose:
#: a blockwise flash call's backward pass is ONE kernel, the dK/dV grid
#: carrying dQ (``ddl_flash_bwd_dkv``; no ``ddl_flash_bwd_dq`` call is
#: left).  Before: PR 33's tree (selective remat saves the blockwise
#: cores' residuals, so the backward holds no second forward kernel) and,
#: for the two ``olmoe`` programs, PR 35's (the routed layer's backward
#: pass gathers where it scatter-added):
#: ``tpu``: lowered for the TPU with each Mosaic kernel's serialised body
#: taken out (it carries the file and line of every operation);
#: ``interpreted``: with the kernels' bodies as the interpreter's HLO, line
#: for line what the kernels compute.
PARENT_JAX = "0.9.0"
PARENT_WINDOW_PROGRAM_SHA256 = {
    ("mistral", "tpu"):
        "2df52d1bc6afd15b8ea6da5a28951bea61ff49523e0a10a71a8921f4591330da",
    ("olmoe", "tpu"):
        "fc2f8019e0a81562dae20e21a3e8a9b9e94d604d6076debf70c760599aedb8c5",
    ("mistral", "interpreted"):
        "9d9d9710868eef06ed644a761c10d6f899cfa2af15cbf21569d3f287e2f4d33b",
    ("olmoe", "interpreted"):
        "51e2b0eae69e4d526b8ae435a85f280cd9742a93d4877b83c45ab9dbb7bb03e5",
}


def _window_program(model, how):
    """``traced()`` -> the 2-step window program of a Mistral-shaped or an
    OLMoE-shaped decoder, traced anew each time (a jitted function keeps
    its first trace)."""
    import optax
    from jax.sharding import Mesh

    from ddl_tpu.parallel.train import make_multistep

    common = dict(
        vocab=512, d_model=256, n_layers=2, n_heads=2, max_seq=2048,
        param_dtype=jnp.bfloat16, remat="selective",
        attn_impl="auto" if how == "tpu" else "flash",
    )
    if model == "mistral":
        mod, cfg = llama, llama.LlamaConfig(
            n_kv_heads=1, d_ff=512, rope_theta=1e6, **common)
    elif model == "olmoe":
        mod, cfg = moe, moe.MoeConfig(
            n_kv_heads=2, d_ff=128, n_experts=8, topk=2, rope_theta=1e4,
            qk_norm=True, norm_topk_prob=False, router_aux_all_slots=True,
            router_z_weight=0.001, **common)
    else:  # "trinity": a share of 4 of 16 experts, top-4: 16,384 rows, bound 8,192
        del common["n_layers"]
        mod, cfg = afmoe, afmoe.AfmoeConfig(
            n_kv_heads=1, head_dim=128, d_ff=512, d_expert=128, n_experts=16,
            topk=4, layer_types=(S, S, F), n_dense_layers=1, sliding_window=512,
            route_scale=2.826, held_experts=(0, 4), **common)
    optimizer = optax.adamw(3e-4)
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    args = (params, jax.eval_shape(optimizer.init, params),
            (jax.ShapeDtypeStruct((2, 2, 2048), jnp.int32),), True)

    def traced():
        _, multi = make_multistep(
            lambda p, b: mod.next_token_loss(p, b[0], cfg), optimizer,
            Mesh(np.array(jax.devices()[:1]), ("dp",)), mod.param_specs(cfg),
            batch_spec=P(("dp",)), n_steps=2,
        )
        run = next(c.cell_contents for c in multi.__closure__
                   if hasattr(c.cell_contents, "lower"))
        return run.trace(*args)

    return traced


@pytest.mark.parametrize("how", ["tpu", "interpreted"])
@pytest.mark.parametrize("model", ["mistral", "olmoe"])
def test_the_window_programs_of_the_other_decoders_are_the_parents(model, how,
                                                                  monkeypatch):
    traced = _window_program(model, how)
    # The causal-full kernels and no other, each once a layer in the scanned
    # step: the forward not a second time for the backward pass, and ONE
    # backward kernel (the dK/dV grid carrying dQ, PR 45: no ``bwd_dq``).
    with monkeypatch.context() as on_tpu:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        text = traced().lower(lowering_platforms=("tpu",)).as_text()
    calls = re.findall(r'kernel_name = "(ddl_flash_\w+)"', text)
    assert sorted(calls) == sorted(2 * [
        "ddl_flash_fwd", "ddl_flash_bwd_dkv"]), calls
    if how == "tpu":
        assert text.count("tpu_custom_call") == 4
        text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", text)
    else:
        text = traced().lower().as_text()
    assert "ddl_flash_swa" not in text
    if jax.__version__ == PARENT_JAX:  # the text is this JAX's
        assert hashlib.sha256(text.encode()).hexdigest() == (
            PARENT_WINDOW_PROGRAM_SHA256[model, how]
        )


@pytest.mark.parametrize("model", ["olmoe", "trinity"])
def test_the_routed_layers_backward_pass_scatters_no_rows(model, monkeypatch):
    """The OLMoE-shaped window program and a share's (4 of 16 experts: the
    bounded pass and its full-width fallback, both in the text), lowered
    for the TPU: the row moves of ``moe.ragged_experts`` transpose into
    gathers (``moe._take_copies``, ``moe._combine_copies``) or sum their
    rows in a grouped matmul (``moe._head_copies``, ``moe._head_combine``),
    so no scatter is left whose updates are (N·k, D) or (B, D) rows — PR
    35's parent had two a routed layer and backward pass (the layers are
    scanned: two in the text).  What stays, by its updates: ``bincount``'s
    N·k ones into the group sizes (forward, its recomputation, and OLMoE's
    auxiliary loss's counts; the bounded pass finds its token blocks' sizes
    by binary search in its sorted keys, ``moe._by_token``), the
    two ``take_along_axis`` transposes of a trained router's top-k, the
    cross-entropy's, and the embedding's (B, T, D) rows."""
    with monkeypatch.context() as on_tpu:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        text = _window_program(model, "tpu")().lower(
            lowering_platforms=("tpu",)).as_text()
    updates = sorted(re.findall(
        r'"stablehlo.scatter"\(.*?\n\s*\}\) : \(tensor<\S+>, tensor<\S+>, '
        r'tensor<(\S+)>\)', text, re.S))
    rows = [u for u in updates if re.fullmatch(r"\d+x256xbf16", u)]
    assert rows == [], rows  # the parent: ["8192x256xbf16", "8192x256xbf16"]
    assert updates == sorted({
        "olmoe": 4 * ["8192xi32"]    # bincount: one count a (token, slot) copy
        + 2 * ["4096x2xf32"],        # the router's top-k
        "trinity": 2 * 2 * ["16384xi32"],  # bincount, forward and recomputed
    }[model]
        + ["2x2048x1xf32"]           # cross-entropy's take_along_axis
        + ["2x2048x256xbf16"]        # the embedding's rows
    ), updates
    assert "unique_indices = true" not in text  # gathers, not hinted scatters


def test_a_shares_conds_pass_no_rows_and_name_their_fallback(monkeypatch):
    """The share's window program again, by its name stacks: two expert
    layers, two ``cond``s each - forward and backward: ``selective`` saves
    the routed result (AFMoE's post-MLP norm reads it in the backward
    pass), so the recomputation holds no ``cond``.  No ``cond``
    returns an array of N·k or of B rows: a residual that crossed one would
    be zero-filled by the branch not taken, the memsets that eat the gain
    (``moe._held_rows``).  Every op of the full-width fallback stands under
    ``ddl.moe_overflow`` and no op of the bounded pass does, so the device
    trace says which ran.  And every private ``_take`` function's callers
    stand under ONE name stack (two layers share a function now; a function
    shared across name stacks is what loses a gather its scope, PR 35)."""
    with monkeypatch.context() as on_tpu:
        on_tpu.setattr(jax, "default_backend", lambda: "tpu")
        text = _window_program("trinity", "tpu")().lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    results = re.findall(r'"stablehlo.case"\(.*?\n\s*\}\) : \(tensor<i32>\) -> (.*?) loc',
                         text, re.S)
    assert len(results) == 2 * 2, len(results)
    for types in results:
        assert not re.search(r"tensor<(16384|8192)x", types), types
    assert sum("4096x256xbf16" in t for t in results) == 4  # out, or d_x

    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    paths = set(locs.values())
    fallback = {p for p in paths if "/branch_0_fun" in p}
    bounded = {p for p in paths if "/branch_1_fun" in p}
    assert len(fallback) > 20 and len(bounded) > 20
    assert all("ddl.moe_overflow" in p for p in fallback), [
        p for p in fallback if "ddl.moe_overflow" not in p][:5]
    assert not any("ddl.moe_overflow" in p for p in paths - fallback)
    for p in fallback | bounded:  # and each under a phase of the routed core
        assert re.search(r"ddl\.moe_(route|experts|combine)", p), p

    callers = collections.defaultdict(set)
    for name, loc in re.findall(r"call @(_take\w*)\(.*?loc\((#loc\d+)\)", text):
        callers[name].add(locs[loc])
    # Full width: the copies and the un-permute forward, the copies again
    # in the backward pass; bounded: the copies, a pass.
    assert len(callers) == 3 + 2, sorted(callers)
    assert all(len(stacks) == 1 for stacks in callers.values()), callers


def test_every_expert_layers_row_gathers_keep_a_function_of_their_own():
    """Four expert layers that are not scanned, under ``selective``, in
    the lowered gradient: every ``jnp.take`` of the routed core is a
    private function with ONE caller, in the forward pass and in its
    recomputation.  A function with two callers reaches XLA as a call, and
    XLA inlines a call without the caller's name stack: its gather runs
    under no ``ddl.`` scope in the device trace and reads as a gain of
    ``moe_dispatch_device_share`` that is none (PR 35: a ``custom_vjp``
    called inside its own forward rule, or ``jnp.take`` inside a backward
    rule, is traced once a signature and does exactly that)."""
    cfg = tiny(held_experts=(4, 4), remat="selective")
    params = jax.eval_shape(lambda: afmoe.init_params(cfg, jax.random.key(0)))
    text = jax.jit(jax.grad(
        lambda p, t: afmoe.next_token_loss(p, t, cfg)
    )).lower(params, jax.ShapeDtypeStruct((B, T), jnp.int32)).as_text()
    callers = collections.Counter(re.findall(r"call @(_take\w*)\(", text))
    assert len(callers) == 4 * 4, callers  # copies + un-permute, twice a layer
    assert set(callers.values()) == {1}, callers


# -- the benchmark's FLOP count of the attended pairs ---------------------------------


@pytest.mark.parametrize("seq,window", [
    (32, 8), (32, 32), (32, 100), (33, 1), (64, 17), (8, None),
])
def test_the_pair_count_is_a_brute_force_masks(seq, window):
    import sys

    sys.path.insert(0, ROOT)
    from benchmarks.lib import afmoe_flops

    assert afmoe_flops.attended_pairs(seq, window) == int(ref.visible(seq, window).sum())
    # ... and the mask is the band the attention oracle applies.
    from ddl_tpu.parallel.ring_attention import attention_reference

    q = jnp.zeros((1, seq, 1, 4), jnp.float32)
    v = jnp.eye(seq, dtype=jnp.float32)[None, :, None, :]  # row i of the output: p[i, :]
    p = attention_reference(q, q, v, window=window)[0, :, 0]
    np.testing.assert_array_equal(np.asarray(p) > 0, ref.visible(seq, window))

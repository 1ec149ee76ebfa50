"""Xing4.0's architecture at Xing4.0-29B-A4B's shape through
``models/xing4.py`` against its plain float32 reference
(``tests/reference_xing4.py``), at a tiny size on the CPU: hidden 32, four
streams, 2 heads of 16 + 8 score and 16 value width over a 16-wide latent
behind a 24-wide query step, YaRN x 8 over 8 positions, one dense layer of
width 48 then two expert layers of 8 experts x 16, top-2, one shared expert,
one multi-token-prediction module; vocab 128 untied, T 32.

Seeded weights with the wraps moved off their near-identity start (``alpha``
x 30, the biases perturbed) so that the mixing matrices depend on the input,
the norm weights moved off 1 and the router scaled up.
"""

import collections
import contextlib
import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_xing4 as ref
from ddl_tpu.models import decoder, deepseek_v3, moe, remat, xing4
from ddl_tpu.models import hyper_connections as hc
from ddl_tpu.models.deepseek_v3 import Yarn

from hcsupport import (
    HC_KERNELS, TILED, TOY, _wrap, close, kernel_names, plain_wrap, xla_passes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
B, T = 2, 32

#: float32 system against float32 reference, as a share of the largest
#: magnitude in the compared array: the same float32 arithmetic on the CPU,
#: differing in summation order alone (the projections a stream at a time,
#: the norm's division behind them; expert rows sorted and summed over 2
#: slots against a masked sum over the held experts).  Measured up to 6e-6;
#: a gradient leaf (``GRAD_TOL``: a wrap's ``alpha`` is ONE number summed over
#: every token and stream) up to 4e-5.
F32_TOL = 3e-5
GRAD_TOL = 2e-4


def tiny(**kw) -> xing4.Xing4Config:
    base = dict(
        vocab=128, d_model=32, n_layers=3, n_heads=2, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, kv_lora_rank=16, q_lora_rank=24, d_ff=48,
        d_expert=16, n_experts=8, topk=2, n_shared_experts=1, n_dense_layers=1,
        route_scale=2.0, max_seq=T, rope_theta=1e4,
        rope_scaling=Yarn(8.0, 8, 4.0, 1.0, 1.0, 1.0), dtype=jnp.float32,
        param_dtype=jnp.float32, attn_impl="dense",
    )
    base.update(kw)
    return xing4.Xing4Config(**base)


def ref_config(cfg, **kw) -> ref.Config:
    yarn = cfg.rope_scaling
    return ref.Config(
        n_heads=cfg.n_heads, qk_nope_dim=cfg.qk_nope_dim,
        qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, n_experts=cfg.n_experts, topk=cfg.topk,
        n_dense_layers=cfg.n_dense_layers, held=cfg.held,
        yarn=None if yarn is None else tuple(yarn), route_norm=cfg.route_norm,
        route_scale=cfg.route_scale, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps, hc_mult=cfg.hc_mult,
        hc_iters=cfg.hc_sinkhorn_iters, hc_eps=cfg.hc_eps, hc_clamp=cfg.hc_clamp,
        n_mtp=cfg.n_mtp, mtp_weight=xing4.MTP_LOSS_WEIGHT, query_block=8,
    )._replace(**kw)


def seeded(cfg):
    """Parameters with the wraps off their start, every norm weight off 1,
    the selection bias off 0 and the router scaled up."""
    params = xing4.init_params(cfg, jax.random.key(46))
    keys = iter(jax.random.split(jax.random.key(47), 512))

    def off(x, by=0.2):
        return x + by * jax.random.normal(next(keys), x.shape, x.dtype)

    def layer_off(layer):
        for name in ("attn_norm", "mlp_norm", "q_a_norm", "kv_a_norm"):
            layer[name] = off(layer[name])
        if "w_router" in layer:
            layer["w_router"] = 4.0 * layer["w_router"]
            layer["expert_bias"] = off(layer["expert_bias"], 0.05)
        for wrap in (layer["hc_attn"], layer["hc_mlp"]):
            for name in wrap:
                wrap[name] = 30.0 * wrap[name] if name.startswith("alpha") else off(
                    wrap[name], 0.3 if name.startswith("b_") else 0.1)

    for layer in params["layers"]:
        layer_off(layer)
    params["final_norm"] = off(params["final_norm"])
    if cfg.n_mtp:
        layer_off(params["mtp"]["layer"])
        for name in ("enorm", "hnorm", "norm"):
            params["mtp"][name] = off(params["mtp"][name])
    return params


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(46).integers(0, 128, (B, T)), jnp.int32)


def reference_side(cfg, params, tokens, c=None):
    """((loss, (main, mtp, logits, mtp_logits, picks)), grads) of the
    reference: one program."""
    c = c or ref_config(cfg)

    def plain(p):
        logits, mtp_logits, picks = ref.forward_all(p, tokens, c)
        main = ref.cross_entropy(logits, tokens)
        mtp = ref.mtp_cross_entropy(mtp_logits, tokens) if c.n_mtp else 0.0
        return main + c.mtp_weight * mtp, (main, mtp, logits, mtp_logits, picks)

    return jax.jit(jax.value_and_grad(plain, has_aux=True))(params)


def both_sides(cfg, params, tokens, c=None, want=None):
    """The system's side, one program, beside the reference's (``want``, or
    :func:`reference_side`)."""

    def system(p):
        logits, mtp_logits, picks = xing4.forward_all(p, tokens, cfg)
        loss = xing4.next_token_loss(p, tokens, cfg)
        main, mtp = xing4.losses(p, tokens, cfg)
        return loss, (main, mtp, logits, mtp_logits, picks)

    got = jax.jit(jax.value_and_grad(system, has_aux=True))(params)
    return got, want or reference_side(cfg, params, tokens, c)


def assert_matches_reference(cfg, params, tokens, c=None, tol=F32_TOL, want=None):
    """Logits and the module's, both losses, the routers' picks and every
    gradient leaf (a leaf whose gradient is rounding noise on both sides - a
    stream's first wrap reads four identical rows - is held to the largest
    leaf's magnitude)."""
    ((got_loss, got), got_grads), ((want_loss, want), want_grads) = both_sides(
        cfg, params, tokens, c, want)
    routed = cfg.n_layers - cfg.n_dense_layers + cfg.n_mtp
    assert got[4].shape == (routed, B, T, cfg.topk)
    stack = routed - cfg.n_mtp
    np.testing.assert_array_equal(
        np.sort(np.asarray(got[4][:stack]), -1), np.sort(np.asarray(want[4][:stack]), -1))
    close(got[2], want[2], tol, "logits")
    close(got_loss, want_loss, tol, "loss")
    close(got[0], want[0], tol, "main loss")
    if cfg.n_mtp:
        # the module's picks and logits: the reference is one position short,
        # the system's last two positions have no target
        np.testing.assert_array_equal(
            np.sort(np.asarray(got[4][-1][:, : T - 1]), -1),
            np.sort(np.asarray(want[4][-1][:, : T - 1]), -1))
        close(got[3][:, : T - 2], want[3][:, : T - 2], tol, "the module's logits")
        close(got[1], want[1], tol, "the module's loss")
    got_leaves = dict(jax.tree_util.tree_leaves_with_path(got_grads))
    want_leaves = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert set(got_leaves) == set(want_leaves)
    largest = max(float(jnp.max(jnp.abs(w))) for w in want_leaves.values())
    for path, want_leaf in want_leaves.items():
        close(got_leaves[path], want_leaf, tol * GRAD_TOL / F32_TOL,
              "d" + jax.tree_util.keystr(path), floor=1e-3 * largest)
    return got_grads


# -- float32: the system is the reference ----------------------------------------


@pytest.mark.parametrize("held,remat_,n_mtp", [
    (None, "none", 1), ((2, 4), "selective", 1), ((2, 4), "full", 1),
    ((4, 4), "none", 0),
], ids=["uncut", "share_selective", "share_full", "last_share_no_module"])
def test_float32_system_matches_the_reference(tokens, held, remat_, n_mtp):
    cfg = tiny(held_experts=held, remat=remat_, n_mtp=n_mtp)
    grads = assert_matches_reference(cfg, seeded(cfg), tokens)
    router = float(jnp.linalg.norm(grads["layers"][1]["w_router"]))
    # The uncut model trains its router; a share does not.
    assert (router > 0) == (held is None)
    assert float(jnp.linalg.norm(grads["layers"][1]["expert_bias"])) == 0.0
    assert ("mtp" in grads) == bool(n_mtp)


def test_a_bf16_reference_fails_the_float32_tolerance(tokens):
    cfg = tiny(held_experts=(2, 4))
    params = seeded(cfg)
    want, _ = ref.forward(params, tokens, ref_config(cfg))
    low, _ = ref.forward(params, tokens, ref_config(cfg), jnp.bfloat16)
    with pytest.raises(AssertionError):
        close(low, want, F32_TOL, "logits")


# -- the wraps ----------------------------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape,path", [
    (TOY, "xla"), (TILED, "kernels"), (TILED, "xla")], ids=["toy", "kernels", "tiled-xla"])
def test_the_wraps_own_backward_is_autodiffs_of_the_plain_form(shape, path, dtype, tol):
    """``hc_pre`` / ``hc_post`` (``custom_vjp``, the stream's passes written
    out or as the ``ddl_hc_*`` kernels in interpret mode, the projections over
    the bfloat16 stream with split weights) against ``jax.grad`` of
    :func:`plain_wrap`: the result and every cotangent - the stream's, ``F``'s
    weight's (which is ``y``'s) and all ten wrap leaves'.  In bfloat16 both
    sides round the stream's cotangent once.  ``hc_pre`` hands the stream on
    as ``xing4._layer_apply`` takes it, so that ``hc_post``'s cotangent of it
    is added inside ``_hc_pre_bwd``'s pass."""
    (Bn, Tn, C), n = shape, 4
    wrap, settings = _wrap(n, C), hc.HyperConnections()
    X = jax.random.normal(jax.random.key(1), (Bn, n, Tn, C)).astype(dtype)
    W = jax.random.normal(jax.random.key(2), (C, C)) / np.sqrt(C)
    F = lambda h, W: jnp.tanh(h.astype(jnp.float32) @ W).astype(h.dtype)
    ct = jax.random.normal(jax.random.key(3), X.shape)

    def system(X, wrap, W):
        h, post, res, X = hc.hc_pre(X, wrap, settings)
        return hc.hc_post(X, F(h, W), post, res)

    plain = lambda X, wrap, W: plain_wrap(X, wrap, settings, lambda h: F(h, W))
    value = lambda f: lambda *a: jnp.sum(f(*a).astype(jnp.float32) * ct)
    with xla_passes() if path == "xla" else contextlib.nullcontext():
        want_kernels = HC_KERNELS if path == "kernels" else set()
        assert kernel_names(jax.grad(value(system)), X, wrap, W) == want_kernels
        out = system(X, wrap, W)
        got = jax.grad(value(system), argnums=(0, 1, 2))(X, wrap, W)
    close(out, plain(X, wrap, W), tol, "X'")
    want = jax.grad(value(plain), argnums=(0, 1, 2))(X, wrap, W)
    # a wide stream's weights' cotangents are sums over 512 tokens of products
    # rounded to bfloat16 once a side
    for (path_, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                             jax.tree.leaves(want)):
        close(g, w, tol, "d" + jax.tree_util.keystr(path_))


def test_hres_is_doubly_stochastic_after_twenty_rounds():
    """Rows and columns of a FRESH wrap's ``Hres`` sum to 1: the rows (the last
    step) to 1e-5, the columns to 2e-4 - measured 1.2e-4: near the identity
    Sinkhorn-Knopp closes 13% of the columns' gap a round (``exp(4)`` on the
    diagonal: the limit's second singular value is 0.93), 6e-5 after 40 rounds;
    with ``alpha`` thirty times its start, 4e-3 after 20.  The system's and the
    reference's agree either way."""
    n, C = 4, 24
    settings = hc.HyperConnections()
    X = jax.random.normal(jax.random.key(4), (2, n, 9, C))
    fresh = {**_wrap(n, C, off=0.0)}
    fresh.update({k: v / 30.0 for k, v in fresh.items() if k.startswith("alpha")})
    _, post, res, _ = hc.hc_pre(X, fresh, settings)
    assert res.shape == (2, n, n, 9) and post.shape == (2, n, 9)
    np.testing.assert_allclose(np.asarray(res.sum(axis=2)), 1.0, atol=1e-5)  # rows
    np.testing.assert_allclose(np.asarray(res.sum(axis=1)), 1.0, atol=2e-4)  # columns
    assert float(jnp.max(jnp.std(res, axis=-1))) > 1e-5  # a token's own matrix
    wrap = _wrap(n, C, off=0.02)
    _, post, res, _ = hc.hc_pre(X, wrap, settings)
    np.testing.assert_allclose(np.asarray(res.sum(axis=2)), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res.sum(axis=1)), 1.0, atol=2e-2)
    c = ref_config(tiny())
    pre_r, post_r, res_r = ref.wrap_matrices(jnp.moveaxis(X, 1, 2), wrap, c)
    close(jnp.moveaxis(res, -1, 1), res_r, 1e-5, "Hres")
    close(jnp.moveaxis(post, -1, 1), post_r, 1e-5, "Hpost")


def test_identity_mixing_is_the_plain_pre_norm_stack(tokens, monkeypatch):
    """With ``Hres = I``, ``Hpre = 1/4``, ``Hpost = 1`` set by hand the stack's
    logits are the plain pre-norm stack's - ``models/deepseek_v3.py``'s on the
    same weights, with its query low-rank step and YaRN (the closing sum's
    factor 4 is lost in the final norm)."""
    cfg = tiny(n_mtp=0)
    params = seeded(cfg)

    def by_hand(z, wrap, settings):
        Bz, _, Tz = z.shape
        n = settings.n
        eye = jnp.broadcast_to(jnp.eye(n)[None, :, :, None], (Bz, n, n, Tz))
        return jnp.full((Bz, n, Tz), 1.0 / n), jnp.ones((Bz, n, Tz)), eye

    monkeypatch.setattr(hc, "matrices", by_hand)
    monkeypatch.setattr(hc, "_hc_matrices", hc._hc_matrices.__wrapped__)
    got = xing4.forward(params, tokens, cfg)
    plain_cfg = deepseek_v3.DeepseekV3Config(**{
        f.name: getattr(cfg, f.name)
        for f in dataclasses.fields(deepseek_v3.DeepseekV3Config)})
    want = deepseek_v3.forward(params, tokens, plain_cfg)
    close(got, want, F32_TOL, "logits")


def test_the_shares_add_up_to_the_uncut_layer():
    """One routed layer on the same stream: with attention, the shared expert
    and ``Hres X`` counted once, the routed parts that all 4 shares of 2
    experts give add up to the uncut reference's layer."""
    whole = tiny()
    layer = seeded(whole)["layers"][2]
    c = ref_config(whole)
    X = jax.random.normal(jax.random.key(5), (B, whole.hc_mult, T, whole.d_model))
    want, want_picks = ref._layer(jnp.moveaxis(X, 1, 2), layer, c, ref._same, False)
    positions = jnp.arange(T)

    # the layer up to its second wrap's sub-block, once
    h, post, res, _ = hc.hc_pre(X, layer["hc_attn"], whole.hc)
    X_mid = hc.hc_post(
        X, deepseek_v3.attn(layer, h, whole, positions, None, residual=False), post, res)
    h, post, res, _ = hc.hc_pre(X_mid, layer["hc_mlp"], whole.hc)
    h = decoder.rms_norm(h, layer["mlp_norm"], whole.norm_eps)
    shared = decoder.swiglu(layer["shared"], h)
    routed, held_choices = jnp.zeros_like(h), 0
    for first in range(0, whole.n_experts, 2):
        cfg = tiny(held_experts=(first, 2))
        mine = {**layer, "experts": jax.tree.map(
            lambda w: w[first : first + 2], layer["experts"])}
        out, picks = moe.sigmoid_expert_mlp(h, mine, cfg, None)
        np.testing.assert_array_equal(np.asarray(picks), np.asarray(want_picks))
        routed = routed + (out - shared)  # a share's routed part
        held_choices += int(np.sum((picks >= first) & (picks < first + 2)))
    assert held_choices == B * T * whole.topk  # every choice is held once
    got = hc.hc_post(X_mid, shared + routed, post, res)
    close(jnp.moveaxis(got, 1, 2), want, F32_TOL, "the layer from its shares")
    # ... and the uncut system's layer is the same
    uncut, _, _ = xing4._layer_apply(layer, X, whole, positions, False, None)
    close(jnp.moveaxis(uncut, 1, 2), want, F32_TOL, "the uncut layer")


# -- YaRN and the query's low-rank step ------------------------------------------------


def test_yarn_at_the_published_numbers_is_hand_computed():
    """factor 64 over 4,096 positions, beta 32 / 1, theta 10,000, 64 rotary
    numbers: ``c(32) = 10.47`` and ``c(1) = 22.51``, so pairs 0-10 keep their
    frequency, pairs 23-31 turn 64 times slower, pair 16 is ``f_16 (1 - 6/13 +
    6/13 / 64)``; ``m = 0.1 ln 64 + 1 = 1.41589`` and the score's scale is
    ``m^2 / sqrt(192) = 0.144680``."""
    yarn = Yarn(64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    got = deepseek_v3.yarn_inv_freq(1e4, 64, yarn)
    f = 1e4 ** (-np.arange(32) / 32.0)
    assert got.shape == (32,) and got.dtype == np.float32
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], f[23:] / 64.0, rtol=1e-6)
    np.testing.assert_allclose(got[16], f[16] * (1 - 6 / 13 + 6 / 13 / 64), rtol=1e-6)
    np.testing.assert_allclose(got[11], f[11] * (1 - 1 / 13 + 1 / 13 / 64), rtol=1e-6)
    np.testing.assert_allclose(got, ref.yarn_inv_freq(1e4, 64, tuple(yarn)), rtol=1e-6)
    cfg = xing4.Xing4Config.xing4_0_29b_a4b()
    assert abs(yarn.m(1.0) - 1.4158883) < 1e-6
    assert abs(cfg.score_scale - 2.0047397) < 1e-6
    assert abs(cfg.score_scale / np.sqrt(192) - 0.1446796) < 1e-6
    assert abs(ref.score_scale(ref_config(cfg)) - cfg.score_scale / np.sqrt(192)) < 1e-9
    # mscale_all_dim 0 (the family's default) leaves the scale alone
    assert dataclasses.replace(
        cfg, rope_scaling=Yarn(64.0, 4096)).score_scale == 1.0
    assert tiny(rope_scaling=None).score_scale == 1.0


def test_the_kernels_one_scale_carries_yarns_factor(monkeypatch):
    """The latent flash kernels' scale is ``score_scale / sqrt(D + R)`` - q is
    not rescaled - and without a factor it is the float it always was."""
    import importlib

    fa = importlib.import_module("ddl_tpu.ops.flash_attention")
    q, rope = jnp.zeros((1, 8, 2, 128)), (jnp.zeros((1, 8, 2, 64)),)
    assert fa._mla_scale(q, rope) == 1.0 / (192**0.5)
    assert fa._mla_scale(q, rope, 2.0) == 2.0 / (192**0.5)
    seen = {}
    real = fa._fwd_impl

    def spy(*a, **kw):
        seen["scale"] = kw.get("score_scale")
        return real(*a, **kw)

    monkeypatch.setattr(fa, "_fwd_impl", spy)
    Tn = 16
    args = [jax.random.normal(jax.random.key(i), (1, Tn, 2, 16)) for i in range(3)]
    q_r = jax.random.normal(jax.random.key(3), (1, Tn, 2, 8))
    k_r = jax.random.normal(jax.random.key(4), (1, Tn, 1, 8))
    from ddl_tpu.parallel.ring_attention import attention, attention_reference

    got = attention(*args, impl="flash", q_rope=q_r, k_rope=k_r, score_scale=2.0)
    assert seen["scale"] == 2.0
    want = attention_reference(*args, q_rope=q_r, k_rope=k_r, score_scale=2.0)
    close(got, want, 2e-5, "the kernels under a score scale")
    plain = attention_reference(*args, q_rope=q_r, k_rope=k_r)
    assert float(jnp.max(jnp.abs(want - plain))) > 1e-3


# -- the train step -----------------------------------------------------------------------


def test_one_window_step_moves_the_parameters_as_plain_adamw_does(tokens):
    """One real ``make_multistep`` window (two steps, the program the Trainer
    runs) from the seeded weights against two plain adamw steps of the
    REFERENCE's gradients."""
    import optax

    from ddl_tpu.parallel.mesh import make_mesh
    from ddl_tpu.parallel.train import make_multistep

    cfg = tiny(held_experts=(2, 4), remat="selective")
    params = seeded(cfg)
    c = ref_config(cfg)
    optimizer = optax.adamw(1e-3)
    init_state, step = make_multistep(
        lambda p, b: xing4.next_token_loss(p, b[0], cfg), optimizer,
        make_mesh({"dp": 1}, devices=jax.devices()[:1]), xing4.param_specs(cfg),
        n_steps=2, donate=False,
    )
    window = (jnp.stack([tokens[:1], tokens[1:]]),)  # two steps of one row
    state, losses = step(init_state(params), window, per_step=True)

    want, opt_state, want_losses = params, optimizer.init(params), []
    for row in window[0]:
        loss, grads = jax.value_and_grad(ref.loss)(want, row, c)
        updates, opt_state = optimizer.update(grads, opt_state, want)
        want = optax.apply_updates(want, updates)
        want_losses.append(loss)
    close(losses, jnp.stack(want_losses), F32_TOL, "the window's losses")
    moved = jax.tree.map(lambda a, b: a - b, state.params, params)
    want_moved = jax.tree.map(lambda a, b: a - b, want, params)
    size = lambda t: float(jnp.sqrt(sum(jnp.sum(x**2) for x in jax.tree.leaves(t))))
    diff = size(jax.tree.map(lambda a, b: a - b, moved, want_moved))
    # adam's first steps are g / (|g| + eps): elements whose gradient is noise
    # (the noise leaves above) move by +-lr on either side
    assert diff / size(want_moved) < 0.02, diff / size(want_moved)
    assert size(want_moved) > 0.1


#: (d_model, T) of the tiny model: the toy's, whose stream takes XLA's passes,
#: and one token tile of one lane's width, which takes the kernels.
STREAMS = {"toy": (32, T), "tiled": (128, 128)}


def hc_passes(text: str) -> collections.Counter:
    """The wraps' jitted passes in a traced program, by name; ``pre_fwd`` is
    the pass over the stream for ``h``: XLA's ``_hc_read`` or the kernel's
    ``_hc_pre_fwd``, which makes the 25 numbers in the same read."""
    calls = collections.Counter(re.findall(r"name=_hc_(\w+)", text))
    assert not (calls["read"] and calls["pre_fwd"]), calls
    calls["pre_fwd"] += calls["read"]
    return calls


@pytest.mark.parametrize("stream", list(STREAMS))
@pytest.mark.parametrize("policy", ["none", "selective", "full", "dots"])
def test_the_benchmarks_pass_counts_are_the_traced_steps(policy, stream):
    """``benchmarks/lib/xing4_flops.py:HC_PASSES_PER_LAYER`` - what the
    residual path's bandwidth floor multiplies - is the number of passes in
    the program's own train step, a layer that carries a stream: XLA's passes
    and the kernels alike."""
    from benchmarks.lib import xing4_flops

    d_model, Tn = STREAMS[stream]
    cfg = tiny(held_experts=(2, 4), remat=policy, d_model=d_model, max_seq=Tn)
    params = jax.eval_shape(lambda: xing4.init_params(cfg, jax.random.key(0)))
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda p, t: xing4.next_token_loss(p, t, cfg)
    ))(params, jax.ShapeDtypeStruct((B, Tn), jnp.int32)))
    calls = hc_passes(text)
    layers = cfg.n_layers + cfg.n_mtp
    got = {which: calls[which] / layers
           for which in ("pre_fwd", "post_fwd", "pre_bwd", "post_bwd")}
    assert got == xing4_flops.HC_PASSES_PER_LAYER[policy], calls
    # nothing kept: a rematerialised wrap reads the stream for the 25 numbers
    # the matrices are made of again, and makes the matrices again
    assert calls["matrices"] == calls["pre_fwd"]
    kernels = set(re.findall(r"ddl_hc_\w+", text))
    if stream == "toy":
        assert calls["project"] == calls["read"] == calls["pre_fwd"] and not kernels
    else:
        assert calls["project"] == calls["read"] == 0 and kernels == HC_KERNELS


@pytest.mark.parametrize("stream", list(STREAMS))
def test_the_mixing_matrices_are_the_first_kind_a_plan_keeps(stream):
    """Under ``selective`` with a budget the 25 numbers a token the wraps'
    matrices are made of are tagged ``remat.HC``, 100 bytes a token and wrap,
    first in the order of worth; kept, a rematerialised wrap makes the
    matrices from them and reads the stream once, for ``h`` - XLA's
    ``_hc_read``, or the kernel again, whose read is the same one; loss and
    gradients are what they were (run at the toy's size; the tiled stream's
    step is traced and counted)."""
    assert remat.KINDS[0] == remat.HC
    d_model, Tn = STREAMS[stream]
    cfg = tiny(held_experts=(2, 4), remat="selective", n_mtp=1, d_model=d_model,
               max_seq=Tn)
    params = seeded(cfg)
    tokens = jnp.asarray(np.random.default_rng(46).integers(0, 128, (B, Tn)), jnp.int32)
    loss = lambda p: xing4.next_token_loss(p, tokens, cfg)
    positions = jnp.arange(Tn)
    body = lambda X, layer: xing4._layer_apply(layer, X, cfg, positions, False, None)
    X = jnp.zeros((B, cfg.hc_mult, Tn, cfg.d_model), cfg.dtype)
    token = remat._TAGGING.set(True)
    try:
        counted = remat.measure(body, X, params["layers"][1])
    finally:
        remat._TAGGING.reset(token)
    n = cfg.hc_mult
    assert counted.by_name[remat.HC] == 2 * B * Tn * 4 * (2 * n + n * n + 1)
    assert counted.inputs == X.size * X.dtype.itemsize  # the four-row stream
    with remat.free_hbm(10**9):
        text = str(jax.make_jaxpr(jax.value_and_grad(loss))(params))
        if stream == "toy":
            got_loss, got = jax.value_and_grad(loss)(params)
    calls = hc_passes(text)
    layers = cfg.n_layers + cfg.n_mtp
    # XLA's pass in front of the matrices: the forward pass's alone
    assert calls["project"] == (2 * layers if stream == "toy" else 0), calls
    assert calls["matrices"] == calls["pre_fwd"] == 4 * layers, calls
    assert remat.HC in text
    if stream != "toy":
        return
    want_loss, want = jax.value_and_grad(loss)(params)
    assert float(got_loss) == float(want_loss)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- the configuration ------------------------------------------------------------------


def test_the_preset_states_the_published_architecture():
    cfg = xing4.Xing4Config.xing4_0_29b_a4b()
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads) == (40, 3584, 32)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank) == (768, 512)
    assert (cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.n_dense_layers, cfg.d_ff) == (2, 9216)
    assert (cfg.n_experts, cfg.topk, cfg.d_expert, cfg.n_shared_experts) == (64, 4, 1024, 1)
    assert cfg.route_scale == 2.0 and cfg.route_norm
    assert cfg.hc == hc.HyperConnections(4, 20, 1e-6, (-30.0, 30.0), 1e-6)
    assert cfg.rope_scaling == Yarn(64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert cfg.n_mtp == 1 and cfg.vocab == 131072 and cfg.rope_theta == 1e4
    shapes = jax.eval_shape(lambda: xing4.init_params(cfg, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 29.0e9 < n < 30.5e9, n  # "29B": every expert, the module, both ends
    assert "lm_head" in shapes and set(shapes["mtp"]) == {
        "enorm", "hnorm", "norm", "w_eh", "layer"}


def test_the_preset_is_what_the_benchmark_builds_uncut():
    from benchmarks.families import xing4 as family

    with open(os.path.join(ROOT, "benchmarks/configs/xing4.0-29b-a4b.json")) as f:
        c = json.load(f)
    with open(os.path.join(ROOT, "benchmarks/jobs/tokens-8k-b1.json")) as f:
        mix = json.load(f)
    cut = family.model_config(c, mix)
    assert cut.n_layers >= 5 and cut.n_dense_layers == 1 and cut.n_mtp == 1
    assert cut.held == (0, 8) and cut.n_experts == 64 and cut.vocab == 16384
    shapes = jax.eval_shape(lambda: family.init_params(cut, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    # ISSUE 46's arithmetic: 399.8 M + 128.5 M an expert layer
    assert abs(n - (399.8e6 + 128.5e6 * (cut.n_layers - 1))) < 1.0e6, n
    assert moe.held_row_bound(8192 * cut.topk, 8, 64) == 8192
    uncut = family.model_config(
        {**c, **c["published"]}, {**mix, "seq": c["max_position_embeddings"]})
    preset = xing4.Xing4Config.xing4_0_29b_a4b()
    # remat is the training section's choice, not the architecture's.
    assert dataclasses.replace(uncut, remat=preset.remat) == preset
    # The check's rows are two of the mix's windows, a step's at a time.
    assert family.CHECK_ROWS == 2 * mix["window_rows"]
    assert family.PAIR_ROWS == mix["batch_rows"] == 1


@pytest.mark.parametrize("bad", [
    dict(n_mtp=2), dict(hc_mult=1), dict(qk_rope_dim=7), dict(held_experts=(6, 4)),
    dict(n_dense_layers=9),
])
def test_the_config_refuses_what_is_not_an_architecture(bad):
    with pytest.raises(ValueError):
        tiny(**bad)


@pytest.mark.parametrize("entry", ["forward_with_cache", "generate"])
def test_serving_is_refused_by_name(entry):
    with pytest.raises(NotImplementedError, match="xing4." + entry):
        getattr(xing4, entry)()


def test_a_mesh_is_refused_by_name(tokens):
    from ddl_tpu.parallel.mesh import make_mesh

    cfg = tiny()
    mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    params = jax.eval_shape(lambda: xing4.init_params(cfg, jax.random.key(0)))
    with pytest.raises(NotImplementedError, match="no layout over a mesh"):
        jax.eval_shape(lambda p: xing4.forward(p, tokens, cfg, mesh), params)


def test_the_train_loss_is_the_two_cross_entropies(tokens):
    """``next_token_loss`` is ``next_token_cross_entropy(forward(...))`` plus
    0.1 of the module's over targets two ahead (what the benchmark's check
    takes of the logits it compares), and the main term's text is there for
    the suites that read it."""
    import inspect

    from ddl_tpu.models.losses import cross_entropy, next_token_cross_entropy

    cfg = tiny(held_experts=(2, 4))
    params = seeded(cfg)
    logits, mtp_logits, _ = xing4.forward_all(params, tokens, cfg)
    main = next_token_cross_entropy(xing4.forward(params, tokens, cfg), tokens)
    targets = jnp.roll(tokens, -2, axis=1)
    mask = jnp.broadcast_to(jnp.arange(T) < T - 2, tokens.shape)
    mtp = cross_entropy(mtp_logits, targets, mask)
    got = xing4.next_token_loss(params, tokens, cfg)
    assert xing4.MTP_LOSS_WEIGHT == 0.1
    np.testing.assert_allclose(float(got), float(main + 0.1 * mtp), rtol=1e-6)
    assert "next_token_cross_entropy(logits, tokens)" in inspect.getsource(xing4.losses)
    assert "next_token_cross_entropy(forward(" in inspect.getsource(
        xing4.next_token_loss)


def test_the_benchmarks_reference_is_this_one():
    with open(os.path.join(ROOT, "tests", "reference_xing4.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmarks", "lib", "xing4_reference.py"), "rb") as f:
        assert f.read() == mine
    # plain jax.numpy: nothing of the program, no kernel, no custom_vjp
    text = mine.decode()
    body = text[text.index("from __future__"):]
    assert "ddl_tpu" not in body and "custom_vjp" not in body and "pallas" not in body
    assert 'default_matmul_precision("highest")' in body


def test_the_references_gradients_a_layer_at_a_time_are_its_gradients(tokens):
    """``loss_and_grads_by_layer`` (what the benchmark's check runs at a size
    whose whole backward pass is too large a program) hands out the numbers
    of ``jax.grad(loss)``, every leaf once."""
    cfg = tiny(held_experts=(2, 4))
    params, c = seeded(cfg), ref_config(cfg, checkpoint_layers=True)
    want_loss, want = ref.loss_and_grads(params, tokens, c)
    got = {"layers": [None] * cfg.n_layers}

    def consume(where, grads):
        if where == ("top",):
            got.update(grads)
        else:
            assert got["layers"][where[1]] is None
            got["layers"][where[1]] = grads

    got_loss = ref.loss_and_grads_by_layer(params, tokens, c, consume)
    close(got_loss, want_loss, 1e-6, "loss")
    assert jax.tree.structure(got) == jax.tree.structure(want)
    largest = max(float(jnp.max(jnp.abs(w))) for w in jax.tree.leaves(want))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        close(g, w, GRAD_TOL, "d" + jax.tree_util.keystr(path), floor=1e-3 * largest)

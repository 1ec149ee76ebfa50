"""The gated delta rule's chunked scan (``ops/gated_delta.py``) against the
plain recurrence, and Olmo-Hybrid's architecture through
``models/olmo_hybrid.py`` against its plain float32 reference
(``tests/reference_olmo_hybrid.py``), at tiny sizes on the CPU (the
kernels in Pallas' interpret mode): forward and every gradient.

Seeded weights (norm weights moved off 1, so that they count) and tokens.
"""

import collections
import functools
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_olmo_hybrid as ref
from ddl_tpu.models import olmo_hybrid
from ddl_tpu.models.olmo_hybrid import FULL, LINEAR
from ddl_tpu.ops import gated_delta
from ddl_tpu.ops.gated_delta import gated_delta_rule

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: float32 scan against the float32 recurrence, as a share of the compared
#: array's root mean square: the same arithmetic in another order (a chunk's
#: 64 steps as matmuls and one triangular inverse).  Measured up to 4e-6.
SCAN_TOL = 5e-5
#: float32 model against float32 reference, the same way.  Measured: logits
#: 3e-6, a gradient leaf up to 3e-4 (``A_log``, ``dt_bias``: a head's sum
#: over every position of a derivative through ``exp(-exp(.))``).
MODEL_TOL = 2e-3


def operands(seed, B, T, H, dk, dv):
    """Unit q (over sqrt(d_k)) and k, normal v, log decays from a few
    thousandths to a half, beta in (0, 2)."""
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.standard_normal((B, T, H, dk))) / np.sqrt(dk)
    k = unit(r.standard_normal((B, T, H, dk)) + 0.3)
    v = r.standard_normal((B, T, H, dv))
    g = -np.exp(r.uniform(np.log(1e-3), np.log(0.5), (B, T, H)))
    beta = 2.0 / (1.0 + np.exp(-r.standard_normal((B, T, H))))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def plain(q, k, v, g, beta):
    with jax.default_matmul_precision("highest"):
        return ref.recurrence(q, k, v, g, beta, block=16)


def rel_rms(got, want):
    return float(jnp.sqrt(jnp.sum((got - want) ** 2) / jnp.sum(want**2)))


# -- the scan against the recurrence ------------------------------------------------

#: (B, T, H, d_k, d_v): the published head (96 / 192, off the 128-lane tile)
#: over three chunks and a ragged fourth; whole chunks of two rows (six
#: (row, head) pairs a grid step); one short chunk; a row shorter than the
#: smallest chunk; eleven heads (no grid step above one divides them) and
#: two rows of five (a step of five) over a ragged row.
SHAPES = {
    "published_head_ragged": (1, 200, 2, 96, 192),
    "whole_chunks": (2, 128, 3, 16, 32),
    "one_short_chunk": (1, 37, 2, 8, 16),
    "shorter_than_a_chunk": (1, 5, 1, 8, 16),
    "eleven_heads_ragged": (1, 130, 11, 8, 16),
    "two_rows_of_five_heads_ragged": (2, 70, 5, 16, 32),
}
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


@functools.lru_cache(maxsize=None)
def both_sides(shape_name, heads_per_pass=None, dtype=jnp.float32):
    """{name: (scan's, recurrence's)} for the output and the five
    gradients of a seeded weighted sum of it; with ``dtype`` bfloat16 both
    sides get q, k, v rounded to it, the recurrence as float32 again."""
    x = operands(0, *SHAPES[shape_name])
    x = tuple(a.astype(dtype) for a in x[:3]) + x[3:]
    widened = lambda fn: lambda q, k, v, g, b: fn(
        *(a.astype(jnp.float32) for a in (q, k, v)), g, b
    ).astype(q.dtype)
    weights = jnp.asarray(
        np.random.default_rng(1).standard_normal(x[2].shape), jnp.float32
    )

    def sides(fn):
        out = fn(*x)
        grads = jax.grad(lambda *a: jnp.sum(fn(*a) * weights), argnums=range(5))(*x)
        return (out,) + tuple(grads)

    want = sides(widened(plain))
    chosen = gated_delta._heads_per_pass
    forced = chosen if heads_per_pass is None else lambda B, T, H: heads_per_pass
    with mock.patch.object(gated_delta, "_heads_per_pass", forced):
        got = sides(gated_delta_rule)
    return dict(zip(NAMES, zip(got, want)))


#: bfloat16 operands against the recurrence on the same rounded operands: a
#: few of bfloat16's roundings (2^-9 each) in the output, and in a gradient
#: the roundings of the cotangents the kernel hands back in bfloat16 too.
#: Measured: output 4.2e-3-4.6e-3, a gradient up to 5.8e-3.
BF16_TOL = 8 * 2.0**-9


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_scan_is_the_recurrence_forward_and_in_every_gradient(shape, name):
    got, want = both_sides(shape)[name]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert rel_rms(got, want) < SCAN_TOL


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("shape", [
    "published_head_ragged", "whole_chunks", "two_rows_of_five_heads_ragged",
])
def test_bfloat16_operands_stay_on_the_recurrence_in_every_gradient(shape, name):
    got, want = both_sides(shape, dtype=jnp.bfloat16)[name]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert rel_rms(got.astype(jnp.float32), want.astype(jnp.float32)) < BF16_TOL


@pytest.mark.parametrize("name", NAMES)
def test_heads_taken_a_pass_at_a_time_change_nothing(name):
    """Three heads as three passes of one (the map the real shape takes
    five times): the same numbers as one pass of three."""
    got, want = both_sides("whole_chunks", heads_per_pass=1)[name]
    assert rel_rms(got, want) < SCAN_TOL


def test_without_decay_it_is_the_plain_delta_rule():
    """alpha -> 1 (g = 0): every chunk's map is ``I - Kd^T W`` and the
    state never fades."""
    q, k, v, g, beta = operands(2, 1, 150, 2, 16, 32)
    got = gated_delta_rule(q, k, v, g * 0, beta)
    assert rel_rms(got, plain(q, k, v, g * 0, beta)) < SCAN_TOL
    assert rel_rms(got, plain(q, k, v, g, beta)) > 0.1  # and the decay counts


def test_without_beta_nothing_is_written():
    """beta -> 0: the state stays zero, and so does the output; the
    gradient with respect to beta is what a first write would add."""
    q, k, v, g, beta = operands(3, 1, 100, 2, 16, 32)
    assert float(jnp.max(jnp.abs(gated_delta_rule(q, k, v, g, beta * 0)))) == 0.0
    d_beta = lambda fn: jax.grad(lambda b: jnp.sum(fn(q, k, v, g, b)))(beta * 0)
    assert rel_rms(d_beta(gated_delta_rule), d_beta(plain)) < SCAN_TOL


def test_beta_near_two_flips_a_direction_and_stays_bounded():
    """``linear_allow_neg_eigval``: at beta = 2 a step reflects the state
    along k; 300 of them keep the scan on the recurrence."""
    q, k, v, g, beta = operands(4, 1, 300, 1, 16, 32)
    beta = jnp.full_like(beta, 1.999)
    assert rel_rms(gated_delta_rule(q, k, v, g, beta), plain(q, k, v, g, beta)) < SCAN_TOL


def test_bfloat16_operands_keep_a_float32_state():
    """bfloat16 operands meet the MXU as they are; against the recurrence
    on the same rounded operands the output differs by a few of bfloat16's
    roundings, however many chunks the state is carried through."""
    q, k, v, g, beta = operands(5, 1, 640, 2, 16, 32)
    g = g / 50  # a state that outlives the ten chunks
    low = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
    got = gated_delta_rule(*low, g, beta)
    assert got.dtype == jnp.bfloat16
    want = plain(*(x.astype(jnp.float32) for x in low), g, beta)
    assert rel_rms(got.astype(jnp.float32), want) < 8 * 2.0**-9


def test_a_state_carried_in_bfloat16_shows_in_float32(monkeypatch):
    """What the benchmark's ``core_rel_rms`` limit is for: with float32
    operands the scan is the recurrence to 1e-5; with the kernel's state
    rounded to bfloat16 from chunk to chunk it is a thousandth off."""
    q, k, v, g, beta = operands(6, 1, 640, 2, 16, 32)
    g = g / 50
    want = plain(q, k, v, g, beta)
    assert rel_rms(gated_delta_rule(q, k, v, g, beta), want) < 1e-5
    monkeypatch.setattr(gated_delta, "_STATE_DTYPE", jnp.bfloat16)
    assert rel_rms(gated_delta_rule(q, k, v, g, beta), want) > 3e-4


@pytest.mark.parametrize("C", [8, 16, 32, 64])
def test_the_triangular_inverse_is_the_inverse(C):
    a = np.tril(np.random.default_rng(C).standard_normal((3, 2, C, C)) * 0.3, -1)
    got = gated_delta._unit_lower_inverse(jnp.asarray(a, jnp.float32))
    want = np.linalg.inv(np.eye(C) + a)
    assert float(np.max(np.abs(np.asarray(got) - want))) < 1e-4 * np.max(np.abs(want))


@pytest.mark.parametrize("C", [8, 16, 32, 64])
def test_the_triangular_inverses_own_backward_is_autodiffs(C):
    """``-X^T dX X^T`` against autodiff through the blocked algorithm, where
    ``a`` lives: below the diagonal."""
    r = np.random.default_rng(C)
    a = jnp.asarray(np.tril(r.standard_normal((3, 2, C, C)) * 0.3, -1), jnp.float32)
    w = jnp.asarray(r.standard_normal((3, 2, C, C)), jnp.float32)
    grad = lambda inverse: jnp.tril(jax.grad(lambda a: jnp.sum(inverse(a) * w))(a), -1)
    with jax.default_matmul_precision("highest"):
        want = grad(gated_delta._unit_lower_inverse.fun)
    assert rel_rms(grad(gated_delta._unit_lower_inverse), want) < 1e-5


@pytest.mark.parametrize("T,want", [(5, 8), (37, 64), (64, 64), (100, 64), (16384, 64)])
def test_the_chunk_comes_from_the_row(T, want):
    assert gated_delta._chunk_len(T) == want


def test_the_grid_and_the_passes_come_from_the_shapes():
    # one row of 16,384 at the published 30 heads of 96 / 192: five passes
    # of six heads, each one grid step a chunk, in bfloat16 and (the
    # benchmark's core check) in float32
    assert gated_delta._heads_per_pass(1, 16384, 30) == 6
    assert gated_delta._heads_per_step(6, 64, 96, 192, 2) == 6
    assert gated_delta._heads_per_step(6, 64, 96, 192, 4) == 6
    assert gated_delta._heads_per_pass(2, 16384, 30) == 3
    assert gated_delta._heads_per_pass(1, 3072, 30) == 30
    # a step's blocks are held to the budget at the operands' width ...
    assert gated_delta._heads_per_step(30, 64, 96, 192, 2) == 6
    assert gated_delta._heads_per_step(8, 64, 96, 192, 2) == 8
    assert gated_delta._heads_per_step(8, 64, 96, 192, 4) == 4
    assert gated_delta._heads_per_step(7, 64, 96, 192, 4) == 7
    # ... and divide the rows: eleven go one at a time
    assert gated_delta._heads_per_step(11, 64, 8, 16, 4) == 1
    assert gated_delta._heads_per_step(10, 64, 16, 32, 4) == 5
    assert gated_delta._heads_per_step(4, 8, 8, 16, 4) == 4


# -- the model against the reference ------------------------------------------------

B, T = 2, 72


def tiny(**kw) -> olmo_hybrid.OlmoHybridConfig:
    base = dict(max_seq=T, dtype=jnp.float32, param_dtype=jnp.float32)
    base.update(kw)
    return olmo_hybrid.OlmoHybridConfig(**base)


def ref_config(cfg, **kw) -> ref.Config:
    return ref.Config(
        n_heads=cfg.n_heads, n_linear_heads=cfg.n_linear_heads,
        key_dim=cfg.linear_key_dim, value_dim=cfg.linear_value_dim,
        linear_layers=tuple(kind == LINEAR for kind in cfg.layer_types),
        allow_neg_eigval=cfg.allow_neg_eigval, norm_eps=cfg.norm_eps,
        query_block=16, scan_block=16, **kw,
    )


def seeded(cfg, seed=0):
    """Weights with every norm weight moved off 1."""
    params = olmo_hybrid.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 64))

    def off_one(path, x):
        if "norm" in jax.tree_util.keystr(path):
            return x * (1.0 + 0.2 * jax.random.normal(next(keys), x.shape))
        return x

    return jax.tree_util.tree_map_with_path(off_one, params)


def seeded_tokens():
    return jnp.asarray(
        np.random.default_rng(7).integers(0, 256, (B, T), dtype=np.int32)
    )


@pytest.fixture(scope="module")
def tokens():
    return seeded_tokens()


@functools.lru_cache(maxsize=None)
def model_and_reference(remat):
    cfg = tiny(remat=remat)
    params = seeded(cfg)
    t = seeded_tokens()
    got = jax.value_and_grad(olmo_hybrid.next_token_loss)(params, t, cfg)
    want = ref.loss_and_grads(params, t, ref_config(cfg))
    return cfg, params, t, got, want


@pytest.mark.parametrize("remat", ["none", "selective", "full"])
def test_float32_system_matches_the_reference(remat):
    """One period, L L L F: logits, loss and every gradient leaf."""
    cfg, params, t, (loss, grads), (want_loss, want_grads) = model_and_reference(remat)
    logits = olmo_hybrid.forward(params, t, cfg)
    assert rel_rms(logits, ref.forward(params, t, ref_config(cfg))) < MODEL_TOL
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    worst = jax.tree.map(rel_rms, grads, want_grads)
    for path, err in jax.tree_util.tree_leaves_with_path(worst):
        assert err < MODEL_TOL, (jax.tree_util.keystr(path), err)


def test_every_leaf_has_a_gradient():
    _, _, _, (_, grads), _ = model_and_reference("selective")
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        assert float(jnp.linalg.norm(g)) > 0, jax.tree_util.keystr(path)


@pytest.mark.parametrize("left_out", ["decay", "double_beta", "conv", "gate"])
def test_leaving_out_part_of_the_mathematics_fails(tokens, left_out, monkeypatch):
    cfg = tiny()
    params = seeded(cfg)
    want = ref.forward(params, tokens, ref_config(cfg))
    real = olmo_hybrid.gated_delta_rule
    if left_out == "decay":
        monkeypatch.setattr(olmo_hybrid, "gated_delta_rule",
                            lambda q, k, v, g, b: real(q, k, v, g * 0, b))
    elif left_out == "double_beta":
        monkeypatch.setattr(olmo_hybrid, "gated_delta_rule",
                            lambda q, k, v, g, b: real(q, k, v, g, b / 2))
    elif left_out == "conv":
        monkeypatch.setattr(olmo_hybrid, "_silu_conv",
                            lambda x, taps: jax.nn.silu(x * taps[-1]))
    else:
        monkeypatch.setattr(jax.nn, "silu", lambda x: x)
    assert rel_rms(olmo_hybrid.forward(params, tokens, cfg), want) > 20 * MODEL_TOL


def test_the_convolutions_own_backward_is_autodiffs():
    r = np.random.default_rng(11)
    x = jnp.asarray(r.standard_normal((2, 19, 12)), jnp.float32)
    taps = jnp.asarray(r.standard_normal((4, 12)), jnp.float32)
    w = jnp.asarray(r.standard_normal((2, 19, 12)), jnp.float32)
    got = jax.grad(lambda x, t: jnp.sum(olmo_hybrid._silu_conv(x, t) * w), (0, 1))(x, taps)
    want = jax.grad(lambda x, t: jnp.sum(jax.nn.silu(ref._conv(x, t)) * w), (0, 1))(x, taps)
    for g, wnt in zip(got, want):
        assert rel_rms(g, wnt) < 1e-5
    # causal: an input moves no earlier output
    moved = olmo_hybrid._silu_conv(x.at[:, 10].add(1.0), taps) - olmo_hybrid._silu_conv(x, taps)
    assert float(jnp.max(jnp.abs(moved[:, :10]))) == 0.0
    assert float(jnp.max(jnp.abs(moved[:, 10:14]))) > 0.0
    assert float(jnp.max(jnp.abs(moved[:, 14:]))) == 0.0


@pytest.mark.parametrize("kinds", [
    (LINEAR, FULL), (FULL, LINEAR), (LINEAR,), (FULL, FULL, LINEAR),
])
def test_the_layer_kinds_follow_layer_types(tokens, kinds):
    """The parameters, the program and the reference take each layer's
    kind from the list, in its order."""
    cfg = tiny(layer_types=kinds)
    params = seeded(cfg)
    assert len(params["layers"]) == len(kinds)
    for layer, spec, kind in zip(
        params["layers"], olmo_hybrid.param_specs(cfg)["layers"], kinds
    ):
        assert ("A_log" in layer) == (kind == LINEAR)
        assert ("q_norm" in layer) == (kind == FULL)
        assert set(layer) == set(spec)
    want = ref.forward(params, tokens, ref_config(cfg))
    assert rel_rms(olmo_hybrid.forward(params, tokens, cfg), want) < MODEL_TOL


def test_the_config_refuses_what_is_not_an_architecture():
    with pytest.raises(ValueError):
        tiny(layer_types=("sliding_attention",))
    with pytest.raises(ValueError):
        tiny(layer_types=())
    with pytest.raises(ValueError):
        tiny(d_model=66)
    with pytest.raises(ValueError):
        tiny(remat="sometimes")


@pytest.mark.parametrize("entry", ["forward_with_cache", "generate"])
def test_serving_is_refused_by_name(entry):
    with pytest.raises(NotImplementedError, match="olmo_hybrid." + entry):
        getattr(olmo_hybrid, entry)()


def test_a_mesh_is_refused_by_name(tokens):
    from jax.sharding import Mesh

    cfg = tiny()
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    with pytest.raises(NotImplementedError, match="shard-mapped"):
        olmo_hybrid.forward(seeded(cfg), tokens, cfg, mesh=mesh)


def test_the_preset_states_the_published_architecture():
    cfg = olmo_hybrid.OlmoHybridConfig.olmo_hybrid_7b()
    assert cfg.n_layers == 32 and cfg.layer_types == (LINEAR, LINEAR, LINEAR, FULL) * 8
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff) == (3840, 30, 128, 11008)
    assert (cfg.n_linear_heads, cfg.linear_key_dim, cfg.linear_value_dim) == (30, 96, 192)
    assert (cfg.vocab, cfg.conv_kernel, cfg.allow_neg_eigval) == (100352, 4, True)
    shapes = jax.eval_shape(lambda: olmo_hybrid.init_params(cfg, jax.random.key(0)))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    # ISSUE 36's counts: 215.6 M a linear layer, 185.8 M a full one
    assert round(count(shapes["layers"][0]) / 1e6, 1) == 215.6
    assert round(count(shapes["layers"][3]) / 1e6, 1) == 185.8


def test_the_preset_is_what_the_benchmark_builds_uncut():
    import dataclasses
    import json

    from benchmarks.families import olmo_hybrid as family

    with open(os.path.join(ROOT, "benchmarks", "configs", "olmo-hybrid-7b.json")) as f:
        c = json.load(f)
    assert sorted(c["reduced"]) == ["layer_types", "num_hidden_layers", "vocab_size"]
    for key in ("norm_placement", "qk_norm", "positions", "conv_bias", "initialisation"):
        assert key in c["assumed"]
    uncut = dict(c, num_hidden_layers=32, vocab_size=100352,
                 layer_types=[LINEAR, LINEAR, LINEAR, FULL] * 8)
    built = family.model_config(uncut, {"seq": 65536})
    preset = olmo_hybrid.OlmoHybridConfig.olmo_hybrid_7b()
    assert built == dataclasses.replace(preset, remat="selective")
    # ... and the cut: one period, an eighth of the vocabulary, every width
    cut = family.model_config(c, {"seq": 16384})
    assert cut == dataclasses.replace(
        built, layer_types=(LINEAR, LINEAR, LINEAR, FULL), vocab=12544, max_seq=16384)
    shapes = jax.eval_shape(lambda: olmo_hybrid.init_params(cut, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert round(n / 1e6, 1) == 928.9


@pytest.mark.parametrize("checkpoint_layers", [False, True])
def test_the_references_layer_hook_changes_nothing_and_sees_every_layer(
    tokens, checkpoint_layers
):
    cfg = tiny()
    params = seeded(cfg)
    c = ref_config(cfg, checkpoint_layers=checkpoint_layers)
    seen = []

    def hook(x, layer, c, r, linear):
        seen.append(linear)
        return ref._layer(x, layer, c, r, linear)

    plain_loss, plain_grads = jax.value_and_grad(ref.loss)(params, tokens, ref_config(cfg))
    loss, grads = jax.value_and_grad(ref.loss)(params, tokens, c, None, hook)
    if checkpoint_layers:  # jax.checkpoint traces a kind of layer once
        assert set(seen) == {True, False}
    else:
        assert seen[:4] == [True, True, True, False]
    assert abs(float(loss) - float(plain_loss)) < 1e-6 * float(plain_loss)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(plain_grads)):
        assert rel_rms(a, b) < 1e-4


def test_the_reference_in_a_lower_precision_is_outside_the_float32_tolerance(tokens):
    cfg = tiny()
    params = seeded(cfg)
    want = ref.forward(params, tokens, ref_config(cfg))
    low = ref.forward(params, tokens, ref_config(cfg), jnp.bfloat16)
    assert rel_rms(low, want) > 2 * MODEL_TOL


def test_the_benchmarks_reference_is_this_one():
    with open(os.path.join(ROOT, "tests", "reference_olmo_hybrid.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmarks", "lib", "olmo_hybrid_reference.py"), "rb") as f:
        assert f.read() == mine
    assert b"ddl_tpu" not in re.sub(rb'""".*?"""', b"", mine, count=1, flags=re.S)


# -- what selective remat keeps ------------------------------------------------------


@pytest.mark.parametrize("remat,fwd", [("none", 1), ("selective", 1), ("full", 2)])
def test_the_backward_pass_reads_the_saved_states(remat, fwd, monkeypatch):
    """The train step lowered for the TPU: under ``selective`` each linear
    layer runs the forward kernel once - the chunk states it writes and the
    scan's output are saved residuals - and the backward kernel once;
    ``full`` keeps neither and runs the forward kernel again."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = olmo_hybrid.OlmoHybridConfig(
        vocab=256, d_model=256, n_heads=2, d_ff=256, n_linear_heads=2,
        linear_key_dim=96, linear_value_dim=192, max_seq=1024,
        param_dtype=jnp.bfloat16, remat=remat,
    )
    params = jax.eval_shape(lambda: olmo_hybrid.init_params(cfg, jax.random.key(0)))
    text = jax.jit(jax.value_and_grad(
        lambda p, t: olmo_hybrid.next_token_loss(p, t, cfg)
    )).trace(params, jax.ShapeDtypeStruct((1, 1024), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    got = collections.Counter(re.findall(r'kernel_name = "(ddl_\w+)"', text))
    assert got["ddl_gdn_fwd"] == 3 * fwd and got["ddl_gdn_bwd"] == 3, got
    assert got["ddl_flash_fwd"] == (2 if remat == "full" else 1), got

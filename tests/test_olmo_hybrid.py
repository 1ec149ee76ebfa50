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
def both_sides(shape_name, heads_per_step=None, dtype=jnp.float32):
    """{name: (scan's, recurrence's)} for the output and the five
    gradients of a seeded weighted sum of it; with ``dtype`` bfloat16 both
    sides get q, k, v rounded to it, the recurrence as float32 again;
    with ``heads_per_step`` that many (row, head) groups to a grid step."""
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
    chosen = gated_delta._heads_per_step
    forced = chosen if heads_per_step is None else lambda *shape: heads_per_step
    with mock.patch.object(gated_delta, "_heads_per_step", forced):
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
@pytest.mark.parametrize("heads", [1, 2, 6])
def test_the_groups_a_grid_step_holds_change_nothing(heads, name):
    """Two rows of three heads as six groups of one to a grid step, three
    of two and (what the shapes choose) one of six: the same numbers."""
    got, want = both_sides("whole_chunks", heads_per_step=heads)[name]
    assert rel_rms(got, want) < SCAN_TOL
    chosen, _ = both_sides("whole_chunks")[name]
    assert float(jnp.max(jnp.abs(got - chosen))) == 0.0


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


def one_chunk(C):
    """Operands of one head and one chunk of ``C`` positions whose output
    IS the kernel's triangular inverse: no decay, beta 1, the values the
    identity and ``q k^T`` the identity too (``q = (k k^T)^-1 k``), so that
    ``o = P T (beta v) = T`` with ``T = (I + strict_lower(k k^T))^-1``;
    and that ``T`` by numpy, float64."""
    r = np.random.default_rng(C)
    k = np.eye(C) + 0.3 * r.standard_normal((C, C))
    q = np.linalg.solve(k @ k.T, k)
    heads = lambda x: jnp.asarray(x, jnp.float32)[None, :, None]  # (1, C, 1, .)
    operands = (heads(q), heads(k), heads(np.eye(C)),
                jnp.zeros((1, C, 1), jnp.float32), jnp.ones((1, C, 1), jnp.float32))
    return operands, np.linalg.inv(np.eye(C) + np.tril(k @ k.T, -1))


@pytest.mark.parametrize("C", [8, 16, 32, 64])
def test_the_kernels_triangular_inverse_is_the_inverse(C):
    """The diagonal blocks' finite product and the merges of two blocks
    into one, on masked tiles, at every size a chunk takes."""
    operands, want = one_chunk(C)
    assert gated_delta._chunk_len(C) == C
    got = np.asarray(gated_delta_rule(*operands))[0, :, 0]
    assert float(np.max(np.abs(np.triu(got, 1)))) < 1e-5
    assert float(np.max(np.abs(got - want))) < 1e-4 * np.max(np.abs(want))


def plain_one_chunk(q, k, v, g, beta):
    """A first chunk (no state yet) as its formula reads, the inverse
    ``jnp.linalg.inv``'s: (1, C, 1, .) operands -> (1, C, 1, d_v)."""
    q, k, v, g, beta = q[0, :, 0], k[0, :, 0], v[0, :, 0], g[0, :, 0], beta[0, :, 0]
    C = q.shape[0]
    gam = jnp.cumsum(g)
    decay = jnp.tril(jnp.exp(jnp.tril(gam[:, None] - gam[None, :])))
    a = jnp.tril(beta[:, None] * (k @ k.T) * decay, -1)
    t = jnp.linalg.inv(jnp.eye(C) + a)
    return (jnp.tril((q @ k.T) * decay) @ t @ (beta[:, None] * v))[None, :, None]


@pytest.mark.parametrize("C", [8, 16, 32, 64])
def test_the_kernels_backward_through_the_inverse_is_autodiffs_of_the_plain_one(C):
    """``dA = -T^T dT T^T`` and what leads to it and from it, in the
    backward kernel, against autodiff through ``jnp.linalg.inv``: the
    gradients that cross the inverse (k's, beta's, g's) and the two that do
    not, with a decay and a beta that count."""
    (q, k, _, _, _), _ = one_chunk(C)
    r = np.random.default_rng(100 + C)
    v = jnp.asarray(r.standard_normal((1, C, 1, 2 * C)), jnp.float32)
    g = jnp.asarray(-np.exp(r.uniform(np.log(1e-3), np.log(0.5), (1, C, 1))), jnp.float32)
    beta = jnp.asarray(2.0 / (1.0 + np.exp(-r.standard_normal((1, C, 1)))), jnp.float32)
    w = jnp.asarray(r.standard_normal(v.shape), jnp.float32)
    grads = lambda fn: jax.grad(
        lambda *a: jnp.sum(fn(*a) * w), argnums=range(5))(q, k / 2, v, g, beta)
    with jax.default_matmul_precision("highest"):
        want = grads(plain_one_chunk)
    for name, got, wanted in zip(NAMES[1:], grads(gated_delta_rule), want):
        assert rel_rms(got, wanted) < 1e-5, name


@pytest.mark.parametrize("T,want", [(5, 8), (37, 64), (64, 64), (100, 64), (16384, 64)])
def test_the_chunk_comes_from_the_row(T, want):
    assert gated_delta._chunk_len(T) == want


def test_the_grid_comes_from_the_shapes():
    # a grid step takes a tile of two chunks, or the one a short row has
    assert gated_delta._tile_len(16384, 64) == gated_delta._tile_len(65, 64) == 128
    assert gated_delta._tile_len(64, 64) == 64 and gated_delta._tile_len(5, 8) == 8
    # one row of 16,384 at the published 30 heads of 96 / 192: five groups
    # of six heads on one grid, in bfloat16; the benchmark's core check's
    # six heads in float32 two groups of three
    assert gated_delta._heads_per_pass(1, 16384, 30) == 6
    assert gated_delta._heads_per_step(30, 128, 96, 192, 2) == 6
    assert gated_delta._heads_per_step(6, 128, 96, 192, 4) == 3
    # the most a grid step holds is eight ...
    assert gated_delta._heads_per_pass(2, 16384, 30) == 6
    assert gated_delta._heads_per_pass(1, 3072, 32) == 8
    assert gated_delta._heads_per_step(8, 128, 16, 32, 2) == 8
    # ... held to the budget at the operands' width, the body's float32
    # tiles counted ...
    assert gated_delta._heads_per_step(8, 128, 96, 192, 2) == 4
    assert gated_delta._heads_per_step(8, 128, 256, 512, 4) == 2
    # ... and they divide the rows: eleven go one at a time
    assert gated_delta._heads_per_step(11, 128, 8, 16, 4) == 1
    assert gated_delta._heads_per_step(10, 128, 16, 32, 4) == 5
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


def lowered_train_step(remat, monkeypatch, **as_text):
    """The text of a small model's train step (two heads of 96 / 192, one
    row of 1,024 positions: sixteen chunks of 64) lowered for the TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = olmo_hybrid.OlmoHybridConfig(
        vocab=256, d_model=256, n_heads=2, d_ff=256, n_linear_heads=2,
        linear_key_dim=96, linear_value_dim=192, max_seq=1024,
        param_dtype=jnp.bfloat16, remat=remat,
    )
    params = jax.eval_shape(lambda: olmo_hybrid.init_params(cfg, jax.random.key(0)))
    return jax.jit(jax.value_and_grad(
        lambda p, t: olmo_hybrid.next_token_loss(p, t, cfg)
    )).trace(params, jax.ShapeDtypeStruct((1, 1024), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text(**as_text)


@pytest.mark.parametrize("remat,fwd", [("none", 1), ("selective", 1), ("full", 2)])
def test_the_backward_pass_reads_the_saved_states(remat, fwd, monkeypatch):
    """The train step lowered for the TPU: under ``selective`` each linear
    layer runs the forward kernel once - the chunk states it writes and the
    scan's output are saved residuals - and the backward kernel once;
    ``full`` keeps neither and runs the forward kernel again."""
    text = lowered_train_step(remat, monkeypatch)
    got = collections.Counter(re.findall(r'kernel_name = "(ddl_\w+)"', text))
    assert got["ddl_gdn_fwd"] == 3 * fwd and got["ddl_gdn_bwd"] == 3, got
    assert got["ddl_flash_fwd"] == (2 if remat == "full" else 1), got


def test_nothing_of_a_chunks_preparation_is_left_to_xla(monkeypatch):
    """The same step under ``selective``, by its name stacks: inside
    ``ddl.gdn_scan`` and outside the two kernels' custom calls there is no
    matmul and no tensor whose last two axes are a chunk by a chunk (Gram
    matrix, decay, the triangular inverse, ``P``) - what XLA keeps of the
    scan is the head-major transposes, the pad and the decay sums, in
    every pass.  Still exactly three forward and three backward kernels."""
    text = lowered_train_step("selective", monkeypatch, debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    # a private function's ops carry no caller's name stack: its lines are
    # the scan's if a line of the scan's calls it
    bodies, name = collections.defaultdict(list), None
    for line in text.splitlines():
        start = re.match(r"\s*func\.func (?:private |public )?@(\w+)\(", line)
        name = start.group(1) if start else name
        bodies[name].append(line)
    scans, called = [], set()

    def take(line):
        scans.append(line)
        for callee in re.findall(r"call @(\w+)", line):
            if callee not in called:
                called.add(callee)
                for inner in bodies[callee]:
                    take(inner)

    for line in text.splitlines():
        at = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if at and "ddl.gdn_scan" in locs.get(at.group(1), ""):
            take(line)
    ops, kernels = collections.Counter(), collections.Counter()
    for line in scans:
        kernel = re.search(r'kernel_name = "(ddl_gdn_\w+)"', line)
        if kernel:
            kernels[kernel.group(1)] += 1
            continue
        op = re.search(r"\b(stablehlo\.\w+|call @[a-z]+)", line)
        ops[op.group(1) if op else line.strip()[:40]] += 1
        assert "dot_general" not in line and "convolution" not in line, line
        assert not re.search(r"tensor<(\d+x)*64x64x\w+>", line), line
    assert kernels == {"ddl_gdn_fwd": 3, "ddl_gdn_bwd": 3}, kernels
    # ... and the scope is not empty of XLA's part: the transposes to and
    # from head-major, and the decay sums
    assert ops["stablehlo.transpose"] >= 3 * 8 and ops["call @cumsum"] >= 3 * 3, ops

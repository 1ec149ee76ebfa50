"""MiniCPM-SALA's architecture through ``models/minicpm_sala.py`` against
its plain float32 reference (``tests/reference_minicpm_sala.py``), at tiny
sizes on the CPU with the SPARSE path live (rows of 320 past a ``dense_len``
of 64, blocks of 16, top-4; the kernels in Pallas' interpret mode):
logits, loss and every gradient leaf.

Seeded weights (norm weights moved off 1, so that they count) and tokens.
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

import reference_minicpm_sala as ref
from ddl_tpu.models import minicpm_sala
from ddl_tpu.models.minicpm_sala import LIGHTNING, SPARSE
from ddl_tpu.ops.sparse_attention import SparseConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: float32 model against float32 reference, as a share of the compared
#: array's root mean square.  Measured: logits 2e-6, a gradient leaf 2e-6.
MODEL_TOL = 2e-4
SC = SparseConfig(
    block=16, kernel=8, stride=4, topk=4, init_blocks=1, local_blocks=2,
)


@pytest.fixture(scope="module", autouse=True)
def tiles_of_32():
    """Several tiles in these short rows (the kernels' tiles are constants
    set for the chip)."""
    from ddl_tpu.ops import sparse_attention

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sparse_attention, "_TILE_Q", 32)
        patch.setattr(sparse_attention, "_TILE_K", 32)
        yield


def tiny(**kw):
    base = dict(
        vocab=128, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        n_lightning_heads=4, lightning_head_dim=16, d_ff=128, sparse=SC,
        dense_len=64, max_seq=320, dtype=jnp.float32, dim_model_base=32,
    )
    base.update(kw)
    return minicpm_sala.MiniCPMSalaConfig(**base)


def seeded(cfg, seed=0):
    params = minicpm_sala.init_params(cfg, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 64))

    def off_one(path, x):
        if "norm" in jax.tree_util.keystr(path):
            return x + 0.2 * jax.random.normal(next(keys), x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(off_one, params)


def ref_config(cfg, **kw):
    s = cfg.sparse
    return ref.Config(
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        lightning_heads=cfg.n_lightning_heads,
        lightning_head_dim=cfg.lightning_head_dim,
        sparse_layers=tuple(kind == SPARSE for kind in cfg.mixer_types),
        rope_theta=cfg.rope_theta, scale_emb=cfg.scale_emb,
        residual_scale=cfg.scale_depth / cfg.mup_denominator**0.5,
        logit_div=cfg.d_model / cfg.dim_model_base, norm_eps=cfg.norm_eps,
        block=s.block, kernel=s.kernel, stride=s.stride, topk=s.topk,
        init_blocks=s.init_blocks, local_blocks=s.local_blocks,
        dense_len=cfg.dense_len, query_block=128, **kw,
    )


def rel_rms(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.sqrt(jnp.sum((a - b) ** 2) / jnp.sum(b**2)))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (1, 320)), jnp.int32
    )


@pytest.fixture(scope="module")
def wanted(tokens):
    cfg = tiny()
    params = seeded(cfg)
    c = ref_config(cfg)
    logits = ref.forward(params, tokens, c)
    loss, grads = ref.loss_and_grads(params, tokens, c)
    return params, logits, loss, grads


@pytest.mark.parametrize("impl,remat", [
    ("dense", "none"), ("flash", "none"), ("flash", "selective"), ("flash", "full"),
])
def test_float32_system_matches_the_reference(tokens, wanted, impl, remat):
    """Logits, loss and every gradient leaf, the sparse path live, through
    the masked softmax and through the kernels, under each remat policy."""
    params, want_logits, want_loss, want_grads = wanted
    cfg = tiny(attn_impl=impl, remat=remat)
    def both(params, tokens):
        logits = minicpm_sala.forward(params, tokens, cfg)
        return logits, jax.value_and_grad(minicpm_sala.next_token_loss)(
            params, tokens, cfg
        )

    with jax.default_matmul_precision("highest"):
        logits, (loss, grads) = jax.jit(both)(params, tokens)
    assert logits.shape == (1, 320, 128) and logits.dtype == jnp.float32
    assert rel_rms(logits, want_logits) < MODEL_TOL
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = jax.tree_util.tree_leaves_with_path(grads)
    want = jax.tree.leaves(want_grads)
    assert len(got) == len(want) == 3 + 4 * 12 + 3
    for (path, a), b in zip(got, want):
        assert float(jnp.max(jnp.abs(b))) > 0, path
        assert rel_rms(a, b) < MODEL_TOL, jax.tree_util.keystr(path)


def test_a_short_row_lowers_to_the_dense_program(monkeypatch):
    """T <= dense_len: the dispatcher's causal-full flash kernels with
    ``kv_repeat``, no selection and no sparse kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = tiny(
        d_model=256, head_dim=128, lightning_head_dim=128, n_heads=4,
        n_lightning_heads=2, dense_len=1024, max_seq=1024, sparse=SparseConfig(),
        param_dtype=jnp.bfloat16, remat="selective",
    )
    params = jax.eval_shape(lambda: minicpm_sala.init_params(cfg, jax.random.key(0)))
    lower = lambda T: jax.jit(jax.value_and_grad(
        lambda p, t: minicpm_sala.next_token_loss(p, t, cfg)
    )).trace(params, jax.ShapeDtypeStruct((1, T), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    count = lambda text: collections.Counter(re.findall(r'kernel_name = "(ddl_\w+)"', text))
    short = count(lower(1024))
    assert short["ddl_flash_fwd"] == 1 and short["ddl_flash_bwd_dkv"] == 1, short
    assert "ddl_flash_bwd_dq" not in short  # one backward kernel (PR 45)
    assert not any("sparse" in name for name in short), short
    # ... and one position more takes the sparse path; under ``selective``
    # every forward kernel runs once (the rule PR 33 / PR 36 set)
    long = count(lower(2048))
    assert long == {
        "ddl_sparse_select": 1, "ddl_flash_sparse_fwd": 1,
        "ddl_flash_sparse_bwd_dq": 1, "ddl_flash_sparse_bwd_dkv": 1,
        "ddl_lightning_fwd": 3, "ddl_lightning_bwd": 3,
    }, long


def test_selective_keeps_one_forward_call_a_kernel_in_the_window_program(monkeypatch):
    """The custom calls of the lowered 2-step window program
    (``parallel.train.make_multistep``, what the Trainer runs)."""
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    from ddl_tpu.parallel.train import make_multistep

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = tiny(
        d_model=256, head_dim=128, lightning_head_dim=128, n_heads=4,
        n_lightning_heads=2, dense_len=1024, max_seq=2048, sparse=SparseConfig(),
        param_dtype=jnp.bfloat16, remat="selective",
    )
    optimizer = optax.adamw(1e-3)
    _, multi = make_multistep(
        lambda p, b: minicpm_sala.next_token_loss(p, b[0], cfg), optimizer,
        Mesh(np.array(jax.devices()[:1]), ("dp",)), minicpm_sala.param_specs(cfg),
        batch_spec=P(("dp",)), n_steps=2,
    )
    run = next(c.cell_contents for c in multi.__closure__
               if hasattr(c.cell_contents, "lower"))
    params = jax.eval_shape(lambda: minicpm_sala.init_params(cfg, jax.random.key(0)))
    batch = (jax.ShapeDtypeStruct((2, 1, 2048), jnp.int32),)
    text = run.trace(
        params, jax.eval_shape(optimizer.init, params), batch, True
    ).lower(lowering_platforms=("tpu",)).as_text()
    got = collections.Counter(re.findall(r'kernel_name = "(ddl_\w+)"', text))
    # the two steps are one scanned body: each kernel once a layer of its kind
    assert got == {
        "ddl_sparse_select": 1, "ddl_flash_sparse_fwd": 1,
        "ddl_flash_sparse_bwd_dq": 1, "ddl_flash_sparse_bwd_dkv": 1,
        "ddl_lightning_fwd": 3, "ddl_lightning_bwd": 3,
    }, got


@pytest.mark.parametrize("left_out", [
    "decay", "scale_emb", "residual_scale", "logit_div", "gate", "selection",
])
def test_leaving_out_part_of_the_mathematics_fails(tokens, wanted, left_out, monkeypatch):
    params, want_logits, _, _ = wanted
    cfg = tiny(attn_impl="dense")
    if left_out == "decay":
        real = minicpm_sala.lightning_attention
        monkeypatch.setattr(
            minicpm_sala, "lightning_attention",
            lambda q, k, v: real(q, k, v, log_decay=(0.0,) * q.shape[2]),
        )
    elif left_out == "scale_emb":
        cfg = dataclasses.replace(cfg, scale_emb=1.0)
    elif left_out == "residual_scale":  # from the cut's depth
        cfg = dataclasses.replace(cfg, mup_denominator=cfg.n_layers)
    elif left_out == "logit_div":
        cfg = dataclasses.replace(cfg, dim_model_base=cfg.d_model)
    elif left_out == "gate":
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: jnp.ones_like(x))
    elif left_out == "selection":  # dense attention in place of the sparse path
        cfg = dataclasses.replace(cfg, dense_len=4096)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: minicpm_sala.forward(p, t, cfg))(params, tokens)
    # random weights: a sparse layer's output is a mean of some hundred
    # values and moves the logits little, whatever it attends
    least = 3 if left_out == "selection" else 20
    assert rel_rms(got, want_logits) > least * MODEL_TOL, left_out


@pytest.mark.parametrize("kinds", [
    (SPARSE,), (LIGHTNING,), (LIGHTNING, SPARSE), (SPARSE, SPARSE, LIGHTNING),
])
def test_the_layer_kinds_follow_mixer_types(tokens, kinds):
    cfg = tiny(mixer_types=kinds, attn_impl="dense")
    params = seeded(cfg)
    assert ["o_norm" in layer for layer in params["layers"]] == [
        kind == LIGHTNING for kind in kinds
    ]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: minicpm_sala.forward(p, t, cfg))(
            params, tokens[:, :200])
        want = ref.forward(params, tokens[:, :200], ref_config(cfg))
    assert rel_rms(got, want) < MODEL_TOL


def test_every_leaf_has_a_spec():
    cfg = tiny()
    shapes = jax.eval_shape(lambda: minicpm_sala.init_params(cfg, jax.random.key(0)))
    specs = minicpm_sala.param_specs(cfg)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)
    )


def test_the_config_refuses_what_is_not_an_architecture():
    with pytest.raises(ValueError, match="mixer_types"):
        tiny(mixer_types=("full_attention",))
    with pytest.raises(ValueError, match="mixer_types"):
        tiny(mixer_types=())
    with pytest.raises(ValueError, match="n_kv_heads"):
        tiny(n_heads=4, n_kv_heads=3)
    with pytest.raises(ValueError, match="remat"):
        tiny(remat="sometimes")


@pytest.mark.parametrize("entry", ["forward_with_cache", "generate"])
def test_serving_is_refused_by_name(entry):
    with pytest.raises(NotImplementedError, match="minicpm_sala." + entry):
        getattr(minicpm_sala, entry)()


def test_a_mesh_is_refused_by_name(tokens):
    from jax.sharding import Mesh

    cfg = tiny()
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    with pytest.raises(NotImplementedError, match="shard-mapped"):
        minicpm_sala.forward(seeded(cfg), tokens, cfg, mesh=mesh)
    from ddl_tpu.ops import sparse_attention
    from ddl_tpu.parallel.ring_attention import attention

    q = jnp.zeros((1, 64, 4, 16))
    kv = jnp.zeros((1, 64, 2, 16))
    sel = sparse_attention.select_blocks(q, kv, SC)
    with pytest.raises(NotImplementedError, match="selection"):
        attention(q, kv, kv, mesh=mesh, kv_repeat=2, selection=sel)


def test_the_preset_states_the_published_architecture():
    cfg = minicpm_sala.MiniCPMSalaConfig.minicpm_sala()
    assert cfg.n_layers == 32
    assert [n for n, kind in enumerate(cfg.mixer_types) if kind == SPARSE] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (4096, 32, 2, 128)
    assert (cfg.n_lightning_heads, cfg.lightning_head_dim, cfg.d_ff) == (32, 128, 16384)
    assert (cfg.vocab, cfg.scale_emb, cfg.scale_depth) == (73448, 12.0, 1.4)
    assert cfg.residual_scale == pytest.approx(1.4 / 32**0.5)
    assert cfg.sparse == SparseConfig(64, 32, 16, 64, 1, 32) and cfg.dense_len == 8192
    shapes = jax.eval_shape(lambda: minicpm_sala.init_params(cfg, jax.random.key(0)))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    # ISSUE 39's counts: 253.8 M a sparse layer, 285.2 M a lightning one
    assert round(count(shapes["layers"][0]) / 1e6, 1) == 253.8
    assert round(count(shapes["layers"][1]) / 1e6, 1) == 285.2


def test_the_preset_is_what_the_benchmark_builds_uncut():
    from benchmarks.families import minicpm_sala as family

    with open(os.path.join(ROOT, "benchmarks", "configs", "minicpm-sala.json")) as f:
        c = json.load(f)
    assert sorted(c["reduced"]) == ["mixer_types", "num_hidden_layers", "vocab_size"]
    for key in ("sparse_config", "selection", "lightning_decay", "residual_scale"):
        assert key in c["assumed"]
    preset = minicpm_sala.MiniCPMSalaConfig.minicpm_sala()
    uncut = dict(c, num_hidden_layers=32, vocab_size=73448,
                 mixer_types=list(preset.mixer_types))
    built = family.model_config(uncut, {"seq": 524288})
    assert built == dataclasses.replace(preset, remat="selective")
    # ... and the cut: layers 0-3, an eighth of the vocabulary, every width
    cut = family.model_config(c, {"seq": 16384})
    assert cut == dataclasses.replace(
        built, mixer_types=preset.mixer_types[:4], vocab=9216, max_seq=16384)
    assert cut.mup_denominator == 32  # the published depth, not the cut's
    shapes = jax.eval_shape(lambda: minicpm_sala.init_params(cut, jax.random.key(0)))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == 1184929152  # ISSUE 39's 1,185 M


def test_the_benchmarks_flops_are_the_issues():
    from benchmarks.families import minicpm_sala as family
    from benchmarks.lib import sala_flops

    with open(os.path.join(ROOT, "benchmarks", "configs", "minicpm-sala.json")) as f:
        c = json.load(f)
    mix = {"seq": 16384}
    # 20,016 of 32,896 block pairs, each query's own block up to itself
    blocks = sum(min(b + 1, 96) for b in range(256))
    assert blocks == 20016
    pairs = sala_flops.visible_pairs(c, 16384)
    assert pairs == (blocks - 256) * 64 * 64 + 256 * (64 * 65 // 2)
    assert sala_flops.visible_pairs(c, 8192) == 8192 * 8193 // 2
    assert sala_flops.select_flops_per_token(c, 8192) == 0.0
    assert round(sala_flops.select_flops_per_token(c, 16384) / 1e6, 1) == 4.2
    assert sala_flops.recurrence_flops(c) == {"fwd": 4 * 128**2, "bwd": 8 * 128**2}
    assert sala_flops.recurrence_bytes(c) == {"fwd": 1024.0, "bwd": 1792.0}
    total = family.flops_per_sample(c, mix)
    assert 7.10e9 < total < 7.20e9, total  # ISSUE 39: ~7.15 GFLOP a token
    least = sala_flops.lightning_least_seconds_per_step(
        c, 1, 16384, "selective", 197e12, 819e9)
    # the bytes decide: 0.66 ms a layer forward (ISSUE 39), three layers
    assert least["fwd"] / 3 == pytest.approx(16384 * 32 * 1024 / 819e9)
    assert round(1e3 * least["fwd"] / 3, 2) == 0.66
    assert family.sizes(c, mix) == {"seq": 16384, "vocab": 9216}
    for key, value in (("attn_use_rope", True), ("lightning_nkv", 8),
                       ("tie_word_embeddings", True), ("lightning_scale", "1")):
        with pytest.raises(ValueError):
            family.model_config(dict(c, **{key: value}), mix)


@pytest.mark.parametrize("checkpoint_layers", [False, True])
def test_the_references_layer_hook_and_given_lists_change_nothing(
    tokens, wanted, checkpoint_layers
):
    params, want_logits, want_loss, want_grads = wanted
    cfg = tiny()
    c = ref_config(cfg, checkpoint_layers=checkpoint_layers)
    seen = []

    def hook(x, layer, c, r, sparse, given):
        seen.append(sparse)
        return ref._layer(x, layer, c, r, sparse, given)

    loss, grads = jax.value_and_grad(ref.loss)(params, tokens, c, None, hook)
    assert set(seen) == {True, False}
    assert abs(float(loss) - float(want_loss)) < 1e-6 * float(want_loss)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert rel_rms(a, b) < 1e-4
    # the reference GIVEN its own lists is the reference
    h = ref._normed(
        cfg.scale_emb * params["embed"][tokens], params["layers"][0]["input_norm"],
        c.norm_eps, ref._same,
    )
    q, k, _ = ref.sparse_inputs(h, params["layers"][0], c)
    own = ref.selection(q, k, c)
    given = ref.forward(params, tokens, c, None, None, [own, None, None, None])
    assert rel_rms(given, want_logits) < 1e-6
    # ... and given other lists it is not
    other = ref.all_blocks(1, 320, 2, c.block)
    dense = ref.forward(params, tokens, c, None, None, [other, None, None, None])
    assert rel_rms(dense, want_logits) > 1e-3


def test_the_reference_in_a_lower_precision_is_outside_the_float32_tolerance(
    tokens, wanted
):
    params, want_logits, _, _ = wanted
    low = ref.forward(params, tokens, ref_config(tiny()), jnp.bfloat16)
    assert rel_rms(low, want_logits) > 2 * MODEL_TOL


def test_the_benchmarks_reference_is_this_one():
    with open(os.path.join(ROOT, "tests", "reference_minicpm_sala.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmarks", "lib", "minicpm_sala_reference.py"), "rb") as f:
        assert f.read() == mine
    assert b"ddl_tpu" not in re.sub(rb'""".*?"""', b"", mine, count=1, flags=re.S)


def test_the_check_rows_are_the_mixs_window():
    from benchmarks.families import minicpm_sala as family

    with open(os.path.join(ROOT, "benchmarks", "jobs", "tokens-16k.json")) as f:
        mix = json.load(f)
    assert family.CHECK_ROWS == mix["window_rows"]
    assert family.PAIR_ROWS == mix["batch_rows"]
    with open(os.path.join(ROOT, "benchmarks", "configs", "minicpm-sala.json")) as f:
        c = json.load(f)
    # the gradient's prefix runs the DENSE path: stated, not hidden
    assert family.GRAD_TOKENS < c["sparse_config"]["dense_len"] < mix["seq"]
    assert set(family.REHEARSAL) == {
        name for name in vars(family) if name.endswith("_LIMIT")
    } | {"MIN_SELECTION_AGREEMENT"}
    assert "GRAD_LEAF_TOLERANCE" not in family.REHEARSAL  # one value, both


@pytest.fixture(scope="module")
def rehearsed():
    """The cell's check at the rehearsal's sizes (``--rehearsal cpu``)."""
    from benchmarks.families import minicpm_sala as family
    from benchmarks.lib import cells

    cell = cells.load_cell("minicpm-sala.tokens-16k", rehearsal=True)
    cfg = family.model_config(cell.config, cell.mix)

    def check(fault, *parts):
        found = family.compare_with_reference(cfg, 7, fault=fault, parts=parts)
        return found, family.problems_of(found, rehearsal=True)

    return check


def test_the_check_holds_one_real_optimizer_step(rehearsed):
    found, problems = rehearsed(None, "gradients")
    assert not problems
    # adamw's first step is the gradient's sign: what differs is the sign of
    # the elements rounding re-rolls, and the two changes are as long
    assert found["update_rel_diff"] < 0.3
    assert found["update_norm_ratio"] == pytest.approx(1.0, abs=1e-3)
    assert found["update_sign_agreement"] > 0.98
    assert found["grad_leaves"] == 54 and not found["grad_outliers"]
    # the leaves limited one by one are the ones off a lightning head's q / k
    leaf = re.fullmatch(
        r"(?:\['layers'\]\[(\d)\])?\['(\w+)'\]", found["grad_norm_worst_sturdy_leaf"]
    )
    assert leaf[1] in (None, "0") or leaf[2] not in ("input_norm", "wq", "wk", "q_norm", "k_norm")
    assert found["grad_norm_rel_diff_sturdy"] < 0.05 > found["grad_norm_rel_diff_fragile"]


def test_a_state_left_unchanged_reads_one_and_is_refused(rehearsed):
    found, problems = rehearsed("skipped_update", "gradients")
    assert found["update_rel_diff"] == 1.0 and found["update_norm_ratio"] == 0.0
    assert len(problems) == 1 and "nothing moved" in problems[0]


def test_the_check_records_the_selection_the_model_itself_made(rehearsed, monkeypatch):
    """A tap inside ``select_blocks``, not a stand-in for it: the routine the
    timed step runs is the one that selected."""
    from ddl_tpu.models import minicpm_sala as model

    calls = []
    real = model.select_blocks
    monkeypatch.setattr(
        model, "select_blocks", lambda *a: calls.append(1) or real(*a)
    )
    found, problems = rehearsed(None, "forward")
    assert not problems
    assert len(calls) == 1  # one sparse layer, traced once for both rows
    assert found["selections_made"] == found["reference_selections_made"] == 2
    assert found["selection_agreement"] >= 0.9


def test_the_cores_run_in_the_timed_dtype_too(rehearsed):
    found, problems = rehearsed(None, "cores")
    assert not problems
    for core in ("lightning", "sparse"):
        assert found[f"{core}_core_rel_rms"] < 1e-5
        assert 1e-4 < found[f"{core}_core_bf16_rel_rms"] < 1e-2  # bfloat16's rounding
    # dense attention where the lists say otherwise is outside both limits
    found, problems = rehearsed("dense_attention", "cores")
    assert found["sparse_core_bf16_rel_rms"] > 0.05
    assert any("bfloat16 operands differs from the masked softmax" in p for p in problems)
    assert not any("scan" in p for p in problems)


# -- the benchmark's roofline readers -------------------------------------------------


class _Table:
    """A stand-in for ``benchmarks/lib/scopes.Table``: own seconds by
    (scope, frame, pass, family)."""

    def __init__(self, own, window_s=8.0):
        self.own, self.window_s = own, window_s

    def seconds(self, select):
        return sum(s for key, s in self.own.items() if select(*key))


def _measured(monkeypatch, own):
    from benchmarks.lib import scopes

    with open(os.path.join(ROOT, "benchmarks", "configs", "minicpm-sala.json")) as f:
        c = json.load(f)
    monkeypatch.setattr(scopes, "table_of_run", lambda m: _Table(own))
    return {
        "config": c, "mix": {"batch_rows": 1, "seq": 16384}, "chips": 1,
        "steps_per_window": 2, "peak_flops": 197e12,
        # four executions of the 2-step window program: 8 steps traced
        "trace": {"window_s": 8.0, "step_program_busy_s": [2.0, 2.0, 2.0, 2.0]},
    }


def test_the_roofline_readers_divide_the_same_work(monkeypatch):
    from benchmarks.lib import cells, sala_flops

    scan = ("ddl.lightning_scan", "ddl.lightning_scan", "forward", "fusion")
    fwd = ("ddl.lightning_scan", "ddl_lightning_fwd", "forward", "ddl_lightning_fwd")
    bwd = ("ddl.lightning_scan", "ddl_lightning_bwd", "backward", "ddl_lightning_bwd")
    select = ("ddl.sparse_select", "ddl_sparse_select", "forward", "ddl_sparse_select")
    topk = ("ddl.sparse_select", "ddl.sparse_select", "forward", "fusion")
    sparse = {
        name: ("ddl.attn", name, "forward", name) for name in sala_flops.SPARSE_PASSES
    }
    own = {scan: 0.01, fwd: 0.05, bwd: 0.10, select: 0.02, topk: 0.02,
           ("ddl.mlp", "ddl.mlp", "forward", "fusion"): 3.0}
    own.update({key: 0.2 for key in sparse.values()})
    m = _measured(monkeypatch, own)
    read = lambda name: cells.layer_reader(name)(m)
    c = m["config"]
    least = sala_flops.lightning_least_seconds_per_step(
        c, 1, 16384, "selective", 197e12, 819e9)
    # 0.66 + 1.15 ms a layer, three layers, eight steps, over 0.16 s
    assert read("lightning_roofline_share") == pytest.approx(
        100 * 8 * sum(least.values()) / 0.16)
    assert read("lightning_device_share") == pytest.approx(100 * 0.16 / 8.0)
    assert read("sparse_select_device_share") == pytest.approx(100 * 0.04 / 8.0)
    # work moved across the line between kernel and XLA holds the share still
    moved = dict(own)
    moved[scan], moved[fwd] = 0.04, 0.02
    before = read("lightning_roofline_share")
    m = _measured(monkeypatch, moved)
    assert cells.layer_reader("lightning_roofline_share")(m) == pytest.approx(before)
    useful = sala_flops.sparse_useful_flops_per_step(c, 1, 16384, "selective")
    pairs = sala_flops.visible_pairs(c, 16384)
    assert useful["ddl_flash_sparse_fwd"] == 2 * 2 * 128 * 32 * pairs
    assert useful["ddl_flash_sparse_bwd_dkv"] == 2 * useful["ddl_flash_sparse_fwd"]
    assert cells.layer_reader("sparse_attn_roofline_share")(m) == pytest.approx(
        100 * 8 * sum(useful.values()) / (0.6 * 197e12))
    assert cells.layer_reader("sparse_select_roofline_share")(m) == pytest.approx(
        100 * 8 * 16384 * sala_flops.select_flops_per_token(c, 16384) / (0.02 * 197e12))
    # every share under 100 at these made-up times, and nothing read where
    # the configuration is another family's or the trace has no table
    for name in ("lightning_roofline_share", "sparse_attn_roofline_share",
                 "sparse_select_roofline_share"):
        assert 0 < cells.layer_reader(name)(m) < 100
        other = dict(m, config={"training": {"remat": "selective"}})
        assert cells.layer_reader(name)(other) is None
    from benchmarks.lib import scopes

    monkeypatch.setattr(scopes, "table_of_run", lambda m: None)
    for name in ("lightning_device_share", "lightning_dense_device_share",
                 "lightning_roofline_share", "sparse_select_device_share",
                 "sparse_select_roofline_share", "sparse_attn_roofline_share"):
        assert cells.layer_reader(name)(m) is None
        assert cells.layer_reader(name)({}) is None


def test_the_cell_is_in_the_benchmark_with_a_reader_a_metric():
    from benchmarks.lib import cells

    cell = cells.load_cell("minicpm-sala.tokens-16k")
    assert cell.chips == 1 and cell.mix["seq"] == 16384
    names = {m["name"] for m in cell.per_layer}
    assert {"lightning_device_share", "lightning_dense_device_share",
            "lightning_roofline_share", "sparse_select_device_share",
            "sparse_select_roofline_share", "sparse_attn_roofline_share",
            "flash_device_share", "attn_dense_device_share", "mlp_device_share",
            "head_device_share", "optimizer_device_share", "recompute_device_share",
            "unscoped_device_share", "peak_hbm_GiB", "mfu_busy"} <= names
    assert not {n for n in names if n.startswith(("gdn_", "gmm_", "mla_"))}
    for name in names:
        assert callable(cells.layer_reader(name))
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "mfu", "setup_s"}

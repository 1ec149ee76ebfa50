"""``selective``'s plan (``ddl_tpu/models/remat.py``): what it keeps beyond
the attention outputs is decided a layer from bytes the program can count.

- no reported limit: nothing is marked, nothing planned — the traced program
  is the one ``selective`` always made (``tests/test_ops.py``,
  ``test_afmoe.py``, ``test_deepseek_v3.py`` and ``test_olmoe_reference.py``
  hold its text by sha256, unedited);
- an injected limit: the kinds fill in their stated order, the last byte is
  respected, and the bytes a kind is counted at are the jaxpr's;
- the plan moves no value: loss and every gradient leaf are the unplanned
  step's;
- and no kernel call: the grouped matmuls, the convolution's passes and the
  ``ddl_*`` kernels are as many under every plan (the benchmark's
  ``*_flops.py`` tables stay true).
"""

import collections
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from ddl_tpu.models import (
    afmoe, deepseek_v3, lfm2_moe, llama, minicpm_sala, moe, olmo_hybrid, remat)
from ddl_tpu.observability import metrics

FAMILIES = ["mistral", "olmoe", "trinity", "kanana", "olmo_hybrid",
            "minicpm_sala", "lfm2"]
B, T = 2, 256


def case(family, policy="selective"):
    """(module, config) of a decoder of that family at a rehearsal width:
    every kind the family tags is in it (a share of 4 of 16 experts behind a
    dense first layer for the three share families, both mixer kinds for the
    hybrids, a row past ``dense_len`` for MiniCPM-SALA's selection)."""
    common = dict(vocab=256, d_model=128, n_heads=2, max_seq=T, remat=policy,
                  attn_impl="flash")
    share = dict(d_ff=256, d_expert=64, n_experts=16, topk=4,
                 n_dense_layers=1, held_experts=(4, 4))
    if family == "mistral":
        return llama, llama.LlamaConfig(n_layers=2, n_kv_heads=1, d_ff=256, **common)
    if family == "olmoe":
        return moe, moe.MoeConfig(
            n_layers=2, d_ff=64, n_kv_heads=2, n_experts=4, topk=2, qk_norm=True,
            norm_topk_prob=False, **common)
    if family == "trinity":
        S, F = afmoe.SLIDING, afmoe.FULL
        return afmoe, afmoe.AfmoeConfig(
            n_kv_heads=1, head_dim=64, layer_types=(S, F, S), sliding_window=128,
            route_scale=2.5, **share, **common)
    if family == "kanana":
        return deepseek_v3, deepseek_v3.DeepseekV3Config(
            n_layers=3, qk_nope_dim=64, qk_rope_dim=32, v_head_dim=64,
            kv_lora_rank=64, route_scale=2.5, **share, **common)
    if family == "olmo_hybrid":
        L, F = olmo_hybrid.LINEAR, olmo_hybrid.FULL
        return olmo_hybrid, olmo_hybrid.OlmoHybridConfig(
            d_ff=256, layer_types=(L, F), n_linear_heads=2, linear_key_dim=32,
            linear_value_dim=64, **common)
    if family == "minicpm_sala":
        G, A = minicpm_sala.LIGHTNING, minicpm_sala.SPARSE
        return minicpm_sala, minicpm_sala.MiniCPMSalaConfig(
            n_kv_heads=1, head_dim=64, n_lightning_heads=2, lightning_head_dim=64,
            d_ff=256, mixer_types=(A, G), dense_len=128, dim_model_base=32, **common)
    assert family == "lfm2", family
    C, F = lfm2_moe.CONV, lfm2_moe.FULL
    return lfm2_moe, lfm2_moe.Lfm2MoeConfig(
        n_kv_heads=1, layer_types=(C, F, C), **share, **common)


def traced(family, free, policy="selective"):
    """The jaxpr of the family's loss and gradient, traced with ``free``
    bytes of HBM for the program (``None``: no device reported a limit)."""
    mod, cfg = case(family, policy)
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)
    with remat.free_hbm(free):
        return jax.make_jaxpr(jax.value_and_grad(
            lambda p, t: mod.next_token_loss(p, t, cfg)))(params, tokens)


@functools.lru_cache(maxsize=None)
def counted(family):
    """``remat.measure`` of each layer of the family's case."""
    mod, cfg = case(family)
    seen = []
    real = remat.plan

    def spy(layers, free):
        seen.extend(layers)
        return real(layers, free)

    remat.plan = spy
    try:
        traced(family, 1 << 40)
    finally:
        remat.plan = real
    assert len(seen) == cfg.n_layers
    return tuple(seen)


def marks(jaxpr_text):
    return collections.Counter(re.findall(r"name\[name=(ddl_\w+)\]", jaxpr_text))


# -- no reported limit: today's program --------------------------------------------


@pytest.mark.parametrize("family", FAMILIES)
def test_without_a_reported_limit_nothing_is_marked_or_planned(family):
    """Outside a step factory, and inside one whose device reports no limit
    (XLA:CPU: every tier-1 trace), the program carries ``ddl_attn_out`` and
    no other mark; it is the text a stated ``None`` gives."""
    text = str(traced(family, None))
    assert set(marks(text)) == {remat.ATTN_OUT_NAME}, marks(text)
    assert not any(kind in text for kind in remat.KINDS)
    assert jax.devices()[0].memory_stats() is None  # what a factory reads here
    assert remat.device_budget(jax.devices()) is None


def test_a_step_factory_on_the_cpu_hands_the_trace_no_budget():
    """``make_multistep`` / ``make_train_step`` on a CPU mesh: the window
    program holds no kind's mark and the plan's gauges stay untouched."""
    import optax

    from ddl_tpu.parallel.train import make_multistep, make_train_step

    mod, cfg = case("trinity")
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    optimizer = optax.adamw(3e-4)
    loss = lambda p, b: mod.next_token_loss(p, b[0], cfg)
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    opt = jax.eval_shape(optimizer.init, params)
    before = metrics().gauge("remat.extra_bytes.max")
    _, multi = make_multistep(loss, optimizer, mesh, mod.param_specs(cfg),
                              batch_spec=P(("dp",)), n_steps=2)
    run = next(c.cell_contents for c in multi.__closure__
               if hasattr(c.cell_contents, "lower"))
    window = (jax.ShapeDtypeStruct((2, B, T), jnp.int32),)
    text = str(run.trace(params, opt, window, True).jaxpr)
    assert set(marks(text)) == {remat.ATTN_OUT_NAME}
    _, step = make_train_step(loss, optimizer, mesh, mod.param_specs(cfg))
    inner = next(c.cell_contents for c in step.__closure__
                 if hasattr(c.cell_contents, "lower"))
    text = str(inner.trace(
        params, opt, (jax.ShapeDtypeStruct((B, T), jnp.int32),)).jaxpr)
    assert set(marks(text)) == {remat.ATTN_OUT_NAME}
    assert metrics().gauge("remat.extra_bytes.max") == before


def test_a_step_factory_counts_what_it_holds_against_a_reported_limit(monkeypatch):
    """Where the devices do report (here: the tool's way, an answer given
    for them) the loss is traced with the budget less parameters, optimizer
    state and the window — exact, from the pytrees."""
    import optax

    from ddl_tpu.parallel.train import make_multistep

    mod, cfg = case("kanana")
    budget = 1 << 30
    monkeypatch.setattr(remat, "device_budget", lambda devices: budget)
    frees = []
    real = remat._plan_stack
    monkeypatch.setattr(
        remat, "_plan_stack",
        lambda *a: frees.append(a[-1]) or real(*a))
    optimizer = optax.adamw(3e-4)
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    opt = jax.eval_shape(optimizer.init, params)
    window = (jax.ShapeDtypeStruct((2, B, T), jnp.int32),)
    _, multi = make_multistep(
        lambda p, b: mod.next_token_loss(p, b[0], cfg), optimizer,
        Mesh(np.array(jax.devices()[:1]), ("dp",)), mod.param_specs(cfg),
        batch_spec=P(("dp",)), n_steps=2)
    run = next(c.cell_contents for c in multi.__closure__
               if hasattr(c.cell_contents, "lower"))
    text = str(run.trace(params, opt, window, True).jaxpr)
    assert frees == [budget - remat.tree_bytes(params, opt, window)]
    assert set(marks(text)) > {remat.ATTN_OUT_NAME}
    assert metrics().gauge("remat.extra_bytes") > 0
    assert metrics().gauge("remat.extra_layers") == cfg.n_layers


@pytest.mark.parametrize("policy", ["none", "full", "dots"])
def test_the_other_policies_take_no_plan(policy):
    """A budget changes nothing under ``none`` / ``full`` / ``dots``: no
    mark, the same text."""
    with_budget = str(traced("trinity", 1 << 40, policy))
    assert not any(kind in with_budget for kind in remat.KINDS)
    strip = lambda s: re.sub(r" at (0x[0-9a-f]+|\S+:\d+)", "", s)
    assert strip(with_budget) == strip(str(traced("trinity", None, policy)))


def test_the_pipelined_forwards_keep_the_always_saved_rule():
    """``llama.forward_pp`` / ``moe._forward_pp`` wrap on their own: no mark
    under a budget (no cell runs them)."""
    import inspect

    for fn in (llama.forward_pp, moe._forward_pp):
        assert "planned" not in inspect.getsource(fn)
    assert "_remat.wrap(one_layer, cfg.remat)" in inspect.getsource(llama.forward_pp)
    assert "_remat.wrap(one_layer, cfg.remat)" in inspect.getsource(moe._forward_pp)


# -- an injected limit: the order, the last byte, the jaxpr's bytes -----------------


def test_the_kinds_and_their_order_are_stated_once():
    assert remat.KINDS == (
        remat.HC, remat.ROUTER, remat.NORMED, remat.PROJ, remat.SWIGLU,
        remat.GATE, remat.LATENT_UP)
    assert remat.ATTN_OUT_NAME not in remat.KINDS
    assert remat.POLICIES == ("none", "full", "selective", "dots")


@pytest.mark.parametrize("family", FAMILIES)
def test_kinds_fill_in_the_stated_order_and_the_last_byte_is_respected(family):
    layers = counted(family)
    kinds_here = [k for k in remat.KINDS if any(c.by_name.get(k) for c in layers)]
    assert kinds_here, "the family tags nothing"
    total = lambda plan: sum(
        c.by_name[k] for c, ks in zip(layers, plan) for k in ks)
    everything = sum(c.by_name.get(k, 0) for c in layers for k in remat.KINDS)
    assert total(remat.plan(layers, everything)) == everything
    assert total(remat.plan(layers, 0)) == 0
    # Up to each kind's end the plan is exactly the kinds before it; a byte
    # short of it, the kind's last layer is left out and nothing worth less
    # that is as large gets in.
    upto = 0
    for kind in kinds_here:
        sizes = [c.by_name.get(kind, 0) for c in layers]
        upto += sum(sizes)
        plan = remat.plan(layers, upto)
        assert total(plan) == upto
        assert {k for ks in plan for k in ks} == set(
            kinds_here[:kinds_here.index(kind) + 1])
        short = remat.plan(layers, upto - 1)
        assert total(short) <= upto - 1
        last = max(i for i, n in enumerate(sizes) if n)
        assert kind not in short[last]
        assert all(kind in ks for ks, n in zip(short[:last], sizes[:last]) if n)
    # every layer's kinds come out in the stated order
    for ks in remat.plan(layers, everything):
        assert list(ks) == [k for k in remat.KINDS if k in ks]


def test_bytes_by_kind_are_the_jaxprs():
    """What a kind is counted at is the bytes of the values marked with it
    in the layer's own jaxpr: here against the shapes, by hand."""
    N, bf16 = B * T, 2
    mod, cfg = case("trinity")
    dense, routed = counted("trinity")[:2]
    q_out, kv_out = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    assert dense.by_name[remat.PROJ] == N * (q_out + 2 * kv_out) * bf16
    assert dense.by_name[remat.GATE] == N * q_out * bf16
    # behind the sandwich norms: ``wo``'s product and the MLP's
    assert dense.by_name[remat.NORMED] == routed.by_name[remat.NORMED] == (
        2 * N * cfg.d_model * bf16)
    assert dense.by_name[remat.SWIGLU] == 2 * N * cfg.d_ff * bf16
    assert remat.ROUTER not in dense.by_name
    assert routed.by_name[remat.SWIGLU] == 2 * N * cfg.d_expert * bf16  # shared
    k, held = cfg.topk, cfg.held[1]
    assert routed.by_name[remat.ROUTER] == (
        N * k * 4 + N * k * 4      # top_w float32, top_e int32
        + N * k * 4 + held * 4     # order, group_sizes
        + N * k)                   # is_held
    assert dense.inputs == routed.inputs == N * cfg.d_model * bf16
    # the always-saved: the attention output and, in a routed layer, its result
    assert dense.by_name[remat.ATTN_OUT_NAME] >= N * q_out * bf16
    assert routed.by_name[remat.ATTN_OUT_NAME] >= N * (q_out + cfg.d_model) * bf16

    mod, cfg = case("kanana")
    layer = counted("kanana")[0]
    H = cfg.n_heads
    assert layer.by_name[remat.PROJ] == N * bf16 * (
        H * (cfg.qk_nope_dim + cfg.qk_rope_dim) + cfg.kv_lora_rank + cfg.qk_rope_dim)
    assert layer.by_name[remat.LATENT_UP] == N * bf16 * H * (
        cfg.qk_nope_dim + cfg.v_head_dim)

    mod, cfg = case("olmoe")  # top_p, top_e, order, inv; group_sizes; no is_held
    assert counted("olmoe")[0].by_name[remat.ROUTER] == (
        4 * N * cfg.topk * 4 + cfg.n_experts * 4)


@pytest.mark.parametrize("family", FAMILIES)
def test_the_traced_step_saves_what_was_planned(family):
    """Through ``decoder.forward``: given room for everything every mark is
    in the program and the gauges say so; given room for the reserve alone,
    the marks are there and nothing is kept."""
    layers = counted(family)
    everything = sum(c.by_name.get(k, 0) for c in layers for k in remat.KINDS)
    text = str(traced(family, 1 << 40))
    assert {k for k in remat.KINDS if k in marks(text)} == {
        k for k in remat.KINDS if any(c.by_name.get(k) for c in layers)}
    assert metrics().gauge("remat.extra_bytes") == everything
    mod, cfg = case(family)
    reserve = remat.reserved(layers, 4 * B * T * cfg.vocab)
    traced(family, reserve)
    assert metrics().gauge("remat.extra_bytes") == 0
    assert metrics().gauge("remat.extra_layers") == 0
    assert metrics().gauge("remat.budget_bytes") == 0
    router = sum(c.by_name.get(remat.ROUTER, 0) for c in layers)
    traced(family, reserve + router)
    assert metrics().gauge("remat.extra_bytes") == router
    assert metrics().gauge("remat.budget_bytes") == router


# -- the plan moves no value and no kernel call -------------------------------------


@pytest.mark.parametrize("family", FAMILIES)
def test_loss_and_gradients_are_the_unplanned_steps(family):
    """The same values, kept where they were made again: loss and every
    gradient leaf equal the unplanned step's to the last bit.  Op by op
    (``disable_jit``): under ``jit`` XLA fuses the recomputed projections
    another way than the kept ones and the last bit moves with it."""
    mod, cfg = case(family)
    params = mod.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (B, T), 0, cfg.vocab)

    def step(free):
        with remat.free_hbm(free), jax.disable_jit():
            return jax.value_and_grad(
                lambda p: mod.next_token_loss(p, tokens, cfg))(params)

    (loss, grads), (want_loss, want) = step(1 << 40), step(None)
    assert metrics().gauge("remat.extra_bytes") > 0
    assert float(loss) == float(want_loss)
    for (path, got), exp in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want)):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(exp), jax.tree_util.keystr(path))


@pytest.mark.parametrize("family", FAMILIES)
def test_every_plan_makes_the_same_kernel_calls(family):
    """Grouped matmuls, convolution passes and ``ddl_*`` kernels in the
    traced step: as many under the empty plan, the router alone and the
    fullest (the benchmark's ``moe_flops`` / ``lfm2_flops`` / ``*_flops``
    tables are keyed by the policy's name and stay true)."""
    layers = counted(family)
    mod, cfg = case(family)
    reserve = remat.reserved(layers, 4 * B * T * cfg.vocab)
    router = sum(c.by_name.get(remat.ROUTER, 0) for c in layers)

    def calls(free):
        text = str(traced(family, free))
        kernels = collections.Counter(re.findall(r"name=(ddl_\w+)", text))
        for name in (remat.ATTN_OUT_NAME,) + remat.KINDS:
            kernels.pop(name, None)
        return (
            len(re.findall(r"= ragged_dot(?:_general)?\[", text)),
            collections.Counter(re.findall(r"name=_shortconv_(fwd|bwd)", text)),
            kernels,
        )

    want = calls(None)
    assert calls(reserve + router) == want
    assert calls(1 << 40) == want
    assert (want[0] > 0) == (family in ("olmoe", "trinity", "kanana", "lfm2"))
    assert bool(want[1]) == (family == "lfm2")
    assert want[2], "no kernel in the step: the case runs the dense path"

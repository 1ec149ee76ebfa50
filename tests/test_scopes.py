"""The model-layer scopes (``ddl_tpu/ops/naming.py:SCOPE_NAMES``): every
matmul of the five families' train steps stands under one, in the forward
pass, the backward pass and the recomputation; the benchmark's reader of
them (``benchmarks/lib/scopes.py``) on path forms, on both recorded v5e
traces and on a made-up one; the compile cache's key moves with the table
and with nothing else."""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from benchmarks.lib import cells, scopes as S, tracered as T
from ddl_tpu.ops import naming

TESTDATA = os.path.join(cells.HERE, "testdata")
RECORDED = os.path.join(TESTDATA, "tpu_v5e_3steps.xplane.pb")
RECORDED_SCOPED = os.path.join(TESTDATA, "tpu_v5e_scopes.xplane.pb")
REPO = os.path.dirname(cells.HERE)


# -- the program: every matmul under a scope of the table ------------------------


def _family(name):
    """(module, tiny config under selective remat, loss(params, batch))."""
    from ddl_tpu.models import (
        afmoe, deepseek_v3, lfm2_moe, llama, minicpm_sala, moe, olmo_hybrid, vit,
        xing4)
    from ddl_tpu.ops.sparse_attention import SparseConfig

    common = dict(vocab=64, d_model=32, n_layers=2, n_heads=2, max_seq=16,
                  remat="selective")
    if name == "vit":  # the family without a remat policy
        cfg = vit.ViTConfig(n_layers=2)
        return vit, cfg, lambda p, b: vit.classification_loss(p, b, cfg)
    mod, cfg = {
        "llama": lambda: (llama, llama.LlamaConfig(
            n_kv_heads=1, d_ff=64, **common)),
        "moe": lambda: (moe, moe.MoeConfig(
            n_kv_heads=2, d_ff=16, n_experts=4, topk=2, qk_norm=True,
            router_z_weight=0.001, moe_impl="ragged", **common)),
        "afmoe": lambda: (afmoe, afmoe.AfmoeConfig(remat="selective")),
        "deepseek_v3": lambda: (
            deepseek_v3, deepseek_v3.DeepseekV3Config(remat="selective")),
        "olmo_hybrid": lambda: (
            olmo_hybrid, olmo_hybrid.OlmoHybridConfig(remat="selective")),
        # rows past ``dense_len``: the selection and the sparse path are live
        "minicpm_sala": lambda: (
            minicpm_sala, minicpm_sala.MiniCPMSalaConfig(
                remat="selective", dense_len=64, sparse=SparseConfig(
                    block=16, kernel=8, stride=4, topk=4, local_blocks=2))),
        "lfm2_moe": lambda: (
            lfm2_moe, lfm2_moe.Lfm2MoeConfig(remat="selective", held_experts=(0, 2))),
        "xing4": lambda: (xing4, xing4.Xing4Config(
            remat="selective", q_lora_rank=16, held_experts=(0, 4),
            rope_scaling=xing4.Yarn(8.0, 8, 4.0, 1.0, 1.0, 1.0))),
    }[name]()
    return mod, cfg, lambda p, b: mod.next_token_loss(p, b[0], cfg)


@functools.lru_cache(maxsize=None)
def _compiled_step_text(name):
    """The optimized HLO of the family's 2-step window program (adamw),
    as ``parallel.train.make_multistep`` builds it for the benchmark."""
    import optax

    from ddl_tpu.parallel.train import make_multistep

    mod, cfg, loss = _family(name)
    optimizer = optax.adamw(1e-3)
    _, multi = make_multistep(
        loss, optimizer, Mesh(np.array(jax.devices()[:1]), ("dp",)),
        mod.param_specs(cfg), batch_spec=P(("dp",)), n_steps=2,
    )
    run = next(c.cell_contents for c in multi.__closure__
               if hasattr(c.cell_contents, "lower"))
    params = jax.eval_shape(lambda: mod.init_params(cfg, jax.random.key(0)))
    if name == "vit":
        pixels = cfg.image_size * cfg.image_size * cfg.n_channels
        batch = (jax.ShapeDtypeStruct((2, 2, pixels), jnp.float32),
                 jax.ShapeDtypeStruct((2, 2, 1), jnp.int32))
    else:
        # the linear-attention scan over whole chunks and a ragged one
        seq = 160 if name in ("olmo_hybrid", "minicpm_sala") else 16
        batch = (jax.ShapeDtypeStruct((2, 2, seq), jnp.int32),)
    args = (params, jax.eval_shape(optimizer.init, params), batch, True)
    return run.lower(*args).compile().as_text()


MATMUL = re.compile(r"= \S+ (dot|convolution|custom-call)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.mark.parametrize(
    "family", ["llama", "moe", "afmoe", "deepseek_v3", "vit", "olmo_hybrid",
               "minicpm_sala", "lfm2_moe", "xing4"])
def test_every_matmul_of_a_train_step_stands_under_a_scope(family):
    text = _compiled_step_text(family)
    seen = {}
    for line in text.splitlines():
        if not MATMUL.search(line):
            continue
        path = OP_NAME.search(line)
        assert path, f"an instruction without a path: {line[:200]}"
        scope, _, which = S.classify(path.group(1))
        assert scope in naming.SCOPE_NAMES, (path.group(1), line[:160])
        seen.setdefault(which, set()).add(scope)
    passes = ("forward", "backward") + (("recompute",) if family != "vit" else ())
    assert set(seen) == set(passes), seen
    for which in passes:
        # Attention's projections and the MLP (or the experts) are there
        # in every pass; the head's matmul is never recomputed.
        assert "ddl.attn" in seen[which] or "ddl.mla_q" in seen[which], seen
        assert seen[which] & {"ddl.mlp", "ddl.moe_experts", "ddl.moe_shared"}, seen
    assert "ddl.head" in seen["forward"] and "ddl.head" in seen["backward"]
    if family == "olmo_hybrid":
        # A linear-attention layer's projections and its output in every
        # pass; its scan (the interpreted kernels: since PR 41 a chunk from
        # q, k, v, decay sums and beta to o) forward and backward but never
        # recomputed: ``selective`` saves its output and chunk states, and
        # the backward kernel prepares a chunk again itself.
        for which in passes:
            assert {"ddl.gdn_proj", "ddl.gdn_out"} <= seen[which], seen
        assert "ddl.gdn_scan" in seen["forward"] & seen["backward"], seen
        assert "ddl.gdn_scan" not in seen["recompute"], seen
    if family == "minicpm_sala":
        # A lightning layer's projections and output in every pass; its
        # scan forward and backward but never recomputed (one kernel from
        # q, k, v to o: ``selective`` saves its output and chunk states);
        # the selection in the forward pass alone: it has no gradient, and
        # ``selective`` saves its lists.
        for which in passes:
            assert {"ddl.lightning_proj", "ddl.lightning_out"} <= seen[which], seen
        assert "ddl.lightning_scan" in seen["forward"] & seen["backward"], seen
        assert "ddl.lightning_scan" not in seen["recompute"], seen
        assert "ddl.sparse_select" in seen["forward"], seen
        assert "ddl.sparse_select" not in seen["backward"] | seen["recompute"], seen
    if family == "lfm2_moe":
        # A conv layer's ``W_out`` in every pass; its ``W_in`` forward and
        # backward but never recomputed: ``selective`` saves ``BCx``, the
        # gated short convolution's one residual.  No shared expert.
        for which in passes:
            assert "ddl.shortconv_out" in seen[which], seen
            assert "ddl.moe_shared" not in seen[which], seen
        assert "ddl.shortconv_proj" in seen["forward"] & seen["backward"], seen
        assert "ddl.shortconv_proj" not in seen["recompute"], seen
    if family == "xing4":
        # A wrap's projections (the pass in front of the matrices) forward,
        # backward (the weights' cotangent over the tokens) and again where
        # the layer is rematerialised - XLA:CPU reports no HBM limit, so the
        # mixing matrices are not kept; the module's ``W_eh`` under its own
        # frame forward and backward.
        for which in passes:
            assert "ddl.hc_pre" in seen[which], seen
        assert "ddl.mtp" in seen["forward"] & seen["backward"], seen
    # The module's name is what the reduction looks for.
    assert "HloModule jit__run" in text


def test_the_optimizer_and_the_ends_carry_their_scopes():
    paths = set(OP_NAME.findall(_compiled_step_text("llama")))
    frames = {f for p in paths for f in S.FRAME.findall(p)}
    assert {"ddl.embed", "ddl.attn", "ddl.mlp", "ddl.head",
            "ddl.optimizer"} <= frames, frames


def test_the_table_is_whole():
    assert len(set(naming.SCOPE_NAMES)) == len(naming.SCOPE_NAMES)
    assert all(n.startswith("ddl.") for n in naming.SCOPE_NAMES)
    grouped = [s for scopes_ in S.GROUPS.values() for s in scopes_]
    # The benchmark's groups, and the scopes its reader counts as ``other``:
    # exactly the eleven the linear-attention, selection and short-convolution
    # readers select themselves, the routed layer's full-width fallback, the
    # hyper-connected path's two and the multi-token-prediction module's.
    from benchmarks.layers import (
        gdn_dense_device_share, gdn_device_share, hc_device_share,
        lightning_dense_device_share, lightning_device_share,
        moe_overflow_device_share, mtp_device_share,
        shortconv_dense_device_share, shortconv_device_share,
        sparse_select_device_share)

    gdn = gdn_dense_device_share.DENSE_SCOPES + (gdn_device_share.SCAN_SCOPE,)
    sala = lightning_dense_device_share.DENSE_SCOPES + (
        lightning_device_share.SCAN_SCOPE, sparse_select_device_share.SELECT_SCOPE)
    overflow = (moe_overflow_device_share.OVERFLOW_SCOPE,)
    conv = shortconv_dense_device_share.DENSE_SCOPES + (
        shortconv_device_share.CONV_SCOPE,)
    xing = hc_device_share.HC_SCOPES + (mtp_device_share.MTP_SCOPE,)
    assert sorted(grouped + list(gdn + sala + overflow + conv + xing)) == sorted(
        naming.SCOPE_NAMES)
    with pytest.raises(AssertionError):
        naming.scope("ddl.not_in_the_table")
    # No model file names a scope past the helper.
    models = os.path.join(REPO, "ddl_tpu", "models")
    for name in os.listdir(models):
        if name.endswith(".py"):
            with open(os.path.join(models, name)) as f:
                assert "jax.named_scope(" not in f.read(), name


def test_the_documented_table_is_the_programs():
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    table = doc[doc.index("<!-- scope table -->"):doc.index("<!-- /scope table -->")]
    documented = re.findall(r"^\| `(ddl\.[a-z_]+)` \|", table, flags=re.M)
    assert documented == list(naming.SCOPE_NAMES)


# -- the classifier ---------------------------------------------------------------


@pytest.mark.parametrize("path, want", [
    ("jit(step)/jvp(ddl.attn)/dot_general",
     ("ddl.attn", "ddl.attn", "forward")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/ddl.attn/dot_general",
     ("ddl.attn", "ddl.attn", "backward")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "ddl.attn/dot_general", ("ddl.attn", "ddl.attn", "recompute")),
    ("jit(_run)/while/body/closed_call/jvp(ddl.attn)/ddl.attn_gate/mul:",
     ("ddl.attn_gate", "ddl.attn_gate", "forward")),
    ("jit(_run)/while/body/closed_call/jvp(ddl.attn)/ddl_flash_fwd/x",
     ("ddl.attn", "ddl_flash_fwd", "forward")),
    ("jit(_run)/while/body/closed_call/jvp(ddl.moe)/ddl.moe_shared/dot_general",
     ("ddl.moe_shared", "ddl.moe_shared", "forward")),
    # A share's two branches (PR 40): the bounded pass under the phase, the
    # full-width fallback under ``ddl.moe_overflow`` inside it.
    ("jit(_run)/while/body/closed_call/jvp(ddl.moe)/cond/branch_1_fun/"
     "ddl.moe_experts/jit(_take)/gather",
     ("ddl.moe_experts", "ddl.moe_experts", "forward")),
    ("jit(_run)/while/body/closed_call/jvp(ddl.moe)/cond/branch_0_fun/"
     "ddl.moe_experts/ddl.moe_overflow/jit(_take)/gather",
     ("ddl.moe_overflow", "ddl.moe_overflow", "forward")),
    ("jit(_run)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/ddl.moe/cond/"
     "branch_0_fun/transpose(jvp(ddl.moe_combine))/ddl.moe_overflow/gather",
     ("ddl.moe_overflow", "ddl.moe_overflow", "backward")),
    ("jit(_run)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/"
     "rematted_computation/ddl.moe/cond/branch_1_fun/ddl.moe_combine/gather",
     ("ddl.moe_combine", "ddl.moe_combine", "recompute")),
    ("jit(_run)/while/body/closed_call/ddl.optimizer/mul",
     ("ddl.optimizer", "ddl.optimizer", "forward")),
    ("jit(_run)/while/body/closed_call/transpose(jvp(ddl.head))/mul;"
     "transpose(jvp(ddl.head))/broadcast_in_dim",
     ("ddl.head", "ddl.head", "backward")),
    ("jit(_run)/while/body/dynamic_update_slice;jit(_run)/while/body/"
     "closed_call/jvp(ddl.embed)/gather", ("ddl.embed", "ddl.embed", "forward")),
    ("jit(_run)/while/body/closed_call/dot_general:", (None, None, "forward")),
    ("", (None, None, "forward")),
])
def test_classifier_on_the_path_forms(path, want):
    assert S.classify(path) == want


# -- the wire reader on the recorded traces -----------------------------------------


def test_wire_reader_reads_what_profiledata_hides():
    (plane,) = S.read_planes(RECORDED)
    assert plane.chip == 0
    assert plane.stats["device_type_string"] == "TPU v5 Lite"
    assert plane.stats["peak_teraflops_per_second"] == pytest.approx(202.7)
    assert plane.stats["peak_hbm_bw_gigabytes_per_second"] == pytest.approx(819.2, rel=1e-3)
    (m,) = [m for m in plane.meta.values()
            if m.name.startswith("%convolution_tanh_fusion.2 =")]
    assert m.tf_op == "jit(_run)/while/body/closed_call/dot_general:"
    assert m.flops == 17_188_257_792
    assert m.bytes_accessed == 25_165_824
    assert m.hlo_category == "convolution fusion"


@pytest.mark.parametrize("path", [RECORDED, RECORDED_SCOPED])
def test_wire_reader_and_profiledata_agree_to_the_last_bit(path):
    trace = T.load(path)
    planes = S.read_planes(path)
    assert sorted(p.chip for p in planes) == sorted(trace.ops)
    for plane in planes:
        named = sorted((a, b, plane.meta[mid].name) for a, b, mid in plane.ops)
        assert named == trace.ops[plane.chip]
        assert plane.modules == trace.modules[plane.chip]
    assert S.tabulate(planes).window_s == T.reduce(trace)["window_s"]


def test_the_unscoped_recorded_trace_has_no_scoped_op():
    table = S.tabulate(S.read_planes(RECORDED))
    assert table.n_scoped_ops == 0
    assert table.unscoped_s() == pytest.approx(table.step_own_s)
    # The matmul fusion's rate by XLA's own count: 17.2 GFLOP in ~90 us.
    row = S.rows(table)[0]
    assert row["scope"] == "unscoped" and 150 < row["tflops"] < 202.7


def test_the_scoped_recorded_trace_is_classified():
    """A real v5e trace of one llama layer under ``value_and_grad`` +
    ``jax.checkpoint`` (``tools/record_scoped_trace.py``)."""
    table = S.tabulate(S.read_planes(RECORDED_SCOPED))
    w = table.window_s
    by = {}
    for (scope, frame, which, family), s in table.own.items():
        by[frame, which] = by.get((frame, which), 0.0) + s
    # Every scope of a dense decoder is there, the layer's in all three
    # passes, the kernels under their own frames inside ``ddl.attn``.
    for scope in ("ddl.attn", "ddl.mlp"):
        for which in S.PASSES:
            assert by.get((scope, which), 0) > 0, (scope, which)
    for scope in ("ddl.embed", "ddl.head", "ddl.optimizer"):
        assert by.get((scope, "forward"), 0) > 0, scope
    assert by.get(("ddl.head", "backward"), 0) > 0
    assert by.get(("ddl_flash_fwd", "forward"), 0) > 0
    # The one backward kernel's family (the dK/dV grid, which carries dQ
    # since PR 45; the trace was recorded before and holds the old pair's
    # ``ddl_flash_bwd_dq`` beside it, classified the same way).
    assert by.get(("ddl_flash_bwd_dkv", "backward"), 0) > 0
    assert all(which == "backward" for frame, which in by
               if (frame or "").startswith("ddl_flash_bwd"))
    # Selective remat saves the kernels' residuals: no second forward.
    assert ("ddl_flash_fwd", "recompute") not in by
    assert table.recompute_s() > 0
    assert table.kernels_s() > 0
    total = (sum(table.group_s(g) for g in list(S.GROUPS) + ["other"])
             + table.kernels_s() + table.unscoped_s())
    assert total == pytest.approx(table.step_own_s, rel=1e-9)
    assert table.group_s("other") == 0
    assert table.unscoped_s() < 0.25 * table.step_own_s
    assert 0 < table.step_own_s <= w
    assert "ddl.optimizer" in S.render(table)


# -- a made-up trace: the shares add up; a stale executable is refused ---------------


_f = S.put


STAT_IDS = {"tf_op": 1, "flops": 2, "bytes_accessed": 3, "hlo_category": 4,
            "peak_teraflops_per_second": 5,
            "peak_hbm_bw_gigabytes_per_second": 6}


def _stat(name, value):
    field = {int: 4, float: 2, str: 5}[type(value)]
    return _f(1, STAT_IDS[name]) + _f(field, value)


def _made_up_trace(path, scoped=True):
    """Three executions of ``jit__run`` (1 ms apart, 0.9 ms long) and one
    of another program; times in microseconds inside an execution."""
    us = 1_000_000  # ps
    ops = [  # name, path, start us, length us, flops, category
        ("while", "jit(_run)/while", 0, 900, 0, "while"),
        ("fusion.12", "jit(_run)/while/body/closed_call/jvp(ddl.lightning_proj)/dot_general", 0, 2, 0, "convolution fusion"),
        ("ddl_lightning_fwd.1", "jit(_run)/while/body/closed_call/jvp(ddl.lightning_scan)/ddl_lightning_fwd", 2, 3, 0, "custom-call"),
        ("fusion.13", "jit(_run)/while/body/closed_call/jvp(ddl.lightning_scan)/pad", 5, 1, 0, "loop fusion"),
        ("fusion.14", "jit(_run)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/ddl.lightning_out/dot_general", 6, 2, 0, "convolution fusion"),
        ("fusion.1", "jit(_run)/while/body/closed_call/jvp(ddl.embed)/gather", 10, 20, 0, "loop fusion"),
        ("fusion.2", "jit(_run)/while/body/closed_call/jvp(ddl.attn)/dot_general", 30, 100, 4_000_000, "convolution fusion"),
        ("ddl_flash_fwd.1", "jit(_run)/while/body/closed_call/jvp(ddl.attn)/ddl_flash_fwd", 130, 50, 0, "custom-call"),
        ("copy.7", "", 180, 10, 0, "data formatting"),
        ("fusion.3", "jit(_run)/while/body/closed_call/jvp(ddl.mlp)/dot_general", 190, 200, 8_000_000, "convolution fusion"),
        ("fusion.4", "jit(_run)/while/body/closed_call/jvp(ddl.moe)/ddl.moe_route/sort", 390, 40, 0, "loop fusion"),
        ("ragged-dot-none.1", "", 430, 60, 0, "custom-call"),
        ("fusion.5", "jit(_run)/while/body/closed_call/jvp(ddl.moe)/ddl.moe_shared/dot_general", 490, 30, 0, "convolution fusion"),
        ("fusion.6", "jit(_run)/while/body/closed_call/jvp(ddl.head)/dot_general", 520, 70, 0, "convolution fusion"),
        ("fusion.7", "jit(_run)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/rematted_computation/ddl.mlp/dot_general", 590, 80, 0, "convolution fusion"),
        ("fusion.8", "jit(_run)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/ddl.attn/dot_general", 670, 90, 0, "convolution fusion"),
        ("fusion.9", "jit(_run)/while/body/closed_call/ddl.optimizer/mul", 760, 110, 0, "loop fusion"),
        ("fusion.10", "jit(_run)/while/body/closed_call/jvp(ddl.gdn_scan)/while/body/checkpoint/dot_general", 870, 6, 0, "convolution fusion"),
        ("ddl_gdn_fwd.1", "jit(_run)/while/body/closed_call/jvp(ddl.gdn_scan)/while/body/checkpoint/ddl_gdn_fwd", 876, 10, 0, "custom-call"),
        ("fusion.11", "jit(_run)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/ddl.gdn_out/dot_general", 886, 4, 0, "convolution fusion"),
        ("fusion.15", "jit(_run)/while/body/closed_call/jvp(ddl.attn)/ddl.sparse_select/top_k", 890, 1, 0, "loop fusion"),
        ("ddl_sparse_select.1", "jit(_run)/while/body/closed_call/jvp(ddl.attn)/ddl.sparse_select/ddl_sparse_select", 891, 2, 0, "custom-call"),
        ("ddl_flash_sparse_fwd.1", "jit(_run)/while/body/closed_call/jvp(ddl.attn)/ddl_flash_sparse_fwd", 893, 3, 0, "custom-call"),
    ]
    if not scoped:
        ops = [(n, re.sub(r"ddl\.[a-z_]+/", "", re.sub(r"jvp\(ddl\.[a-z_]+\)", "jvp()", p)),
                a, d, fl, c) for n, p, a, d, fl, c in ops]
    meta = [_f(4, _f(1, 1) + _f(2, _f(1, 1) + _f(2, "jit__run(77)"))),
            _f(4, _f(1, 2) + _f(2, _f(1, 2) + _f(2, "jit_other(5)"))),
            _f(4, _f(1, 3) + _f(2, _f(1, 3) + _f(2, "%other.1 = f32[] add()")))]
    events = []
    for i, (name, path_, a, d, fl, cat) in enumerate(ops):
        mid = 10 + i
        stats = _f(5, _stat("flops", fl)) + _f(5, _stat("hlo_category", cat))
        if path_:
            stats += _f(5, _stat("tf_op", path_))
        md = _f(1, mid) + _f(2, f"%{name} = f32[8]{{0}} fusion()") + stats
        meta.append(_f(4, _f(1, mid) + _f(2, md)))
        for k in range(3):
            events.append((k * 1000 * us + a * us, d * us, mid))
    events.append((950 * us, 20 * us, 3))  # the other program's op
    events.sort()
    ops_line = _f(2, "XLA Ops") + b"".join(
        _f(4, _f(1, mid) + _f(2, off) + _f(3, dur)) for off, dur, mid in events)
    mods_line = _f(2, "XLA Modules") + b"".join(
        _f(4, _f(1, 1) + _f(2, k * 1000 * us) + _f(3, 900 * us)) for k in range(3)
    ) + _f(4, _f(1, 2) + _f(2, 940 * us) + _f(3, 40 * us))
    plane = (
        _f(2, "/device:TPU:0") + _f(3, mods_line) + _f(3, ops_line)
        + b"".join(meta)
        + b"".join(_f(5, _f(1, i) + _f(2, _f(1, i) + _f(2, n)))
                   for n, i in STAT_IDS.items())
        + _f(6, _stat("peak_teraflops_per_second", 200.0))
        + _f(6, _stat("peak_hbm_bw_gigabytes_per_second", 800.0))
    )
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(_f(1, plane))


@pytest.fixture
def made_up(tmp_path, monkeypatch):
    """A run's scratch directory as ``run.py`` lays it out, under a temp
    directory of the test's own; the reduced dict the readers are handed."""
    monkeypatch.setattr(S.tempfile, "gettempdir", lambda: str(tmp_path))
    monkeypatch.setattr(S, "_MEMO", {})

    def make(scoped=True):
        path = str(tmp_path / "ddl_bench_x" / "trace" / "plugins" / "profile"
                   / "t" / "host.xplane.pb")
        _made_up_trace(path, scoped)
        return {"trace": T.reduce(T.load(path))}

    return make


SHARES = ("attn_dense", "mlp", "moe_dispatch", "head", "optimizer", "unscoped")


def _read(metric, m):
    return cells.layer_reader(metric)(m)


def test_shares_add_up_to_the_step_programs_own_time(made_up, capsys):
    m = made_up()
    w = m["trace"]["window_s"]
    assert w == pytest.approx(2e-3)
    got = {n: _read(n + "_device_share", m) for n in SHARES}
    us = 100.0 * 1e-6 / w  # one microsecond a window, as a share
    assert got["attn_dense"] == pytest.approx(2 * (100 + 90) * us)
    assert got["mlp"] == pytest.approx(2 * (200 + 30 + 80) * us)
    assert got["moe_dispatch"] == pytest.approx(2 * 40 * us)
    assert got["head"] == pytest.approx(2 * (20 + 70) * us)
    assert got["optimizer"] == pytest.approx(2 * 110 * us)
    # XLA's own copy and the %while's own 6 us; the other program's op
    # is nobody's.
    assert got["unscoped"] == pytest.approx(2 * (10 + 6) * us)
    assert _read("recompute_device_share", m) == pytest.approx(2 * 80 * us)
    kernels = _read("flash_device_share", m) + _read("gmm_device_share", m)
    assert kernels == pytest.approx(2 * (50 + 3 + 60) * us)
    # The linear-attention layers' three: the chain's kernels by their
    # family, what XLA runs of the recurrence under ``ddl.gdn_scan``, and
    # the three scopes outside the recurrence - together what the
    # benchmark's own table calls ``other``.
    gdn = _read("gdn_device_share", m)
    gdn_scan = _read("gdn_scan_device_share", m)
    gdn_dense = _read("gdn_dense_device_share", m)
    assert gdn == pytest.approx(2 * 10 * us)
    assert gdn_scan == pytest.approx(2 * 6 * us)
    assert gdn_dense == pytest.approx(2 * 4 * us)
    # MiniCPM-SALA's three: the fixed-decay recurrence as executed (its
    # scope and its kernels' family), the lightning blocks outside it, and
    # the block selection (its scope, the selection kernel included).
    lightning = _read("lightning_device_share", m)
    lightning_dense = _read("lightning_dense_device_share", m)
    select = _read("sparse_select_device_share", m)
    assert lightning == pytest.approx(2 * (3 + 1) * us)
    assert lightning_dense == pytest.approx(2 * (2 + 2) * us)
    assert select == pytest.approx(2 * (1 + 2) * us)
    table = S.table_of_run(m)
    other = gdn + gdn_scan + gdn_dense + lightning + lightning_dense + select
    assert other == pytest.approx(table.summary()["other"])
    assert sum(got.values()) + kernels + other == pytest.approx(
        100.0 * table.step_own_s / w, rel=1e-9)
    # ... which is every op of the window but the other program's.
    assert table.step_own_s == pytest.approx(
        m["trace"]["ops_own_time_s"] - 20e-6, rel=1e-9)
    # XLA's own counts, over the own time.
    (attn,) = [r for r in S.rows(table)
               if (r["scope"], r["pass"]) == ("ddl.attn", "forward")]
    assert attn["tflops"] == pytest.approx(4e6 / 100e-6 / 1e12)
    assert table.peak_flops == 200e12 and table.peak_bytes == 800e9
    # Parsed once a process, and said once.
    said = [l for l in capsys.readouterr().out.splitlines() if '"scopes"' in l]
    assert len(said) == 1 and '"parse_s"' in said[0]
    summary = table.summary()
    assert summary["kernels"] == pytest.approx(kernels)
    assert sum(summary[k] for k in list(S.GROUPS) + [
        "other", "kernels", "unscoped"]) == pytest.approx(summary["step_own"])
    # XLA's own kernels keep no path: their rows go by the family's name.
    assert ("ragged-dot-none", "forward") in {
        (r["scope"], r["pass"]) for r in S.rows(table)}


def test_a_stale_executable_is_refused_not_read_as_unscoped(made_up, capsys):
    m = made_up(scoped=False)
    for n in SHARES + ("recompute",):
        assert _read(n + "_device_share", m) is None
    assert "compiled before the scopes" in capsys.readouterr().out


def test_no_trace_no_number_and_another_runs_file_is_not_taken(made_up):
    m = made_up()
    assert _read("head_device_share", {"trace": None}) is None
    other = dict(m["trace"], window_s=m["trace"]["window_s"] * (1 + 2 ** -52))
    assert _read("head_device_share", {"trace": other}) is None
    assert _read("head_device_share", m) is not None


def test_a_program_without_a_scope_table_reads_nothing(made_up, monkeypatch):
    m = made_up()
    monkeypatch.setattr(S, "_program_scopes", lambda: ())
    assert _read("unscoped_device_share", m) is None


def test_operators_command_prints_the_table(capsys):
    assert S.main([RECORDED_SCOPED]) == 0
    out = capsys.readouterr().out
    assert "ddl.mlp" in out and "recompute" in out and "TFLOP/s" in out
    assert S.main([]) == 2


# -- the compile cache's key ----------------------------------------------------------


def _cache_key_of(leading_lines):
    from jax._src import cache_key, compiler
    from jax._src.lib import xla_client  # noqa: F401

    ns = {"scope": naming.scope}
    src = "\n" * leading_lines + (
        "def f(x):\n    with scope('ddl.mlp'):\n        return x * 2 + 1\n")
    exec(compile(src, "made_up.py", "exec"), ns)
    lowered = jax.jit(ns["f"]).lower(jnp.ones((4,), jnp.float32))
    backend = jax.devices()[0].client
    options = compiler.get_compile_options(num_replicas=1, num_partitions=1)
    return cache_key.get(
        lowered.compiler_ir(), np.array(jax.devices()[:1]), options, backend)


def test_the_cache_key_moves_with_the_table_and_not_with_a_line_number(monkeypatch):
    from jax._src import cache_key

    from ddl_tpu import bringup

    # A pristine hook, whatever an earlier test of this process installed.
    monkeypatch.setattr(cache_key, "custom_hook", lambda: "")
    unsalted = _cache_key_of(0)
    bringup._salt_cache_key()
    bringup._salt_cache_key()  # once is enough: the hook is not stacked
    assert cache_key.custom_hook().count("ddl.scopes=") == 1
    salted = _cache_key_of(0)
    assert salted != unsalted
    assert _cache_key_of(7) == salted  # a caller's line number is not in it
    with monkeypatch.context() as table:
        table.setattr(naming, "SCOPE_NAMES", naming.SCOPE_NAMES + ("ddl.new",))
        assert _cache_key_of(0) != salted
    assert _cache_key_of(0) == salted

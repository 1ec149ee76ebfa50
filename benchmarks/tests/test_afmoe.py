"""What the Trinity-Mini cell adds to the benchmark: the FLOP functions
against counts made by hand, the flash kernels' calls in the program's own
step against the count the roofline reader uses, the two readers, the
configuration file against the catalog row, and the reference check
inside the runner."""

import collections
import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks.lib import afmoe_flops, cells

CELL = "trinity-mini.tokens-8k"
RUN = os.path.join(cells.HERE, "run.py")
S, F = "sliding_attention", "full_attention"


def _config():
    with open(os.path.join(cells.HERE, "configs", "trinity-mini.json")) as f:
        return json.load(f)


def test_model_flops_by_hand():
    # d=8, 2 heads x 8 (attention width 16), 1 kv head, window 2, seq 4:
    # a dense layer (sliding) then an expert layer (full) holding 2 of the
    # router's 8 experts, 4 per token, one shared; vocab slice 32.
    c = {
        "hidden_size": 8, "head_dim": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "intermediate_size": 24,
        "moe_intermediate_size": 16, "num_experts": 2, "num_experts_per_tok": 4,
        "num_shared_experts": 1, "num_dense_layers": 1, "layer_types": [S, F],
        "sliding_window": 2, "vocab_size": 32, "published": {"num_experts": 8},
    }
    proj = 2 * 8 * (2 * 2 + 2 * 1) * 8 + 2 * 16 * 8  # q, gate, k, v; out
    pairs_sliding = 1 + 2 + 2 + 2  # min(i + 1, 2) over 4 queries
    pairs_full = 1 + 2 + 3 + 4
    attn = lambda pairs: 2 * 2 * 16 * pairs / 4  # noqa: E731
    dense = 3 * 2 * 8 * 24
    expert = 3 * 2 * 8 * 16
    routed = 4 * 2 / 8  # experts a token runs here at balance
    want = 3 * (
        proj + attn(pairs_sliding) + dense
        + proj + attn(pairs_full) + 2 * 8 * 8 + expert + routed * expert
        + 2 * 8 * 32
    )
    assert afmoe_flops.afmoe_flops_per_token(c, seq=4) == want
    assert afmoe_flops.held_expectation(c) == 1.0


def test_model_flops_of_the_configuration():
    """ISSUE 30's count: 2.2 GFLOP a token; 14.7 M of the 33.6 M causal
    pairs in a sliding layer; one routed expert a token at balance."""
    c = _config()
    assert afmoe_flops.attended_pairs(8192, 2048) == 14_681_088
    assert afmoe_flops.attended_pairs(8192) == 33_558_528
    assert afmoe_flops.held_expectation(c) == 1.0
    per_token = afmoe_flops.afmoe_flops_per_token(c, 8192)
    assert per_token == pytest.approx(2.2138e9, rel=1e-4)
    # Attended pairs are 25% of it, the routed experts 1.7%.
    pairs = 3 * 2 * 2 * 4096 * (4 * 14_681_088 + 33_558_528) / 8192
    assert pairs / per_token == pytest.approx(0.25, abs=0.005)


def test_flash_flops_by_hand():
    c = {"num_attention_heads": 2, "head_dim": 8, "sliding_window": 2,
         "layer_types": [S, S, F]}
    got = afmoe_flops.flash_flops_per_step(c, batch_rows=3, seq=4, remat="selective")
    a_pass = lambda pairs: pairs * 3 * 2 * 2 * 8  # noqa: E731
    assert got == {
        "ddl_flash_swa_fwd": 2 * a_pass(7) * 2 * 2,  # 2 layers, 2 passes, 2 calls
        "ddl_flash_swa_bwd_dq": 2 * a_pass(7) * 3,
        "ddl_flash_swa_bwd_dkv": 2 * a_pass(7) * 4,
        "ddl_flash_fwd": a_pass(10) * 2 * 2,
        "ddl_flash_bwd_dq": a_pass(10) * 3,
        "ddl_flash_bwd_dkv": a_pass(10) * 4,
    }
    none = afmoe_flops.flash_flops_per_step(c, 3, 4, "none")
    assert none["ddl_flash_fwd"] == a_pass(10) * 2
    # A window that covers the row runs the causal-full kernels.
    wide = afmoe_flops.flash_flops_per_step({**c, "sliding_window": 4}, 3, 4, "none")
    assert set(wide) == {"ddl_flash_fwd", "ddl_flash_bwd_dq", "ddl_flash_bwd_dkv"}
    # The cell: 16.6 TFLOP of useful attention a step.
    cell = afmoe_flops.flash_flops_per_step(_config(), 2, 8192, "selective")
    assert sum(cell.values()) == pytest.approx(16.64e12, rel=1e-3)


@pytest.mark.parametrize("remat", sorted(afmoe_flops.FLASH_CALLS_PER_LAYER))
def test_the_call_count_is_the_programs(remat, monkeypatch):
    """``FLASH_CALLS_PER_LAYER`` against the program's own train step,
    lowered for the TPU: two sliding layers and a full one."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.models import afmoe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = afmoe.AfmoeConfig(
        vocab=256, d_model=256, n_heads=2, n_kv_heads=1, head_dim=128,
        d_ff=128, d_expert=128, n_experts=8, topk=2, layer_types=(S, S, F),
        n_dense_layers=1, sliding_window=2048, held_experts=(0, 2),
        max_seq=8192, param_dtype=jnp.bfloat16, remat=remat,
    )
    params = jax.eval_shape(lambda: afmoe.init_params(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    text = jax.jit(jax.value_and_grad(
        lambda p, t: afmoe.next_token_loss(p, t, cfg)
    )).trace(params, tokens).lower(lowering_platforms=("tpu",)).as_text()
    got = collections.Counter(re.findall(r'kernel_name = "(ddl_flash_\w+)"', text))
    calls = afmoe_flops.FLASH_CALLS_PER_LAYER[remat]
    want = {}
    for prefix, layers in (("ddl_flash_swa_", 2), ("ddl_flash_", 1)):
        for kernel, n in calls.items():
            want[prefix + kernel] = layers * n
    assert dict(got) == want


# -- the readers ----------------------------------------------------------------


def measured(device_ops, programs=4, window_s=10.0, config=None):
    return {
        "chips": 1, "steps_per_window": 2, "peak_flops": 197e12,
        "config": config or _config(),
        "mix": {"batch_rows": 2, "seq": 8192},
        "trace": {
            "window_s": window_s, "device_ops": [list(o) for o in device_ops],
            "step_program_busy_s": [1.6] * programs,
        },
    }


def test_the_roofline_reader_on_a_made_up_window():
    roof = cells.layer_reader("flash_roofline_share")
    per_step = afmoe_flops.flash_flops_per_step(_config(), 2, 8192, "selective")
    ops = [("fusion", 3.0), ("ddl_flash_swa_bwd_dkv", 0.8), ("ddl_flash_swa_fwd", 0.7),
           ("ddl_flash_swa_bwd_dq", 0.5), ("ddl_flash_bwd_dkv", 0.3),
           ("ddl_flash_fwd", 0.25), ("ddl_flash_bwd_dq", 0.2), ("ragged-dot-none", 0.2)]
    # 4 programs x 2 steps of all six families in 2.75 s of their own time.
    want = 100 * 8 * sum(per_step.values()) / (2.75 * 197e12)
    assert roof(measured(ops)) == pytest.approx(want) and 20 < want < 100
    # A family outside the reduction's top ten takes its FLOPs with it.
    fewer = [o for o in ops if o[0] != "ddl_flash_bwd_dq"]
    assert roof(measured(fewer)) == pytest.approx(
        100 * 8 * (sum(per_step.values()) - per_step["ddl_flash_bwd_dq"])
        / (2.55 * 197e12)
    )
    # The traced window's first execution is cut short: counted by time.
    cut = measured(ops)
    cut["trace"]["step_program_busy_s"] = [0.8, 1.6, 1.6, 1.6]
    assert roof(cut) == pytest.approx(want * 3.5 / 4)
    # The flash share's accepted reader sums both sets by their prefix.
    assert cells.layer_reader("flash_device_share")(measured(ops)) == pytest.approx(27.5)
    assert cells.layer_reader("gmm_device_share")(measured(ops)) == pytest.approx(2.0)


def test_the_readers_find_nothing_where_there_is_nothing(monkeypatch):
    roof = cells.layer_reader("flash_roofline_share")
    assert roof({"trace": None}) is None  # a rehearsal: no device plane
    assert roof(measured([("fusion", 4.0), ("ragged-dot-none", 1.0)])) is None
    with open(os.path.join(cells.HERE, "configs", "mistral-7b-v0.3.json")) as f:
        mistral = json.load(f)  # states no layer_types
    assert roof(measured([("ddl_flash_fwd", 1.0)], config=mistral)) is None

    from benchmarks.families import afmoe

    held = cells.layer_reader("held_choice_share")
    monkeypatch.setattr(afmoe, "LAST_CHECK", None)
    assert held({"config": _config()}) is None  # the check has not run
    monkeypatch.setattr(afmoe, "LAST_CHECK", {"held_choice_share": 0.1182})
    assert held({"config": _config()}) == pytest.approx(11.82)
    assert held({"config": mistral}) is None  # another family's cell
    # A program without the model: the family cannot be imported.
    import benchmarks.families

    monkeypatch.delattr(benchmarks.families, "afmoe")
    monkeypatch.setitem(sys.modules, "benchmarks.families.afmoe", None)
    assert held({"config": _config()}) is None


def test_the_entries_name_the_layer_and_the_cell():
    bench = cells.benchmark_file()
    names = [e["name"] for e in bench["per_layer"]]
    at = names.index("flash_roofline_share")
    assert names[at : at + 2] == ["flash_roofline_share", "held_choice_share"]
    flash = next(e for e in bench["per_layer"] if e["name"] == "flash_device_share")
    roof, held = bench["per_layer"][at], bench["per_layer"][at + 1]
    assert roof["layer"] == held["layer"] == flash["layer"]
    assert (roof["moves"], roof["source"], roof["unit"]) == ("mfu", "device_trace", "%")
    assert (held["moves"], held["source"]) == ("tokens_per_s", "program_counter")
    assert roof["workloads"] == held["workloads"] == [CELL]
    assert CELL in flash["workloads"]
    # The grouped matmuls of ~1,024 rows an expert are 5% of the step and
    # among the reduction's ten largest families (PERF.md section 6, third
    # session); the roofline's count takes every choice as computed, eight
    # times too high here: the cell stays off that list.
    by_name = {e["name"]: e for e in bench["per_layer"]}
    assert by_name["gmm_device_share"]["workloads"][-1] == CELL
    assert CELL not in by_name["gmm_roofline_share"]["workloads"]
    rate = next(e for e in bench["end_to_end"] if e["name"] == "tokens_per_s")
    assert CELL in rate["workloads"]
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.mix["name"] == "tokens-8k"
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "mfu", "setup_s"}
    assert {"flash_roofline_share", "held_choice_share", "flash_device_share",
            "step_device_ms", "mfu_busy", "device_idle_share",
            "peak_hbm_GiB"} <= {m["name"] for m in cell.per_layer}


def test_every_width_is_the_catalog_rows():
    c = _config()
    row = {  # architectures.jsonl, Trinity-Mini, ``config``
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144,
        "layer_types": [S, S, S, F] * 8, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_expert_groups": 1,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_key_value_heads": 4, "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_mm": True, "vocab_size": 200192,
    }
    reduced = ["num_hidden_layers", "num_dense_layers", "layer_types",
               "num_experts", "vocab_size"]
    assert list(c["reduced"]) == reduced
    assert {k: c[k] for k in row if k not in reduced} == {
        k: v for k, v in row.items() if k not in reduced
    }
    assert c["published"] == {k: row[k] for k in reduced}
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"]) == (5, 1, 16, 25024)
    assert c["layer_types"] == [S, S, S, S, F]
    # The floors: a whole period after the dense layer, >= 8 experts, >= 1/8 vocab.
    assert c["layer_types"][c["num_dense_layers"]:] == row["layer_types"][:4]
    assert c["num_experts"] >= 8 and c["vocab_size"] * 8 >= row["vocab_size"]
    assert c["deployment"]["chips_per_layer"] * c["num_experts"] == row["num_experts"]
    assert {"attention_gate", "qk_norm", "expert_bias", "param_dtype",
            "initialisation"} <= set(c["assumed"])


def test_the_check_compares_the_mixs_window():
    import inspect

    from benchmarks.families import afmoe
    from ddl_tpu.models import afmoe as model

    # The check takes the loss of the logits it compares, in one pass:
    # the same function the model's train loss is.
    assert "next_token_cross_entropy(forward(" in inspect.getsource(
        model.next_token_loss
    )

    mix = cells.load_cell(CELL).mix
    assert afmoe.CHECK_ROWS == mix["window_rows"] == 2 * mix["batch_rows"]
    assert afmoe.PAIR_ROWS == mix["batch_rows"]  # a forward pass on a step's rows
    assert afmoe.GRAD_TOKENS > _config()["sliding_window"]  # past the band's edge


def test_the_probes_read_the_norms_jax_grad_gives():
    """One program a side, no gradient tree: the reference's leaves leave
    their layer's backward pass as sums of squares, a layer at a time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.families import afmoe
    from benchmarks.lib import afmoe_reference as reference

    cell = cells.load_cell(CELL, rehearsal=True)
    cfg = afmoe.model_config(cell.config, cell.mix)
    exact = jax.tree.map(
        lambda x: x.astype(jnp.float32), afmoe.init_params(cfg, jax.random.key(3))
    )
    row = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (1, cfg.max_seq), dtype=np.int32
    ))
    got = afmoe.check_programs(cfg)["want_norms"](exact, row)
    grads = jax.grad(reference.loss)(exact, row, afmoe.reference_config(cfg, reference))
    want = {
        jax.tree_util.keystr(path): float(jnp.sqrt(jnp.sum(g**2)))
        for path, g in jax.tree_util.tree_leaves_with_path(grads)
    }
    assert set(got) == set(want) and len(want) == 3 + 14 + 4 * 19
    for leaf, norm in want.items():
        assert got[leaf] == pytest.approx(norm, rel=1e-5, abs=1e-12), leaf


# -- the reference check inside the runner ---------------------------------------


def _run(*argv, code=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, RUN, *argv] if code is None else [sys.executable, "-c", code]
    proc = subprocess.run(cmd, cwd=cells.ROOT, env=env, capture_output=True,
                          text=True, timeout=600)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc, lines


REHEARSE = ("--workload", CELL, "--seed", "2147483659", "--seconds", "0.5",
            "--trace", "1", "--rehearsal", "cpu")


def test_the_rehearsal_holds_the_system_to_the_reference_before_it_trains():
    proc, lines = _run(*REHEARSE)
    assert proc.returncode == 0, proc.stderr[-2000:]
    tags = [ln.get("line") for ln in lines]
    check = lines[tags.index("reference_check")]
    assert tags.index("reference_check") < tags.index("weights")
    assert check["problems"] == [] and check["seed"] == 2147483659
    cell = cells.load_cell(CELL, rehearsal=True)
    # The model the window trains, not a slice of it: all five layers, the
    # configured remat, the share; every leaf's gradient but the bias's.
    assert check["layers"] == cell.config["num_hidden_layers"] == 5
    assert check["remat"] == cell.config["training"]["remat"] == "selective"
    assert check["held"] == [0, cell.config["num_experts"]]
    # ... and the router's: a share does not train it.
    assert check["grad_leaves"] == 3 + 14 + 4 * 19 - 8 and check["frozen_leaves"] == 8
    assert check["frozen_grad_norm"] == 0.0
    assert len(check["held_choice_share_by_layer"]) == 4
    assert 0.1 < check["held_choice_share"] < 0.4  # 4 of 16: 0.25 at balance
    # The traced rehearsal says which readers found data: the held share
    # comes from this run's check.
    rehearsal = lines[tags.index("rehearsal")]
    assert "held_choice_share" in rehearsal["readers_with_data"]
    assert lines[-1]["correct"] is True and lines[-1]["metrics"] == {}


def _in_the_runner(patch: str):
    """The rehearsal's command with ``patch`` run first in its process."""
    return (
        "import sys, runpy\n"
        f"sys.path.insert(0, {cells.ROOT!r})\n"
        + patch +
        f"sys.argv = {[RUN, *REHEARSE]!r}\n"
        f"runpy.run_path({RUN!r}, run_name='__main__')\n"
    )


def test_a_fault_in_the_reference_check_is_a_non_zero_exit_and_no_result():
    """The reference with rotary embeddings in its full layer too: the
    system no longer agrees with it, the run ends before a weight exists."""
    proc, lines = _run(code=_in_the_runner(
        "from benchmarks.lib import afmoe_reference as r\n"
        "layer = r._layer\n"
        "r._layer = lambda x, l, c, rr, sliding, dense: layer(x, l, c, rr, True, dense)\n"
    ))
    assert proc.returncode != 0
    assert "not the float32 reference" in proc.stderr
    tags = [ln.get("line") for ln in lines]
    assert "reference_check" in tags and "weights" not in tags
    assert not any("correct" in ln for ln in lines)


def test_a_program_without_the_model_refuses_the_cell_at_once():
    """The parent commit with this PR's benchmark files laid over it: the
    family's import fails while the runner loads the cell."""
    proc, lines = _run(code=(
        "import sys, runpy\n"
        f"sys.path.insert(0, {cells.ROOT!r})\n"
        "sys.modules['ddl_tpu.models.afmoe'] = None\n"
        f"sys.argv = {[RUN, *REHEARSE]!r}\n"
        f"runpy.run_path({RUN!r}, run_name='__main__')\n"
    ))
    assert proc.returncode != 0 and lines == []
    assert "ImportError" in proc.stderr or "ModuleNotFoundError" in proc.stderr


def test_the_limits_refuse_both_stand_ins():
    """bf16 is what the configuration states: the reference computed in
    float8_e4m3fn is outside the limits, and so is the system with the
    window ignored."""
    import jax.numpy as jnp

    from benchmarks.families import afmoe

    cell = cells.load_cell(CELL, rehearsal=True)
    cfg = afmoe.model_config(cell.config, cell.mix)
    assert cfg.sliding_window < cfg.max_seq
    as_configured = afmoe.compare_with_reference(cfg, seed=5)
    assert afmoe.problems_of(as_configured, rehearsal=True) == []
    lower = afmoe.compare_with_reference(cfg, seed=5, compute_dtype=jnp.float8_e4m3fn)
    assert lower["logits_rel_rms"] > 2 * afmoe.REHEARSAL_LOGITS_RMS_LIMIT
    assert lower["grad_norm_rel_diff"] > 2 * afmoe.REHEARSAL_GRAD_NORM_LIMIT
    ignored = afmoe.compare_with_reference(cfg, seed=5, window_ignored=True)
    assert ignored["logits_rel_rms"] > 2 * afmoe.REHEARSAL_LOGITS_RMS_LIMIT
    assert ignored["loss_rel_diff"] > 2 * afmoe.REHEARSAL_LOSS_REL_LIMIT
    for found in (lower, ignored):
        assert afmoe.problems_of(found, rehearsal=True)
        assert afmoe.problems_of(found, rehearsal=False)
    # The limits the chip is held to are tighter than the rehearsal's.
    assert afmoe.LOSS_REL_LIMIT < afmoe.REHEARSAL_LOSS_REL_LIMIT
    assert afmoe.GRAD_NORM_LIMIT < afmoe.REHEARSAL_GRAD_NORM_LIMIT
    assert afmoe.LOGITS_RMS_LIMIT < afmoe.REHEARSAL_LOGITS_RMS_LIMIT

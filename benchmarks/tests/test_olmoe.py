"""What the OLMoE cell adds to the benchmark: the two FLOP functions
against counts made by hand, the grouped-matmul calls of the program's
own step against the count the roofline reader uses, the two readers,
and the reference check inside the runner."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import cells, moe_flops

CELL = "olmoe-1b-7b.tokens-4k"
RUN = os.path.join(cells.HERE, "run.py")


def _config():
    with open(os.path.join(cells.HERE, "configs", "olmoe-1b-7b.json")) as f:
        return json.load(f)


def test_model_flops_by_hand():
    # One layer, tiny: d=8, 2 heads x 4, 2 kv heads, 4 experts of width 16,
    # 2 per token, vocab=32, seq=4.
    c = {
        "hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
        "num_key_value_heads": 2, "intermediate_size": 16, "num_experts": 4,
        "num_experts_per_tok": 2, "num_hidden_layers": 1, "vocab_size": 32,
    }
    qkv = 3 * (2 * 8 * 8)
    out = 2 * 8 * 8
    attn = (2 * 4 * 2 * 4 + 2 * 4 * 2 * 4) / 2  # scores + attn @ v, causal half
    router = 2 * 8 * 4
    experts = 2 * (3 * 2 * 8 * 16)  # the 2 ACTIVE experts, not the 4
    head = 2 * 8 * 32
    assert moe_flops.moe_decoder_flops_per_token(c, seq=4) == 3 * (
        qkv + out + attn + router + experts + head
    )


def test_model_flops_of_the_configuration():
    """ISSUE 26's count: 1.53 GFLOP a token at depth 2, the experts 40%."""
    c = _config()
    per_token = moe_flops.moe_decoder_flops_per_token(c, 4096)
    assert per_token == pytest.approx(1.5257e9, rel=1e-4)
    experts = 3 * 2 * 8 * 3 * 2 * 2048 * 1024
    assert experts / per_token == pytest.approx(0.396, abs=0.002)
    assert c["num_hidden_layers"] == 2 and c["published"]["num_hidden_layers"] == 16
    c["num_hidden_layers"] = 3
    assert moe_flops.moe_decoder_flops_per_token(c, 4096) == pytest.approx(
        1.979e9, rel=1e-3
    )


def test_grouped_matmul_flops_by_hand():
    c = {"hidden_size": 8, "intermediate_size": 16, "num_experts_per_tok": 2,
         "num_hidden_layers": 3}
    one_call = 2 * (5 * 2) * 8 * 16  # 5 tokens x 2 slots rows, 8 x 16 a row
    assert moe_flops.gmm_flops_per_step(c, 5, "none") == 3 * 9 * one_call
    assert moe_flops.gmm_flops_per_step(c, 5, "selective") == 3 * 12 * one_call
    # The cell: 12 calls x 2 layers x 2 x 131,072 rows x 2048 x 1024.
    assert moe_flops.gmm_flops_per_step(_config(), 4 * 4096, "selective") == (
        24 * 2 * 131072 * 2048 * 1024
    )


@pytest.mark.parametrize("remat", sorted(moe_flops.GMM_CALLS_PER_LAYER))
def test_the_call_count_is_the_programs(remat):
    """``GMM_CALLS_PER_LAYER`` against the program's own train step,
    lowered for the TPU (on the CPU a ragged dot is expanded at lowering
    and leaves no instruction to count).  ``tests/test_tpu_compile.py``
    holds the same count to the TPU-COMPILED step: XLA eliminates none."""
    import jax
    import jax.numpy as jnp

    from ddl_tpu.models import moe

    layers = 2
    cfg = moe.MoeConfig(
        vocab=64, d_model=32, n_layers=layers, n_heads=2, n_kv_heads=2,
        d_ff=16, n_experts=4, topk=2, max_seq=16, qk_norm=True,
        norm_topk_prob=False, router_aux_all_slots=True, router_z_weight=0.001,
        remat=remat, attn_impl="dense",
    )
    params = moe.init_params(cfg, jax.random.key(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    step = jax.jit(jax.value_and_grad(lambda p: moe.next_token_loss(p, tokens, cfg)))
    text = step.trace(params).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count('"chlo.ragged_dot"(') == (
        layers * moe_flops.GMM_CALLS_PER_LAYER[remat]
    )


# -- the readers ----------------------------------------------------------------


def measured(device_ops, programs=4, window_s=10.0, config=None):
    return {
        "chips": 1, "steps_per_window": 2, "peak_flops": 197e12,
        "config": config or _config(),
        "mix": {"batch_rows": 4, "seq": 4096},
        "trace": {
            "window_s": window_s, "device_ops": [list(o) for o in device_ops],
            "step_program_busy_s": [2.4] * programs,
        },
    }


def test_the_readers_on_a_made_up_window():
    share = cells.layer_reader("gmm_device_share")
    roof = cells.layer_reader("gmm_roofline_share")
    m = measured([("fusion", 4.0), ("ragged-dot-none", 3.0),
                  ("ddl_flash_fwd", 1.0), ("ragged-dot-metadata", 0.2)])
    assert share(m) == pytest.approx(32.0)
    # 4 programs x 2 steps x 24 calls x 2 x 131072 x 2048 x 1024 FLOP in 3.2 s.
    flops = 4 * 2 * 24 * 2 * 131072 * 2048 * 1024
    assert roof(m) == pytest.approx(100 * flops / (3.2 * 197e12))
    assert roof(m) < 100
    # The traced window's first execution is cut short by the profiler's
    # start: it counts for the part of it that ran.
    cut = measured([("ragged-dot-none", 3.0)], programs=4)
    cut["trace"]["step_program_busy_s"] = [1.2, 2.4, 2.4, 2.4]
    assert roof(cut) == pytest.approx(roof(measured([("ragged-dot-none", 3.0)])) * 3.5 / 4)
    # A kernel of the repo's own would be read under its name.
    assert share(measured([("ddl_gmm_fwd", 1.0)])) == pytest.approx(10.0)


def test_the_readers_find_nothing_where_there_is_nothing():
    for name in ("gmm_device_share", "gmm_roofline_share"):
        read = cells.layer_reader(name)
        assert read({"trace": None}) is None  # a rehearsal: no device plane
        assert read(measured([("fusion", 4.0), ("ddl_flash_fwd", 1.0)])) is None
    dense = {k: v for k, v in _config().items() if k != "num_experts"}
    assert cells.layer_reader("gmm_roofline_share")(
        measured([("ragged-dot-none", 3.0)], config=dense)
    ) is None


def test_the_entries_name_the_layer_and_the_cell():
    bench = cells.benchmark_file()
    names = [e["name"] for e in bench["per_layer"]]
    assert names[-2:] == ["gmm_device_share", "gmm_roofline_share"]
    flash = next(e for e in bench["per_layer"] if e["name"] == "flash_device_share")
    for e in bench["per_layer"][-2:]:
        assert e["layer"] == flash["layer"] and e["moves"] == "mfu"
        assert e["unit"] == "%" and e["workloads"] == [CELL]
    # The cell runs the Mistral cell's flash kernels: the accepted reader
    # finds them, so the cell is on its list (appended, like the rate's).
    assert flash["workloads"][-1] == CELL
    rate = next(e for e in bench["end_to_end"] if e["name"] == "tokens_per_s")
    assert rate["workloads"] == ["mistral-7b.tokens-4k", CELL]
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.mix["name"] == "tokens-4k"
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "mfu", "setup_s"}


def test_every_width_is_the_catalog_rows():
    c = _config()
    assert {k: c[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "num_experts", "num_experts_per_tok",
        "vocab_size", "max_position_embeddings", "rope_theta", "rms_norm_eps",
        "norm_topk_prob", "tie_word_embeddings", "attention_bias", "clip_qkv",
        "rope_scaling", "hidden_act", "model_type",
    )} == {
        "hidden_size": 2048, "intermediate_size": 1024,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8, "vocab_size": 50304,
        "max_position_embeddings": 4096, "rope_theta": 10000,
        "rms_norm_eps": 1e-05, "norm_topk_prob": False,
        "tie_word_embeddings": False, "attention_bias": False,
        "clip_qkv": None, "rope_scaling": None, "hidden_act": "silu",
        "model_type": "olmoe",
    }
    assert list(c["reduced"]) == ["num_hidden_layers"]
    assert {"head_dim", "router_aux_loss_coef", "router_z_loss_coef",
            "output_router_logits"} <= set(c["assumed"])


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
            "--trace", "0", "--rehearsal", "cpu")


def test_the_rehearsal_holds_the_system_to_the_reference_before_it_trains():
    proc, lines = _run(*REHEARSE)
    assert proc.returncode == 0, proc.stderr[-2000:]
    tags = [ln.get("line") for ln in lines]
    check = lines[tags.index("reference_check")]
    assert tags.index("reference_check") < tags.index("weights")
    assert check["problems"] == [] and check["seed"] == 2147483659
    from benchmarks.families import olmoe

    assert check["agree_share"] >= olmoe.MIN_AGREE_SHARE
    assert check["logits_rel_rms"] <= olmoe.LOGITS_RMS_LIMIT
    assert check["loss_rel_diff"] <= olmoe.REHEARSAL_LOSS_REL_LIMIT
    assert check["grad_norm_rel_diff"] <= olmoe.REHEARSAL_GRAD_NORM_LIMIT
    assert check["expert_load_max_over_mean"] >= 1.0
    # The model the window trains, not a slice of it: both layers, the
    # configured remat, the mix's batch; every leaf's gradient.
    cell = cells.load_cell(CELL, rehearsal=True)
    assert check["layers"] == cell.config["num_hidden_layers"] == 2
    assert check["remat"] == cell.config["training"]["remat"] == "selective"
    assert check["rows"] == olmoe.CHECK_ROWS == cells.load_cell(CELL).mix["batch_rows"]
    assert check["grad_leaves"] == 3 + 12 * check["layers"]
    assert lines[-1]["correct"] is True and lines[-1]["metrics"] == {}


def test_a_fault_in_the_reference_check_is_a_non_zero_exit_and_no_result():
    """The reference without its rotary embedding: the system no longer
    agrees with it, the run ends before a weight exists."""
    proc, lines = _run(code=_in_the_runner(
        "from benchmarks.lib import olmoe_reference\n"
        "olmoe_reference._rope = lambda x, theta: x\n"
    ))
    assert proc.returncode != 0
    assert "not the float32 reference" in proc.stderr
    tags = [ln.get("line") for ln in lines]
    assert "reference_check" in tags and "weights" not in tags
    assert not any("correct" in ln for ln in lines)


def _in_the_runner(patch: str):
    """The rehearsal's command with ``patch`` run first in its process."""
    return (
        "import sys, runpy\n"
        f"sys.path.insert(0, {cells.ROOT!r})\n"
        + patch +
        f"sys.argv = {[RUN, *REHEARSE]!r}\n"
        f"runpy.run_path({RUN!r}, run_name='__main__')\n"
    )


def test_a_trainer_that_skips_its_update_reads_correct_false():
    """``loss_tolerance`` is tight enough to see the optimizer: the
    second step's loss is taken on the first step's update, so a Trainer
    that throws its updates away leaves the plain loop's first-window
    loss by more than the tolerance."""
    proc, lines = _run(code=_in_the_runner(
        "import optax\n"
        "from ddl_tpu import trainer\n"
        "init = trainer.Trainer.__init__\n"
        "def skipping(self, *a, optimizer, **kw):\n"
        "    init(self, *a, optimizer=optax.chain(optimizer, optax.scale(0.0)), **kw)\n"
        "trainer.Trainer.__init__ = skipping\n"
    ))
    assert proc.returncode == 0, proc.stderr[-2000:]  # the run ends, its verdict is the line's
    assert lines[-1]["correct"] is False
    steady = next(ln for ln in lines if ln.get("line") == "steady")
    assert any("first-window loss" in p for p in steady["problems"]), steady["problems"]
    tol = _config()["loss_tolerance"]["relative"]
    assert steady["loss_rel_diff"] > tol


def test_a_fault_in_the_backward_pass_alone_is_refused(monkeypatch):
    """The forward pass as it is and d loss / d w_down twice too large:
    the logits and the loss agree with the reference, the gradient check
    does not."""
    import jax

    from benchmarks.families import olmoe
    from ddl_tpu.models import moe

    @jax.custom_vjp
    def too_steep(w):
        return w

    too_steep.defvjp(lambda w: (w, None), lambda _, g: (2.0 * g,))
    sound = moe.next_token_loss

    def faulty(params, tokens, cfg, **kw):
        layers = [{**lyr, "w_down": too_steep(lyr["w_down"])}
                  for lyr in params["layers"]]
        return sound({**params, "layers": layers}, tokens, cfg, **kw)

    cell = cells.load_cell(CELL, rehearsal=True)
    cfg = olmoe.model_config(cell.config, cell.mix)
    monkeypatch.setattr(moe, "next_token_loss", faulty)
    seed = int(REHEARSE[3])  # the rehearsal's: sound, it passes every limit
    found = olmoe.compare_with_reference(cfg, seed)
    assert found["logits_rel_rms"] <= olmoe.LOGITS_RMS_LIMIT
    assert found["loss_rel_diff"] <= olmoe.REHEARSAL_LOSS_REL_LIMIT
    assert "w_down" in found["grad_norm_worst_leaf"]
    assert found["grad_norm_rel_diff"] > 2 * olmoe.REHEARSAL_GRAD_NORM_LIMIT
    with pytest.raises(SystemExit, match="differs in norm"):
        olmoe.reference_check(cfg, seed)


def test_the_limit_refuses_the_next_precision_down():
    """bf16 is what the configuration states; the reference computed in
    float8_e4m3fn, three bits of mantissa, is outside the limit."""
    import jax.numpy as jnp

    from benchmarks.families import olmoe

    cell = cells.load_cell(CELL, rehearsal=True)
    cfg = olmoe.model_config(cell.config, cell.mix)
    as_configured = olmoe.compare_with_reference(cfg, seed=5)
    lower = olmoe.compare_with_reference(cfg, seed=5, compute_dtype=jnp.float8_e4m3fn)
    assert as_configured["logits_rel_rms"] <= olmoe.LOGITS_RMS_LIMIT
    assert lower["logits_rel_rms"] > 2 * olmoe.LOGITS_RMS_LIMIT
    assert lower["agree_share"] < olmoe.MIN_AGREE_SHARE
    assert lower["grad_norm_rel_diff"] > 2 * olmoe.GRAD_NORM_LIMIT
    # The limits the chip is held to are tighter than the rehearsal's.
    assert olmoe.LOSS_REL_LIMIT < olmoe.REHEARSAL_LOSS_REL_LIMIT
    assert olmoe.GRAD_NORM_LIMIT < olmoe.REHEARSAL_GRAD_NORM_LIMIT

"""What the LFM2-24B-A2B cell adds to the benchmark: the FLOP and byte
functions against counts made by hand, the three readers on a made-up
window, the entries, the configuration file against the catalog row, and
the reference check inside the runner - the rehearsal, a fault planted in
it, a program without the model."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import cells, lfm2_flops

CELL = "lfm2-24b-a2b.tokens-8k"
RUN = os.path.join(cells.HERE, "run.py")
CONV, FULL = lfm2_flops.CONV, lfm2_flops.FULL


def _config():
    with open(os.path.join(cells.HERE, "configs", "lfm2-24b-a2b.json")) as f:
        return json.load(f)


# -- FLOPs and bytes ----------------------------------------------------------------


def test_model_flops_by_hand():
    # d=8, 2 query heads over 1 key head of 4, 3 taps, seq 4: a dense conv
    # layer then an attention layer holding 2 of the router's 8 experts, 4 a
    # token; vocab slice 32.
    c = {
        "hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "conv_L_cache": 3, "intermediate_size": 24, "moe_intermediate_size": 16,
        "num_experts": 2, "num_experts_per_tok": 4, "num_dense_layers": 1,
        "layer_types": [CONV, FULL], "vocab_size": 32,
        "published": {"num_experts": 8},
    }
    conv = 2 * 8 * 24 + 2 * 8 * 8 + 2 * 3 * 8
    pairs = 1 + 2 + 3 + 4
    attn = 2 * 8 * (8 + 4 + 4 + 8) + 2 * 2 * 8 * pairs / 4
    dense = 3 * 2 * 8 * 24
    routed = 2 * 8 * 8 + (4 * 2 / 8) * 3 * 2 * 8 * 16
    want = 3 * (conv + dense + attn + routed + 2 * 8 * 32)
    assert lfm2_flops.lfm2_flops_per_token(c, seq=4) == want
    assert lfm2_flops.held_experts_per_token(c) == 1.0


def test_model_flops_of_the_configuration():
    """ISSUE 43's counts a token at 8,192: ~600 MFLOP forward, ~1.8 GFLOP a
    step; the seven conv blocks ~39%, the two attention layers ~18%, the one
    dense SwiGLU 24%, the held experts ~13%, the head 6%."""
    c = _config()
    assert lfm2_flops.causal_pairs(8192) == 33_558_528
    assert lfm2_flops.held_experts_per_token(c) == 0.5
    total = lfm2_flops.lfm2_flops_per_token(c, 8192)
    assert total == pytest.approx(1.80e9, rel=0.01) and total / 3 == pytest.approx(
        0.60e9, rel=0.01)
    d = 2048
    conv = 7 * (8 * d * d + 2 * 3 * d)
    assert 7 * 8 * d * d / 7 == 33_554_432  # ISSUE 43's 33.6 M a conv block
    attn = 2 * (2 * d * (2 * d + 2 * 512) + 4 * d * 33_558_528 / 8192)
    dense = 6 * d * 11776
    experts = 8 * (2 * d * 64 + 0.5 * 6 * d * 1536)
    head = 2 * d * 8192
    assert 3 * (conv + attn + dense + experts + head) == pytest.approx(total)
    shares = [round(100 * x / (total / 3)) for x in (conv, attn, dense, experts, head)]
    assert shares == [39, 18, 24, 13, 6]


def test_the_convolutions_floor_by_hand():
    c = _config()
    assert lfm2_flops.shortconv_bytes(c) == {"fwd": 16384.0, "bwd": 28672.0}
    per_step = lfm2_flops.shortconv_least_seconds_per_step(
        c, 2, 8192, "selective", 819e9)
    tokens_layers = 2 * 8192 * 7
    assert per_step == {
        "fwd": 2 * tokens_layers * 16384 / 819e9, "bwd": tokens_layers * 28672 / 819e9}
    assert sum(per_step.values()) == pytest.approx(8.6e-3, rel=0.01)  # a step
    none = lfm2_flops.shortconv_least_seconds_per_step(c, 2, 8192, "none", 819e9)
    assert none["fwd"] == per_step["fwd"] / 2 and none["bwd"] == per_step["bwd"]


# -- the readers --------------------------------------------------------------------


class _Table:
    """What the readers ask of ``scopes.Table``."""

    def __init__(self, own, window_s=10.0):
        self.own, self.window_s = own, window_s

    def seconds(self, select):
        return sum(s for key, s in self.own.items() if select(*key))


OWN = {
    ("ddl.shortconv", "ddl.shortconv", "forward", "fusion"): 0.3,
    ("ddl.shortconv", "ddl.shortconv", "recompute", "fusion"): 0.3,
    ("ddl.shortconv", "ddl.shortconv", "backward", "fusion"): 0.6,
    ("ddl.shortconv_proj", "ddl.shortconv_proj", "forward", "convolution fusion"): 0.5,
    ("ddl.shortconv_proj", "ddl.shortconv_proj", "backward", "convolution fusion"): 1.0,
    ("ddl.shortconv_out", "ddl.shortconv_out", "forward", "convolution fusion"): 0.2,
    ("ddl.attn", "ddl_flash_fwd", "forward", "ddl_flash_fwd"): 0.5,
    ("ddl.mlp", "ddl.mlp", "forward", "fusion"): 4.0,
    (None, None, "forward", "copy"): 0.1,
}


def measured(table, programs=4, config=None):
    return {
        "chips": 1, "steps_per_window": 2, "peak_flops": 197e12,
        "config": config or _config(), "mix": {"batch_rows": 2, "seq": 8192},
        "trace": {"window_s": 10.0, "step_program_busy_s": [2.4] * programs},
        "_table": table,
    }


@pytest.fixture
def tables(monkeypatch):
    from benchmarks.lib import scopes

    monkeypatch.setattr(scopes, "table_of_run", lambda m: m.get("_table"))


def test_the_three_readers_on_a_made_up_window(tables):
    m = measured(_Table(OWN))
    share = cells.layer_reader("shortconv_device_share")
    dense = cells.layer_reader("shortconv_dense_device_share")
    roof = cells.layer_reader("shortconv_roofline_share")
    assert share(m) == pytest.approx(100 * 1.2 / 10.0)
    assert dense(m) == pytest.approx(100 * 1.7 / 10.0)
    per_step = lfm2_flops.shortconv_least_seconds_per_step(
        _config(), 2, 8192, "selective", 819e9)
    want = 100 * 8 * sum(per_step.values()) / 1.2  # 4 programs x 2 steps
    assert roof(m) == pytest.approx(want) and 3 < want < 100
    cut = measured(_Table(OWN))  # the window's first execution is cut short
    cut["trace"]["step_program_busy_s"] = [1.2, 2.4, 2.4, 2.4]
    assert roof(cut) == pytest.approx(want * 3.5 / 4)


def test_the_roofline_share_does_not_see_what_implements_the_convolution(tables):
    """The same seconds in a kernel of the repo's own read the same share;
    a faster whole raises it: the floor is of the work."""
    roof = cells.layer_reader("shortconv_roofline_share")
    share = cells.layer_reader("shortconv_device_share")
    before = measured(_Table(OWN))
    kernel = {
        (k[0], "ddl_shortconv_fwd", k[2], "ddl_shortconv_fwd")
        if k[0] == "ddl.shortconv" and k[2] != "backward" else k: v
        for k, v in OWN.items()
    }
    assert roof(measured(_Table(kernel))) == pytest.approx(roof(before))
    assert share(measured(_Table(kernel))) == pytest.approx(share(before))
    faster = dict(OWN)
    faster[("ddl.shortconv", "ddl.shortconv", "backward", "fusion")] = 0.2
    assert roof(measured(_Table(faster))) == pytest.approx(roof(before) * 1.2 / 0.8)


def test_the_readers_find_nothing_where_there_is_nothing(tables):
    readers = [cells.layer_reader(n) for n in (
        "shortconv_device_share", "shortconv_dense_device_share",
        "shortconv_roofline_share")]
    for read in readers:
        assert read({"trace": None}) is None  # a rehearsal: no device plane
        assert read(measured(None)) is None  # a trace without a scope table
    # A program without the scopes (another family's cell, or the parent):
    others = _Table({k: v for k, v in OWN.items()
                     if not (k[0] or "").startswith("ddl.shortconv")})
    for read in readers:
        assert read(measured(others)) is None
    with open(os.path.join(cells.HERE, "configs", "trinity-mini.json")) as f:
        trinity = json.load(f)  # another family's configuration
    assert readers[2](measured(_Table(OWN), config=trinity)) is None


# -- the entries --------------------------------------------------------------------


def _run_of(names, wanted):
    """Where ``wanted`` stands in ``names`` as a contiguous run (a later PR
    may append behind it: a tail is not compared)."""
    start = names.index(wanted[0])
    assert names[start : start + len(wanted)] == list(wanted)
    return start


APPENDED_TO = (
    "flash_device_share", "attn_dense_device_share", "mlp_device_share",
    "moe_dispatch_device_share", "head_device_share",
    "optimizer_device_share", "recompute_device_share", "unscoped_device_share",
)


def test_the_entries_name_the_layer_and_the_cell():
    bench = cells.benchmark_file()
    new = ["shortconv_device_share", "shortconv_dense_device_share",
           "shortconv_roofline_share"]
    _run_of([e["name"] for e in bench["per_layer"]], new)
    by_name = {e["name"]: e for e in bench["per_layer"]}
    for name, better in zip(new, ("lower", "lower", "higher")):
        e = by_name[name]
        assert (e["layer"], e["moves"], e["source"], e["unit"], e["better"]) == (
            by_name["flash_device_share"]["layer"], "mfu", "device_trace", "%", better)
        assert e["workloads"] == [CELL]
    at = _run_of([w["name"] for w in bench["workloads"]],
                 ["minicpm-sala.tokens-16k", CELL])
    entry = bench["workloads"][at + 1]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "lfm2-24b-a2b", "tokens-8k", 1)
    assert all(len(x["why"]) <= 200 for x in bench["workloads"] + bench["configs"])
    _run_of([c["name"] for c in bench["configs"]], ["minicpm-sala", "lfm2-24b-a2b"])
    config = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    assert config["reduced"] == list(_config()["reduced"])
    assert config["source"] == _config()["source"]
    for name in APPENDED_TO:
        assert CELL in by_name[name]["workloads"], name
    # ~1,024 rows a held expert: the grouped matmuls run, but an accepted
    # test pins Trinity-Mini as the last of gmm_device_share's cells
    # (benchmarks/tests/test_afmoe.py, not this PR's to edit), so the cell
    # stays off that list (PERF.md section 7), and benchmarks/tests/
    # test_moe_overflow.py pins moe_overflow_device_share's to the two older
    # share cells; held_choice_share's reader knows one family; the flash
    # roofline's count another's head size.
    for other in ("gmm_device_share", "gmm_roofline_share", "flash_roofline_share",
                  "held_choice_share", "moe_overflow_device_share"):
        assert CELL not in by_name[other]["workloads"]
    rate = next(e for e in bench["end_to_end"] if e["name"] == "tokens_per_s")
    assert CELL in rate["workloads"]
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.mix["name"] == "tokens-8k"
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s", "mfu", "setup_s"}
    assert set(new) | set(APPENDED_TO) | {
        "step_device_ms", "mfu_busy", "device_idle_share", "peak_hbm_GiB",
    } <= {m["name"] for m in cell.per_layer}


def test_every_width_is_the_catalog_rows():
    c = _config()
    period = [CONV, CONV, FULL, CONV]
    row = {  # architectures.jsonl, LFM2-24B-A2B, ``config``
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "layer_types": period * 10,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
        "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    }
    reduced = ["num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
               "vocab_size"]
    assert list(c["reduced"]) == reduced
    assert {k: c[k] for k in row if k not in reduced} == {
        k: v for k, v in row.items() if k not in reduced
    }
    assert c["published"] == {k: row[k] for k in reduced}
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"]) == (9, 1, 8, 8192)
    # one leading dense conv layer, then the published layers 2-9: two whole
    # periods full_attention, conv, conv, conv
    assert c["layer_types"] == [CONV] + row["layer_types"][2:10]
    assert row["layer_types"][2:10] == [FULL, CONV, CONV, CONV] * 2
    assert c["published"]["vocab_size"] == 8 * c["vocab_size"]
    assert c["published"]["num_experts"] == 8 * c["num_experts"]
    assert c["deployment"]["chips_per_layer"] == 8
    assert {"tie_word_embeddings", "conv_split_order", "conv_activation", "qk_norm",
            "route_eps", "expert_bias", "param_dtype", "initialisation",
            "router_training", "learning_rate"} <= set(c["assumed"])
    assert c["training"] == {
        "optimizer": "adamw", "learning_rate": 3e-5, "param_dtype": "bfloat16",
        "remat": "selective", "attn_impl": "auto",
    }
    for key in ("departures", "notes", "guarantees", "loss_tolerance", "rehearsal"):
        assert c[key]
    assert set(c["guarantees"]) == {
        "delivery", "no_fallback", "isolation", "steady", "arithmetic", "dropless",
        "reference"}


def test_the_check_compares_the_mixs_window():
    from benchmarks.families import lfm2_moe

    mix = cells.load_cell(CELL).mix
    assert (mix["seq"], mix["batch_rows"], mix["window_rows"]) == (8192, 2, 4)
    assert (lfm2_moe.CHECK_ROWS, lfm2_moe.PAIR_ROWS) == (4, 2)
    assert lfm2_moe.GRAD_TOKENS == 3072 <= mix["seq"]
    for name, loose in lfm2_moe.REHEARSAL.items():
        tight = getattr(lfm2_moe, name)
        # The limits the chip is held to are no looser than the rehearsal's.
        assert tight >= loose if name == "MIN_AGREE_SHARE" else tight <= loose, name


# -- the runner -----------------------------------------------------------------------


def _run(*args, code=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-c", code] if code else [sys.executable, RUN, *args]
    proc = subprocess.run(cmd, cwd=cells.ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc, lines


REHEARSE = ("--workload", CELL, "--seed", "2147483659", "--seconds", "0.5",
            "--trace", "1", "--rehearsal", "cpu")


def test_the_rehearsal_holds_the_system_to_the_reference_before_it_trains():
    """``Trainer.fit(window_stream=True, mode="process")`` of the cell at
    its rehearsal size on the CPU, the check first; no metric is printed."""
    proc, lines = _run(*REHEARSE)
    assert proc.returncode == 0, proc.stderr[-2000:]
    tags = [ln.get("line") for ln in lines]
    check = lines[tags.index("reference_check")]
    assert tags.index("reference_check") < tags.index("weights")
    assert check["problems"] == [] and check["seed"] == 2147483659
    cell = cells.load_cell(CELL, rehearsal=True)
    # The model the window trains, not a slice of it: every layer, the
    # configured remat, the share; every leaf's gradient but the bias's
    # and the router's (a share does not train it).
    assert check["layers"] == cell.config["num_hidden_layers"] == 5
    assert check["remat"] == cell.config["training"]["remat"] == "selective"
    assert check["held"] == [0, cell.config["num_experts"]]
    # embed + final_norm; a dense conv layer 8, an attention expert layer 13,
    # three conv expert layers 10 each; less a bias and a router a layer
    assert check["grad_leaves"] == 2 + 8 + 13 + 3 * 10 - 8
    assert check["frozen_leaves"] == 8 and check["frozen_grad_norm"] == 0.0
    assert len(check["held_choice_share_by_layer"]) == 4
    assert 0.1 < check["held_choice_share"] < 0.4  # 4 of 16: 0.25 at balance
    assert 0 < check["update_rel_diff"] < 0.7 and check["update_sign_agreement"] > 0.9
    assert check["logits_rel_rms_worst_position"] >= check["logits_rel_rms_median_position"]
    steady = lines[tags.index("steady")]
    assert steady["problems"] == [] and steady["loss_rel_diff"] <= 1e-4
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


def test_a_planted_fault_is_a_non_zero_exit_and_no_result():
    """The system with its taps one position early (an input from the
    future): it no longer agrees with the reference, and the run ends
    before a weight exists."""
    proc, lines = _run(code=_in_the_runner(
        "from benchmarks.families import lfm2_moe as f\n"
        "compare = f.compare_with_reference\n"
        "f.compare_with_reference = lambda cfg, seed: compare(cfg, seed, "
        "fault='taps_shifted')\n"
    ))
    assert proc.returncode != 0
    assert "not the float32 reference" in proc.stderr
    tags = [ln.get("line") for ln in lines]
    assert "reference_check" in tags and "weights" not in tags
    assert not any("correct" in ln for ln in lines)


def test_a_corrupted_window_reads_correct_false_in_rehearsal():
    proc, lines = _run(*REHEARSE[:-4], "--trace", "0", "--rehearsal", "cpu",
                       "--fault", "alter-row")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert lines[-1]["correct"] is False and lines[-1]["failed"] >= 1
    assert lines[-1]["metrics"] == {}


def test_a_program_without_the_model_refuses_the_cell_at_once():
    """The parent commit with this PR's benchmark files laid over it: the
    family's import fails while the runner loads the cell."""
    proc, lines = _run(code=(
        "import sys, runpy\n"
        f"sys.path.insert(0, {cells.ROOT!r})\n"
        "sys.modules['ddl_tpu.models.lfm2_moe'] = None\n"
        f"sys.argv = {[RUN, *REHEARSE]!r}\n"
        f"runpy.run_path({RUN!r}, run_name='__main__')\n"
    ))
    assert proc.returncode != 0 and lines == []
    assert "ImportError" in proc.stderr or "ModuleNotFoundError" in proc.stderr


def test_the_limits_refuse_every_stand_in():
    """bf16 is what the configuration states: the reference computed in
    float8_e4m3fn is outside the limits, and so is the system with the taps
    shifted, the C gate dropped or the head untied; a step whose update is
    thrown away reads exactly 1."""
    import jax.numpy as jnp

    from benchmarks.families import lfm2_moe

    cell = cells.load_cell(CELL, rehearsal=True)
    cfg = lfm2_moe.model_config(cell.config, cell.mix)
    as_configured = lfm2_moe.compare_with_reference(cfg, seed=5)
    assert lfm2_moe.problems_of(as_configured, rehearsal=True) == []
    stand_ins = [dict(compute_dtype=jnp.float8_e4m3fn)] + [
        dict(fault=fault) for fault in lfm2_moe.FAULTS if fault != "skipped_update"
    ]
    for kw in stand_ins:
        found = lfm2_moe.compare_with_reference(cfg, seed=5, **kw)
        assert len(lfm2_moe.problems_of(found, rehearsal=True)) >= 3, kw
        assert lfm2_moe.problems_of(found, rehearsal=False)
    skipped = lfm2_moe.compare_with_reference(
        cfg, seed=5, fault="skipped_update", parts=("gradients",))
    assert skipped["update_rel_diff"] == 1.0 and skipped["update_norm_ratio"] == 0.0
    assert len(lfm2_moe.problems_of(skipped, rehearsal=True)) == 1

"""The FLOP functions against a count made by hand."""

import json
import os

import pytest

from benchmarks.lib import cells, flops, peaks


def _config(name):
    with open(os.path.join(cells.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_decoder_flops_by_hand():
    # One layer, tiny: d=8, 2 heads x 4, 1 kv head, ff=16, vocab=32, seq=4.
    c = {
        "hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "intermediate_size": 16,
        "num_hidden_layers": 1, "vocab_size": 32,
    }
    q = 2 * 8 * 8  # x @ wq: 8 -> 2 heads x 4
    kv = 2 * (2 * 8 * 4)  # x @ wk, x @ wv: 8 -> 1 head x 4
    out = 2 * 8 * 8
    # scores: each token against seq keys, 2 heads x 4 dims, 2 FLOPs a MAC,
    # and attn @ v the same; the causal half of both.
    attn = (2 * 4 * 2 * 4 + 2 * 4 * 2 * 4) / 2
    mlp = 3 * (2 * 8 * 16)
    head = 2 * 8 * 32
    assert flops.decoder_flops_per_token(c, seq=4) == 3 * (q + kv + out + attn + mlp + head)


def test_vit_flops_by_hand():
    # One layer: 8x8 image, patch 4 -> 4 tokens of 4*4*3 = 48; d=8, 2 heads,
    # mlp 16, 10 classes.
    c = {
        "hidden_size": 8, "num_attention_heads": 2, "intermediate_size": 16,
        "num_hidden_layers": 1, "image_size": 8, "patch_size": 4,
        "num_channels": 3, "num_labels": 10,
    }
    tokens = 4
    embed = tokens * 2 * 48 * 8
    qkvo = tokens * 4 * (2 * 8 * 8)
    attn = tokens * 2 * (2 * tokens * 8)  # scores + attn@v over EVERY position
    mlp = tokens * 2 * (2 * 8 * 16)
    head = 2 * 8 * 10
    assert flops.vit_flops_per_image(c) == 3 * (embed + qkvo + attn + mlp + head)


def test_published_sizes_give_the_known_totals():
    m = _config("mistral-7b-v0.3")
    per_token = flops.decoder_flops_per_token(m, seq=4096)
    # 6 N for the matmul parameters plus the attention term.
    layer_params = 4096 * (32 + 16) * 128 + 32 * 128 * 4096 + 3 * 4096 * 14336
    n = m["num_hidden_layers"] * layer_params + 4096 * 32768
    attn = m["num_hidden_layers"] * 3 * (2 * 2 * 4096 * 32 * 128 / 2)
    assert per_token == pytest.approx(6 * n + attn)
    v = _config("vit-b16")
    assert flops.vit_flops_per_image(v) == pytest.approx(104.8e9, rel=0.01)


def test_an_unknown_device_is_an_error_not_a_default():
    assert peaks.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(LookupError):
        peaks.peak_flops("cpu")

"""The operations-per-token function against hand-worked numbers."""

import json
import os

import pytest

from chipbench.lib.flops import matmul_params, train_flops_per_token
from chipbench.lib.peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_smollm2_360m():
    cfg = _config("smollm2-360m")
    # a layer: q,o 2*960*960 + k,v 2*960*320 + mlp 3*960*2560 = 9,830,400
    # 32 layers + the tied head 49152*960 = 361,758,720
    assert matmul_params(cfg) == 32 * 9_830_400 + 47_185_920 == 361_758_720
    # attention, causal once: 6 * S * 960 * 32 layers
    assert train_flops_per_token(cfg, 4096) == pytest.approx(2.93e9, rel=3e-3)
    assert train_flops_per_token(cfg, 4096) == (
        6 * 361_758_720 + 6 * 4096 * 960 * 32)
    # the cell's own shape (16 x 1024)
    assert train_flops_per_token(cfg, 1024) == pytest.approx(2.359e9,
                                                             rel=1e-3)


MISTRAL_7B_V03 = {      # mistralai/Mistral-7B-v0.3 config.json, as ISSUE 23
    "hidden_size": 4096, "intermediate_size": 14336,      # quotes it
    "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
    "vocab_size": 32768, "num_hidden_layers": 32}


def test_mistral_7b_at_8_layers():
    cfg = dict(MISTRAL_7B_V03, num_hidden_layers=8)
    # a layer: 2*4096*4096 + 2*4096*1024 + 3*4096*14336 = 218,103,808
    assert matmul_params(cfg) == 8 * 218_103_808 + 32768 * 4096
    assert train_flops_per_token(cfg, 4096) == pytest.approx(12.1e9,
                                                             rel=3e-3)


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit):
        peaks_for("TPU v9")

"""The operations function against hand counts for both configurations."""

import json
import os

import pytest

from bench_paths import BENCH

import flops


def model(name):
    return json.load(open(os.path.join(BENCH, "configs",
                                       name + ".json")))["model"]


def test_mistral_matmul_parameters_by_hand():
    # q 4096x4096, k and v 4096x1024, o 4096x4096, gate/up/down 4096x14336
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert flops.matmul_params(model("mistral_7b")) == \
        16 * layer + 4096 * 32000


def test_qwen2_matmul_parameters_by_hand():
    layer = 3584 * 3584 * 2 + 3584 * 512 * 2 + 3 * 3584 * 18944
    assert flops.matmul_params(model("qwen2_7b")) == \
        14 * layer + 3584 * 152064


def test_lora_counts_4n_and_a_full_fine_tune_6n():
    m = model("mistral_7b")
    n = flops.matmul_params(m)
    no_attention = dict(m, num_attention_heads=32)
    full = flops.train_flops_per_token(no_attention, 0, 0.0)
    assert full == 6 * n
    r = 16
    adapters = 16 * (r * (4096 + 4096) * 2 + r * (4096 + 1024) * 2)
    assert flops.lora_params(m, r) == adapters
    assert flops.train_flops_per_token(m, r, 0.0) == 4 * n + 6 * adapters


def test_attention_is_counted_by_the_band_it_sees():
    # one document of 10 tokens, window 4: 1+2+3+4 then 4 x 6 keys
    assert flops.mean_keys_seen([10], 4) == pytest.approx((10 + 24) / 10)
    assert flops.mean_keys_seen([10], None) == pytest.approx(5.5)
    # two packed documents never see each other
    assert flops.mean_keys_seen([4, 4], None) == pytest.approx(2.5)
    m = model("mistral_7b")
    fwd = flops.attention_flops_per_token(m, 100.0, backward=False)
    assert fwd == 2 * 2 * 32 * 128 * 100.0 * 16
    assert flops.attention_flops_per_token(m, 100.0, backward=True) == 3 * fwd


def test_an_unknown_device_kind_is_an_error_not_a_default():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        flops.peaks("TPU v9")
    with pytest.raises(KeyError):
        flops.peaks("cpu")

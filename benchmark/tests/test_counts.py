"""``counts.py`` against values worked by hand for both configurations."""
import json
import os

import pytest

from conftest import ROOT

from benchmark import counts


def cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_medium():
    c = cfg("gpt2-medium")
    # one block: 4 d^2 attention + 8 d^2 MLP = 12 x 1024^2
    assert counts.layer_matmul_params(c) == 12 * 1024 ** 2 == 12582912
    # N = 24 blocks + the untied 50257 x 1024 head
    assert counts.matmul_params(c) == 24 * 12582912 + 50257 * 1024 == 353453056
    # every stored parameter: 406 M with the untied head (355 M published)
    per_layer = 12582912 + (4 * 1024 + 4096 + 1024) + 4 * 1024
    total = 24 * per_layer + 2 * 50257 * 1024 + 1024 * 1024 + 2 * 1024
    assert counts.total_params(c) == total == 406286336
    # per token at T 1024: 6 N + 3 x (4 x 1024 x 64 x 16 heads / 2) x 24
    attn = 3 * 24 * (4 * 1024 * 64 * 16 // 2)
    assert counts.train_flops_per_token(c, 1024) == 6 * 353453056 + attn
    assert attn == 150994944


def test_mistral_l4():
    c = cfg("mistral-7b-v0.3-l4")
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096, three 4096x14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert counts.layer_matmul_params(c) == layer == 218103808
    assert counts.matmul_params(c) == 4 * layer + 4096 * 32768 == 1006632960
    assert counts.total_params(c) == (4 * (layer + 2 * 4096)
                                      + 2 * 4096 * 32768 + 4096) == 1140887552
    # prefill of 8 x 2048, head at the last position only
    body = 2 * 4 * layer * 8 * 2048
    head = 2 * 4096 * 32768 * 8
    attn = 4 * (4 * 8 * 32 * 2048 * 2048 * 128 // 2)
    assert counts.forward_flops(c, 8, 2048, head_positions=1) == body + head + attn
    # a decode step for 16 rows attending to 176 positions
    weights = 2 * 1006632960
    cache = 2 * 4 * 16 * 8 * 176 * 128 * 2
    assert counts.decode_step_bytes(c, 16, 176) == weights + cache


def test_flash_call_and_roofline():
    # 128 heads-times-batch, T 1024, head 64, causal, bf16
    c = counts.flash_call(128, 1024, 64)
    mat = 2 * 128 * 1024 * 1024 * 64 // 2
    assert c["fwd_flops"] == 2 * mat and c["bwd_flops"] == 5 * mat
    tensor = 128 * 1024 * 64 * 2
    assert c["fwd_bytes"] == 4 * tensor + 128 * 1024 * 4
    assert c["bwd_bytes"] == 8 * tensor + 2 * 128 * 1024 * 4
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    secs, bound = counts.roofline_seconds(c["fwd_flops"], c["fwd_bytes"], peaks)
    assert bound == "compute" and secs == pytest.approx(2 * mat / 197e12)
    secs, bound = counts.roofline_seconds(1e9, 819e9, peaks)
    assert bound == "memory" and secs == pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        table = json.load(f)
    assert counts.peaks_for("TPU v5 lite", table)["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks_for("TPU v9 imaginary", table)

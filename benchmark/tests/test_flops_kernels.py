"""benchmark/flops.py and benchmark/kernels/*.py against hand-worked counts,
and flops.py against the program's own "model" convention today."""

import json
import os

import pytest

from benchmark import flops
from benchmark.kernels import decode_tick, ragged_paged_attention
from benchmark.peaks import peaks_of
from conftest import CHECKOUT


def _model(name):
    return json.load(open(os.path.join(CHECKOUT, "benchmark", "configs",
                                       name + ".json")))["model"]


def test_mamba2_280m_forward_by_hand():
    m = _model("mamba2-280m")
    # per layer: in_proj 768 x (2*1536 + 2*128 + 24) = 768 x 3352;
    # conv (1536 + 256) x 4; state 2 x 24 x 128 x 64; out_proj 1536 x 768
    layer = 2 * 768 * 3352 + 2 * 1792 * 4 + 2 * (2 * 24 * 128 * 64) + 2 * 1536 * 768
    want = 64 * layer + 2 * 768 * 50304
    assert flops.forward_flops_per_token(m, 512) == want
    assert flops.train_flops_per_token(m, 1024) == 3 * want


def test_hybrid_attention_term_by_hand():
    m = _model("hybrid-280m")
    base = flops.forward_flops_per_token(m, 0)
    # 8 attention layers, 12 heads of 64: 4 * context * 768 each
    assert flops.forward_flops_per_token(m, 1000) - base == 8 * 4 * 1000 * 12 * 64
    qkv_out = 2 * 768 * (12 + 8) * 64 + 2 * 768 * 768
    mamba = (flops.forward_flops_per_token(_model("mamba2-280m"), 0)
             - 2 * 768 * 50304) / 64
    assert base == 56 * mamba + 8 * qkv_out + 2 * 768 * 50304


@pytest.mark.parametrize("name,preset", [("mamba2-280m", "mamba2-280m"),
                                         ("hybrid-280m", "hybrid-280m")])
def test_equal_to_the_programs_model_convention(name, preset):
    from mamba_distributed_tpu.config import get_preset
    from mamba_distributed_tpu.utils.flops import flops_per_token

    cfg = get_preset(preset).model
    m = _model(name)
    for t in (1, 256, 1024):
        assert flops.train_flops_per_token(m, t) == pytest.approx(
            flops_per_token(cfg, t, convention="model"), rel=1e-12)
        assert flops.forward_flops_per_token(m, t / 2) == pytest.approx(
            flops_per_token(cfg, t, training=False, convention="model"), rel=1e-12)


def test_decode_tick_bytes_by_hand():
    m = _model("mamba2-280m")
    # state per slot: 64 layers x (24*64*128 fp32 + 3*1792 bf16)
    per_slot = 64 * (24 * 64 * 128 * 4 + 3 * 1792 * 2)
    assert decode_tick.state_bytes_per_slot(m) == per_slot == 51019776
    w = 2 * (64 * (768 * 3352 + 1536 * 768 + 1792 * 4) + 50304 * 768)
    assert decode_tick.weight_bytes(m) == w
    assert decode_tick.tick_bytes(m, 96, 8) == 8 * (w + 2 * 96 * per_slot)
    # 96 full slots: 9.8 GB of state and 0.56 GB of weights a sub-step
    assert 12e-3 < decode_tick.tick_bytes(m, 96, 1) / 819e9 < 13e-3


def test_ragged_paged_attention_by_hand():
    m = _model("hybrid-280m")
    ops, by = ragged_paged_attention.decode_call(m, [1000, 3000])
    assert ops == 4 * 4000 * 12 * 64 and by == 2 * 4000 * 4 * 64 * 2
    ops, by = ragged_paged_attention.prefill_call(m, 512, 256)
    assert ops == 4 * 256 * (512 + 128.5) * 12 * 64
    assert by == 2 * (512 + 512) * 4 * 64 * 2
    peaks = peaks_of("TPU v5 lite")
    # decode is bound by bytes, a late prefill chunk by operations
    d = ragged_paged_attention.decode_call(m, [4000] * 32)
    assert d[1] / peaks["hbm_bytes_per_s"] > d[0] / peaks["flops_bf16"]
    p = ragged_paged_attention.prefill_call(m, 4096, 256)
    assert p[0] / peaks["flops_bf16"] > p[1] / peaks["hbm_bytes_per_s"]
    assert ragged_paged_attention.roofline_seconds([d, p], peaks) == pytest.approx(
        d[1] / 819e9 + p[0] / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks_of("cpu")
    assert peaks_of("TPU v5 lite")["flops_bf16"] == 197e12

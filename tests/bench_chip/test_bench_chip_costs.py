"""The benchmark's op and byte arithmetic against hand-counted shapes."""
import json

import pytest

from benchmarks.chip import costs, peaks
from bench_chip_smoke import ROOT


def _cfg(name):
    with open(ROOT / "benchmarks" / "chip" / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def qwen():
    return _cfg("qwen2.5-3b-w4a8")


@pytest.fixture(scope="module")
def resnet():
    return _cfg("resnet8-w842")


def test_qwen_param_bytes_match_the_served_tree(qwen):
    # per layer: packed W4 projections, float32 scales, q/k/v biases and
    # two norms; then the final norm and the float32 embedding padded to
    # 152,064 rows. PR 11 measured this total on the chip.
    layer = ((2048 * 2048 + 2 * 2048 * 256 + 2048 * 2048
              + 3 * 2048 * 11008) // 2
             + 4 * (2048 + 256 + 256 + 2048 + 11008 + 11008 + 2048)
             + 4 * (2048 + 256 + 256) + 2 * 4 * 2048)
    assert layer == 38_676_480
    total = 36 * layer + 4 * 2048 + 152_064 * 2048 * 4
    assert costs.lm_param_bytes(qwen) == total == 2_638_069_760


def test_qwen_kv_bytes_per_position(qwen):
    assert costs.lm_kv_bytes_per_position(qwen) == 36 * 2 * 2 * 128 * 2 \
        == 36_864
    # the decode cell's whole cache: 128 slots x 640 positions
    assert 128 * 640 * costs.lm_kv_bytes_per_position(qwen) == 3_019_898_880


@pytest.mark.parametrize("context", [1, 100, 640])
def test_qwen_macs_per_token(qwen, context):
    dense = 36 * (2048 * 2048 + 2 * 2048 * 256 + 2048 * 2048
                  + 3 * 2048 * 11008)
    assert dense == 2_774_532_096
    head = 2048 * 151_936
    attn = 36 * 2 * 16 * 128 * context
    assert costs.lm_macs_per_token(qwen, context) == dense + head + attn
    assert costs.lm_ops_per_token(qwen, context) == 2 * (dense + head + attn)


def test_qwen_step_bytes(qwen):
    assert costs.lm_step_bytes(qwen, 1000) == 2_638_069_760 + 36_864_000


def test_resnet8_macs_per_image(resnet):
    per_layer = {t["layer"]["path"]: costs.layer_macs(t)
                 for t in costs.cnn_layer_shapes(resnet)}
    assert per_layer["stem"] == 32 * 32 * 16 * 9 * 3
    assert per_layer["s1/c1"] == per_layer["s1/c2"] == 32 * 32 * 16 * 9 * 16
    assert per_layer["s2/c1"] == 16 * 16 * 32 * 9 * 16
    assert per_layer["s2/skip"] == 16 * 16 * 32 * 16
    assert per_layer["s3/c2"] == 8 * 8 * 64 * 9 * 64
    assert per_layer["head"] == 64 * 10
    assert per_layer["s1/add"] == per_layer["pool"] == 0
    assert sum(per_layer.values()) == 12_501_632
    assert costs.cnn_ops_per_image(resnet) == 25_003_264


def test_resnet8_macs_agree_with_the_e2e_count(resnet):
    """Same count as `benchmarks/e2e_networks.py::_layer_macs` over the
    program's own graph."""
    from benchmarks.e2e_networks import _layer_macs
    from repro.vision.configs import get_vision_config
    from repro.vision.models import trace_shapes

    theirs = sum(_layer_macs(t) for t in trace_shapes(
        get_vision_config("resnet8")))
    assert 2 * theirs == costs.cnn_ops_per_image(resnet)


def test_qconv_call_cost_and_roofline(resnet):
    t = next(t for t in costs.cnn_layer_shapes(resnet)
             if t["layer"]["path"] == "s3/c2")
    ops, nbytes = costs.qconv_call_cost(t, 2, 256)
    assert ops == 2 * 256 * 8 * 8 * 64 * 9 * 64 == 1_207_959_552
    act = 256 * 8 * 8 * 64
    w = 9 * 64 * 64 * 2 // 8           # true widths, 4 codes per byte
    assert nbytes == act + act + w + 3 * 64 * 4 == 2_107_136
    p = peaks.peaks_for("TPU v5 lite")
    # s3/c2 at batch 256 is bound by its ops, not its bytes
    assert ops / p["int8_ops"] > nbytes / p["hbm_bytes_per_s"]
    least = costs.qconv_least_seconds(resnet, 256, p)
    # s3/c1, s3/c2 and s3/skip share the 8x8 output size
    assert least[(8, 8)] > ops / p["int8_ops"] / 3
    assert len(least) == 3


def test_peaks_table_refuses_unknown_devices():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"]) == \
        (197e12, 393e12, 819e9)
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")

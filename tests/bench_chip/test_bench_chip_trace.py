"""The trace reduction: busy union, idle share, gap labels, program sums,
custom calls read by shape and the conv roofline over them, on
hand-made events and on a trimmed TPU trace recorded on a v5e by this
benchmark."""
import gzip
import json

import pytest

from benchmarks.chip import cell as cells, costs, trace_reduce as tr
from bench_chip_smoke import ROOT

DATA = ROOT / "benchmarks" / "chip" / "testdata"


def test_merge_clip_and_gaps():
    m = tr.merge([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)])
    assert m == [[0, 3], [5, 9]]
    assert tr.clip(m, 1, 6) == [[1, 3], [5, 6]]
    assert tr.gaps(m, -1, 12) == [(-1, 0), (3, 5), (9, 12)]


def _synthetic():
    # window 0..100 ns from the host spans; device 0 busy 10..40 and
    # 35..50 (overlap) and 80..90; device 1 busy 0..100
    return {
        "host": [["bench.step", 0.0, 30.0], ["bench.consume", 50.0, 25.0],
                 ["bench.begin", 76.0, 24.0]],
        "devices": {
            "/device:TPU:0": {
                "ops": [["fusion.1", 10.0, 30.0], ["my_qconv_kernel", 35.0,
                                                   15.0],
                        ["fusion.1", 80.0, 10.0], ["late", 150.0, 5.0]],
                "modules": [["jit_decode_step(1)", 10.0, 40.0],
                            ["jit_other", 80.0, 10.0]]},
            "/device:TPU:1": {
                "ops": [["my_qconv_kernel", 0.0, 100.0]],
                "modules": [["jit_decode_step(1)", 0.0, 100.0]]}}}


def test_reduce_on_hand_made_events():
    r = tr.reduce(_synthetic(), ["decode"])
    assert r["window_s"] == pytest.approx(100e-9)
    # device 0: union 10..50 + 80..90 = 50 ns; device 1: 100 ns
    assert r["busy_s"] == pytest.approx(75e-9)
    assert r["idle_share"] == pytest.approx(0.25)
    assert r["modules"]["decode"] == (pytest.approx(140e-9), 2)
    # device 0's gaps: 0..10 (step), 50..80 (consume), 90..100 (begin)
    assert r["idle_gaps"] == [["bench.consume", pytest.approx(30e-9)],
                              ["bench.step", pytest.approx(10e-9)],
                              ["bench.begin", pytest.approx(10e-9)]]
    assert r["device_ops"][0] == ["my_qconv_kernel", pytest.approx(57.5e-9)]


# a fused conv call as a v5e trace names it (copied from a ResNet-8 trace
# of this benchmark): the HLO text of a custom call, shapes and all
CONV_32 = ('%_lambda_.12 = s8[{n},32,32,128]{{3,2,1,0:T(8,128)(4,1)S(1)}} '
           'custom-call(s8[{n},1,34,40,128]{{4,3,2,1,0:T(8,128)(4,1)S(1)}} '
           '%pad_bitcast_fusion, s8[576,128]{{1,0:T(8,128)(4,1)S(1)}} '
           '%copy-done, s32[1,128]{{1,0:T(1,128)S(1)}} %copy-done.22, '
           's32[1,128]{{1,0:T(1,128)S(1)}} %copy-done.23, '
           's32[1,128]{{1,0:T(1,128)S(1)}} %pad_bitcast_fusion.11), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints='
           '{{s8[{n},1,34,40,128]{{4,3,2,1,0}}, s8[576,128]{{1,0}}}}, '
           'frontend_attributes={{kernel_metadata={{}}}}')


def _conv(n, h, w):
    return CONV_32.replace("32,32,128]", f"{h},{w},128]").format(n=n)


def test_custom_calls_are_read_by_shape():
    ev = {"host": [["bench.step", 0.0, 100.0]], "devices": {
        "/device:TPU:0": {"modules": [], "ops": [
            [_conv(256, 32, 32), 10.0, 20.0],
            ["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 30.0, 5.0],
            [_conv(256, 8, 8), 200.0, 5.0]]}}}       # outside the window
    calls = tr.custom_calls(ev)
    assert calls == [{"out": [256, 32, 32, 128],
                      "operands": [[256, 1, 34, 40, 128], [576, 128],
                                   [1, 128], [1, 128], [1, 128]],
                      "seconds": pytest.approx(20e-9)}]


def _run_with(ops, resnet, peaks):
    from benchmarks.chip.run import Run
    ev = {"host": [["bench.step", 0.0, 1e9]],
          "devices": {"/device:TPU:0": {"modules": [], "ops": ops}}}
    return Run(config=resnet, traffic={}, window=None, setup_s=0.0,
               peaks=peaks, chips=1, trace=tr.reduce(ev), events=ev)


def test_conv_roofline_on_hand_made_events():
    """Two forwards' worth of conv calls at batch 256, each taking twice
    the least time its layers need, read 50%; calls of no conv layer's
    output size (the head's, a fusion) are left out."""
    resnet = json.loads((ROOT / "benchmarks" / "chip" / "configs"
                         / "resnet8-w842.json").read_text())
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    least = costs.qconv_least_seconds(resnet, 256, peaks)
    assert sorted(least) == [(8, 8), (16, 16), (32, 32)]
    ops, t = [], 0.0
    for _ in range(2):
        for (h, w), s in least.items():
            for _ in range(3):                  # three layers per size
                ops.append([_conv(256, h, w), t, 2 * s * 1e9])
                t += 2 * s * 1e9
    ops.append([CONV_32.replace("[{n},32,32,128]", "[256,128]")
                .format(n=256), t, 1e6])
    reader = cells.metric_module("qconv_roofline.cnn")
    assert reader.read(_run_with(ops, resnet, peaks)) == pytest.approx(50.0)
    # the reader's least time is a bound: the chip's own numbers, summed
    # over the nine layers, are what the calls' least times add up to
    per_forward = sum(3 * s for s in least.values())
    layers = [t for t in costs.cnn_layer_shapes(resnet)
              if t["layer"]["kind"] == "conv"]
    assert per_forward == pytest.approx(sum(
        max(o / 393e12, b / 819e9) for o, b in (
            costs.qconv_call_cost(t, resnet["plan"][t["layer"]["path"]], 256)
            for t in layers)))


def test_conv_roofline_reads_nothing_without_conv_calls():
    resnet = json.loads((ROOT / "benchmarks" / "chip" / "configs"
                         / "resnet8-w842.json").read_text())
    run = _run_with([["%fusion = f32[8]{0} fusion()", 0.0, 10.0]], resnet,
                    {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9})
    assert cells.metric_module("qconv_roofline.cnn").read(run) is None


def test_no_host_span_no_window():
    ev = _synthetic()
    ev["host"] = []
    with pytest.raises(ValueError):
        tr.reduce(ev)


def _recorded():
    with gzip.open(DATA / "v5e_decode_slice.events.json.gz", "rt") as f:
        return json.load(f)


def test_reduce_on_a_recorded_v5e_decode_step():
    """A slice of a decode step traced on one TPU v5 lite: the
    reduction against a nanosecond-by-nanosecond count of the same
    events, and against numbers read off the slice by hand."""
    import numpy as np
    ev = _recorded()
    lo, hi = tr.window_of(ev)
    assert hi - lo == 76_597_684            # first to last harness span
    ops = ev["devices"]["/device:TPU:0"]["ops"]
    grid = np.zeros(int(hi - lo), bool)
    for _, s, d in ops:
        a, b = int(max(s, lo) - lo), int(min(s + d, hi) - lo)
        if b > a:
            grid[a:b] = True
    assert grid.sum() == 42_695_434
    r = tr.reduce(ev, ["decode"])
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-9)
    assert r["window_s"] == pytest.approx(76_597_684e-9)
    assert r["idle_share"] == pytest.approx(1 - 42_695_434 / 76_597_684)
    # the LM path runs no Pallas kernel; its custom calls allocate the
    # (layers, slots, max_len, kv heads, head) K and V caches
    assert tr.custom_calls(ev) == []
    alloc = tr.custom_calls(ev, "AllocateBuffer")
    assert [c["out"] for c in alloc] == [[36, 128, 640, 2, 128]] * 2
    assert r["modules"]["decode"][1] == 1        # one jit_decode program
    # the device waits on the host inside the adapter's step: the logits
    # copy to the host after the program
    assert r["idle_gaps"][0][0] == "bench.step"
    assert r["device_ops"][0][0] == "while.14"

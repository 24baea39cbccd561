"""Benchmark-artifact schemas (benchmarks/schema.py).

The perf-trajectory tooling diffs BENCH_kernels.json / BENCH_cluster.json
/ BENCH_e2e.json run over run, so their shapes are load-bearing. This file
pins the checked-in validators against known-good fixture payloads (the
exact shapes the writers emit, incl. the PR's pipeline + frac_of_peak
roofline columns) and proves every validator actually rejects the breakage
it claims to catch. The slow test runs the real fig8 benchmark and
validates the artifact run.py would write.
"""
import copy
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # `import benchmarks` from any rootdir
    sys.path.insert(0, str(ROOT))

from benchmarks import schema
from benchmarks.schema import SchemaError

KERNELS_OK = {
    "us_per_call": {"fig8_8bit_off": 171714.1,
                    "fig8_8bit_double_buffer": 320165.3,
                    "fig11_conv16x16_8bit_full": 1234.5},
    "derived": {"fig8_8bit_off": "v5e_us=2.723;macs=134217728"},
    "backend": {"fig8_8bit_off": "pallas_interpret"},
    "pipeline": {"fig8_8bit_off": "off",
                 "fig8_8bit_double_buffer": "double_buffer"},
    "frac_of_peak": {"fig8_8bit_off": 0.5004,
                     "fig8_8bit_double_buffer": 1.0},
    # counter-measured columns (PR 7): effective MAC/us + packed bytes
    # moved, sourced from repro.obs.counters during the timed run
    "macs_per_us": {"fig8_8bit_off": 781.5,
                    "fig8_8bit_double_buffer": 419.2},
    "packed_bytes": {"fig8_8bit_off": 786432,
                     "fig8_8bit_double_buffer": 786432},
    # fine-grain mixed-precision ladder (PR 9): "|"-joined container
    # widths the row's kernel consumed, widest first
    "segment_bits": {"fig8_8bit_off": "8",
                     "fig8_8bit_double_buffer": "8",
                     "fig11_conv16x16_8bit_full": "8|2"},
}

TRACE_OK = {
    "traceEvents": [
        {"name": "qdot", "cat": "kernel", "ph": "X", "ts": 10.0,
         "dur": 120.5, "pid": 0, "tid": 0,
         "args": {"backend": "pallas_interpret", "pipeline": "off",
                  "a_bits": 8, "w_bits": 4, "macs": 1048576,
                  "packed_bytes": 28672}},
        {"name": "dispatch:qdot", "cat": "dispatch", "ph": "i", "ts": 9.0,
         "pid": 0, "tid": 0, "s": "t"},
    ],
    "displayTimeUnit": "ms",
    "repro": {
        "version": 1,
        "counters": {"engine.requests": 2},
        "op_counters": {
            "qdot|w4a8|pallas_interpret|off": {
                "calls": 3, "macs": 3145728, "logical_bytes": 135168,
                "packed_bytes": 86016}},
        "dispatch": [
            {"op": "qdot", "backend": "pallas_interpret",
             "backend_source": "explicit", "pipeline": "off",
             "pipeline_source": "default", "ts": 9.0,
             "tune_cache_hit": False}],
    },
}

CLUSTER_OK = {
    "version": 1,
    "gemm": {"M": 256, "K": 2048, "N": 1024},
    "path": "repro.kernels.api.qdot_sharded",
    "rows": [{"name": "fig9_8bit_dev2", "bits": 8, "devices": 2,
              "us_per_call": 1813.1, "speedup": 1.91,
              "efficiency": 0.955, "per_dev_flops": 5.4e8,
              "coll_bytes": 0, "proj_us_v5e": 6.82}],
}

E2E_OK = {
    "version": 1,
    "batch": 8,
    "rows": [
        {"name": "e2e_resnet8_8_conv1_dev1", "net": "resnet8",
         "layer": "conv1", "bits": "8", "devices": 1,
         "us_per_call": 812.0, "macs_per_image": 1769472},
        {"name": "e2e_resnet8_mixed_total_dev2", "net": "resnet8",
         "layer": "total", "bits": "mixed", "devices": 2,
         "us_per_call": 9120.4, "macs_per_image": 12501504,
         "speedup": 1.8, "efficiency": 0.9, "bytes_streamed": 91032,
         "proj_us_v5e": 4.1},
    ],
}


def _mutated(payload, fn):
    p = copy.deepcopy(payload)
    fn(p)
    return p


# ------------------------------------------------------------- kernels ---

def test_kernels_fixture_valid():
    schema.validate_kernels(KERNELS_OK)


@pytest.mark.parametrize("mutate,match", [
    (lambda p: p.pop("us_per_call"), "missing required field"),
    (lambda p: p.pop("pipeline"), "missing required field 'pipeline'"),
    (lambda p: p.pop("frac_of_peak"), "frac_of_peak"),
    (lambda p: p["pipeline"].update(fig8_8bit_off="triple_buffer"),
     r"\$\.pipeline\.fig8_8bit_off"),
    (lambda p: p["frac_of_peak"].update(fig8_8bit_off=1.5),
     "out of range"),
    (lambda p: p["frac_of_peak"].update(ghost_row=0.5),
     "not in us_per_call"),
    (lambda p: p["us_per_call"].update(fig8_8bit_off="fast"),
     "expected"),
    (lambda p: p["us_per_call"].update(fig8_8bit_off=True), "bool"),
    (lambda p: p.pop("macs_per_us"), "missing required field"),
    (lambda p: p.pop("packed_bytes"), "missing required field"),
    (lambda p: p["macs_per_us"].update(fig8_8bit_off=-1.0),
     "out of range"),
    (lambda p: p["packed_bytes"].update(fig8_8bit_off=1.5), "expected"),
    (lambda p: p.pop("segment_bits"), "missing required field"),
    (lambda p: p["segment_bits"].update(fig8_8bit_off="3"),
     "out of range"),
    (lambda p: p["segment_bits"].update(fig8_8bit_off="2|8"),
     "out of range"),         # must be widest first
    (lambda p: p["segment_bits"].update(fig8_8bit_off="8|8"),
     "out of range"),         # no duplicate widths
    (lambda p: p["segment_bits"].update(ghost_row="8"),
     "not in us_per_call"),
])
def test_kernels_rejects(mutate, match):
    with pytest.raises(SchemaError, match=match):
        schema.validate_kernels(_mutated(KERNELS_OK, mutate))


def test_fig8_roofline_acceptance_shape():
    """Per bit-width: an 'off' and a 'double_buffer' row, both with
    frac_of_peak, pipelined >= exposed-DMA."""
    schema.validate_fig8_roofline(KERNELS_OK, bits=(8,))
    with pytest.raises(SchemaError, match="missing fig8 roofline row"):
        schema.validate_fig8_roofline(KERNELS_OK, bits=(8, 4))
    bad = _mutated(KERNELS_OK,
                   lambda p: p["frac_of_peak"].update(
                       fig8_8bit_double_buffer=0.3))
    with pytest.raises(SchemaError, match="below the exposed-DMA"):
        schema.validate_fig8_roofline(bad, bits=(8,))
    nofrac = _mutated(KERNELS_OK,
                      lambda p: p["frac_of_peak"].pop("fig8_8bit_off"))
    with pytest.raises(SchemaError, match="missing roofline column"):
        schema.validate_fig8_roofline(nofrac, bits=(8,))
    nomacs = _mutated(KERNELS_OK,
                      lambda p: p["macs_per_us"].pop("fig8_8bit_off"))
    with pytest.raises(SchemaError, match="counter-measured column"):
        schema.validate_fig8_roofline(nomacs, bits=(8,))


# ------------------------------------------------------------- cluster ---

def test_cluster_fixture_valid():
    schema.validate_cluster(CLUSTER_OK)


@pytest.mark.parametrize("mutate,match", [
    (lambda p: p.update(version=2), "out of range"),
    (lambda p: p["gemm"].pop("K"), "missing required field 'K'"),
    (lambda p: p.update(rows=[]), "empty rows"),
    (lambda p: p["rows"][0].pop("speedup"), r"\$\.rows\[0\]"),
    (lambda p: p["rows"][0].update(bits=3), "out of range"),
    (lambda p: p["rows"][0].update(devices=0), "out of range"),
    (lambda p: p["rows"][0].update(coll_bytes=1.5), "expected"),
])
def test_cluster_rejects(mutate, match):
    with pytest.raises(SchemaError, match=match):
        schema.validate_cluster(_mutated(CLUSTER_OK, mutate))


# ----------------------------------------------------------------- e2e ---

def test_e2e_fixture_valid():
    schema.validate_e2e(E2E_OK)


@pytest.mark.parametrize("mutate,match", [
    (lambda p: p.pop("batch"), "missing required field 'batch'"),
    (lambda p: p["rows"][0].pop("macs_per_image"), "macs_per_image"),
    (lambda p: p["rows"][1].update(efficiency=-1.0), "out of range"),
    (lambda p: p["rows"][0].update(bits=None), "expected"),
    (lambda p: p["rows"][1].update(bytes_streamed="91032"), "expected"),
])
def test_e2e_rejects(mutate, match):
    with pytest.raises(SchemaError, match=match):
        schema.validate_e2e(_mutated(E2E_OK, mutate))


# ------------------------------------------------------------ accuracy ---

def _accuracy_row(name, mode, plan, w_bits, acc, bytes_, seg=0):
    return {"name": name, "mode": mode, "plan": plan, "w_bits": w_bits,
            "accuracy": acc, "correct": int(acc * 1000), "n": 1000,
            "packed_weight_bytes": bytes_, "train_steps": 600,
            "segmented_rules": seg}


ACCURACY_OK = {
    "version": 1, "net": "qat-cnn", "mode": "full",
    "dataset": {"name": "synthetic-digits", "noise": 0.45, "jitter": 3,
                "seed": 0, "eval_images": 1000},
    "budget_frac": 0.35,
    "path": "repro.vision.models.forward_int",
    "rows": [
        _accuracy_row("float", "float", "none", 32, 0.934, 326592),
        _accuracy_row("ptq_w8", "ptq", "uniform", 8, 0.928, 114872),
        _accuracy_row("qat_w8", "qat", "uniform", 8, 0.952, 114872),
        _accuracy_row("ptq_w4", "ptq", "uniform", 4, 0.874, 59320),
        _accuracy_row("qat_w4", "qat", "uniform", 4, 0.931, 59320),
        _accuracy_row("ptq_w2", "ptq", "uniform", 2, 0.103, 31544),
        _accuracy_row("qat_w2", "qat", "uniform", 2, 0.251, 31544),
        _accuracy_row("ptq_plan_layer", "ptq", "layer", 0, 0.315, 58168),
        _accuracy_row("qat_plan_layer", "qat", "layer", 0, 0.889, 58168),
        _accuracy_row("ptq_plan_channel_group", "ptq", "channel_group",
                      0, 0.528, 46520, seg=1),
        _accuracy_row("qat_plan_channel_group", "qat", "channel_group",
                      0, 0.921, 46520, seg=1),
    ],
    "acceptance": {"qat_ge_ptq_w4": True, "qat_ge_ptq_w2": True,
                   "plans_on_frontier": True,
                   "fine_dominates_layer": True, "all": True},
}


def test_accuracy_fixture_valid():
    schema.validate_accuracy(ACCURACY_OK)


def test_accuracy_smoke_mode_skips_gates():
    p = _mutated(ACCURACY_OK, lambda p: p.update(mode="smoke"))
    p["rows"][6]["accuracy"] = 0.01          # qat_w2 below ptq_w2
    schema.validate_accuracy(p)              # gates off, shapes still on
    with pytest.raises(SchemaError, match="missing required field"):
        schema.validate_accuracy(
            _mutated(p, lambda q: q["rows"][0].pop("packed_weight_bytes")))


@pytest.mark.parametrize("mutate,match", [
    (lambda p: p.pop("dataset"), "missing required field 'dataset'"),
    (lambda p: p.update(budget_frac=1.5), "out of range"),
    (lambda p: p["rows"][0].update(mode="train"), "out of range"),
    (lambda p: p["rows"][0].update(accuracy=1.2), "out of range"),
    (lambda p: p["rows"][0].update(accuracy=True), "got bool"),
    (lambda p: p["rows"][1].update(correct=2000),
     "correct 2000 > n 1000"),
    (lambda p: p.update(rows=[r for r in p["rows"]
                              if r["name"] != "qat_w4"]),
     "missing uniform row mode=qat w_bits=4"),
    (lambda p: p["acceptance"].pop("fine_dominates_layer"),
     "missing required field"),
    # gates recomputed from rows — lying booleans don't help:
    (lambda p: p["rows"][6].update(accuracy=0.01),
     "QAT .* below PTQ .* at W2"),
    (lambda p: p["rows"][4].update(accuracy=0.5),
     "QAT .* below PTQ .* at W4"),
    # a uniform row that dominates a plan row breaks the frontier gate
    (lambda p: p["rows"][8].update(accuracy=0.2, packed_weight_bytes=99999),
     "dominates qat_plan_layer"),
    # channel_group must dominate-or-match layer (bytes AND accuracy)
    (lambda p: p["rows"][10].update(accuracy=0.7),
     "does not dominate-or-match"),
    (lambda p: p["acceptance"].update(all=False),
     "gates hold but 'all' is false"),
])
def test_accuracy_rejects(mutate, match):
    with pytest.raises(SchemaError, match=match):
        schema.validate_accuracy(_mutated(ACCURACY_OK, mutate))


# --------------------------------------------------------------- trace ---

def test_trace_fixture_valid():
    schema.check_trace(TRACE_OK)


@pytest.mark.parametrize("mutate,match", [
    (lambda p: p.pop("traceEvents"), "missing required field"),
    (lambda p: p["traceEvents"][0].pop("name"), "missing required field"),
    (lambda p: p["traceEvents"][0].update(ph="Z"), "out of range"),
    (lambda p: p["traceEvents"][0].update(ts=-1.0), "out of range"),
    (lambda p: p["traceEvents"][0].pop("dur"), "missing required field"),
    (lambda p: p["traceEvents"][0].update(args=[]), "expected"),
    (lambda p: p["repro"].update(version=99), "out of range"),
    (lambda p: p["repro"]["op_counters"].update(bad_key={
        "calls": 1, "macs": 0, "logical_bytes": 0, "packed_bytes": 0}),
     "key is not op"),
    (lambda p: p["repro"]["op_counters"]
     ["qdot|w4a8|pallas_interpret|off"].pop("macs"),
     "missing required field"),
    (lambda p: p["repro"]["dispatch"][0].pop("backend_source"),
     "missing required field"),
    (lambda p: p["repro"]["dispatch"][0].update(pipeline="triple_buffer"),
     "out of range"),
])
def test_trace_rejects(mutate, match):
    with pytest.raises(SchemaError, match=match):
        schema.check_trace(_mutated(TRACE_OK, mutate))


def test_trace_roundtrips_from_live_modules():
    """A trace exported by repro.obs itself must pass check_trace — pins
    the writer and the validator to the same shape."""
    from repro.obs import counters, trace

    trace.reset()
    counters.reset()
    with trace.enabled_scope():
        with trace.span("qdot", cat="kernel", backend="xla", pipeline="off",
                        a_bits=8, w_bits=4, macs=100, packed_bytes=10):
            pass
        trace.dispatch_event(op="qdot", backend="xla",
                             backend_source="default", pipeline="off",
                             pipeline_source="default",
                             tune_cache_hit=False)
        counters.record("qdot", (32, 256, 128), 8, 4, backend="xla",
                        pipeline="off")
        doc = trace.chrome_trace()
    trace.reset()
    counters.reset()
    schema.check_trace(doc)


# ------------------------------------------------------------ dispatch ---

def test_validate_file_dispatch(tmp_path):
    import json

    for name, payload in (("BENCH_kernels.json", KERNELS_OK),
                          ("BENCH_cluster.json", CLUSTER_OK),
                          ("BENCH_e2e.json", E2E_OK),
                          ("BENCH_accuracy.json", ACCURACY_OK),
                          ("BENCH_trace.json", TRACE_OK)):
        f = tmp_path / name
        f.write_text(json.dumps(payload))
        schema.validate_file(f)
    unknown = tmp_path / "BENCH_other.json"
    unknown.write_text("{}")
    with pytest.raises(SchemaError, match="no schema registered"):
        schema.validate_file(unknown)


# --------------------------------------------------- the real artifact ---

@pytest.mark.slow
def test_fig8_artifact_passes_roofline_schema():
    """Run the real fig8 benchmark in-process and validate the exact
    payload run.py would write — the PR's acceptance shape."""
    from benchmarks import common, fig8_macs_per_issue, run

    saved = common.ROWS[:]
    common.ROWS.clear()
    try:
        fig8_macs_per_issue.main()
        payload = run.payload_from_rows(common.ROWS)
    finally:
        common.ROWS[:] = saved
    schema.validate_fig8_roofline(payload, bits=(8, 4, 2))

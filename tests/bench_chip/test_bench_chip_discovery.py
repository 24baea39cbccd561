"""A cell's parts are found by name: a new configuration, traffic mix
and per-layer metric are new files plus new BENCHMARK.json entries, with
no edit to an existing file. Also the benchmark file's own shape."""
import json
import re
import shutil
import types

import pytest

from benchmarks.chip import cell as cells
from bench_chip_smoke import ROOT, bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture
def tree(tmp_path):
    """A copy of the benchmark's data and code directories."""
    src = ROOT / "benchmarks" / "chip"
    for sub in ("configs", "traffic", "families", "metrics"):
        shutil.copytree(src / sub, tmp_path / sub)
    return tmp_path


def test_new_config_mix_and_metric_are_found_by_name(tree):
    before = {p: p.read_bytes() for p in tree.rglob("*") if p.is_file()}
    qwen = json.loads((tree / "configs" / "qwen2.5-3b-w4a8.json").read_text())
    (tree / "configs" / "qwen2.5-3b-w8a8.json").write_text(json.dumps(
        dict(qwen, serving=dict(qwen["serving"], w_bits=8))))
    (tree / "traffic" / "lm_bursty.json").write_text(json.dumps(
        {"slots": 32, "max_len": 256, "rate_per_s": 3.0, "drain_s": 0,
         "check_sample": 4,
         "prompt_len": {"dist": "uniform", "min": 8, "max": 64},
         "output_len": {"dist": "uniform", "min": 8, "max": 64}}))
    (tree / "metrics" / "queue_wait_ms.lm.py").write_text(
        "def read(run):\n    return 7.0\n")
    b = bench()
    b["configs"].append({"name": "qwen2.5-3b-w8a8"})
    b["workloads"].append({"name": "qwen3b-w8a8-bursty",
                           "config": "qwen2.5-3b-w8a8",
                           "traffic": "lm_bursty", "chips": 1})
    b["per_layer"].append({"name": "queue_wait_ms.lm", "unit": "ms",
                           "moves": "itl_p95_ms",
                           "workloads": ["qwen3b-w8a8-bursty"]})
    for m in b["end_to_end"]:
        if m["name"] in ("tokens_per_s", "itl_p95_ms"):
            m["workloads"].append("qwen3b-w8a8-bursty")

    c = cells.load_cell("qwen3b-w8a8-bursty", b, base=tree)
    assert c.config["serving"]["w_bits"] == 8
    assert c.config["name"] == "qwen2.5-3b-w8a8"
    assert c.traffic["rate_per_s"] == 3.0
    assert c.family.__name__.endswith("families_lm_py")
    assert {m["name"] for m in c.end_to_end} == {
        "tokens_per_s", "itl_p95_ms", "setup_s"}
    assert "queue_wait_ms.lm" in {m["name"] for m in c.per_layer}
    assert cells.metric_module("queue_wait_ms.lm", tree).read(None) == 7.0
    # the old cells still resolve, and no file that was there changed
    assert cells.load_cell("qwen3b-w4a8-decode", b, base=tree).per_layer
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_every_cell_resolves_and_every_metric_has_a_reader():
    b = bench()
    for w in b["workloads"]:
        c = cells.load_cell(w["name"], b)
        assert c.family.build
        names = [m["name"] for m in c.end_to_end + c.per_layer]
        assert "setup_s" in names
        assert len(c.end_to_end) >= 2 and c.per_layer
        for n in names:
            mod = cells.metric_module(n)
            assert callable(mod.read)


def test_benchmark_file_keeps_the_contract_shape():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for p in b["paths"]:
        assert (ROOT / p).is_dir()
    assert 1 <= b["run_seconds"] <= 51
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    layers = {}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in names
        names.add(m["name"])
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        moves = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        for w in m["workloads"]:
            assert "workloads" not in moves or w in moves["workloads"]
        layers.setdefault(m["layer"], []).append(m["name"])
    assert set(layers) == {"scheduler", "model step", "kernels", "device"}


def test_a_reader_that_finds_nothing_reports_nothing():
    run = types.SimpleNamespace(trace=None, events=None)
    for name in ("idle_share.lm", "idle_share.cnn", "step_hbm_share.lm",
                 "qconv_roofline.cnn"):
        assert cells.metric_module(name).read(run) is None

"""A CPU rehearsal of `benchmarks/chip/run.py` on small cells: the whole
run (set-up, open-loop window, check, metrics, result line) with the
look for a chip skipped. No number from these runs means anything about
speed; they prove the control flow and the check."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmarks.chip import run
from bench_chip_smoke import ROOT, cnn_cell, lm_cell, no_persistent_cache

SEED = 2**31 + 977


def _run(cell, seconds=2.0):
    return run.run_cell(cell, seed=SEED, seconds=seconds, trace=False,
                        require_tpu=False, t_start=time.perf_counter())


def test_lm_cell_rehearsal(monkeypatch):
    no_persistent_cache(monkeypatch)
    out = _run(lm_cell())
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert list(out)[-1] == "check"
    assert out["check"]["max_logit_gap"]["value"] <= \
        out["check"]["max_logit_gap"]["limit"]
    assert out["device"]["platform"] == "cpu"
    json.dumps(out)


def test_lm_prefill_mix_reports_time_to_first_token(monkeypatch):
    """The prefill cell with its preroll and drain."""
    no_persistent_cache(monkeypatch)
    out = _run(lm_cell("qwen3b-w4a8-prefill"))
    assert out["correct"] is True
    assert set(out["metrics"]) == {"itl_p95_ms", "ttft_p95_ms", "setup_s"}


def test_cnn_cell_rehearsal_under_the_interpreter(monkeypatch):
    no_persistent_cache(monkeypatch)
    monkeypatch.setenv("REPRO_QBACKEND", "pallas_interpret")
    out = _run(cnn_cell())
    assert out["correct"] is True
    assert out["check"]["mismatched_answers"]["value"] == 0
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}


def _cli(cwd, env):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "resnet8-stream", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = _cli(ROOT, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_cli_refuses_without_the_program(tmp_path):
    """Only BENCHMARK.json and the benchmark's own files: no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _cli(tmp_path, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""

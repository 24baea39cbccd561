"""Small cells of the chip benchmark that the CPU can run in seconds: the
benchmark's own configurations and mixes with their sizes cut, built by
`benchmarks.chip.cell` as a run builds them. Helpers for the tests in
this directory; nothing here touches a TPU."""
from __future__ import annotations

import copy
import json
import pathlib

from benchmarks.chip import cell as cells

ROOT = pathlib.Path(__file__).resolve().parents[2]

# Qwen2.5-3B's block at toy widths. With two layers and a tied head a
# large embedding makes each token predict itself whatever the context;
# at sqrt(64) * 0.125 = 1 the logits spread by about 1 and the context
# decides, as it does at full depth.
LM_SMOKE = dict(hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=2, vocab_size=128)
LM_SMOKE_EMBED_STD = 0.125
# The smoke cell's own limit on the widest logit gap, set from CPU
# readings at this size on seeds 11-13: sound runs 0.045-0.090; the A4
# control 2.10-3.38; the planted faults (state unchanged, half batch,
# token altered) 3.43-4.91. The full-size limit is in the config file.
LM_SMOKE_LIMIT = 0.5
LM_SMOKE_TRAFFIC = dict(
    slots=4, max_len=48, rate_per_s=40.0, check_sample=8, preroll_s=0.5,
    prompt_len={"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2,
                "max": 12},
    output_len={"dist": "lognormal", "median": 10, "sigma": 0.6, "min": 4,
                "max": 24})
CNN_SMOKE_TRAFFIC = dict(slots=4, rate_per_s=200.0, check_sample=16,
                         preroll_s=0.5)


def bench() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def lm_cell(workload: str = "qwen3b-w4a8-decode") -> cells.Cell:
    """An LM cell of the benchmark at the smoke sizes."""
    c = cells.load_cell(workload, bench())
    c.config = copy.deepcopy(c.config)
    c.config.update(LM_SMOKE)
    c.config["weights"] = dict(c.config["weights"],
                               embed_std=LM_SMOKE_EMBED_STD)
    c.config["check"] = dict(c.config["check"], max_logit_gap=LM_SMOKE_LIMIT)
    c.config["serving"] = dict(c.config["serving"])
    c.traffic = dict(c.traffic, **LM_SMOKE_TRAFFIC)
    c.traffic["drain_s"] = min(c.traffic.get("drain_s", 0), 2.0)
    return c


def cnn_cell(workload: str = "resnet8-stream") -> cells.Cell:
    c = cells.load_cell(workload, bench())
    c.traffic = dict(c.traffic, **CNN_SMOKE_TRAFFIC)
    return c


def no_persistent_cache(monkeypatch):
    """Keep the tests' CPU programs out of the checkout's compile cache."""
    from benchmarks.chip import run
    monkeypatch.setattr(run, "enable_cache", lambda: None)

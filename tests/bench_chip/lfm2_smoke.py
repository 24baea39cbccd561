"""The LFM2 decode cell of the chip benchmark at sizes the CPU runs in
seconds, built as `bench_chip_smoke` builds the other cells. Helpers for
the tests in this directory; nothing here touches a TPU."""
from __future__ import annotations

import copy

from benchmarks.chip import cell as cells
from bench_chip_smoke import LM_SMOKE_TRAFFIC, bench

# LFM2's block at toy widths: conv, conv, attention, conv; the first
# feed-forward dense, then 8 experts top-2.
LFM2_CELL = "lfm2-8b-w4a8-decode"
LFM2_SMOKE = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=64,
    num_attention_heads=4, num_key_value_heads=2, num_experts=8,
    num_experts_per_tok=2, num_hidden_layers=4, num_dense_layers=1,
    layer_types=["conv", "conv", "full_attention", "conv"], vocab_size=128)
LFM2_SMOKE_EMBED_STD = 0.125
# The LFM2 smoke cell's own limit on the widest logit gap, set from CPU
# readings at this size (the configuration's write gains) on 12 seeds:
# sound runs 0-0.159; the A4 control 2.04-3.83; the planted faults
# (state unchanged, half batch, token altered) 1.34-6.39, or NaN where
# the altered token falls outside the vocabulary. The full-size limit is
# in the config file.
LFM2_SMOKE_LIMIT = 0.5


def lfm2_config() -> dict:
    """The LFM2 configuration file as the benchmark reads it."""
    return cells.load_cell(LFM2_CELL, bench()).config


def lfm2_smoke_config(layout=None, **serving) -> dict:
    """The LFM2 configuration at the smoke sizes; ``layout`` replaces
    the layer kinds, ``serving`` entries the serving precision."""
    cfg = copy.deepcopy(lfm2_config())
    cfg.update(LFM2_SMOKE)
    cfg.update(layout or {})
    cfg["weights"]["embed_std"] = LFM2_SMOKE_EMBED_STD
    cfg["check"]["max_logit_gap"] = LFM2_SMOKE_LIMIT
    cfg["serving"].update(serving)
    return cfg


def lfm2_cell() -> cells.Cell:
    """The LFM2 decode cell at the smoke sizes."""
    c = cells.load_cell(LFM2_CELL, bench())
    c.config = lfm2_smoke_config()
    c.traffic = dict(c.traffic, **LM_SMOKE_TRAFFIC)
    return c

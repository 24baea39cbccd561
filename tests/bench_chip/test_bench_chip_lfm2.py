"""LFM2-8B-A1B in the chip benchmark, on the CPU at a small size: the
costs pinned to the published sizes and to the program's served tree,
prefill and decode through the program's cache and conv state against
`reference/lfm2.py`, the cell rehearsed untraced and traced, and its
readers. No number from these runs means anything about speed."""
import copy
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import cell as cells, moe_costs, run, trace_reduce
from benchmarks.chip.families import lfm2 as family
from benchmarks.chip.reference import lfm2 as reference
from bench_chip_smoke import no_persistent_cache
from lfm2_smoke import (LFM2_SMOKE_LIMIT, lfm2_cell as smoke_cell,
                        lfm2_config as config,
                        lfm2_smoke_config as smoke_config)

SEED = 2**31 + 977
SMOKE_LIMIT = LFM2_SMOKE_LIMIT


# Layouts whose decode scans runs of layers of one kind, as the published
# one does (runs of two and three conv layers with MoE). After the dense
# first layer: two conv+MoE, attention+MoE, three conv+MoE,
# attention+MoE; and two attention+MoE (a scan over the KV stack), two
# conv+MoE, attention+MoE.
RUNS = dict(num_hidden_layers=8, layer_types=[
    "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
    "full_attention"])
RUNS_ATTN = dict(num_hidden_layers=6, layer_types=[
    "conv", "full_attention", "full_attention", "conv", "conv",
    "full_attention"])


# -------------------------------------------------------------- costs ---

def test_costs_pin_the_published_sizes():
    cfg = config()
    total = moe_costs.param_bytes(cfg)
    experts = 22 * 32 * moe_costs.expert_bytes(cfg)
    assert total == 4_662_220_544                     # 4.66 GB served
    assert experts == 3_891_396_608                   # 177 MB a layer
    assert 0.83 < experts / total < 0.84
    assert moe_costs.kv_bytes_per_position(cfg) == 12_288
    assert moe_costs.macs_per_token(cfg, 0) == pytest.approx(1.5576e9,
                                                             rel=1e-3)
    assert moe_costs.step_bytes(cfg, 0, 22 * 32) == total
    assert moe_costs.step_bytes(cfg, 10, 0) == \
        moe_costs.non_expert_bytes(cfg) + 10 * 12_288


def test_costs_count_the_program_served_tree():
    """The program's parameter tree at the published sizes (shapes only)
    holds exactly the bytes `moe_costs` counts."""
    served = family.Served(config(), {"max_len": 16})
    shapes = jax.eval_shape(served.model.init, jax.random.PRNGKey(0))
    assert sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes)) \
        == moe_costs.param_bytes(config())


# ------------------------------------------------- against the reference ---

def _decode_logits(served, tokens, dtype):
    """Prefill by feeding, then decode, through the program's cache and
    conv state: the last position's logits of every step, (B, T, V)."""
    b, t = tokens.shape
    cache = served.model.init_cache(b, t, dtype)
    step = jax.jit(served.model.decode)
    out = []
    for i in range(t):
        lg, cache, _ = step(served.params, cache,
                            jnp.asarray(tokens[:, i:i + 1]),
                            jnp.full((b,), i, jnp.int32))
        out.append(np.asarray(lg[:, -1], np.float32))
    return np.stack(out, 1)[..., : served.cfg["vocab_size"]]


@pytest.mark.parametrize("seed,layout", [(11, None), (12, None),
                                         (13, RUNS), (14, RUNS_ATTN)],
                         ids=["smoke-11", "smoke-12", "conv_runs",
                              "attn_runs"])
def test_prefill_and_decode_through_the_cache_match_the_reference(seed,
                                                                  layout):
    """Computed in float32, the program's step by step logits are the
    reference's full-sequence logits; the A4 control is far off. The
    smoke layout runs every layer unrolled; the others scan runs of
    layers of one kind, as the published layout does."""
    from repro.models.lm import _hybrid_layout, _runs

    served = family.Served(smoke_config(layout, compute_dtype="float32"),
                           {"max_len": 24})
    runs = _runs(_hybrid_layout(served.model.cfg)[0])
    assert any(len(r[2]) > 1 for r in runs) == (layout is not None)
    served.load(seed)
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 128, (3, 20)).astype(np.int32)
    got = _decode_logits(served, tokens, jnp.float32)
    want = np.asarray(reference.logits(served.params, jnp.asarray(tokens),
                                       served.cfg))
    a4 = np.asarray(reference.logits(served.params, jnp.asarray(tokens),
                                     served.cfg, {"a_bits": 4}))
    assert np.abs(got - want).max() < 1e-3
    assert np.abs(a4 - want).max() > 1.0


def test_served_bf16_logits_stay_near_the_reference():
    served = family.Served(smoke_config(), {"max_len": 24})
    served.load(13)
    tokens = np.random.default_rng(13).integers(0, 128, (3, 20)).astype(
        np.int32)
    got = _decode_logits(served, tokens, jnp.bfloat16)
    want = np.asarray(reference.logits(served.params, jnp.asarray(tokens),
                                       served.cfg))
    assert np.median(np.abs(got - want)) < 0.1 * want.std()


# The published depth and layout at a quarter of the width: 24 layers,
# 32 experts top-4.
DEEP = dict(hidden_size=256, intermediate_size=896, moe_intermediate_size=224,
            num_attention_heads=4, num_key_value_heads=1, vocab_size=2048)


@pytest.mark.parametrize("equal_writes,lo,hi", [(True, 0.4, 1.0),
                                                (False, 0.0, 0.2)],
                         ids=["equal_writes", "configured_writes"])
def test_bf16_drift_over_the_published_depth(equal_writes, lo, hi):
    """Why the configuration writes its conv and MoE layers small. With
    every layer writing to the residual stream at one scale, random conv
    layers and routing flips between near-tied experts grow bf16 rounding
    over 24 layers until the served logits are far from the float32
    reference's (CPU, seeds 5 and 9: 0.58 of their spread); at the
    configuration's write gains they stay near it (0.11-0.12)."""
    cfg = copy.deepcopy(config())
    cfg.update(DEEP)
    if equal_writes:
        cfg["weights"]["write_gain"] = {}
    served = family.Served(cfg, {"max_len": 16})
    served.load(5)
    tokens = np.random.default_rng(5).integers(0, 2048, (2, 16)).astype(
        np.int32)
    got = _decode_logits(served, tokens, jnp.bfloat16)
    want = np.asarray(reference.logits(served.params, jnp.asarray(tokens),
                                       cfg))
    drift = np.sqrt(np.mean((got - want) ** 2)) / want.std()
    assert lo < drift < hi


# ---------------------------------------------------------- rehearsals ---

def test_lfm2_cell_rehearsal(monkeypatch):
    no_persistent_cache(monkeypatch)
    out = run.run_cell(smoke_cell(), seed=SEED, seconds=2.0, trace=False,
                       require_tpu=False, t_start=time.perf_counter())
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert out["check"]["kv_bits_short"]["value"] == 0
    assert out["check"]["max_logit_gap"]["value"] <= SMOKE_LIMIT
    assert out["device"]["platform"] == "cpu"


def test_lfm2_traced_rehearsal_reads_its_per_layer_metrics(monkeypatch):
    """A traced run on the CPU. Its profile has no device plane, so each
    ``bench.step`` gets a made-up op and a ``jit_decode`` program over its
    first 30%."""
    from repro.obs import trace as obs

    no_persistent_cache(monkeypatch)
    extract = trace_reduce.extract

    def with_device(xp):
        ev = extract(xp)
        ops = [["fusion", s, d * 0.3] for n, s, d in ev["host"]
               if n == "bench.step"]
        mods = [["jit_decode", s, d] for _, s, d in ops]
        ev["devices"] = {"/device:TPU:0": {"ops": ops, "modules": mods}}
        return ev

    monkeypatch.setattr(trace_reduce, "extract", with_device)
    obs.reset()
    out = run.run_cell(smoke_cell(), seed=SEED, seconds=3.0, trace=True,
                       require_tpu=False, t_start=time.perf_counter())
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"step_mfu.moe", "step_hbm_share.moe", "idle_share.lm"} <= set(m)
    assert 60 < m["idle_share.lm"] < 80
    steps = len(obs.spans("lm.dispatch"))
    per_step = obs.counter_values()["moe.experts_hit"] / steps
    assert 3 * 2 <= per_step <= 3 * 8     # 3 MoE layers, top-2 of 8
    assert len(obs.spans("lm.state_reset")) > 0


def test_new_readers_report_nothing_without_their_inputs(monkeypatch):
    from repro.obs import trace as obs

    run_ = types.SimpleNamespace(trace=None, events=None, window=None)
    for name in ("idle_share.lm", "step_hbm_share.moe"):
        assert cells.metric_module(name).read(run_) is None
    monkeypatch.setattr(obs, "counter_values", lambda: {})
    reader = cells.metric_module("step_hbm_share.moe")
    assert reader.experts_hit_per_step() is None


def test_kv_bits_short_counts_the_attention_layers_only():
    cfg = config()
    traffic = {"slots": 256, "max_len": 1024}
    served = family.Served.__new__(family.Served)
    served.cfg, served.traffic = cfg, traffic
    kv = 1024 * 256 * moe_costs.kv_bytes_per_position(cfg)
    conv = 18 * 256 * 2 * 2048 * 2
    win = types.SimpleNamespace(state_bytes=kv + conv)
    assert served.kv_bits_short(win) == 0
    win = types.SimpleNamespace(state_bytes=kv // 2 + conv)
    assert 7.5 < served.kv_bits_short(win) < 8.0        # an int8 cache

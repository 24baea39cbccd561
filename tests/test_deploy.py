"""Mixed-precision deployment planner: policy resolution + JSON round-trip,
calibration stats, budgeted bit-width search, plan-driven packing
(bit-exact vs the uniform path per layer), and plan serving."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.qwen2p5_3b import smoke_config
from repro.deploy.apply import (apply_plan, dense_inventory,
                                quantized_dense_paths)
from repro.deploy.calibrate import CalibStats, calibrate
from repro.deploy.planner import (auto_budget, packed_weight_bytes,
                                  plan_mixed_precision)
from repro.deploy.policy import (PlanRule, PrecisionPlan, load_plan,
                                 resolve_qcfg, save_plan)
from repro.launch.convert import artifact_bytes, convert_params
from repro.models.api import Model, build
from repro.nn.layers import QuantConfig, dense_apply
from repro.serve.runtime import LMDecodeAdapter, Request, Scheduler

QINT = QuantConfig(mode="int", w_bits=8, a_bits=8)

EXPECTED_PATHS = {"layers/attn/wq", "layers/attn/wk", "layers/attn/wv",
                  "layers/attn/wo", "layers/mlp/wi", "layers/mlp/wg",
                  "layers/mlp/wo"}


def _smoke_models(plan=None):
    cfg = smoke_config()
    fp = build(cfg)
    q = Model(dataclasses.replace(cfg, quant=QINT, quant_plan=plan))
    return fp, q


# ---------------------------------------------------------------- policy ---

def test_policy_resolution_first_match_wins():
    plan = PrecisionPlan(rules=(
        PlanRule("layers/mlp/wi", 2, a_absmax=3.0),
        PlanRule("layers/mlp/*", 4),
        PlanRule("layers/attn/w[qk]", 8),
    ))
    base = QuantConfig(mode="int", a_absmax=5.0)
    assert plan.resolve("layers/mlp/wi", base).w_bits == 2
    assert plan.resolve("layers/mlp/wi", base).a_absmax == 3.0
    assert plan.resolve("layers/mlp/wg", base).w_bits == 4
    assert plan.resolve("layers/mlp/wg", base).a_absmax == 5.0  # inherited
    assert plan.resolve("layers/attn/wq", base).w_bits == 8
    # unmatched path -> plan defaults, base mode preserved
    r = plan.resolve("layers/attn/wo", base)
    assert r.w_bits == 8 and r.mode == "int"
    assert resolve_qcfg(None, "anything", base) is base


def test_plan_json_roundtrip(tmp_path):
    plan = PrecisionPlan(
        rules=(PlanRule("layers/mlp/*", 4, a_bits=8, a_absmax=2.5),
               PlanRule("layers/attn/*", 2, backend="pallas_interpret")),
        default_w_bits=8, meta={"arch": "qwen-smoke", "budget": 0.5})
    f = tmp_path / "plan.json"
    save_plan(plan, f)
    got = load_plan(f)
    assert got == plan                      # eq over rules + defaults
    assert got.meta["arch"] == "qwen-smoke"
    assert got.distinct_w_bits() == (2, 4, 8)
    # plans are hashable (they ride inside frozen ModelConfig)
    assert hash(got) == hash(plan)


# ----------------------------------------------------------- calibration ---

def test_calibrate_covers_all_quantized_paths(rng):
    fp, q = _smoke_models()
    params = fp.init(jax.random.PRNGKey(0))
    assert set(quantized_dense_paths(q.defs())) == EXPECTED_PATHS
    batches = [rng.integers(2, fp.cfg.vocab, size=(2, 16)).astype(np.int32)
               for _ in range(2)]
    stats = calibrate(fp, params, batches)
    assert set(stats) == EXPECTED_PATHS
    for st in stats.values():
        assert st.taps > 0 and st.a_absmax > 0
        # narrower grids hurt more (the knapsack's monotonicity premise)
        assert st.sens(2) > st.sens(4) > st.sens(8) >= 0
    inv = dense_inventory(params, stats)
    assert inv["layers/mlp/wi"] == (2, 64, 128)  # (L, K, N) of the smoke cfg


def test_calibrate_weight_only_fallback(rng):
    from repro.configs.mamba2_370m import smoke_config as mamba_smoke
    cfg = mamba_smoke()
    fp = build(cfg)
    params = fp.init(jax.random.PRNGKey(0))
    batches = [rng.integers(2, cfg.vocab, size=(2, 8)).astype(np.int32)]
    stats = calibrate(fp, params, batches)
    assert stats and all(st.sens(2) > st.sens(8) for st in stats.values())
    assert {"layers/mixer/in_proj", "layers/mixer/out_proj"} <= set(stats)


# ---------------------------------------------------------------- planner ---

def _fake_stats():
    """Hand-built stats: one cheap-to-narrow path, one expensive."""
    a = CalibStats("layers/mlp/wi", 2, 64, 128, a_absmax=3.0,
                   sq_err={8: 1e-6, 4: 1e-4, 2: 1e-3}, sq_ref=1.0, taps=1)
    b = CalibStats("layers/attn/wq", 2, 64, 64, a_absmax=2.0,
                   sq_err={8: 1e-6, 4: 0.5, 2: 5.0}, sq_ref=1.0, taps=1)
    return {a.path: a, b.path: b}


def test_planner_respects_budget_and_mixes():
    stats = _fake_stats()
    base = sum(st.sens(8) for st in stats.values())
    # budget admits wi all the way down but forbids touching wq
    plan = plan_mixed_precision(stats, base + 0.01)
    bits = {r.pattern: r.w_bits for r in plan.rules}
    assert bits["layers/mlp/wi"] == 2
    assert bits["layers/attn/wq"] == 8
    assert plan.meta["total_sensitivity"] <= base + 0.01
    assert len(set(bits.values())) >= 2
    # zero headroom -> nothing demoted
    all8 = plan_mixed_precision(stats, base)
    assert all(r.w_bits == 8 for r in all8.rules)
    # unbounded -> everything at the narrowest candidate
    all2 = plan_mixed_precision(stats, 1e9)
    assert all(r.w_bits == 2 for r in all2.rules)


def test_planner_monotone_in_budget():
    stats = _fake_stats()
    budgets = np.linspace(0.0, 6.0, 8)
    prev = None
    for b in budgets:
        plan = plan_mixed_precision(stats, b)
        total = plan.meta["packed_weight_bytes"]
        if prev is not None:
            assert total <= prev  # more budget never costs bytes
        prev = total


def test_packed_weight_bytes_matches_artifact():
    """The planner's byte accounting == actual packed artifact bytes."""
    fp, q = _smoke_models()
    fp_params = fp.init(jax.random.PRNGKey(0))
    q_params = convert_params(q.init(jax.random.PRNGKey(0)), fp_params, 8)
    inv = dense_inventory(fp_params, quantized_dense_paths(q.defs()))
    planned = sum(packed_weight_bytes(*shape, 8) for shape in inv.values())
    # difference = everything convert leaves fp (embeds, norms, biases)
    fp_rest = artifact_bytes(q_params) - planned
    assert fp_rest >= 0
    got = sum(
        q_params["layers"][g][n]["w_packed"].nbytes
        + q_params["layers"][g][n]["w_scale"].nbytes
        for g, names in (("attn", ("wq", "wk", "wv", "wo")),
                         ("mlp", ("wi", "wg", "wo"))) for n in names)
    assert got == planned


def test_int_dense_honors_a_bits_and_matches_sim(rng):
    """The serving int path quantizes activations on the qcfg.a_bits grid,
    and the calibrator's sensitivity simulation uses that exact grid —
    what the planner prices is what serving runs."""
    import jax.numpy as jnp

    from repro.deploy.calibrate import _sim_int_dense
    from repro.nn.layers import pack_dense_weights

    w = (rng.normal(size=(128, 32)) * 0.1).astype(np.float32)
    x = rng.normal(size=(4, 128)).astype(np.float32)
    packed, scale = pack_dense_weights(jnp.asarray(w), 8)
    p = {"w_packed": packed, "w_scale": scale}
    outs = {}
    for a_bits in (8, 4, 2):
        qcfg = QuantConfig(mode="int", w_bits=8, a_bits=a_bits, a_absmax=4.0)
        outs[a_bits] = np.asarray(dense_apply(p, jnp.asarray(x), qcfg=qcfg))
        sim = np.asarray(_sim_int_dense(jnp.asarray(x), jnp.asarray(w), 8,
                                        a_bits, 4.0))
        np.testing.assert_allclose(outs[a_bits], sim, rtol=1e-5, atol=1e-6)
    assert not np.allclose(outs[8], outs[4])
    assert not np.allclose(outs[4], outs[2])


# ------------------------------------------------------------------ apply ---

def _mixed_plan():
    return PrecisionPlan(rules=(
        PlanRule("layers/attn/*", 8, a_absmax=4.0),
        PlanRule("layers/mlp/wi", 4, a_absmax=4.0),
        PlanRule("layers/mlp/wg", 4, a_absmax=4.0),
        PlanRule("layers/mlp/wo", 2, a_absmax=4.0),
    ))


def test_apply_plan_bit_exact_vs_uniform_per_layer():
    """Every plan-quantized dense == the uniform int path at that layer's
    bit-width: identical packed containers, scales, and dense outputs."""
    plan = _mixed_plan()
    fp, q = _smoke_models(plan)
    fp_params = fp.init(jax.random.PRNGKey(0))
    q_params = apply_plan(q.init(jax.random.PRNGKey(0)), fp_params, plan)

    per_path_bits = {"layers/attn/wq": 8, "layers/attn/wk": 8,
                     "layers/attn/wv": 8, "layers/attn/wo": 8,
                     "layers/mlp/wi": 4, "layers/mlp/wg": 4,
                     "layers/mlp/wo": 2}
    rng = np.random.default_rng(1)
    for bits in (8, 4, 2):
        _, u = _smoke_models()
        u_model = Model(dataclasses.replace(
            u.cfg, quant=dataclasses.replace(QINT, w_bits=bits)))
        u_params = convert_params(u_model.init(jax.random.PRNGKey(0)),
                                  fp_params, bits)
        for path, b in per_path_bits.items():
            if b != bits:
                continue
            grp, name = path.split("/")[1:]
            got = q_params["layers"][grp][name]
            want = u_params["layers"][grp][name]
            np.testing.assert_array_equal(np.asarray(got["w_packed"]),
                                          np.asarray(want["w_packed"]))
            np.testing.assert_array_equal(np.asarray(got["w_scale"]),
                                          np.asarray(want["w_scale"]))
            # and the integer GEMM output is bit-identical layer-by-layer
            d_in = fp_params["layers"][grp][name]["w"].shape[1]
            x = rng.normal(size=(3, d_in)).astype(np.float32)
            qcfg = plan.resolve(path, QINT)
            ucfg = dataclasses.replace(QINT, w_bits=bits,
                                       a_absmax=qcfg.a_absmax)
            for layer in range(got["w_packed"].shape[0]):
                lg = {k: v[layer] for k, v in got.items()}
                lw = {k: v[layer] for k, v in want.items()}
                yg = dense_apply(lg, x, qcfg=qcfg)
                yw = dense_apply(lw, x, qcfg=ucfg)
                np.testing.assert_array_equal(np.asarray(yg),
                                              np.asarray(yw))


def test_apply_plan_shrinks_artifact_below_uniform_w8():
    plan = _mixed_plan()
    fp, q = _smoke_models(plan)
    fp_params = fp.init(jax.random.PRNGKey(0))
    q_params = apply_plan(q.init(jax.random.PRNGKey(0)), fp_params, plan)
    _, u8 = _smoke_models()
    u8_params = convert_params(u8.init(jax.random.PRNGKey(0)), fp_params, 8)
    assert artifact_bytes(q_params) < artifact_bytes(u8_params)


def test_apply_plan_wrong_plan_raises():
    plan = _mixed_plan()
    fp, q = _smoke_models(plan)
    fp_params = fp.init(jax.random.PRNGKey(0))
    other = PrecisionPlan(rules=(PlanRule("layers/*/w*", 2),))
    with pytest.raises(ValueError, match="not built with this plan"):
        apply_plan(q.init(jax.random.PRNGKey(0)), fp_params, other)


def test_plan_json_apply_roundtrip(tmp_path):
    """plan JSON -> apply -> identical artifact as the in-memory plan."""
    plan = _mixed_plan()
    f = tmp_path / "plan.json"
    save_plan(plan, f)
    loaded = load_plan(f)
    fp, q = _smoke_models(plan)
    fp_params = fp.init(jax.random.PRNGKey(0))
    a = apply_plan(q.init(jax.random.PRNGKey(0)), fp_params, plan)
    b = apply_plan(q.init(jax.random.PRNGKey(0)), fp_params, loaded)
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


# ---------------------------------------------------------------- serving ---

def test_engine_serves_mixed_plan():
    plan = _mixed_plan()
    fp, q = _smoke_models(plan)
    fp_params = fp.init(jax.random.PRNGKey(0))
    q_params = apply_plan(q.init(jax.random.PRNGKey(0)), fp_params, plan)
    adapter = LMDecodeAdapter(q, q_params, 32, plan=plan)
    reqs = [Request(prompt=np.array([3, 5, 7], np.int32), max_new_tokens=4),
            Request(prompt=np.array([11, 2], np.int32), max_new_tokens=4),
            Request(prompt=np.array([9], np.int32), max_new_tokens=4)]
    out = Scheduler(adapter, 2).serve(reqs)
    assert len(out) == 3
    for r in out:
        assert r.out is not None and 1 <= len(r.out) <= 4
        assert (r.out >= 0).all() and (r.out < fp.cfg.vocab).all()
    assert adapter.plan is plan
    assert artifact_bytes(adapter.params) == artifact_bytes(q_params)


def test_end_to_end_calibrate_plan_pack(rng):
    """The full subsystem flow at smoke scale: calibrate -> auto budget ->
    plan (>= 2 distinct bit-widths) -> pack (< uniform w8)."""
    fp, _ = _smoke_models()
    fp_params = fp.init(jax.random.PRNGKey(0))
    batches = [rng.integers(2, fp.cfg.vocab, size=(2, 16)).astype(np.int32)]
    stats = calibrate(fp, fp_params, batches)
    plan = plan_mixed_precision(stats, auto_budget(stats))
    assigned = {r.w_bits for r in plan.rules}
    assert len(assigned) >= 2
    q = Model(dataclasses.replace(fp.cfg, quant=QINT, quant_plan=plan))
    q_params = apply_plan(q.init(jax.random.PRNGKey(0)), fp_params, plan)
    _, u8 = _smoke_models()
    u8_params = convert_params(u8.init(jax.random.PRNGKey(0)), fp_params, 8)
    assert artifact_bytes(q_params) < artifact_bytes(u8_params)
    assert plan.meta["packed_weight_bytes"] < plan.meta["uniform_w8_bytes"]


# ------------------------------------------ fine-grain (channel groups) ---

def _fine_stats():
    """Skewed intra-layer sensitivity: path a's FIRST channel group is hot
    (demoting it below 8 bits is catastrophic) while the rest of the layer
    is nearly free to narrow; path b is uniformly cheap. A per-layer plan
    must keep ALL of a at 8 bits to protect the hot group — the
    channel-group plan carves it out and demotes the remaining channels."""
    import numpy as np
    from repro.core.packing import CHUNK

    def stats_for(path, d_out, hot_first_group):
        base = {8: 1e-8, 4: 1e-4, 2: 1e-2}
        col = {}
        for b, tot in base.items():
            cols = np.full((d_out,), tot / d_out, np.float64)
            if hot_first_group and b < 8:
                cols[:CHUNK] = 10.0 / CHUNK
            col[b] = cols
        return CalibStats(path, layers=2, d_in=256, d_out=d_out,
                          a_absmax=3.0,
                          sq_err={b: float(c.sum()) for b, c in col.items()},
                          sq_ref=1.0, taps=1, col_sq_err=col)

    a = stats_for("layers/mlp/wi", 3 * 128, hot_first_group=True)
    b = stats_for("layers/attn/wq", 2 * 128, hot_first_group=False)
    return {a.path: a, b.path: b}


def test_fine_plan_beats_per_layer_at_equal_budget():
    """At equal sensitivity budget the channel-group plan packs STRICTLY
    fewer bytes than the best per-layer plan on skewed stats (the ISSUE's
    acceptance bar), and the winning rule carries segments with the hot
    group kept wide."""
    stats = _fine_stats()
    budget = sum(st.sens(8) for st in stats.values()) + 0.05
    coarse = plan_mixed_precision(stats, budget, granularity="layer")
    fine = plan_mixed_precision(stats, budget, granularity="channel_group")
    assert (fine.meta["packed_weight_bytes"]
            < coarse.meta["packed_weight_bytes"])
    assert fine.meta["total_sensitivity"] <= budget
    assert fine.meta["granularity"] == "channel_group"
    by_pat = {r.pattern: r for r in fine.rules}
    wi = by_pat["layers/mlp/wi"]
    assert wi.segments is not None and len(wi.segments) >= 2
    s0, e0, b0 = wi.segments[0]
    assert (s0, e0, b0)[2] == 8 and e0 >= 128  # hot group survives at w8
    assert wi.w_bits == max(b for _, _, b in wi.segments)
    assert all(b < 8 for _, _, b in wi.segments[1:])
    # uniformly-cheap path stays a plain uniform rule (no segments)
    assert by_pat["layers/attn/wq"].segments is None


def test_fine_plan_never_worse_budget_sweep():
    """Best-of-both guarantee: across the whole budget range the fine plan
    never packs more bytes than per-layer at the same budget."""
    stats = _fine_stats()
    base = sum(st.sens(8) for st in stats.values())
    full = sum(st.sens(2) for st in stats.values())
    for frac in (0.0, 0.001, 0.01, 0.1, 0.5, 1.0):
        budget = base + frac * (full - base)
        coarse = plan_mixed_precision(stats, budget, granularity="layer")
        fine = plan_mixed_precision(stats, budget,
                                    granularity="channel_group")
        assert (fine.meta["packed_weight_bytes"]
                <= coarse.meta["packed_weight_bytes"]), frac
        # group-wise summation of the starting (all-w8) sensitivity can
        # differ from the layer sum in the last ulp — compare with slack
        assert fine.meta["total_sensitivity"] <= budget * (1 + 1e-9) + 1e-12


def test_fine_plan_group_size_validation():
    with pytest.raises(ValueError, match="CHUNK"):
        plan_mixed_precision(_fine_stats(), 1.0,
                             granularity="channel_group", group_size=100)
    with pytest.raises(ValueError, match="granularity"):
        plan_mixed_precision(_fine_stats(), 1.0, granularity="column")


def test_fine_plan_without_channel_detail_matches_layer_bytes():
    """No col_sq_err recorded: sensitivity is apportioned by group width,
    so groups demote together and the fine plan degenerates to (at worst)
    the per-layer answer — never an error, never more bytes."""
    stats = {p: dataclasses.replace(st, col_sq_err={})
             for p, st in _fine_stats().items()}
    budget = sum(st.sens(8) for st in stats.values()) + 0.05
    coarse = plan_mixed_precision(stats, budget, granularity="layer")
    fine = plan_mixed_precision(stats, budget, granularity="channel_group")
    assert (fine.meta["packed_weight_bytes"]
            <= coarse.meta["packed_weight_bytes"])


def test_plan_v4_json_roundtrip_with_segments(tmp_path):
    from repro.deploy.policy import PLAN_VERSION
    import json
    plan = plan_mixed_precision(
        _fine_stats(),
        sum(st.sens(8) for st in _fine_stats().values()) + 0.05,
        granularity="channel_group", backend="xla")
    assert any(r.segments for r in plan.rules)
    p = tmp_path / "plan.json"
    save_plan(plan, p)
    d = json.loads(p.read_text())
    assert d["version"] == PLAN_VERSION == 4
    loaded = load_plan(p)
    assert loaded.rules == plan.rules
    assert loaded.distinct_w_bits() == plan.distinct_w_bits()
    # segment widths surface in distinct_w_bits even when no uniform rule
    # uses them (the engine preloads kernels for every width it will see)
    seg_widths = {b for r in plan.rules if r.segments
                  for _, _, b in r.segments}
    assert seg_widths <= set(loaded.distinct_w_bits())


def test_plan_v3_artifact_loads_without_segments(tmp_path):
    """A v3 artifact (no segments field) loads clean: no warning, segments
    None everywhere, and resolution behaves exactly as before."""
    import json
    import warnings
    v3 = {
        "version": 3,
        "default": {"w_bits": 8, "a_bits": 8},
        "rules": [{"pattern": "layers/mlp/*", "w_bits": 4, "a_bits": 8,
                   "backend": "xla", "a_absmax": 3.0,
                   "pipeline": "double_buffer"}],
        "meta": {},
    }
    p = tmp_path / "v3.json"
    p.write_text(json.dumps(v3))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        plan = load_plan(p)
    assert all(r.segments is None for r in plan.rules)
    qcfg = plan.resolve("layers/mlp/wi", QINT)
    assert qcfg.w_bits == 4 and qcfg.segments is None
    # re-save upgrades to v4 with explicit null segments
    save_plan(plan, p)
    d = json.loads(p.read_text())
    assert d["version"] == 4
    assert d["rules"][0]["segments"] is None


def test_plan_rule_segment_validation():
    # w_bits must equal the widest run width
    with pytest.raises(ValueError, match="widest"):
        PlanRule("layers/*", 4, segments=((0, 128, 8), (128, 256, 2)))
    # malformed maps fail loudly through SegmentMap
    with pytest.raises(ValueError, match="multiple of CHUNK"):
        PlanRule("layers/*", 8, segments=((0, 100, 8), (100, 256, 2)))
    r = PlanRule("layers/*", 8, segments=[[0, 128, 8], [128, 200, 2]])
    assert r.segments == ((0, 128, 8), (128, 200, 2))  # normalized tuples


def test_apply_plan_segmented_dense_bit_exact(rng):
    """A v4 rule with segments packs through the segmented container and
    serves bit-exactly as the composition of per-run uniform denses."""
    import jax.numpy as jnp
    from repro.core import packing
    from repro.nn.layers import dense_def, pack_dense_weights
    from repro.nn.module import init_params

    d_in, d_out = 200, 300
    segs = ((0, 128, 8), (128, 256, 4), (256, 300, 2))
    plan = PrecisionPlan(rules=(
        PlanRule("blk/proj", 8, a_absmax=3.0, segments=segs),))
    qcfg = plan.resolve("blk/proj", QINT)
    defs = {"blk": {"proj": dense_def(d_in, d_out, qcfg=qcfg)}}
    q0 = init_params(defs, jax.random.PRNGKey(0))
    w = rng.normal(size=(d_in, d_out)).astype(np.float32) * 0.1
    fp_tree = {"blk": {"proj": {"w": jnp.asarray(w)}}}
    q_params = apply_plan(q0, fp_tree, plan)
    assert q_params["blk"]["proj"]["w_packed"].shape == (
        packing.SegmentMap(segs).packed_bytes(d_in),)

    x = rng.normal(size=(5, d_in)).astype(np.float32)
    got = np.asarray(dense_apply(q_params["blk"]["proj"], x, qcfg=qcfg))
    # oracle: each run packed/served by the plain uniform dense path
    parts = []
    for s, e, b in segs:
        packed, scale = pack_dense_weights(jnp.asarray(w[:, s:e]), b,
                                           assert_range=True)
        ucfg = dataclasses.replace(QINT, w_bits=b, a_absmax=3.0)
        parts.append(np.asarray(dense_apply(
            {"w_packed": packed, "w_scale": scale}, x, qcfg=ucfg)))
    np.testing.assert_array_equal(got, np.concatenate(parts, axis=-1))

"""The program's LFM2 pieces at a small size on the CPU: packed experts
and dropless routing (`nn/mlp.py`), the gated short conv and its state
(`nn/shortconv.py`, the serving adapter's reset), and the dense Qwen
decode program they sit beside, unchanged. The comparison of the whole
model with the plain reference is in `tests/bench_chip`."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import lfm2_8b_a1b, qwen2p5_3b
from repro.core import packing
from repro.models.api import build
from repro.nn.layers import QuantConfig, pack_dense_weights
from repro.nn.mlp import MoeConfig, moe_apply, moe_dropless
from repro.nn.module import init_params
from repro.nn.shortconv import (ShortConvConfig, shortconv_apply,
                                shortconv_decode, shortconv_def,
                                shortconv_init_state)
from repro.serve.runtime import LMDecodeAdapter, Request, Scheduler

W4A8 = QuantConfig(mode="int", w_bits=4, a_bits=8, a_absmax=4.0)


def _random_tree(params, key):
    """Every leaf random: int8 containers uniform bytes, floats normal
    around their init (norm scales near 1)."""
    flat, tree = jax.tree_util.tree_flatten(params)
    out = []
    for i, a in enumerate(flat):
        k = jax.random.fold_in(key, i)
        if a.dtype == jnp.int8:
            out.append(jax.random.randint(k, a.shape, -128, 128, jnp.int8))
        else:
            out.append(a + 0.1 * jax.random.normal(k, a.shape, a.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


# ------------------------------------------------- the dense Qwen decode ---

# The Qwen smoke model's serving program at W4A8 and what it serves, as
# the tree before hybrid stacks and packed experts came in produced them:
# the lowered StableHLO of the adapter's jitted step (no debug info, so
# source lines do not enter) and the greedy tokens of two requests.
QWEN_DECODE_HLO = (
    "f5bc11de8158f5b4732076dbce2b571de26cd293c81bd4c718579478e745d930")
QWEN_TOKENS = [[121, 95, 99, 120, 12, 1], [24, 73, 95, 7, 121]]


def _qwen_adapter():
    cfg = dataclasses.replace(qwen2p5_3b.smoke_config(), quant=W4A8)
    model = build(cfg)
    params = _random_tree(model.init(jax.random.PRNGKey(3)),
                          jax.random.PRNGKey(4))
    return LMDecodeAdapter(model, params, max_len=16, eos_id=-1)


def test_qwen_smoke_decode_program_unchanged():
    ad = _qwen_adapter()
    lowered = ad._decode.lower(ad.params, ad.init_state(2),
                               jnp.zeros((2, 1), jnp.int32),
                               jnp.zeros((2,), jnp.int32))
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest() == \
        QWEN_DECODE_HLO
    outs = Scheduler(ad, 2, policy="continuous").serve(
        [Request(prompt=np.array([5, 9, 3], np.int32), max_new_tokens=6),
         Request(prompt=np.array([7], np.int32), max_new_tokens=5)])
    assert [o.out.tolist() for o in outs] == QWEN_TOKENS


# ------------------------------------------------------------- experts ---

def _moe_cfg(qcfg=W4A8, **kw):
    base = dict(d_model=128, d_ff=128, n_experts=8, top_k=2,
                shared_expert=False, qcfg=qcfg, router="sigmoid",
                expert_bias=True, norm_topk=True)
    base.update(kw)
    return MoeConfig(**base)


def _packed_experts(key, cfg: MoeConfig):
    """Float experts and the same experts packed at W4 (per expert and
    output channel scales), with the float router."""
    ks = jax.random.split(key, 5)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    w = {"wi": jax.random.normal(ks[0], (e, d, f)) / d ** 0.5,
         "wg": jax.random.normal(ks[1], (e, d, f)) / d ** 0.5,
         "wo": jax.random.normal(ks[2], (e, f, d)) / f ** 0.5}
    packed = {}
    for n, a in w.items():
        wp, sc = pack_dense_weights(a, cfg.qcfg.w_bits)
        packed[n] = {"w_packed": wp, "w_scale": sc}
    common = {"router": jax.random.normal(ks[3], (d, e)) / d ** 0.5,
              "expert_bias": 0.05 * jax.random.normal(ks[4], (e,))}
    return dict(w, **common), dict(packed, **common)


def _float_moe(p, x, cfg: MoeConfig, a_grid: bool):
    """Per-token top-k in float32, every token its own experts."""
    from repro.nn.mlp import moe_route

    xs = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    weights, idx, _ = moe_route(p, xs, cfg)
    step = cfg.qcfg.a_absmax / 127.0

    def q(v):
        return jnp.clip(jnp.round(v / step), -127, 127) * step if a_grid \
            else v

    out = np.zeros(xs.shape, np.float32)
    for t in range(xs.shape[0]):
        for k in range(cfg.top_k):
            e = int(idx[t, k])
            h = q(xs[t]) @ p["wi"][e]
            g = q(xs[t]) @ p["wg"][e]
            y = q(jax.nn.silu(g) * h) @ p["wo"][e]
            out[t] += float(weights[t, k]) * np.asarray(y)
    return out.reshape(x.shape)


def test_packed_w4_expert_layer_matches_the_float_reference():
    """The packed layer, served in float32 so only the packing and the
    A8 grid differ from the float experts: it agrees with the float
    experts at their W4-dequantized values on the A8 grid, to rounding."""
    cfg = _moe_cfg()
    fl, pk = _packed_experts(jax.random.PRNGKey(0), cfg)
    deq = dict(fl)
    for n in ("wi", "wg", "wo"):
        codes = packing.unpack(pk[n]["w_packed"], 4, True, axis=1)
        deq[n] = codes.astype(jnp.float32) * pk[n]["w_scale"][:, None, :]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.d_model))
    got, hits = moe_dropless(pk, x, cfg)
    want = _float_moe(deq, x, cfg, a_grid=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=1e-4)
    assert 2 <= int(hits) <= cfg.n_experts
    # moe_apply serves a packed layer through the same dropless path
    y, aux = moe_apply(pk, x, cfg)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(got))


def test_dropless_routing_keeps_a_token_the_capacity_path_drops():
    """A router skewed so that every token picks expert 0 first: the
    training path's capacity (1.25 x the mean load) drops tokens there,
    the serving path drops none."""
    cfg = _moe_cfg(qcfg=QuantConfig(), router="softmax", expert_bias=False,
                   norm_topk=False, group_size=16)
    fl, _ = _packed_experts(jax.random.PRNGKey(2), cfg)
    skew = jnp.zeros((cfg.d_model, cfg.n_experts)).at[:, 0].set(1.0)
    p = dict(fl, router=fl["router"] + skew)
    p.pop("expert_bias")
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3),
                                  (1, 16, cfg.d_model)))
    want = _float_moe(p, x, cfg, a_grid=False)
    kept, _ = moe_dropless(p, x, cfg)
    dropped, _ = moe_apply(p, x, cfg)
    err = lambda y: np.abs(np.asarray(y) - want).max(axis=-1)[0]
    assert cfg.capacity(16) < 16                  # expert 0 overflows
    assert err(kept).max() < 1e-4
    assert (err(dropped) > 1e-2).sum() >= 1       # at least one lost


def test_sigmoid_router_lets_the_bias_choose_but_not_weigh():
    from repro.nn.mlp import moe_route

    cfg = _moe_cfg(qcfg=QuantConfig(), n_experts=4)
    x = jnp.ones((1, cfg.d_model))
    p = {"router": jnp.zeros((cfg.d_model, 4)).at[:, 0].set(0.01),
         "expert_bias": jnp.array([0.0, 0.0, 0.0, 1.0])}
    weights, idx, scores = moe_route(p, x, cfg)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 3]
    s = np.asarray(scores[0])
    want = s[[0, 3]] / (s[0] + s[3] + 1e-6)
    got = dict(zip(np.asarray(idx[0]).tolist(), np.asarray(weights[0])))
    np.testing.assert_allclose([got[0], got[3]], want, rtol=1e-6)


# ---------------------------------------------------------- short conv ---

def test_short_conv_decode_through_its_state_matches_the_sequence():
    cfg = ShortConvConfig(d_model=32, kernel=3)
    p = init_params(shortconv_def(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 32))
    want = shortconv_apply(p, x, cfg)
    state = shortconv_init_state(cfg, 2, jnp.float32)
    got = []
    for t in range(7):
        y, state = shortconv_decode(p, x[:, t:t + 1], state, cfg)
        got.append(y)
    np.testing.assert_allclose(np.concatenate(got, 1), want, atol=1e-5,
                               rtol=1e-5)


def _lfm2_adapter(slots_max_len=12):
    cfg = dataclasses.replace(lfm2_8b_a1b.smoke_config(), quant=W4A8)
    model = build(cfg)
    params = _random_tree(model.init(jax.random.PRNGKey(5)),
                          jax.random.PRNGKey(6))
    return LMDecodeAdapter(model, params, max_len=slots_max_len, eos_id=-1)


def test_readmitted_slot_starts_from_a_zero_conv_state():
    ad = _lfm2_adapter()
    state = ad.init_state(3)
    state["conv"] = jnp.ones_like(state["conv"])
    out = ad.reset_state(state, np.array([False, True, False]))
    conv = np.asarray(out["conv"], np.float32)
    assert (conv[:, 1] == 0).all() and (conv[:, [0, 2]] == 1).all()
    np.testing.assert_array_equal(np.asarray(out["kv"]["k"]),
                                  np.asarray(state["kv"]["k"]))


def test_a_reused_slot_serves_as_a_fresh_one():
    """One slot, two requests one after the other: the second's tokens
    are those it gets alone, so no conv state of the first leaks."""
    reqs = [np.array([3, 4, 5, 6], np.int32), np.array([9, 2], np.int32)]
    ad = _lfm2_adapter()
    both = Scheduler(ad, 1, policy="continuous").serve(
        [Request(prompt=r, max_new_tokens=4) for r in reqs])
    alone = Scheduler(ad, 1, policy="continuous").serve(
        [Request(prompt=reqs[1], max_new_tokens=4)])
    assert both[1].out.tolist() == alone[0].out.tolist()


def test_lfm2_step_copies_tokens_and_experts_hit_once():
    from repro.obs import trace as obs

    ad = _lfm2_adapter()
    assert ad._n_hits == 3        # layers 1-3 are MoE in the smoke model
    obs.reset()
    obs.enable()
    try:
        rows, _ = ad.step(ad.init_state(2), np.array([[1], [2]], np.int32),
                          np.array([0, 0], np.int32))
        counts = obs.counter_values()
    finally:
        obs.disable()
    assert rows.shape == (2, 1)
    assert counts["lm.bytes_to_host"] == 4 * (2 + 3)
    assert 3 * 2 <= counts["moe.experts_hit"] <= 3 * 4   # top-2 of 8


@pytest.mark.parametrize("kind", ["conv", "full_attention"])
def test_hybrid_layout_counts_each_stack(kind):
    from repro.models.lm import _hybrid_layout

    layout, n = _hybrid_layout(lfm2_8b_a1b.CONFIG)
    assert (n["conv"], n["full_attention"], n["mlp"], n["moe"]) == \
        (18, 6, 2, 22)
    at = [i for i, (op, *_) in enumerate(layout) if op == kind]
    if kind == "full_attention":
        assert at == [2, 6, 10, 14, 18, 21]
    assert [layout[i][1] for i in at] == list(range(len(at)))


def test_lfm2_serves_the_same_on_a_data_parallel_mesh():
    """The conv state and the merged KV stack shard their slot axis over
    ``data`` (`cache_shardings`); the tokens are the meshless ones."""
    from repro.parallel.ctx import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs two host devices (tests/conftest.py sets 8)")
    reqs = [Request(prompt=np.array(p, np.int32), max_new_tokens=4)
            for p in ([3, 4, 5], [9], [1, 2], [7, 7, 7, 7])]
    plain = _lfm2_adapter()
    mesh = make_mesh((2, 1), ("data", "model"), devices=jax.devices()[:2])
    sharded = LMDecodeAdapter(plain.model, plain.params, max_len=12,
                              eos_id=-1, mesh=mesh)
    state = sharded.init_state(4)
    assert state["conv"].sharding.spec[1] == "data"
    assert state["kv"]["k"].sharding.spec[1] == "data"
    want = Scheduler(plain, 4, policy="continuous").serve(
        [Request(prompt=r.prompt, max_new_tokens=4) for r in reqs])
    got = Scheduler(sharded, 4, mesh=mesh, policy="continuous").serve(reqs)
    assert [o.out.tolist() for o in got] == [o.out.tolist() for o in want]

"""Serving engine: batched generation with prefill+decode, incl. packed
int weights (the paper's deployment mode)."""
import dataclasses

import jax
import numpy as np

from repro.configs.qwen2p5_3b import smoke_config
from repro.models.api import build
from repro.parallel.ctx import make_mesh
from repro.serve.engine import Engine, Request


def test_generate_greedy():
    cfg = smoke_config()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, batch_size=2, max_len=32)
    reqs = [Request(prompt=np.array([3, 5, 7], np.int32), max_new_tokens=5),
            Request(prompt=np.array([11, 2], np.int32), max_new_tokens=5)]
    out = eng.generate(reqs)
    assert len(out) == 2
    for r in out:
        assert r.out is not None and 1 <= len(r.out) <= 5
        assert (r.out >= 0).all() and (r.out < cfg.vocab).all()


def test_generate_multiwave_pads_never_leak():
    """requests % batch != 0: the last wave is padded with filler requests;
    `generate` must return exactly the caller's request objects, in order —
    the old `max_new_tokens > 1 or out is not None` filter admitted pads
    once outputs were assigned."""
    cfg = smoke_config()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, batch_size=2, max_len=32)
    reqs = [Request(prompt=np.array([3 + i, 5], np.int32),
                    max_new_tokens=(1 if i == 0 else 3))  # real max_new=1 too
            for i in range(5)]
    out = eng.generate(reqs)
    assert len(out) == 5
    # identity, not just count: every returned object IS an input request
    for got, want in zip(out, reqs):
        assert got is want
        assert got.out is not None and len(got.out) <= got.max_new_tokens
    # single-prompt pathological case: one request, batch 4
    eng4 = Engine(model, params, batch_size=4, max_len=32)
    solo = [Request(prompt=np.array([7], np.int32), max_new_tokens=2)]
    out4 = eng4.generate(solo)
    assert len(out4) == 1 and out4[0] is solo[0]


def test_engine_wave_sharding_ragged():
    """Mesh-sharded engine == meshless engine on a ragged request list
    (5 requests, batch 4 -> a full wave + a 1/4 wave), with a sane
    per-device utilization report."""
    import pytest

    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    tp = len(jax.devices()) // 4
    mesh = make_mesh((4, tp), ("data", "model"),
                     devices=jax.devices()[: 4 * tp])
    cfg = smoke_config()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mk = lambda: [Request(prompt=np.array([3 + i, 5], np.int32),
                          max_new_tokens=3) for i in range(5)]
    want = Engine(model, params, batch_size=4, max_len=32).generate(mk())
    eng = Engine(model, params, batch_size=4, max_len=32, mesh=mesh)
    reqs = mk()
    got = eng.generate(reqs)
    assert len(got) == 5 and all(g is r for g, r in zip(got, reqs))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.out, w.out)
    rep = eng.utilization_report()
    assert rep["devices"] == 4 and rep["waves"] == 2
    # wave 1 full (all devices 100%), wave 2 has 1 real slot of 4 ->
    # device 0 busy, devices 1-3 idle; means are [1, .5, .5, .5]
    assert rep["per_device"] == [1.0, 0.5, 0.5, 0.5]
    assert abs(rep["mean_util"] - 0.625) < 1e-9
    # ragged batch % dp: physical slots are padded to whole per-device
    # blocks (pads never admitted) instead of the old ValueError —
    # outputs still equal the meshless engine's
    eng3 = Engine(model, params, batch_size=3, max_len=32, mesh=mesh)
    reqs3 = mk()
    got3 = eng3.generate(reqs3)
    assert len(got3) == 5 and all(g is r for g, r in zip(got3, reqs3))
    for g, w in zip(got3, want):
        np.testing.assert_array_equal(g.out, w.out)
    assert eng3.utilization_report()["devices"] == 4
    # a mesh without the dp axis serves replicated (pure-TP tolerance,
    # same as the kernel cluster path) rather than crashing mid-wave
    tp_mesh = make_mesh((2,), ("model",), devices=jax.devices()[:2])
    eng_tp = Engine(model, params, batch_size=4, max_len=32, mesh=tp_mesh)
    got_tp = eng_tp.generate(mk())
    for g, w in zip(got_tp, want):
        np.testing.assert_array_equal(g.out, w.out)
    assert eng_tp.utilization_report()["devices"] == 1


def test_generate_deterministic():
    cfg = smoke_config()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, batch_size=2, max_len=32)
    mk = lambda: [Request(prompt=np.array([3, 5, 7], np.int32),
                          max_new_tokens=6),
                  Request(prompt=np.array([1], np.int32), max_new_tokens=6)]
    a = eng.generate(mk())
    b = eng.generate(mk())
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.out, y.out)

"""Batched generation through the serving loop: the `Scheduler` over an
`LMDecodeAdapter`, prefill + decode, incl. packed int weights (the
paper's deployment mode)."""
import jax
import numpy as np
import pytest

from repro.configs.qwen2p5_3b import smoke_config
from repro.models.api import build
from repro.parallel.ctx import make_mesh
from repro.serve.runtime import LMDecodeAdapter, Request, Scheduler


@pytest.fixture(scope="module")
def lm():
    cfg = smoke_config()
    model = build(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _sched(lm, slots, mesh=None):
    _, model, params = lm
    return Scheduler(LMDecodeAdapter(model, params, 32, mesh=mesh), slots,
                     mesh=mesh)


def test_generate_greedy(lm):
    cfg = lm[0]
    reqs = [Request(prompt=np.array([3, 5, 7], np.int32), max_new_tokens=5),
            Request(prompt=np.array([11, 2], np.int32), max_new_tokens=5)]
    out = _sched(lm, 2).serve(reqs)
    assert len(out) == 2
    for r in out:
        assert r.out is not None and 1 <= len(r.out) <= 5
        assert (r.out >= 0).all() and (r.out < cfg.vocab).all()


def test_generate_multiwave_pads_never_leak(lm):
    """requests % slots != 0: the slot array is never padded with filler
    requests; `serve` must return exactly the caller's request objects,
    in order."""
    reqs = [Request(prompt=np.array([3 + i, 5], np.int32),
                    max_new_tokens=(1 if i == 0 else 3))  # real max_new=1 too
            for i in range(5)]
    out = _sched(lm, 2).serve(reqs)
    assert len(out) == 5
    # identity, not just count: every returned object IS an input request
    for got, want in zip(out, reqs):
        assert got is want
        assert got.out is not None and len(got.out) <= got.max_new_tokens
    # single-prompt pathological case: one request, 4 slots
    solo = [Request(prompt=np.array([7], np.int32), max_new_tokens=2)]
    out4 = _sched(lm, 4).serve(solo)
    assert len(out4) == 1 and out4[0] is solo[0]


def test_engine_wave_sharding_ragged(lm):
    """A dp=4 scheduler == a meshless one on a ragged request list (5
    requests over 4 slots), with one per-device occupancy column per
    device; ragged slots % dp pads to whole per-device blocks; a mesh
    without the dp axis serves replicated."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    tp = len(jax.devices()) // 4
    mesh = make_mesh((4, tp), ("data", "model"),
                     devices=jax.devices()[: 4 * tp])
    mk = lambda: [Request(prompt=np.array([3 + i, 5], np.int32),
                          max_new_tokens=3) for i in range(5)]
    want = _sched(lm, 4).serve(mk())
    sched = _sched(lm, 4, mesh)
    reqs = mk()
    got = sched.serve(reqs)
    assert len(got) == 5 and all(g is r for g, r in zip(got, reqs))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.out, w.out)
    assert sched.serving_report()["devices"] == 4
    # the first step runs one request on each device's one slot
    assert sched.step_log[0]["per_device"] == [1.0, 1.0, 1.0, 1.0]
    # ragged slots % dp: physical slots are padded to whole per-device
    # blocks (pads never admitted); outputs still equal the meshless ones
    sched3 = _sched(lm, 3, mesh)
    reqs3 = mk()
    got3 = sched3.serve(reqs3)
    assert len(got3) == 5 and all(g is r for g, r in zip(got3, reqs3))
    for g, w in zip(got3, want):
        np.testing.assert_array_equal(g.out, w.out)
    assert sched3.serving_report()["devices"] == 4
    assert sched3.step_log[0]["per_device"] == [1.0, 1.0, 1.0, 0.0]
    # a mesh without the dp axis serves replicated (pure-TP tolerance,
    # same as the kernel cluster path) rather than crashing mid-serve
    tp_mesh = make_mesh((2,), ("model",), devices=jax.devices()[:2])
    sched_tp = _sched(lm, 4, tp_mesh)
    for g, w in zip(sched_tp.serve(mk()), want):
        np.testing.assert_array_equal(g.out, w.out)
    assert sched_tp.serving_report()["devices"] == 1


def test_generate_deterministic(lm):
    sched = _sched(lm, 2)
    mk = lambda: [Request(prompt=np.array([3, 5, 7], np.int32),
                          max_new_tokens=6),
                  Request(prompt=np.array([1], np.int32), max_new_tokens=6)]
    a = sched.serve(mk())
    b = sched.serve(mk())
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.out, y.out)

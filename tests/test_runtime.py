"""The continuous-batching serving runtime (`repro.serve.runtime`).

* slot/page manager unit behaviour: deterministic lowest-free placement,
  page reservation/used accounting, ragged-dp physical padding, capacity
  admission control, obs counters;
* scheduler edge cases: backpressure on a bounded admission queue,
  eviction that refills a slot (a freed slot is re-admitted before the
  other requests finish), zero-length prompts, max_new_tokens=0, drain
  on an empty queue;
* the bit-exactness invariant: per-request outputs identical across
  slot counts and meshes (incl. ragged slots % dp), admission orders,
  and greedy vs per-request-seeded sampling, for the LM and the CNN;
* `serving_report`'s field invariants on real serves, and the
  ``engine.requests`` counter;
* the LM CLI in process: its tokens equal a direct serve, and with a
  mesh it prints the per-device occupancy line.
"""
import dataclasses
import sys

import numpy as np
import pytest

import jax

from repro.configs.qwen2p5_3b import smoke_config
from repro.models.api import build
from repro.obs import trace as obs
from repro.parallel.ctx import make_mesh
from repro.serve.runtime import (Backpressure, LMDecodeAdapter, Request,
                                 Scheduler, VisionAdapter)
from repro.serve.runtime.slots import CapacityError, SlotManager


@pytest.fixture(scope="module")
def lm():
    cfg = smoke_config()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _adapter(lm, mesh=None, max_len=32):
    _, model, params = lm
    return LMDecodeAdapter(model, params, max_len=max_len, mesh=mesh)


def _reqs(n=5, plen=2, max_new=3):
    """Equal-length prompts, mixed generation budgets unless pinned."""
    return [Request(prompt=np.array([3 + i] + [5] * (plen - 1), np.int32),
                    max_new_tokens=(max_new if np.isscalar(max_new)
                                    else max_new[i]))
            for i in range(n)]


def _outs(reqs):
    return [r.out.tolist() for r in reqs]


# ------------------------------------------------------- slot manager ---

def test_slot_manager_lifecycle_and_pages():
    sm = SlotManager(3, max_len=32, page_tokens=8)  # 4 pages per slot
    assert (sm.real, sm.phys, sm.pages_per_slot, sm.capacity_pages) == \
        (3, 3, 4, 12)
    a = sm.admit(rid=10, reserve_tokens=9)    # ceil(9/8) = 2 pages
    b = sm.admit(rid=11, reserve_tokens=40)   # clamped to max_len -> 4
    assert (a, b) == (0, 1)                   # lowest-free placement
    assert sm.pages_reserved() == 6 and sm.pages_used() == 0
    sm.advance(a, 5)
    assert sm.slots[a].pos == 5 and sm.pages_used() == 1
    sm.advance(a, 9)
    assert sm.pages_used() == 2
    assert sm.occupancy() == pytest.approx(2 / 3)
    sm.evict(a)
    assert sm.free_slots == 2 and sm.pages_reserved() == 4
    # freed slot 0 is re-used before untouched slot 2 (deterministic)
    assert sm.admit(rid=12, reserve_tokens=1) == 0
    with pytest.raises(CapacityError, match="exceeds max_len"):
        sm.check_fits(33)
    sm.check_fits(32)  # exactly full is admissible


def test_slot_manager_ragged_dp_blocks():
    sm = SlotManager(3, max_len=16, dp=4)
    # padded to one whole slot per device; the pad is never in the free
    # list, so it can never be admitted
    assert (sm.block, sm.phys, sm.real, sm.free_slots) == (1, 4, 3, 3)
    for rid in range(3):
        sm.admit(rid, 4)
    assert sm.free_slots == 0
    assert sm.device_occupancy() == [1.0, 1.0, 1.0, 0.0]
    sm.evict(1)
    assert sm.device_occupancy() == [1.0, 0.0, 1.0, 0.0]


def test_slot_manager_obs_counters():
    obs.reset()
    with obs.enabled_scope():
        sm = SlotManager(2, max_len=32, page_tokens=16)
        sm.admit(0, 20)   # 2 pages
        sm.admit(1, 3)    # 1 page
        sm.evict(0)
        vals = obs.counter_values()
    assert vals["serve.admits"] == 2 and vals["serve.evicts"] == 1
    assert vals["serve.pages_reserved"] == 3
    assert vals["serve.pages_released"] == 2


# --------------------------------------------------- scheduler edges ---

def test_backpressure_on_full_queue(lm):
    sched = Scheduler(_adapter(lm), 1, max_queue=2)
    for i in range(2):
        sched.submit(Request(prompt=np.array([3 + i], np.int32),
                             max_new_tokens=1))
    with pytest.raises(Backpressure, match="admission queue full"):
        sched.submit(Request(prompt=np.array([9], np.int32),
                             max_new_tokens=1))
    sched.drain()          # queue empties ...
    rid = sched.submit(Request(prompt=np.array([9], np.int32),
                               max_new_tokens=1))  # ... and admits again
    sched.drain()
    assert sched.results[rid].out is not None


def test_mid_wave_eviction_refills_slot(lm):
    """With 2 slots and 3 requests, the third request must be admitted
    the moment the short first request frees its slot — strictly before
    the long second request finishes."""
    sched = Scheduler(_adapter(lm), 2, policy="continuous")
    sched.serve(_reqs(3, max_new=[1, 6, 6]))
    log = {r["rid"]: r for r in sched.request_log}
    assert log[2]["admit_t"] < log[1]["finish_t"]   # mid-serve admission
    assert log[2]["admit_t"] >= log[0]["finish_t"]  # into slot 0's grave


def test_policy_accepts_only_continuous(lm):
    for name in ("wave", "static"):
        with pytest.raises(ValueError, match=f"unknown policy '{name}'"):
            Scheduler(_adapter(lm), 2, policy=name)


def test_degenerate_requests(lm):
    sched = Scheduler(_adapter(lm), 2)
    # max_new_tokens=0 completes instantly without ever taking a slot
    rid0 = sched.submit(Request(prompt=np.array([3, 5], np.int32),
                                max_new_tokens=0))
    assert sched.results[rid0].out.tolist() == []
    assert sched.idle
    log0 = next(r for r in sched.request_log if r["rid"] == rid0)
    assert log0["admit_t"] is None and log0["tokens_out"] == 0
    # zero-length prompt is padded to a single BOS filler token
    rid1 = sched.submit(Request(prompt=np.array([], np.int32),
                                max_new_tokens=2))
    sched.drain()
    out = sched.results[rid1].out
    assert 1 <= len(out) <= 2
    # a prompt that can never fit its cache is rejected at submission
    with pytest.raises(CapacityError, match="exceeds max_len"):
        sched.submit(Request(prompt=np.zeros(40, np.int32) + 3,
                             max_new_tokens=1))


def test_drain_on_empty_queue_is_noop(lm):
    sched = Scheduler(_adapter(lm), 2)
    sched.drain()
    assert sched.step() == []
    assert sched.idle and sched.step_log == [] and sched.results == {}


# ------------------------------------------------------- bit-exactness ---

def _ragged_reqs():
    """Ragged prompts (1 to 5 tokens) and mixed generation budgets."""
    return [Request(prompt=np.arange(2, 3 + i, dtype=np.int32),
                    max_new_tokens=m) for i, m in enumerate([1, 4, 2, 5, 3])]


def _dp4_mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    return make_mesh((4, 1), ("data", "model"), devices=jax.devices()[:4])


@pytest.fixture(scope="module")
def one_slot_outs(lm):
    return _outs(Scheduler(_adapter(lm), 1).serve(_ragged_reqs()))


@pytest.mark.parametrize("dp", [None, 4])
@pytest.mark.parametrize("num_slots", [1, 2, 3, 5])
def test_outputs_independent_of_slots_and_mesh(lm, one_slot_outs,
                                               num_slots, dp):
    """Per-request outputs equal a one-slot meshless serve's, whatever
    the slot count (ragged slots % dp included) and mesh."""
    mesh = _dp4_mesh() if dp else None
    sched = Scheduler(_adapter(lm, mesh=mesh), num_slots, mesh=mesh)
    assert _outs(sched.serve(_ragged_reqs())) == one_slot_outs


def test_admission_order_invariance(lm):
    fwd = Scheduler(_adapter(lm), 2).serve(_reqs(5, max_new=[1, 4, 2, 5, 3]))
    rev = Scheduler(_adapter(lm), 2).serve(
        list(reversed(_reqs(5, max_new=[1, 4, 2, 5, 3]))))
    assert _outs(fwd) == _outs(list(reversed(rev)))


def test_nongreedy_sampling_is_per_request(lm):
    """Sampled decoding draws from a per-request (seed, rid) generator,
    so outputs replay across runs AND across slot counts."""
    mk = lambda: _reqs(4, max_new=6)
    a = Scheduler(_adapter(lm), 2).serve(mk(), greedy=False, seed=7)
    b = Scheduler(_adapter(lm), 2).serve(mk(), greedy=False, seed=7)
    c = Scheduler(_adapter(lm), 3).serve(mk(), greedy=False, seed=7)
    assert _outs(a) == _outs(b) == _outs(c)
    # (the smoke model's softmax is near-degenerate, so different seeds
    # usually sample the argmax too — seed sensitivity is exercised at
    # the rng level below, not through the model)
    rng1 = np.random.default_rng((7, 0))
    rng2 = np.random.default_rng((8, 0))
    p = np.full(8, 1 / 8)
    assert [rng1.choice(8, p=p) for _ in range(16)] != \
        [rng2.choice(8, p=p) for _ in range(16)]


@pytest.mark.parametrize("num_slots", [4, 3])
def test_dp_sharded_parity(lm, num_slots):
    """Mesh-sharded runtime == meshless, bit-exact, including ragged
    num_slots % dp != 0 (physical pad slots are never admitted)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    mixed = [3, 1, 4, 2, 5]
    want = Scheduler(_adapter(lm), num_slots).serve(_reqs(5, max_new=mixed))
    tp = len(jax.devices()) // 4
    mesh = make_mesh((4, tp), ("data", "model"),
                     devices=jax.devices()[: 4 * tp])
    sched = Scheduler(_adapter(lm, mesh=mesh), num_slots, mesh=mesh)
    got = sched.serve(_reqs(5, max_new=mixed))
    assert _outs(got) == _outs(want)
    assert sched._dp == 4
    assert len(sched.step_log[0]["per_device"]) == 4
    assert sched.slots.phys % 4 == 0


def test_slot_state_reset_between_tenants(lm):
    """A slot's second tenant must produce the same output it would in a
    fresh scheduler — nothing carries over from the evicted request."""
    solo = Scheduler(_adapter(lm), 1).serve(
        [Request(prompt=np.array([9, 4], np.int32), max_new_tokens=4)])
    sched = Scheduler(_adapter(lm), 1)
    got = sched.serve(
        [Request(prompt=np.array([3, 5], np.int32), max_new_tokens=4),
         Request(prompt=np.array([9, 4], np.int32), max_new_tokens=4)])
    assert got[1].out.tolist() == solo[0].out.tolist()


# ------------------------------------------------------------- vision ---

@pytest.fixture(scope="module")
def vision_net():
    from repro.deploy.calibrate import calibrate_vision
    from repro.vision.configs import get_vision_config
    from repro.vision.models import (forward_int, init_fp, quantize_input,
                                     quantize_net)

    cfg = get_vision_config("resnet8", smoke=True)
    params = init_fp(cfg, seed=0)
    rng = np.random.default_rng(0)
    cal = rng.uniform(0, 1, (4, *cfg.in_hw, cfg.in_ch)).astype(np.float32)
    _, absmax = calibrate_vision(cfg, params, [cal])
    qnet = quantize_net(cfg, params, absmax)
    images = rng.uniform(0, 1, (5, *cfg.in_hw, cfg.in_ch)).astype(
        np.float32)
    want = np.asarray(forward_int(qnet, quantize_input(qnet, images),
                                  backend="xla"))
    return qnet, images, want


@pytest.mark.parametrize("dp", [None, 4])
@pytest.mark.parametrize("num_slots", [1, 3])
def test_vision_outputs_independent_of_slots_and_mesh(vision_net,
                                                      num_slots, dp):
    """Served logits equal the batch forward's, whatever the slot count
    and mesh; an empty serve returns nothing."""
    qnet, images, want = vision_net
    mesh = _dp4_mesh() if dp else None
    sched = Scheduler(VisionAdapter(qnet, mesh=mesh, backend="xla"),
                      num_slots, mesh=mesh)
    assert np.array_equal(np.stack(sched.serve(list(images))), want)
    assert sched.serve([]) == []


# ------------------------------------------------------ serving report ---

def test_engine_shim_stats_and_counters(lm):
    """`serving_report` and the ``engine.requests`` counter on a serve of
    5 requests over 2 slots."""
    sched = Scheduler(_adapter(lm), 2)
    obs.reset()
    with obs.enabled_scope():
        out = sched.serve(_reqs(5, max_new=2))
        vals = obs.counter_values()
    assert [len(r.out) for r in out] == [2] * 5
    assert vals["engine.requests"] == 5
    assert vals["serve.admits"] == 5 and vals["serve.evicts"] == 5
    rep = sched.serving_report()
    assert (rep["requests"], rep["slots"], rep["devices"]) == (5, 2, 1)
    assert rep["tokens_out"] == 10 and rep["latency"] is not None
    # 2 of 5 admitted at the first step: 3 wait behind them
    assert rep["queue_depth"]["max"] == 3
    assert "policy" not in rep


@pytest.mark.parametrize("num_slots,n_requests,dp", [
    (1, 3, None), (2, 5, None), (4, 4, None), (3, 5, 4)])
def test_serving_report_invariants(lm, num_slots, n_requests, dp):
    """The report's fields hold their invariants on real serves: ordered
    latency percentiles, occupancy in [0, 1], integer queue depths, and
    output tokens that sum over the requests."""
    mesh = _dp4_mesh() if dp else None
    sched = Scheduler(_adapter(lm, mesh=mesh), num_slots, mesh=mesh)
    out = sched.serve(_reqs(n_requests, max_new=[1, 4, 2, 5, 3][
        :n_requests]))
    rep = sched.serving_report()
    lat = rep["latency"]
    assert 0 <= lat["p50"] <= lat["p95"] <= lat["p99"] <= lat["max"]
    assert 0 <= lat["mean"] <= lat["max"]
    for key in ("mean", "min"):
        assert 0 <= rep["occupancy"][key] <= 1
    assert rep["occupancy"]["min"] <= rep["occupancy"]["mean"]
    assert isinstance(rep["queue_depth"]["max"], int)
    assert 0 <= rep["queue_depth"]["mean"] <= rep["queue_depth"]["max"]
    assert rep["tokens_out"] == sum(len(r.out) for r in out) == \
        sum(r["tokens_out"] for r in sched.request_log)
    assert rep["requests"] == n_requests
    assert rep["steps"] == len(sched.step_log) >= 1
    assert rep["devices"] == (dp or 1)
    for step in sched.step_log:
        assert len(step["per_device"]) == rep["devices"]
        assert all(0 <= u <= 1 for u in step["per_device"])


# ---------------------------------------------------------- the LM CLI ---

@pytest.mark.parametrize("mesh_arg", [None, "4,1"])
def test_serve_cli_in_process(monkeypatch, capsys, mesh_arg):
    """`python -m repro.launch.serve --smoke --quant w4a8` serves the
    same tokens as a one-slot meshless serve of the same requests, and
    with ``--mesh`` prints each device's mean slot occupancy."""
    from repro.launch import serve as serve_cli

    if mesh_arg:
        _dp4_mesh()
    argv = ["serve", "--arch", "qwen2.5-3b", "--smoke", "--quant", "w4a8",
            "--requests", "3", "--batch", "2", "--max-new", "3",
            "--max-len", "32"]
    if mesh_arg:
        argv += ["--mesh", mesh_arg]
    monkeypatch.setattr(sys, "argv", argv)
    monkeypatch.setattr(serve_cli, "enable_compile_cache", lambda: None)
    got = serve_cli.main()
    printed = capsys.readouterr().out

    cfg = dataclasses.replace(smoke_config(), kv_quant_bits=16)
    model, params, _, _ = serve_cli.build_serving(cfg, quant="w4a8")
    want = Scheduler(LMDecodeAdapter(model, params, 32), 1).serve(
        serve_cli.make_requests(cfg, 3, 3))
    assert _outs(got) == _outs(want)
    assert "request latency: p50=" in printed
    if mesh_arg:
        assert "mesh: data=4 model=1" in printed
        assert "per-device occupancy: mean " in printed
    else:
        assert "per-device occupancy" not in printed

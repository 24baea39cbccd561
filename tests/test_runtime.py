"""The continuous-batching serving runtime (`repro.serve.runtime`).

* slot/page manager unit behaviour: deterministic lowest-free placement,
  page reservation/used accounting, ragged-dp physical padding, capacity
  admission control, obs counters;
* scheduler edge cases: backpressure on a bounded admission queue,
  mid-wave eviction (a freed slot is re-admitted before the cohort
  finishes — the tentpole behaviour), zero-length prompts,
  max_new_tokens=0, drain on an empty queue;
* the bit-exactness invariant: per-request outputs identical across
  policies (continuous == wave == legacy Engine), admission orders,
  meshless vs dp-sharded (incl. ragged slots % dp), and greedy vs
  per-request-seeded sampling;
* engine-shim compat: `Engine`/`VisionEngine` wave stats and obs
  counters match the legacy semantics;
* the load generator: deterministic replay from a fixed seed and a
  BENCH_serving.json that passes its schema with continuous batching
  strictly beating the wave baseline.
"""
import pathlib
import sys

import numpy as np
import pytest

import jax

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # `import benchmarks` from any rootdir
    sys.path.insert(0, str(ROOT))

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
    """Equal-length prompts (bit-comparable to the legacy wave prefill),
    mixed generation budgets unless pinned."""
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
    """The tentpole behaviour: with 2 slots and 3 requests, the third
    request must be admitted the moment the short first request frees
    its slot — strictly before the long second request finishes. The
    wave policy on the same workload must instead hold it back until
    the whole cohort drains."""
    reqs = _reqs(3, max_new=[1, 6, 6])
    sched = Scheduler(_adapter(lm), 2, policy="continuous")
    sched.serve([Request(prompt=r.prompt.copy(),
                         max_new_tokens=r.max_new_tokens) for r in reqs])
    log = {r["rid"]: r for r in sched.request_log}
    assert log[2]["admit_t"] < log[1]["finish_t"]   # mid-wave admission
    assert log[2]["admit_t"] >= log[0]["finish_t"]  # into slot 0's grave

    wave = Scheduler(_adapter(lm), 2, policy="wave")
    wave.serve([Request(prompt=r.prompt.copy(),
                        max_new_tokens=r.max_new_tokens) for r in reqs])
    wlog = {r["rid"]: r for r in wave.request_log}
    assert wlog[2]["admit_t"] >= wlog[1]["finish_t"]  # waits for cohort
    # fewer engine steps for the same work is the whole point
    assert sched.serving_report()["steps"] < wave.serving_report()["steps"]


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

def test_policies_and_legacy_engine_bit_exact(lm):
    """continuous == wave == legacy Engine per request (equal-length
    prompts so the legacy pad-replaying prefill is comparable), and
    ragged prompt lengths agree across the two runtime policies."""
    from repro.serve.engine import Engine

    _, model, params = lm
    mixed = [1, 4, 2, 5, 3]
    want = Engine(model, params, batch_size=4, max_len=32).generate(
        _reqs(5, max_new=mixed))
    for policy in ("wave", "continuous"):
        got = Scheduler(_adapter(lm), 4, policy=policy).serve(
            _reqs(5, max_new=mixed))
        assert _outs(got) == _outs(want)
    # ragged prompts: per-request outputs are batching-independent
    rag = lambda: [Request(prompt=np.arange(2, 3 + i, dtype=np.int32),
                           max_new_tokens=4) for i in range(5)]
    a = Scheduler(_adapter(lm), 4, policy="wave").serve(rag())
    b = Scheduler(_adapter(lm), 2, policy="continuous").serve(rag())
    assert _outs(a) == _outs(b)


def test_admission_order_invariance(lm):
    fwd = Scheduler(_adapter(lm), 2).serve(_reqs(5, max_new=[1, 4, 2, 5, 3]))
    rev = Scheduler(_adapter(lm), 2).serve(
        list(reversed(_reqs(5, max_new=[1, 4, 2, 5, 3]))))
    assert _outs(fwd) == _outs(list(reversed(rev)))


def test_nongreedy_sampling_is_per_request(lm):
    """Sampled decoding draws from a per-request (seed, rid) generator,
    so outputs replay across runs AND across policies — the legacy
    shared-rng drew in wave order, which no admission-order-invariant
    scheduler can reproduce."""
    mk = lambda: _reqs(4, max_new=6)
    a = Scheduler(_adapter(lm), 2).serve(mk(), greedy=False, seed=7)
    b = Scheduler(_adapter(lm), 2).serve(mk(), greedy=False, seed=7)
    c = Scheduler(_adapter(lm), 3, policy="wave").serve(
        mk(), greedy=False, seed=7)
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


# -------------------------------------------------------- engine shims ---

def test_engine_shim_stats_and_counters(lm):
    from repro.serve.engine import Engine

    _, model, params = lm
    eng = Engine(model, params, batch_size=2, max_len=32)
    obs.reset()
    with obs.enabled_scope():
        out = eng.generate(_reqs(5, max_new=2))
        vals = obs.counter_values()
    assert [len(r.out) for r in out] == [2] * 5
    # 5 requests in waves of 2 -> 3 waves, legacy counter semantics
    assert vals["engine.waves"] == 3 and vals["engine.requests"] == 5
    assert vals["serve.admits"] == 5 and vals["serve.evicts"] == 5
    rep = eng.utilization_report()
    assert rep["waves"] == 3 and rep["devices"] == 1
    assert rep["per_device"] == [pytest.approx((1 + 1 + 0.5) / 3)]
    assert rep["latency_us"] is not None and rep["latency_us"]["waves"] == 3
    assert rep["queue_depth"]["max"] == 3
    # the runtime's request-granular report rides along on the shim
    srep = eng.serving_report()
    assert srep["requests"] == 5 and srep["policy"] == "wave"


def test_vision_shim_matches_runtime(art=None):
    from repro.deploy.calibrate import calibrate_vision
    from repro.serve.engine import VisionEngine
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
    shim = VisionEngine(qnet, batch_size=2, backend="xla").run(images)
    assert np.array_equal(shim, want)
    cont = Scheduler(VisionAdapter(qnet, backend="xla"), 2).serve(
        list(images))
    assert np.array_equal(np.stack(cont), want)
    empty = VisionEngine(qnet, batch_size=2, backend="xla").run(
        np.zeros((0, *cfg.in_hw, cfg.in_ch), np.float32))
    assert empty.shape == (0, cfg.num_classes)


# ----------------------------------------------------------- load gen ---

def test_loadgen_deterministic_replay_and_schema(tmp_path):
    """Same seed -> byte-identical BENCH_serving.json (virtual clock, no
    wall time anywhere), the artifact passes its validator, and the
    acceptance holds: continuous strictly beats wave on throughput and
    p99 at the same offered load."""
    from benchmarks import loadgen, schema

    args = ["--requests", "10", "--qps", "0.8", "--slots", "3",
            "--seed", "3", "--json", str(tmp_path / "BENCH_serving.json")]
    a = loadgen.main(args)
    b = loadgen.main(args)
    assert a == b
    schema.validate_file(tmp_path / "BENCH_serving.json")
    assert a["acceptance"]["throughput_gain"] > 1.0
    assert a["acceptance"]["p99_ratio"] < 1.0
    wave, cont = (next(r for r in a["rows"] if r["policy"] == p)
                  for p in ("wave", "continuous"))
    assert cont["steps"] < wave["steps"]
    assert cont["occupancy"]["mean"] > wave["occupancy"]["mean"]

"""The serving path's spans (`repro.obs`) and the device names the
program gives its kernels, jitted steps and scopes.

* obs off: a scheduler run over the LM and vision adapters records
  nothing, and every span site gets the shared null span;
* obs on: each serving span appears once per step or per request, with
  ``rid`` where it names a request, nested inside the span that encloses
  it; the LM counts the bytes it copies to the host;
* the spans' clock maps onto `time.perf_counter`, the ring buffer counts
  what it drops, and no span name in the program starts with the
  harness's ``bench.`` prefix;
* kernel calls staged into a `jit` trace record no kernel span and no op
  count (eager calls still do);
* the five Pallas calls, the vision step and the decode step's scopes
  carry stable names.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.qwen2p5_3b import smoke_config
from repro.models.api import build
from repro.obs import counters as obs_counters
from repro.obs import trace as obs
from repro.serve.runtime import (LMDecodeAdapter, Request, Scheduler,
                                 VisionAdapter)

STEP_SPANS = ("serve.admit", "serve.feed", "serve.step", "serve.consume")


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    obs_counters.reset()
    yield
    obs.disable()
    obs.reset()
    obs_counters.reset()


@pytest.fixture(scope="module")
def lm_adapter():
    cfg = smoke_config()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, LMDecodeAdapter(model, params, max_len=32)


@pytest.fixture(scope="module")
def vision_adapter():
    from repro.deploy.calibrate import calibrate_vision
    from repro.vision.configs import get_vision_config
    from repro.vision.models import init_fp, quantize_net

    cfg = get_vision_config("resnet8", smoke=True)
    params = init_fp(cfg, seed=0)
    rng = np.random.default_rng(0)
    cal = rng.uniform(0, 1, (4, *cfg.in_hw, cfg.in_ch)).astype(np.float32)
    _, absmax = calibrate_vision(cfg, params, [cal])
    qnet = quantize_net(cfg, params, absmax)
    images = list(rng.uniform(0, 1, (5, *cfg.in_hw, cfg.in_ch)).astype(
        np.float32))
    return VisionAdapter(qnet, backend="xla"), images


def _lm_requests(n=3):
    return [Request(prompt=np.array([3 + i, 5], np.int32),
                    max_new_tokens=2 + i) for i in range(n)]


def _serve(adapter, payloads, slots=2):
    sched = Scheduler(adapter, slots)
    for p in payloads:
        sched.submit(p)
    sched.drain()
    return sched


def _inside(inner, outer) -> bool:
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
            + 1e-3)


# ------------------------------------------------------------ obs off ---

@pytest.mark.parametrize("which", ["lm", "vision"])
def test_off_records_nothing_and_every_site_gets_the_null_span(
        which, lm_adapter, vision_adapter, monkeypatch):
    handed = []
    real = obs.span

    def spy(name, *a, **k):
        sp = real(name, *a, **k)
        handed.append((name, sp))
        return sp

    monkeypatch.setattr(obs, "span", spy)
    if which == "lm":
        _serve(lm_adapter[1], _lm_requests())
        prefix = "lm."
    else:
        adapter, images = vision_adapter
        _serve(adapter, images)
        prefix = "vision."
    names = {n for n, _ in handed}
    assert {"serve.submit", *STEP_SPANS, prefix + "dispatch",
            prefix + "device_wait", prefix + "logits_to_host"} <= names
    assert all(sp is obs._NULL_SPAN for _, sp in handed)
    assert obs.events() == [] and obs.counter_values() == {}
    assert obs.dropped() == 0


# ------------------------------------------------------------- obs on ---

def _check_serving_spans(sched, n_requests, prefix):
    steps = len(sched.step_log)
    evs = obs.spans()
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e)
    for name in STEP_SPANS:
        assert len(by[name]) == steps, name
    for name in ("dispatch", "device_wait", "logits_to_host"):
        assert len(by[prefix + name]) == steps, prefix + name
    # one submit and one queue wait per request, each naming its rid
    for name in ("serve.submit", "serve.queue"):
        assert sorted(e["args"]["rid"] for e in by[name]) == list(
            range(n_requests)), name
    # the adapter's three phases nest in serve.step, in order, and the
    # scheduler's phases do not overlap it
    for i, st in enumerate(sorted(by["serve.step"], key=lambda e: e["ts"])):
        phases = [sorted(by[prefix + p], key=lambda e: e["ts"])[i]
                  for p in ("dispatch", "device_wait", "logits_to_host")]
        assert all(_inside(p, st) for p in phases)
        assert [p["ts"] for p in phases] == sorted(p["ts"] for p in phases)
        for name in ("serve.admit", "serve.feed", "serve.consume"):
            other = sorted(by[name], key=lambda e: e["ts"])[i]
            assert not _inside(other, st) and not _inside(st, other)
    # a request's wait ends at its admission, after its submit
    submits = {e["args"]["rid"]: e for e in by["serve.submit"]}
    admits = by["serve.admit"]
    for q in by["serve.queue"]:
        sub = submits[q["args"]["rid"]]
        assert q["ts"] >= sub["ts"] + sub["dur"] - 1e-3
        end = q["ts"] + q["dur"]
        assert any(a["ts"] <= end <= a["ts"] + a["dur"] + 1e-3
                   for a in admits)
    assert not any(e["name"].startswith("bench.") for e in evs)
    return by


def test_lm_serving_spans_once_per_step_and_request(lm_adapter):
    cfg, adapter = lm_adapter
    obs.enable()
    sched = _serve(adapter, _lm_requests())
    _check_serving_spans(sched, 3, "lm.")
    # each greedy step copies one int32 token for each of both slots
    steps = len(sched.step_log)
    assert obs.counter_values()["lm.bytes_to_host"] == steps * 2 * 4


def test_vision_serving_spans_once_per_step_and_request(vision_adapter):
    adapter, images = vision_adapter
    obs.enable()
    sched = _serve(adapter, images)
    _check_serving_spans(sched, len(images), "vision.")


def test_queue_wait_covers_the_wait_for_a_slot(lm_adapter):
    """Three requests on one slot: the second and third wait while the
    first runs, so their waits are longer than the first's."""
    obs.enable()
    _serve(lm_adapter[1], _lm_requests(), slots=1)
    waits = {e["args"]["rid"]: e["dur"] for e in obs.spans("serve.queue")}
    assert waits[0] < waits[1] < waits[2]
    steps = obs.spans("serve.step")
    assert waits[2] > sum(e["dur"] for e in steps[:2])


# -------------------------------------------------------- obs plumbing ---

def test_spans_map_onto_perf_counter():
    obs.enable()
    t0 = time.perf_counter()
    with obs.span("x", cat="test"):
        time.sleep(0.01)
    t1 = time.perf_counter()
    (e,) = obs.spans("x")
    start, end = (obs.to_perf_counter(e["ts"]),
                  obs.to_perf_counter(e["ts"] + e["dur"]))
    assert t0 <= start < end <= t1 + 1e-6
    assert end - start >= 0.01
    before = obs.now_us()
    obs.complete("y", before - 5e3, cat="test", rid=7)
    (y,) = obs.spans("y")
    assert y["ts"] == pytest.approx(before - 5e3, abs=1e-3)
    assert y["dur"] >= 5e3 and y["args"] == {"rid": 7}


def test_ring_buffer_counts_what_it_drops():
    obs.enable(capacity=8)
    try:
        for i in range(20):
            with obs.span(f"s{i}", cat="test"):
                pass
        assert obs.dropped() == 12
        obs.enable(capacity=4)             # shrinking drops the oldest too
        assert obs.dropped() == 16 and len(obs.events()) == 4
        obs.reset()
        assert obs.dropped() == 0
    finally:
        obs.enable(capacity=obs.DEFAULT_CAPACITY)


def test_no_program_span_name_uses_the_harness_prefix():
    import pathlib
    import re
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    pat = re.compile(r"""(?:span|complete)\(\s*f?["']bench\.""")
    hits = [p for p in src.rglob("*.py") if pat.search(p.read_text())]
    assert hits == []


# ------------------------------------------------ kernel spans under jit ---

def _qdot_operands(rng):
    from repro.core import packing
    from repro.core.quantize import QuantizedLinearParams
    K, N = 256, 128
    w = rng.integers(-8, 8, size=(K, N)).astype(np.int8)
    params = QuantizedLinearParams(
        w_packed=packing.pack(jnp.asarray(w), 4, axis=0), w_bits=4,
        a_bits=8, a_signed=False,
        kappa=jnp.asarray(rng.integers(-64, 64, (N,)).astype(np.int32)),
        lam=jnp.asarray(rng.integers(-2**16, 2**16, (N,)).astype(np.int32)),
        m=jnp.asarray(rng.integers(0, 2**15, (N,)).astype(np.int32)),
        d=18, out_bits=8, k_logical=K)
    x = jnp.asarray(rng.integers(0, 256, (16, K)).astype(np.int8))
    return params, x


def test_jit_trace_records_no_kernel_span_or_op_count(rng):
    from repro.kernels import api
    params, x = _qdot_operands(rng)
    obs.enable()
    staged = jax.jit(lambda xx: api.qdot(params, xx, backend="xla"))
    want = staged(x)
    assert obs.spans(cat="kernel") == []
    assert obs_counters.snapshot() == {}
    assert len(obs.dispatch_log()) == 1        # the decision, once a trace
    # the same call eager: one span, one count, the same answer
    got = api.qdot(params, x, backend="xla")
    assert np.array_equal(np.asarray(got), np.asarray(want))
    (sp,) = obs.spans(cat="kernel")
    assert sp["name"] == "qdot"
    (bucket,) = obs_counters.snapshot().values()
    assert bucket["calls"] == 1


def test_compat_wrappers_record_no_span_of_their_own(rng):
    from repro.kernels.qmatmul.ops import qlinear_apply
    params, x = _qdot_operands(rng)
    obs.enable()
    qlinear_apply(params, x, backend="xla")
    assert [e["name"] for e in obs.spans()] == ["qdot"]
    assert obs.spans(cat="compat") == []


# ------------------------------------------------------- device names ---

def _jaxpr_text(fn, *args):
    return str(jax.make_jaxpr(fn)(*args))


def test_pallas_calls_carry_names(rng):
    from repro.kernels import api
    params, x = _qdot_operands(rng)
    text = _jaxpr_text(
        lambda xx: api.qdot(params, xx, backend="pallas_interpret"), x)
    assert "qmatmul" in text
    text_db = _jaxpr_text(
        lambda xx: api.qdot(params, xx, backend="pallas_interpret",
                            pipeline="double_buffer"), x)
    assert "qmatmul_db" in text_db


def test_pallas_conv_and_segmented_calls_carry_names():
    import pathlib
    import re
    root = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    names = []
    for f in ("kernels/qconv/kernel.py", "kernels/qmatmul/kernel.py"):
        text = (root / f).read_text()
        calls = text.count("pl.pallas_call(")
        found = re.findall(r'name="(\w+)"', text)
        assert len(found) == calls, f
        names += found
    assert sorted(names) == ["qconv_fused", "qconv_fused_db", "qmatmul",
                             "qmatmul_db", "qmatmul_segmented"]


def test_vision_step_is_a_named_program(vision_adapter):
    adapter, images = vision_adapter
    shape, dtype = adapter.input_spec()
    feed = np.zeros((2, *shape), dtype)
    text = adapter._forward.lower(jnp.asarray(feed), adapter._eps).as_text()
    assert "jit_vision_forward" in text


def test_decode_step_scopes_attention_mlp_and_head(lm_adapter):
    _, adapter = lm_adapter
    cache = adapter.init_state(2)
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    text = adapter._decode.lower(adapter.params, cache, tok, pos).as_text(
        debug_info=True)
    # the scan body's ops are located under their scope, the head's
    # under the program's
    for scope in ('loc("attn/', 'loc("mlp/', 'loc("jit(decode)/head/'):
        assert scope in text, scope

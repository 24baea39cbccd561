"""The observability subsystem (`repro.obs`): spans, software perf
counters, the dispatch decision log, Chrome-trace export, env knobs.

* span nesting/attrs and the null-singleton disabled path;
* counter MAC/byte accounting against hand-computed GEMM/conv costs
  across the {8,4,2}^2 bit grid, recorded at the api entry points;
* one dispatch event per resolution with correct provenance for every
  layer of the order (explicit / plan hint / env / tune-cache / default);
* chrome_trace() round-trips through json and passes the checked-in
  artifact validator (benchmarks/schema.py::check_trace);
* disabled mode records nothing — the backend-parity invariant;
* the scheduler's request-latency percentiles on injected timestamps;
* the shared timer dedupe (tune._time == obs.time_call / 1e6) and the
  env-knob registry (validation, legacy alias, unknown-var warning).
"""
import json
import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # `import benchmarks` from any rootdir
    sys.path.insert(0, str(ROOT))

from repro.core import packing
from repro.core.quantize import QuantizedLinearParams
from repro.kernels import api, tune
from repro.obs import counters as obs_counters
from repro.obs import env as obsenv
from repro.obs import trace as obs

BITS = (8, 4, 2)


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with empty buffers + disabled state."""
    obs.disable()
    obs.reset()
    obs_counters.reset()
    tune.clear()
    yield
    obs.disable()
    obs.reset()
    obs_counters.reset()
    tune.clear()


# ------------------------------------------------------------- fixtures ---

def _mk_qdot_params(rng, a_bits, w_bits, K=256, N=128):
    lo, hi = packing.int_range(w_bits, True)
    w = rng.integers(lo, hi + 1, size=(K, N)).astype(np.int8)
    wp = packing.pack(jnp.asarray(w), w_bits, axis=0)
    return QuantizedLinearParams(
        w_packed=wp, w_bits=w_bits, a_bits=a_bits, a_signed=False,
        kappa=jnp.asarray(rng.integers(-64, 64, (N,)).astype(np.int32)),
        lam=jnp.asarray(rng.integers(-2**16, 2**16, (N,)).astype(np.int32)),
        m=jnp.asarray(rng.integers(0, 2**15, (N,)).astype(np.int32)),
        d=18, out_bits=8, k_logical=K)


def _mk_acts(rng, a_bits, M=16, K=256):
    lo, hi = packing.int_range(a_bits, False)
    return jnp.asarray(rng.integers(lo, hi + 1, (M, K)).astype(np.int8))


def _mk_conv(rng, a_bits, w_bits, H=8, W=8, cin=24, cout=40):
    from repro.core import (QuantSpec, calibrate_activation,
                            calibrate_weight, quantize)
    from repro.kernels.qconv import quantize_conv

    x = np.maximum(rng.normal(size=(1, H, W, cin)), 0).astype(np.float32)
    w = rng.normal(size=(3, 3, cin, cout)).astype(np.float32) * 0.08
    sw = calibrate_weight(jnp.asarray(w), w_bits)
    sx = calibrate_activation(x, a_bits, 100.0)
    sy = QuantSpec.activation(a_bits, 8.0)
    qp = quantize_conv(jnp.asarray(w), sw,
                       rng.normal(size=(cout,)).astype(np.float32) * .05 + .3,
                       np.zeros((cout,), np.float32), sx, sy, 1, 1)
    return qp, quantize(jnp.asarray(x), sx)


# ----------------------------------------------------------------- spans ---

def test_span_records_attrs_and_nesting():
    with obs.enabled_scope():
        with obs.span("outer", cat="test", depth=0) as sp:
            sp.set(extra="late")
            with obs.span("inner", cat="test", depth=1):
                pass
    evs = obs.spans(cat="test")
    assert [e["name"] for e in evs] == ["inner", "outer"]  # exit order
    inner, outer = evs
    assert outer["args"] == {"depth": 0, "extra": "late"}
    assert inner["args"] == {"depth": 1}
    # inner lies within outer's [ts, ts+dur] window
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


def test_span_records_exception_and_reraises():
    with obs.enabled_scope():
        with pytest.raises(RuntimeError):
            with obs.span("boom", cat="test"):
                raise RuntimeError("x")
    (ev,) = obs.spans(name="boom")
    assert ev["args"]["error"] == "RuntimeError"


def test_counter_accumulates_and_survives_handle_caching():
    with obs.enabled_scope():
        c = obs.counter("hits")
        c.add().add(4)
        assert obs.counter_values() == {"hits": 5}
    # the cached handle is inert once disabled
    c.add(100)
    assert obs.counter_values() == {"hits": 5}


def test_disabled_mode_is_a_noop(rng):
    """The backend-parity invariant: with observability off the api path
    records nothing, and span/counter return the shared null singletons."""
    assert obs.span("a") is obs.span("b")
    assert obs.counter("a") is obs.counter("b")
    params = _mk_qdot_params(rng, 8, 8)
    api.qdot(params, _mk_acts(rng, 8), backend="xla")
    assert obs.events() == []
    assert obs.dispatch_log() == []
    assert obs.counter_values() == {}
    assert obs_counters.snapshot() == {}


# -------------------------------------------------------------- counters ---

@pytest.mark.parametrize("ab", BITS)
@pytest.mark.parametrize("wb", BITS)
def test_qdot_mac_accounting(ab, wb, rng):
    M, K, N = 16, 256, 128
    params = _mk_qdot_params(rng, ab, wb, K=K, N=N)
    x = _mk_acts(rng, ab, M=M, K=K)
    with obs.enabled_scope():
        api.qdot(params, x, backend="xla")
    snap = obs_counters.snapshot()
    k = obs_counters.key("qdot", wb, ab, "xla", "off")
    assert set(snap) == {k}
    b = snap[k]
    assert b["calls"] == 1
    assert b["macs"] == M * K * N
    assert b["logical_bytes"] == M * K + K * N + M * N
    assert b["packed_bytes"] == (M * K // (8 // ab) + K * N // (8 // wb)
                                 + M * N)
    # the kernel span mirrors the same costs in its args
    (ev,) = obs.spans(name="qdot", cat="kernel")
    assert ev["args"]["macs"] == M * K * N
    assert ev["args"]["w_bits"] == wb and ev["args"]["a_bits"] == ab


@pytest.mark.parametrize("ab,wb", [(8, 8), (8, 4), (4, 2)])
def test_qconv_mac_accounting(ab, wb, rng):
    H = W = 8
    cin, cout, fh = 24, 40, 3
    qp, xq = _mk_conv(rng, ab, wb, H=H, W=W, cin=cin, cout=cout)
    with obs.enabled_scope():
        api.qconv(qp, xq, backend="xla")
    snap = obs_counters.snapshot()
    k = obs_counters.key("qconv", wb, ab, "xla", "off")
    assert k in snap
    ho = wo = H  # stride 1, padding 1, 3x3
    assert snap[k]["macs"] == 1 * ho * wo * fh * fh * cin * cout
    assert snap[k]["calls"] == 1


def test_counter_delta_attribution(rng):
    params = _mk_qdot_params(rng, 8, 4)
    x = _mk_acts(rng, 8)
    with obs.enabled_scope():
        api.qdot(params, x, backend="xla")
        before = obs_counters.snapshot()
        api.qdot(params, x, backend="xla")
        api.qdot(params, x, backend="xla")
        d = obs_counters.delta(obs_counters.snapshot(), before)
    k = obs_counters.key("qdot", 4, 8, "xla", "off")
    assert d[k]["calls"] == 2
    assert d[k]["macs"] == 2 * 16 * 256 * 128
    # unchanged buckets are dropped entirely
    assert obs_counters.delta(before, before) == {}


# ---------------------------------------------------------- dispatch log ---

def _one_dispatch(rng, monkeypatch=None, **kw):
    params = _mk_qdot_params(rng, 8, 4)
    x = _mk_acts(rng, 8)
    with obs.enabled_scope():
        api.qdot(params, x, **kw)
    log = obs.dispatch_log()
    assert len(log) == 1
    return log[0]


def test_dispatch_source_explicit(rng):
    ev = _one_dispatch(rng, backend="xla")
    assert ev["backend"] == "xla"
    assert ev["backend_source"] == "explicit"
    assert ev["pipeline_source"] == "default"
    assert ev["tune_cache_hit"] is False
    assert ev["op"] == "qdot" and ev["w_bits"] == 4 and ev["a_bits"] == 8


def test_dispatch_source_plan_hint(rng):
    ev = _one_dispatch(rng, plan_hints={"backend": "xla",
                                        "pipeline": "double_buffer"})
    assert ev["backend_source"] == "plan"
    assert ev["plan_backend"] == "xla"
    assert ev["pipeline"] == "double_buffer"
    assert ev["pipeline_source"] == "plan"


def test_dispatch_source_env(rng, monkeypatch):
    monkeypatch.setenv("REPRO_QBACKEND", "xla")
    monkeypatch.setenv("REPRO_QPIPELINE", "double_buffer")
    ev = _one_dispatch(rng)
    assert ev["backend_source"] == "env"
    assert ev["env_backend"] == "xla"
    assert ev["pipeline_source"] == "env"
    assert ev["env_pipeline"] == "double_buffer"


def test_dispatch_source_default(rng):
    ev = _one_dispatch(rng)
    assert ev["backend_source"] == "default"
    assert ev["backend"] in api.DEFAULT_ORDER
    assert ev["pipeline"] == "off" and ev["pipeline_source"] == "default"


def test_dispatch_source_tune_cache(rng):
    # first resolution reveals the registry's exact shape key ...
    first = _one_dispatch(rng, backend="xla")
    assert first["tune_cache_hit"] is False
    obs.reset()
    # ... which a recorded sweep winner then serves on the next call
    tune.record_block("qdot", first["shape"], 8, 4, "xla",
                      block=(16, 128, 128), pipeline="double_buffer",
                      us=12.5)
    ev = _one_dispatch(rng, backend="xla")
    assert ev["tune_cache_hit"] is True
    assert ev["block_source"] == "tuned"
    assert ev["block"] == (16, 128, 128)
    assert ev["pipeline"] == "double_buffer"
    assert ev["pipeline_source"] == "tuned"
    assert ev["tune_winner"] == {"block": [16, 128, 128],
                                 "pipeline": "double_buffer", "us": 12.5}


def test_dispatch_mirrors_instant_event(rng):
    _one_dispatch(rng, backend="xla")
    instants = [e for e in obs.events() if e["ph"] == "i"]
    assert len(instants) == 1
    assert instants[0]["name"] == "dispatch:qdot"
    assert instants[0]["args"]["backend"] == "xla"


# ---------------------------------------------------------- trace export ---

def test_chrome_trace_roundtrip(rng, tmp_path):
    from benchmarks import schema

    params = _mk_qdot_params(rng, 8, 4)
    x = _mk_acts(rng, 8)
    with obs.enabled_scope():
        api.qdot(params, x, backend="xla")
        path = obs.export_chrome_trace(str(tmp_path / "t.json"))
    doc = json.loads(pathlib.Path(path).read_text())
    schema.check_trace(doc)
    assert doc["repro"]["version"] == obs.TRACE_SCHEMA_VERSION
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"qdot", "dispatch:qdot"} <= names
    assert "qdot|w4a8|xla|off" in doc["repro"]["op_counters"]


def test_export_if_configured(rng, tmp_path, monkeypatch):
    assert obs.export_if_configured(str(tmp_path / "no.json")) is None
    with obs.enabled_scope():
        obs.counter("x").add()
        assert obs.export_if_configured(None) is None
        target = tmp_path / "via_env.json"
        monkeypatch.setenv("REPRO_OBS_TRACE", str(target))
        assert obs.export_if_configured("ignored.json") == str(target)
    assert json.loads(target.read_text())["repro"]["counters"] == {"x": 1}


def test_report_cli_renders_table(rng, tmp_path, capsys):
    from repro.obs import report

    params = _mk_qdot_params(rng, 8, 4)
    x = _mk_acts(rng, 8)
    with obs.enabled_scope():
        api.qdot(params, x, backend="xla")
        path = obs.export_chrome_trace(str(tmp_path / "t.json"))
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "MAC/us per bit-width" in out
    assert "dispatch decisions" in out
    assert "qdot" in out
    assert report.main([str(tmp_path / "missing.json")]) == 2


def test_ring_buffer_bounds_memory():
    with obs.enabled_scope():
        obs.enable(capacity=8)
        for i in range(50):
            with obs.span(f"s{i}", cat="test"):
                pass
        evs = obs.events()
    assert len(evs) == 8
    assert evs[-1]["name"] == "s49"  # newest survive, oldest fall off
    obs.enable(capacity=obs.DEFAULT_CAPACITY)


# ----------------------------------------------------- serving latency ---

def _stepping_scheduler(num_slots):
    """A `Scheduler` over a host-only adapter whose request is the number
    of steps it runs: no device, so only the loop's bookkeeping shows."""
    from repro.serve.runtime import Scheduler, WorkloadAdapter

    class Cursor:
        def __init__(self, steps):
            self.payload, self.left = steps, steps

    class Steps(WorkloadAdapter):
        name, max_len = "steps", 8

        def input_spec(self):
            return (1,), np.int32

        def step(self, state, feed, positions):
            return feed, state

        def begin(self, payload, *, rid, greedy=True, seed=0):
            return Cursor(payload)

        def feed(self, cursor):
            return np.zeros(1, np.int32), 0

        def consume(self, cursor, row):
            cursor.left -= 1
            return cursor.left == 0

        def tokens_out(self, cursor):
            return cursor.payload

    return Scheduler(Steps(), num_slots)


def test_wave_latency_percentiles_fake_clock():
    """Request latency is finish minus submit on the caller's clock: the
    report's percentiles, queue depths and occupancy are exact."""
    sched = _stepping_scheduler(2)
    for steps in (1, 2, 1):
        sched.submit(steps, now=0.0)
    sched.submit(1, now=0.015)
    for t in (0.010, 0.020, 0.040):
        sched.step(now=t)
    assert sched.idle
    rep = sched.serving_report()
    lat = rep["latency"]
    # r0 ends at step 1, r1 and r2 at step 2, r3 (submitted at 15 ms)
    # waits for a slot until step 3
    want = [0.010, 0.020, 0.020, 0.025]
    for q in (50, 95, 99):
        assert lat[f"p{q}"] == pytest.approx(np.percentile(want, q))
    assert lat["mean"] == pytest.approx(np.mean(want))
    assert lat["max"] == pytest.approx(0.025)
    assert rep["queue_depth"] == {"mean": 1.0, "max": 2}
    assert rep["occupancy"] == {"mean": pytest.approx(2.5 / 3),
                                "min": 0.5}
    assert [s["per_device"] for s in sched.step_log] == [[1.0], [1.0],
                                                         [0.5]]
    assert (rep["requests"], rep["steps"], rep["tokens_out"]) == (4, 3, 5)


def test_wave_counters_bump_when_enabled():
    sched = _stepping_scheduler(2)
    for t in range(2):
        sched.submit(1, now=float(t))
    sched.drain()
    assert obs.counter_values() == {}    # off: nothing is counted
    with obs.enabled_scope():
        for t in range(3):
            sched.submit(1, now=float(t))
        sched.drain()
    vals = obs.counter_values()
    assert vals["engine.requests"] == 3
    assert vals["serve.admits"] == 3 and vals["serve.evicts"] == 3


def test_empty_report_has_null_latency():
    rep = _stepping_scheduler(2).serving_report()
    assert rep["latency"] is None
    assert rep["queue_depth"] is None
    assert rep["occupancy"] is None
    assert (rep["requests"], rep["steps"], rep["tokens_out"]) == (0, 0, 0)


# ----------------------------------------------------------- shared timer ---

def test_time_call_dedupe():
    """One timer implementation behind tune._time and benchmarks'
    time_call (the PR's dedupe satellite): same semantics, µs vs s."""
    from benchmarks import common

    calls = []
    us = obs.time_call(lambda: calls.append(1), warmup=2, iters=5)
    assert us >= 0 and len(calls) == 7  # warmup + iters
    calls.clear()
    common.time_call(lambda: calls.append(1), warmup=2, iters=5)
    assert len(calls) == 7  # same implementation behind the alias
    s = tune._time(lambda: None, iters=2)
    assert 0 <= s < 1.0  # seconds, not µs


def test_counted_time_call_attributes_per_call(rng):
    from benchmarks import common

    params = _mk_qdot_params(rng, 8, 4)
    x = _mk_acts(rng, 8)
    us, per_call = common.counted_time_call(
        lambda: api.qdot(params, x, backend="xla"), warmup=1, iters=3)
    assert us > 0
    assert per_call["macs"] == pytest.approx(16 * 256 * 128)
    assert per_call["packed_bytes"] == pytest.approx(
        16 * 256 // 1 + 256 * 128 // 2 + 16 * 128)
    # counted_time_call force-enables, then restores the prior state
    assert not obs.enabled()


# -------------------------------------------------------------- env knobs ---

def test_env_get_validates(monkeypatch):
    with pytest.raises(KeyError, match="undeclared env knob"):
        obsenv.get("REPRO_NOT_A_KNOB")
    monkeypatch.setenv("REPRO_QPIPELINE", "triple_buffer")
    with pytest.raises(ValueError, match="choices"):
        obsenv.get("REPRO_QPIPELINE")
    monkeypatch.setenv("REPRO_QPIPELINE", "double_buffer")
    assert obsenv.get("REPRO_QPIPELINE") == "double_buffer"
    monkeypatch.delenv("REPRO_QPIPELINE")
    assert obsenv.get("REPRO_QPIPELINE") is None
    monkeypatch.setenv("REPRO_OBS", "maybe")
    with pytest.raises(ValueError, match="not boolean"):
        obsenv.get_bool("REPRO_OBS")
    monkeypatch.setenv("REPRO_OBS", "yes")
    assert obsenv.get_bool("REPRO_OBS") is True
    monkeypatch.setenv("REPRO_OBS", "0")
    assert obsenv.get_bool("REPRO_OBS") is False


def test_env_legacy_alias_warns(monkeypatch):
    monkeypatch.delenv("REPRO_EXTRA_XLA", raising=False)
    monkeypatch.setenv("_REPRO_EXTRA_XLA", "--flag")
    with pytest.warns(DeprecationWarning, match="_REPRO_EXTRA_XLA"):
        assert obsenv.get("REPRO_EXTRA_XLA") == "--flag"
    # the canonical name wins over the legacy alias
    monkeypatch.setenv("REPRO_EXTRA_XLA", "--new")
    assert obsenv.get("REPRO_EXTRA_XLA") == "--new"


def test_env_warn_unknown(monkeypatch):
    monkeypatch.setenv("REPRO_TYPO_KNOB", "1")
    monkeypatch.setattr(obsenv, "_warned_unknown", False)
    with pytest.warns(UserWarning, match="REPRO_TYPO_KNOB"):
        assert "REPRO_TYPO_KNOB" in obsenv.warn_unknown()
    # second scan still reports, but silently
    assert "REPRO_TYPO_KNOB" in obsenv.warn_unknown()


def test_env_table_covers_every_knob():
    t = obsenv.table()
    for name in obsenv.KNOBS:
        assert f"`{name}`" in t

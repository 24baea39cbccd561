"""The per-layer metrics that read the program's own spans
(`benchmarks/chip/program_spans.py`): each reader on a hand-made window
and span list, None when the spans are missing or lost, the pairing that
puts program spans on the trace's clock, and a CPU profiler capture in
which the program's spans sit on the host plane beside the harness's."""
import time
import types

import pytest

from benchmarks.chip import cell as cells, driver, program_spans, \
    trace_reduce
from bench_chip_smoke import lm_cell, no_persistent_cache

from repro.obs import trace

NEW = ("sched_self_ms.lm", "sched_self_ms.cnn", "logits_to_host_ms.lm",
       "queue_wait_p95_ms.prefill", "idle_in_copy.lm")
MS = 1e3                      # the spans' clock counts microseconds


def _perf(us: float) -> float:
    return trace.to_perf_counter(us)


def _window(t0_ms, t1_ms, t_stop_ms, due=None, paused=()):
    due = due or {}
    return driver.Window(
        t0=_perf(t0_ms * MS), t1=_perf(t1_ms * MS),
        t_stop=_perf(t_stop_ms * MS),
        due={r: _perf(t * MS) for r, t in due.items()},
        index={}, token_times={}, finished={}, failed=0, lateness=[],
        steps=0, sched_s=0.0, adapter_step_s=0.0, begin_s=0.0,
        active_slot_steps=0, live_positions=0, queue_depth=[], compiles=0,
        paused=[(_perf(a * MS), _perf(b * MS)) for a, b in paused],
        state_bytes=None)


def _ev(name, start_ms, dur_ms, **args):
    return {"name": name, "cat": "serve", "ph": "X", "ts": start_ms * MS,
            "dur": dur_ms * MS, "pid": 0, "tid": 0, "args": args}


def _run(window, events=None):
    return types.SimpleNamespace(window=window, events=events, trace=None,
                                 config={}, traffic={}, peaks={}, chips=1)


@pytest.fixture
def recorded(monkeypatch):
    """Hand the readers ``evs`` as the program's buffer."""
    def put(evs, dropped=0):
        monkeypatch.setattr(trace, "events", lambda: list(evs))
        monkeypatch.setattr(trace, "dropped", lambda: dropped)
    return put


def _read(name, run):
    return cells.metric_module(name).read(run)


def _steps(starts_ms, admit=1.0, feed=2.0, step=30.0, consume=3.0,
           copy=20.0):
    """One scheduler step per start: its phases back to back."""
    evs = []
    for t in starts_ms:
        evs += [_ev("serve.admit", t, admit),
                _ev("serve.feed", t + admit, feed),
                _ev("serve.step", t + admit + feed, step),
                _ev("lm.logits_to_host", t + admit + feed + step - copy,
                    copy),
                _ev("serve.consume", t + admit + feed + step, consume)]
    return evs


# ------------------------------------------------------------ loading ---

@pytest.mark.parametrize("name", NEW)
def test_loading_a_reader_turns_program_spans_on(name):
    trace.disable()
    trace._XLA_ANNOTATIONS = False
    cells.metric_module(name)
    assert trace.enabled() and trace._XLA_ANNOTATIONS


# ------------------------------------------------------------ readers ---

def test_sched_self_ms_on_hand_made_spans(recorded):
    # a step before the window (at 50 ms) counts in nothing; the two in
    # it spend 1 + 2 + 3 ms outside the adapter's step
    evs = _steps([50.0, 200.0, 400.0])
    evs += [_ev("serve.submit", 60.0, 5.0, rid=0)]
    evs += [_ev("serve.submit", 150.0 + 10 * i, 5.0, rid=1 + i)
            for i in range(4)]
    recorded(evs)
    run = _run(_window(100.0, 900.0, 1000.0))
    assert _read("sched_self_ms.lm", run) == pytest.approx(6.0)
    # the CNN's twin spreads the window's four 5 ms submits over its
    # two steps
    assert _read("sched_self_ms.cnn", run) == pytest.approx(6.0 + 10.0)


def test_logits_to_host_ms_on_hand_made_spans(recorded):
    evs = (_steps([50.0], copy=100.0) + _steps([200.0], copy=20.0)
           + _steps([400.0], copy=30.0))
    recorded(evs)
    run = _run(_window(100.0, 900.0, 1000.0))
    assert _read("logits_to_host_ms.lm", run) == pytest.approx(25.0)


def test_queue_wait_p95_on_hand_made_spans(recorded):
    """Requests 0-3 waited 10-40 ms; request 4 was submitted and never
    admitted, so it waits from its submit's end to the drain's end;
    request 5 was due before the window."""
    import numpy as np
    evs = []
    for r, wait in enumerate((10.0, 20.0, 30.0, 40.0)):
        evs += [_ev("serve.submit", 200.0 + r, 0.5, rid=r),
                _ev("serve.queue", 200.5 + r, wait, rid=r)]
    evs += [_ev("serve.submit", 899.0, 1.0, rid=4),
            _ev("serve.submit", 50.0, 0.5, rid=5),
            _ev("serve.queue", 50.5, 500.0, rid=5)]
    recorded(evs)
    run = _run(_window(100.0, 900.0, 1000.0,
                       due={0: 150, 1: 160, 2: 170, 3: 180, 4: 880,
                            5: 40}))
    want = np.percentile([10.0, 20.0, 30.0, 40.0, 100.0], 95)
    assert _read("queue_wait_p95_ms.prefill", run) == pytest.approx(want)


def test_readers_find_nothing_without_spans(recorded):
    recorded([])
    run = _run(_window(100.0, 900.0, 1000.0, due={0: 150},
                       paused=[(90.0, 95.0)]),
               events={"host": [["bench.step", 0.0, 1e6]],
                       "devices": {"/device:TPU:0": {"ops": [],
                                                     "modules": []}}})
    for name in NEW:
        assert _read(name, run) is None, name
    # spans there, but none of the ones a reader reads
    recorded([_ev("other", 200.0, 1.0)])
    for name in NEW:
        assert _read(name, run) is None, name


def test_a_window_that_lost_spans_reads_nothing(recorded):
    evs = _steps([200.0, 400.0])
    run = _run(_window(100.0, 900.0, 1000.0))
    # events fell off the front, but all of them before the window
    recorded(_steps([50.0]) + evs, dropped=3)
    assert _read("sched_self_ms.lm", run) == pytest.approx(6.0)
    # the oldest event left ends inside the window: some of the window's
    # spans may be gone
    recorded(evs, dropped=3)
    for name in ("sched_self_ms.lm", "logits_to_host_ms.lm"):
        assert _read(name, run) is None, name


def test_a_program_without_the_clock_reads_nothing(recorded, monkeypatch):
    """An older program (no `to_perf_counter`, no `dropped`): the readers
    return None and raise nothing."""
    recorded(_steps([200.0]))
    run = _run(_window(100.0, 900.0, 1000.0))
    monkeypatch.delattr(trace, "to_perf_counter")
    for name in NEW:
        assert _read(name, run) is None, name


# ---------------------------------------------------------- alignment ---

OFFSET_NS = 7_654_321_000.0   # the trace's clock minus perf_counter


def _paired(starts_ms, jitter_us=()):
    """``serve.step`` spans at ``starts_ms`` and the ``bench.step`` event
    of each on the trace's clock, a few microseconds later."""
    evs, host = [], []
    for i, t in enumerate(starts_ms):
        evs.append(_ev("serve.step", t, 30.0))
        j = jitter_us[i] if i < len(jitter_us) else 0.0
        host.append(["bench.step",
                     _perf(t * MS) * 1e9 + OFFSET_NS + 4e3 + j * 1e3, 29e6])
    return evs, host


STARTS = [100.0 + 41.3 * i + (i % 3) * 7.0 for i in range(20)]


def test_offset_pairs_steps_after_the_profiler_start(recorded):
    evs, host = _paired(STARTS, jitter_us=[(-1) ** i * 60 for i in
                                           range(20)])
    # two steps ran before the profiler started: not in the trace
    recorded(evs)
    run = _run(_window(0.0, 2000.0, 2000.0, paused=[(170.0, 175.0)]),
               events={"host": host[2:], "devices": {}})
    off = program_spans.trace_offset_ns(run)
    assert off == pytest.approx(OFFSET_NS + 4e3, abs=100.0)


def test_offset_refuses_a_shuffled_pairing(recorded):
    """The trace's steps at the program's gaps in another order: the
    pairs disagree, and no offset comes back."""
    import numpy as np
    evs, host = _paired(STARTS)
    recorded(evs)
    run = _run(_window(0.0, 2000.0, 2000.0, paused=[(50.0, 60.0)]),
               events={"host": host, "devices": {}})
    assert program_spans.trace_offset_ns(run) is not None
    gaps = np.diff([h[1] for h in host])
    perm = np.random.default_rng(0).permutation(len(gaps))
    starts = host[0][1] + np.concatenate([[0.0], np.cumsum(gaps[perm])])
    run.events = {"host": [["bench.step", float(t), 29e6] for t in starts],
                  "devices": {}}
    assert program_spans.trace_offset_ns(run) is None
    # a trace that lost its first step pairs every step with the next
    run.events = {"host": host[1:], "devices": {}}
    assert program_spans.trace_offset_ns(run) is None
    # three pairs of twenty off by more than half a millisecond
    evs2, host2 = _paired(STARTS, jitter_us=[0, 0, 900, 0, 0, 0, 0, 0, 0,
                                             -800, 0, 0, 0, 0, 0, 0, 0,
                                             700])
    recorded(evs2)
    run.events = {"host": host2, "devices": {}}
    assert program_spans.trace_offset_ns(run) is None


def test_offset_needs_the_profiler_start(recorded):
    evs, host = _paired(STARTS)
    recorded(evs)
    run = _run(_window(0.0, 2000.0, 2000.0), events={"host": host,
                                                    "devices": {}})
    assert program_spans.trace_offset_ns(run) is None


def test_idle_in_copy_on_hand_made_events(recorded):
    """Two 100 ms steps; the device works the first 40 ms of each, and
    the copy spans 50-95 ms: 90 of the 120 idle ms are in the copy."""
    evs = []
    for t in (100.0, 200.0):
        evs += [_ev("serve.step", t, 99.0),
                _ev("lm.logits_to_host", t + 50.0, 45.0)]
    recorded(evs)
    b0 = _perf(100.0 * MS) * 1e9 + OFFSET_NS
    host = [["bench.step", b0, 100e6], ["bench.step", b0 + 100e6, 100e6]]
    ops = [["fusion", b0, 40e6], ["fusion", b0 + 100e6, 40e6]]
    run = _run(_window(0.0, 1000.0, 1000.0, paused=[(50.0, 60.0)]),
               events={"host": host, "devices": {
                   "/device:TPU:0": {"ops": ops, "modules": []}}})
    assert _read("idle_in_copy.lm", run) == pytest.approx(75.0)
    # the pairing refused: nothing
    run.window.paused = []
    assert _read("idle_in_copy.lm", run) is None


def _two_steps(recorded, ops_ms=40.0):
    """Two 100 ms LM steps on the trace's clock, the device busy for
    the first ``ops_ms`` of each: dispatch 0-10, wait 10-45, copy 50-95,
    consume 99-100 ms of each step (serve.step spans 0-99)."""
    evs = []
    for t in (100.0, 200.0):
        evs += [_ev("serve.step", t, 99.0),
                _ev("lm.dispatch", t, 10.0),
                _ev("lm.device_wait", t + 10.0, 35.0),
                _ev("lm.logits_to_host", t + 50.0, 45.0),
                _ev("serve.consume", t + 99.0, 1.0)]
    recorded(evs)
    b0 = _perf(100.0 * MS) * 1e9 + OFFSET_NS
    host = [["bench.step", b0, 100e6], ["bench.step", b0 + 100e6, 100e6]]
    ops = [["fusion", b0, ops_ms * 1e6],
           ["fusion", b0 + 100e6, ops_ms * 1e6]]
    return _run(_window(0.0, 1000.0, 1000.0, paused=[(50.0, 60.0)]),
                events={"host": host, "devices": {
                    "/device:TPU:0": {"ops": ops, "modules": []}}})


def test_idle_breakdown_puts_idle_time_down_to_spans(recorded):
    """Of 120 idle ms: 10 in the wait (40-45 ms of each step), 90 in
    the copy, 18 in serve.step outside its inner spans (45-50 and 95-99),
    2 in consume, none outside every span."""
    from benchmarks.chip import span_breakdown
    run = _two_steps(recorded)
    got = span_breakdown.idle_by_span(run)
    assert got["lm.device_wait"] == pytest.approx(100 * 10 / 120)
    assert got["lm.logits_to_host"] == pytest.approx(100 * 90 / 120)
    assert got["lm.dispatch"] == pytest.approx(0.0, abs=1e-6)
    assert got["serve.consume"] == pytest.approx(100 * 2 / 120)
    assert got["serve.step (rest)"] == pytest.approx(100 * 18 / 120)
    assert got["no program span"] == pytest.approx(0.0, abs=1e-6)
    # the reader and the breakdown agree on the copy
    assert _read("idle_in_copy.lm", run) == pytest.approx(
        got["lm.logits_to_host"])
    gaps = span_breakdown.longest_gaps(run)
    assert [g[0] for g in gaps[:2]] == ["lm.logits_to_host"] * 2
    # the pairing refused: nothing to put the time down to
    run.window.paused = []
    assert span_breakdown.idle_by_span(run) is None
    assert span_breakdown.longest_gaps(run) is None


def test_copy_rate_reads_the_byte_counter(monkeypatch):
    from benchmarks.chip import span_breakdown
    trace.reset()
    trace.enable()
    for _ in range(3):
        trace.complete("lm.logits_to_host", trace.now_us() - 2000.0)
        trace.counter("lm.bytes_to_host").add(8_000_000)
    took = sum(e["dur"] for e in trace.spans("lm.logits_to_host"))
    assert span_breakdown.copy_gb_per_s() == pytest.approx(
        24e6 / took * 1e-3)
    assert span_breakdown.copy_gb_per_s() == pytest.approx(4.0, rel=0.05)
    monkeypatch.setattr(trace, "dropped", lambda: 1)  # spans were lost
    assert span_breakdown.copy_gb_per_s() is None
    monkeypatch.undo()
    trace.reset()
    assert span_breakdown.copy_gb_per_s() is None   # nothing counted


def test_breakdown_script_on_a_traced_cpu_run(monkeypatch):
    """`span_breakdown.traced_run` over a small LM cell on the CPU. The
    CPU profile has no device plane, so each ``bench.step`` gets a made-up
    op over its first 30%: the rest of the step is idle and lies inside
    the program's spans."""
    from benchmarks.chip import span_breakdown

    no_persistent_cache(monkeypatch)
    extract = trace_reduce.extract

    def with_device(xp):
        ev = extract(xp)
        ops = [["fusion", s, d * 0.3] for n, s, d in ev["host"]
               if n == "bench.step"]
        ev["devices"] = {"/device:TPU:0": {"ops": ops, "modules": []}}
        return ev

    monkeypatch.setattr(trace_reduce, "extract", with_device)
    line, summary = span_breakdown.traced_run(
        lm_cell(), seed=2**31 + 977, seconds=3.0, require_tpu=False)
    assert line["correct"] is True
    assert "logits_to_host_ms.lm" in line["metrics"]
    assert trace_reduce.extract is with_device       # put back
    shares = summary["idle_share_by_span"]
    assert shares["lm.logits_to_host"] == pytest.approx(
        line["metrics"]["idle_in_copy.lm"]["value"])
    assert sum(shares.values()) == pytest.approx(100.0)
    assert set(summary["ms_per_step"]) >= {
        "serve.admit", "serve.feed", "serve.consume", "lm.dispatch",
        "lm.device_wait", "lm.logits_to_host"}
    assert not any(n.startswith("vision.") for n in summary["ms_per_step"])
    assert summary["copy_gb_per_s"] > 0
    assert len(summary["longest_idle_gaps"]) == 10


# ------------------------------------------------- a CPU profile capture ---

def test_cpu_profile_holds_program_spans_beside_the_harness(
        tmp_path, monkeypatch):
    """A tiny LM scheduler run under `jax.profiler` with the harness's
    annotations and the program's spans on: the host plane holds both,
    and the offset from the pairing puts each program ``serve.step`` on
    the profiler's own ``serve.step`` event."""
    import jax
    from jax.profiler import ProfileData

    from repro.serve.runtime import Scheduler

    no_persistent_cache(monkeypatch)
    c = lm_cell()
    served = c.family.build(c.config, c.traffic, None)
    served.load(3)
    adapter = driver.TimedAdapter(served.adapter(), annotate=True)

    def serve():
        sched = Scheduler(adapter, c.traffic["slots"])
        for p in served.warm_payloads():
            sched.submit(p)
        sched.drain()

    serve()                                      # compile outside the trace
    trace.reset()
    cells.metric_module("idle_in_copy.lm")       # turns spans on
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    paused = [(t0, time.perf_counter())]
    serve()
    jax.profiler.stop_trace()
    xp = trace_reduce.find_xplane(str(tmp_path))

    host = {}
    for plane in ProfileData.from_file(xp).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, []).append(ev.start_ns)
    for name in ("bench.step", "serve.step", "lm.logits_to_host",
                 "lm.device_wait", "serve.consume"):
        assert name in host, name
    assert len(host["lm.logits_to_host"]) == len(host["bench.step"])

    ev = trace_reduce.extract(xp)
    win = driver.Window(
        t0=t0, t1=time.perf_counter(), t_stop=time.perf_counter(), due={},
        index={}, token_times={}, finished={}, failed=0, lateness=[],
        steps=0, sched_s=0.0, adapter_step_s=0.0, begin_s=0.0,
        active_slot_steps=0, live_positions=0, queue_depth=[], compiles=0,
        paused=paused, state_bytes=None)
    run = _run(win, events=ev)
    off = program_spans.trace_offset_ns(run)
    assert off is not None
    placed = sorted(s * 1e9 + off for n, s, _, _ in program_spans.spans(run)
                    if n == "serve.step")
    profiled = sorted(host["serve.step"])
    assert len(placed) == len(profiled)
    assert max(abs(a - b) for a, b in zip(placed, profiled)) < 0.2e6

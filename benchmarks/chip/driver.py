"""The measured window: an open loop over the program's
``Scheduler.step`` (``policy="continuous"``).

`TimedAdapter` wraps the program's workload adapter and delegates every
hook to it. It times ``begin`` and ``step`` on the host clock in every
run, notes when each output reached the host (the end of the ``step``
that produced it), and, in a traced run, puts a
``jax.profiler.TraceAnnotation`` around ``begin``, ``feed``, ``step``
and ``consume`` so the trace shows what the host did in each gap of the
device.

`serve` offers each request at its due time and times it from then, not
from when it was submitted, so a late generator or a stalled step shows
in the latencies. It reports how late the generator ran. The load starts
``preroll_s`` before the window, so the window measures a server whose
slots have filled, not the ramp from an empty one.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

clock = time.perf_counter


class TimedAdapter:
    """Delegating wrapper over a `repro.serve.runtime` workload adapter."""

    def __init__(self, inner, *, annotate: bool = False):
        self.inner = inner
        self.name = inner.name
        self.max_len = inner.max_len
        self.reset(annotate=annotate)

    def reset(self, *, annotate: bool):
        """Forget what earlier schedulers did (the warm-up's)."""
        self.annotate = annotate
        self.step_s = 0.0          # host seconds inside inner.step
        self.begin_s = 0.0         # host seconds inside inner.begin
        self.steps = 0
        self.live_positions = 0    # sum over steps of the fed positions
        self.t_step_end = 0.0
        self.token_times: Dict[int, List[float]] = {}
        self._rid: Dict[int, int] = {}
        self.state_bytes = None    # bytes of the state the steps carry

    def _span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    # ---- delegated cache spec and bookkeeping ----
    def init_state(self, phys_slots):
        return self.inner.init_state(phys_slots)

    def place_state(self, state, mesh, dp_axis):
        return self.inner.place_state(state, mesh, dp_axis)

    def reset_state(self, state, slot_mask):
        return self.inner.reset_state(state, slot_mask)

    def input_spec(self):
        return self.inner.input_spec()

    def reserve_tokens(self, cur):
        return self.inner.reserve_tokens(cur)

    def prompt_len(self, cur):
        return self.inner.prompt_len(cur)

    def tokens_out(self, cur):
        return self.inner.tokens_out(cur)

    def finish(self, cur):
        return self.inner.finish(cur)

    def result(self, cur):
        return self.inner.result(cur)

    # ---- timed hooks ----
    def begin(self, payload, *, rid, greedy=True, seed=0):
        t0 = clock()
        with self._span("bench.begin"):
            cur = self.inner.begin(payload, rid=rid, greedy=greedy,
                                   seed=seed)
        self.begin_s += clock() - t0
        self._rid[id(cur)] = rid
        self.token_times[rid] = []
        return cur

    def feed(self, cur):
        with self._span("bench.feed"):
            return self.inner.feed(cur)

    def step(self, state, feed, positions):
        if self.state_bytes is None:
            import jax
            self.state_bytes = sum(int(x.nbytes)
                                   for x in jax.tree_util.tree_leaves(state))
        t0 = clock()
        with self._span("bench.step"):
            rows, state = self.inner.step(state, feed, positions)
        self.t_step_end = clock()
        self.step_s += self.t_step_end - t0
        self.steps += 1
        self.live_positions += int(np.sum(positions))
        return rows, state

    def consume(self, cur, row):
        with self._span("bench.consume"):
            n0 = self.inner.tokens_out(cur)
            done = self.inner.consume(cur, row)
            n = self.inner.tokens_out(cur)
            times = self.token_times[self._rid[id(cur)]]
            # an LM cursor counts its outputs as they come; an image's
            # one answer counts from the start and arrives with `done`
            if len(times) < n and (n > n0 or done):
                times.append(self.t_step_end)
        return done


@dataclasses.dataclass
class Window:
    """What one measured window produced, on the host clock."""
    t0: float
    t1: float                       # window end
    t_stop: float                   # end of the drain after it
    due: Dict[int, float]           # rid -> due time
    index: Dict[int, int]           # rid -> index into the request list
    token_times: Dict[int, List[float]]
    finished: Dict[int, object]     # rid -> the program's result
    failed: int
    lateness: List[float]           # submit time - due time, per request
    steps: int                      # Scheduler.step calls that ran a step
    sched_s: float                  # host seconds inside Scheduler.step
    adapter_step_s: float           # ... of which inside adapter.step
    begin_s: float                  # host seconds inside adapter.begin
    active_slot_steps: int          # sum over steps of occupied slots
    live_positions: int             # sum over steps of fed positions
    queue_depth: List[int]          # due, not yet admitted, per step
    compiles: int                   # compile requests inside the window
    paused: List[tuple]             # (start, end) of the tracer's calls
    state_bytes: Optional[int]      # bytes of the state the steps carried

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def in_window(self, rid: int) -> bool:
        return self.t0 <= self.due[rid] < self.t1

    def serving_seconds(self, lo: float, hi: float) -> float:
        """Seconds of [lo, hi) in which the loop served: the profiler's
        start and stop calls, which stall the loop, left out."""
        stalled = sum(max(0.0, min(e, hi) - max(s, lo))
                      for s, e in self.paused)
        return (hi - lo) - stalled


def serve(sched, adapter: TimedAdapter, payloads: list, offsets,
          seconds: float, *, preroll_s: float = 0.0, drain_s: float = 0.0,
          tracer=None,
          compile_counter: Optional[Callable[[], int]] = None) -> Window:
    """Offer ``payloads[i]`` at ``t_load + offsets[i]``; measure the
    window [t_load + preroll_s, + seconds).

    Steps that start before the window fill the server and count in no
    window statistic. With ``drain_s`` > 0 the loop goes on after the
    window, arrivals included, until every request due in the window has
    its first output or ``drain_s`` more seconds have passed.
    ``tracer(now, t0)`` is called every iteration (it starts and stops
    the profiler) and lists the spans of its calls in ``tracer.paused``.
    """
    n = len(payloads)
    t_load = clock() + 0.05
    due_at = t_load + np.asarray(offsets, np.float64)
    t0 = t_load + preroll_s
    t1 = t0 + seconds
    i = 0
    due: Dict[int, float] = {}
    index: Dict[int, int] = {}
    lateness: List[float] = []
    failed = 0
    sched_s = 0.0
    steps = 0
    active_slot_steps = 0
    depth: List[int] = []
    base = None                      # the adapter's counters at t0
    c0 = 0
    finished: Dict[int, object] = {}

    def waiting_first() -> bool:
        return any(not adapter.token_times[r] for r in due
                   if t0 <= due[r] < t1)

    while True:
        now = clock()
        if base is None and now >= t0:
            base = (adapter.step_s, adapter.begin_s, adapter.live_positions)
            c0 = compile_counter() if compile_counter else 0
        if tracer is not None:
            tracer(now, t0)
        if now >= t1:
            if drain_s <= 0 or now >= t1 + drain_s or not waiting_first():
                break
        # at most one batch of arrivals per step: a backlog of due
        # requests waits in the generator (timed from its due time)
        # instead of starving the step of the host
        budget = sched.slots.phys
        while i < n and due_at[i] <= now and budget > 0:
            budget -= 1
            try:
                rid = sched.submit(payloads[i], now=float(due_at[i]))
            except Exception:          # a request the server refused
                failed += int(t0 <= due_at[i] < t1)
                i += 1
                continue
            due[rid] = float(due_at[i])
            index[rid] = i
            lateness.append(now - float(due_at[i]))
            i += 1
        if sched.idle:
            nxt = due_at[i] if i < n else t1
            time.sleep(max(0.0, min(nxt, t1) - clock()))
            if i >= n and clock() >= t1:
                break
            continue
        ts = clock()
        done = sched.step(now=ts)
        if base is not None:
            sched_s += clock() - ts
            steps += 1
            active_slot_steps += sched.step_log[-1]["active"]
            depth.append(sched.queue_depth + int(np.searchsorted(
                due_at, clock()) - i))
        for rid in done:
            finished[rid] = sched.results[rid]
    t_stop = clock()
    if tracer is not None:
        tracer(float("inf"), t0)
    if base is None:
        base = (adapter.step_s, adapter.begin_s, adapter.live_positions)
    compiles = (compile_counter() - c0) if compile_counter else 0
    return Window(
        t0=t0, t1=t1, t_stop=t_stop, due=due, index=index,
        token_times=adapter.token_times, finished=finished, failed=failed,
        lateness=lateness, steps=steps, sched_s=sched_s,
        adapter_step_s=adapter.step_s - base[0],
        begin_s=adapter.begin_s - base[1],
        active_slot_steps=active_slot_steps,
        live_positions=(adapter.live_positions - base[2]) + active_slot_steps,
        queue_depth=depth, compiles=compiles,
        paused=list(getattr(tracer, "paused", ())),
        state_bytes=adapter.state_bytes)

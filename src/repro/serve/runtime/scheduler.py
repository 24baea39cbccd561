"""Event-driven serving scheduler: admission queue, per-slot occupancy,
mid-wave eviction.

The loop is token-synchronous: every `step()` runs the adapter's jitted
engine step once over the full physical slot array, feeds each occupied
slot its next input (prompt token, generated token, or image), folds the
per-slot outputs back into the request cursors, and **evicts finished
slots immediately** — under the default ``policy="continuous"`` the
freed slot is re-admitted from the queue at the very next step, so a
long request never holds the whole batch hostage (Orca-style iteration-
level scheduling). ``policy="wave"`` only admits when *all* slots are
free, which reproduces the legacy synchronous wave engines — same
per-request outputs, same `utilization_report()` — and is the baseline
the serving benchmark compares against.

Timestamps are injected (``submit(x, now=...)`` / ``step(now=...)``) so
the load generator can drive a deterministic virtual clock; when omitted
they fall back to ``self.clock`` (wall time). Latency is measured
submit→finish in the caller's time unit.

Because every adapter step is row-independent and sampling is keyed per
request, per-request outputs are **bit-exact across policies, admission
orders, and slot placements** — continuous batching changes *when* a
request runs, never *what* it computes.

With `repro.obs` on, each phase records a ``cat="serve"`` span:
``serve.submit`` (arg ``rid``: the adapter's ``begin`` and the fit
check), and per step ``serve.admit`` (admission, ``reset_state``, the
active set), ``serve.feed`` (the feed rows), ``serve.step`` (the
adapter's step) and ``serve.consume`` (taking the new state, which
releases the old; consume and evict; the step record), so that the
scheduler's own host time is the step minus ``serve.step``.
``serve.queue`` (arg ``rid``) spans a request's wait from submit to
admission, on the spans' clock.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Deque, Dict, List, Optional

import numpy as np

from repro.obs import trace as obs
from repro.serve.runtime.slots import SlotManager


class Backpressure(RuntimeError):
    """Admission queue is full; retry after requests drain."""


class WaveStats:
    """Per-wave per-device slot utilization + latency bookkeeping (the
    legacy engines' `_WaveStats`, hoisted here so the runtime and the
    compat shims share one implementation): device d owns the contiguous
    slot range [d*B/dp, (d+1)*B/dp); real slots fill from 0, so a padded
    slot is an idle cluster core (the paper's fig. 9 readout).

    Each wave records its latency (stamped by ``clock``, an instance-
    overridable callable so tests inject a deterministic fake) and the
    request-queue depth at admission; `utilization_report()` aggregates
    p50/p95/p99 latency and queue-depth stats next to the utilization
    columns."""

    batch: int
    _dp: int
    clock = staticmethod(time.perf_counter)   # seconds; override in tests

    def __init__(self, batch: int = 0, dp: int = 1):
        self.batch = batch
        self._dp = dp
        self.wave_stats: List[dict] = []

    def _record_wave(self, n_real: int, queue_depth: int = 0):
        b_loc = self.batch // self._dp
        per_dev = [min(max(n_real - d * b_loc, 0), b_loc) / b_loc
                   for d in range(self._dp)]
        self.wave_stats.append({"n_real": n_real, "batch": self.batch,
                                "per_device": per_dev,
                                "queue_depth": queue_depth,
                                "t0": self.clock(), "latency_us": None})

    def _finish_wave(self):
        w = self.wave_stats[-1]
        w["latency_us"] = (self.clock() - w.pop("t0")) * 1e6
        obs.counter("engine.waves").add(1)
        obs.counter("engine.requests").add(w["n_real"])
        return w

    def utilization_report(self) -> dict:
        """Aggregate per-device slot utilization, wave-latency
        percentiles, and queue-depth stats across the waves served so
        far — a device whose slots were padding did no useful work."""
        if not self.wave_stats:
            return {"devices": self._dp, "waves": 0, "mean_util": 0.0,
                    "per_device": [0.0] * self._dp, "latency_us": None,
                    "queue_depth": None, "occupancy_timeline": []}
        per_dev = [float(np.mean([w["per_device"][d]
                                  for w in self.wave_stats]))
                   for d in range(self._dp)]
        lats = [w["latency_us"] for w in self.wave_stats
                if w.get("latency_us") is not None]
        latency = None
        if lats:
            latency = {"p50": float(np.percentile(lats, 50)),
                       "p95": float(np.percentile(lats, 95)),
                       "p99": float(np.percentile(lats, 99)),
                       "mean": float(np.mean(lats)),
                       "max": float(np.max(lats)),
                       "waves": len(lats)}
        depths = [w.get("queue_depth", 0) for w in self.wave_stats]
        return {"devices": self._dp, "waves": len(self.wave_stats),
                "mean_util": float(np.mean(per_dev)),
                "per_device": per_dev,
                "latency_us": latency,
                "queue_depth": {"mean": float(np.mean(depths)),
                                "max": int(np.max(depths))},
                # per-device real-slot occupancy over time, wave by wave
                "occupancy_timeline": [list(w["per_device"])
                                       for w in self.wave_stats]}


@dataclasses.dataclass
class _Entry:
    """One submitted request's lifecycle record."""
    rid: int
    cursor: Any
    submit_t: float
    admit_t: Optional[float] = None
    finish_t: Optional[float] = None
    sid: Optional[int] = None
    submit_us: Optional[float] = None    # spans' clock, when obs is on


class Scheduler(WaveStats):
    """Workload-agnostic serving loop over a `WorkloadAdapter`.

    Parameters: ``num_slots`` is the number of *real* request slots (the
    legacy engines' ``batch_size``); with ``mesh=`` the physical slot
    array is padded to the data-parallel axis size and sharded so device
    *d* owns a contiguous block (ragged ``num_slots % dp`` is absorbed
    by pad slots that are never admitted — the old hard divisibility
    constraint is gone). ``max_queue`` bounds the admission queue:
    `submit` raises `Backpressure` when it is full.
    """

    def __init__(self, adapter, num_slots: int, *, mesh=None,
                 dp_axis: str = "data", policy: str = "continuous",
                 max_queue: Optional[int] = None, page_tokens: int = 16):
        if policy not in ("continuous", "wave"):
            raise ValueError(f"unknown policy {policy!r}")
        if mesh is not None:
            from repro.parallel.sharding import cluster_axis_size
            dp = cluster_axis_size(mesh, dp_axis)
        else:
            dp = 1
        self.adapter = adapter
        self.policy = policy
        self.max_queue = max_queue
        self.slots = SlotManager(num_slots, adapter.max_len, dp=dp,
                                 page_tokens=page_tokens)
        # wave stats run over the *physical* array so per-device columns
        # line up with the mesh blocks even when num_slots % dp != 0
        super().__init__(batch=self.slots.phys, dp=dp)
        self.state = adapter.init_state(self.slots.phys)
        self._queue: Deque[_Entry] = collections.deque()
        self._entries: Dict[int, _Entry] = {}
        self.results: Dict[int, Any] = {}
        self.request_log: List[dict] = []
        self.step_log: List[dict] = []
        self._next_rid = 0
        self._rid0 = 0              # sampling-key base of the live serve()
        self._greedy = True
        self._seed = 0
        self._wave_live = 0

    # ------------------------------------------------------- admission ---

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def idle(self) -> bool:
        return not self._queue and not self.slots.active

    def submit(self, payload, now: Optional[float] = None) -> int:
        """Enqueue one request; returns its rid. Raises `Backpressure`
        when the admission queue is full and `CapacityError` when the
        request can never fit the cache."""
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            raise Backpressure(
                f"admission queue full ({self.max_queue} pending)")
        now = self.clock() if now is None else now
        rid = self._next_rid
        self._next_rid += 1
        with obs.span("serve.submit", cat="serve", rid=rid):
            cur = self.adapter.begin(payload, rid=rid - self._rid0,
                                     greedy=self._greedy, seed=self._seed)
            self.slots.check_fits(self.adapter.prompt_len(cur))
        e = _Entry(rid=rid, cursor=cur, submit_t=now,
                   submit_us=obs.now_us() if obs.enabled() else None)
        self._entries[rid] = e
        if getattr(cur, "done", False):
            # degenerate request (e.g. max_new_tokens == 0): completes
            # without ever occupying a slot
            self._finish(e, now)
        else:
            self._queue.append(e)
        return rid

    def _admit(self, now: float):
        admitted = []
        if self.policy == "wave":
            # legacy semantics: only admit when the whole array is free
            if self.slots.active or not self._queue:
                return
            n = min(self.slots.real, len(self._queue))
            for _ in range(n):
                admitted.append(self._admit_one(now))
            self._wave_live = n
            self._record_wave(n, queue_depth=len(self._queue))
        else:
            while self._queue and self.slots.free_slots:
                admitted.append(self._admit_one(now))
        if admitted:
            mask = np.zeros(self.slots.phys, bool)
            mask[[e.sid for e in admitted]] = True
            self.state = self.adapter.reset_state(self.state, mask)

    def _admit_one(self, now: float) -> _Entry:
        e = self._queue.popleft()
        e.sid = self.slots.admit(
            e.rid, self.adapter.reserve_tokens(e.cursor))
        e.admit_t = now
        if e.submit_us is not None:
            obs.complete("serve.queue", e.submit_us, cat="serve", rid=e.rid)
        return e

    # ------------------------------------------------------ event loop ---

    def step(self, now: Optional[float] = None) -> List[int]:
        """Admit from the queue, run one engine step over the slot
        array, evict finished requests. Returns finished rids. A step
        with nothing admitted and nothing active is a no-op (drain on an
        empty queue is safe)."""
        now = self.clock() if now is None else now
        with obs.span("serve.admit", cat="serve"):
            self._admit(now)
            active = self.slots.active
        if not active:
            return []
        with obs.span("serve.feed", cat="serve", active=len(active)):
            shape, dtype = self.adapter.input_spec()
            feed = np.zeros((self.slots.phys, *shape), dtype)
            pos = np.zeros(self.slots.phys, np.int32)
            for s in active:
                row, p = self.adapter.feed(self._entries[s.rid].cursor)
                feed[s.sid] = row
                pos[s.sid] = p
        with obs.span("serve.step", cat="serve", active=len(active),
                      queue_depth=len(self._queue)):
            rows, state = self.adapter.step(self.state, feed, pos)
        finished: List[int] = []
        with obs.span("serve.consume", cat="serve"):
            self.state = state          # releases the previous step's state
            for s in active:
                e = self._entries[s.rid]
                self.slots.advance(s.sid, int(pos[s.sid]) + 1)
                if self.adapter.consume(e.cursor, rows[s.sid]):
                    self._finish(e, now)
                    finished.append(e.rid)
            self.step_log.append({
                "t": now, "active": len(active),
                "queue_depth": len(self._queue),
                "occupancy": self.slots.occupancy(),
                "per_device": self.slots.device_occupancy()})
        return finished

    def _finish(self, e: _Entry, now: float):
        self.adapter.finish(e.cursor)
        if e.sid is not None:
            self.slots.evict(e.sid)
        e.finish_t = now
        self.results[e.rid] = self.adapter.result(e.cursor)
        self.request_log.append({
            "rid": e.rid, "submit_t": e.submit_t, "admit_t": e.admit_t,
            "finish_t": now,
            "prompt_len": self.adapter.prompt_len(e.cursor),
            "tokens_out": self.adapter.tokens_out(e.cursor)})
        if self.policy == "wave":
            if e.sid is not None:
                self._wave_live -= 1
                if self._wave_live == 0:
                    self._finish_wave()
        else:
            obs.counter("engine.requests").add(1)

    # ------------------------------------------------ batch convenience ---

    def serve(self, payloads, greedy: bool = True, seed: int = 0) -> list:
        """Submit everything, run to drain, return per-request results in
        submission order (the synchronous `Engine.generate` shape)."""
        self._greedy, self._seed = greedy, seed
        self._rid0 = self._next_rid
        rids = [self.submit(p) for p in payloads]
        self.drain()
        return [self.results[r] for r in rids]

    def drain(self):
        """Step until the queue and slot array are empty."""
        while not self.idle:
            self.step()

    # ---------------------------------------------------------- report ---

    def serving_report(self) -> dict:
        """Request-granular latency/occupancy stats (the continuous-
        batching analogue of `utilization_report`, which is wave-
        granular). Time unit is whatever the caller's clock used."""
        lats = [r["finish_t"] - r["submit_t"] for r in self.request_log]
        lat = None
        if lats:
            lat = {"p50": float(np.percentile(lats, 50)),
                   "p95": float(np.percentile(lats, 95)),
                   "p99": float(np.percentile(lats, 99)),
                   "mean": float(np.mean(lats)),
                   "max": float(np.max(lats))}
        depths = [s["queue_depth"] for s in self.step_log]
        occ = [s["occupancy"] for s in self.step_log]
        return {
            "policy": self.policy,
            "slots": self.slots.real,
            "devices": self._dp,
            "requests": len(self.request_log),
            "steps": len(self.step_log),
            "tokens_out": int(sum(r["tokens_out"]
                                  for r in self.request_log)),
            "latency": lat,
            "queue_depth": ({"mean": float(np.mean(depths)),
                             "max": int(np.max(depths))}
                            if depths else None),
            "occupancy": ({"mean": float(np.mean(occ)),
                           "min": float(np.min(occ))} if occ else None),
            "pages": {"per_slot": self.slots.pages_per_slot,
                      "capacity": self.slots.capacity_pages},
        }

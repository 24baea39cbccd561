"""Run one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process per run: it checks the devices, makes the weights and the
traffic from the seed, warms up the cell's one shape (all of that is
``setup_s``), offers the traffic for ``--seconds`` on the host clock,
checks what the window served against the plain reference, and prints
one JSON line last on stdout. With ``--trace 1`` it records a profiler
trace of a slice of the window and reports the cell's per-layer metrics
instead of its end-to-end ones.

It exits non-zero, printing no result, when JAX finds no TPU, when the
device kind has no entry in ``peaks.py``, or when there are fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import cell as cells  # noqa: E402
from benchmarks.chip import driver, peaks as peak_table  # noqa: E402
from benchmarks.chip import trace_reduce  # noqa: E402


# seconds of the window, centred, that a traced run records: some tens
# of steps, a trace small enough to read back within the run
TRACE_S = 2.0


class NoChip(RuntimeError):
    """The machine lacks what the cell needs; no result is printed."""


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    config: dict
    traffic: dict
    window: driver.Window
    setup_s: float
    peaks: dict
    chips: int
    trace: Optional[dict] = None     # trace_reduce.reduce of the slice
    events: Optional[dict] = None    # trace_reduce.extract of the slice


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def enable_cache():
    """The program's persistent compile cache, for every program."""
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def devices_for(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def make_mesh(traffic: dict, devs):
    if not traffic.get("mesh"):
        return None
    from repro.parallel.ctx import make_mesh as program_mesh
    return program_mesh(tuple(traffic["mesh"]), ("data", "model"),
                        devices=devs)


class CompileCounter:
    """Counts compile requests (persistent-cache hits included)."""

    def __init__(self):
        import jax
        self.n = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.n += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __call__(self) -> int:
        return self.n


class Tracer:
    """Starts the profiler ``start`` seconds into the window and stops it
    ``length`` seconds later; the host spans of that slice bound the
    traced window. The start and stop calls stall the loop (stopping
    writes the trace, seconds of it); ``paused`` lists their spans so
    that rates over the window can leave them out."""

    def __init__(self, start: float, length: float, out_dir: str):
        self.start, self.length, self.dir = start, length, out_dir
        self.on = False
        self.done = False
        self.paused = []

    def __call__(self, now: float, t0: float):
        """Called every loop iteration; ``now`` is inf once the loop ends."""
        import jax
        if self.done:
            return
        if not self.on:
            if now == float("inf"):
                self.done = True           # the slice never began
            elif now >= t0 + self.start:
                jax.profiler.start_trace(self.dir)
                self.on, self.t_on = True, now
                self.paused.append((now, time.perf_counter()))
        elif now >= self.t_on + self.length:
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.on, self.done = False, True
            self.paused.append((t, time.perf_counter()))


def warm_up(adapter, payloads: list, slots: int, mesh):
    """Drive a few tiny requests through a scheduler of the cell's shape
    over the adapter the window uses (its jitted step is compiled once):
    compiles the step, the eager ops around it, and the adapter's
    per-request path. The scheduler and its state are dropped after."""
    from repro.serve.runtime import Scheduler
    sched = Scheduler(adapter, slots, mesh=mesh, policy="continuous")
    for p in payloads:
        sched.submit(p)
    sched.drain()


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


@dataclasses.dataclass
class Setup:
    """A cell built and warmed up, ready for windows."""
    cell: cells.Cell
    devs: list
    peaks: dict
    mesh: object
    served: object
    adapter: driver.TimedAdapter
    compiles: CompileCounter
    setup_s: float
    last_window: Optional[driver.Window] = None


def setup(cell: cells.Cell, *, seed: int, require_tpu: bool = True,
          t_start: float = T_START) -> Setup:
    """Devices, weights from the seed, the adapter, and its warm-up."""
    enable_cache()
    chips = cell.workload["chips"]
    devs = devices_for(chips, require_tpu)
    kind = devs[0].device_kind
    try:
        peaks = (peak_table.peaks_for(kind) if require_tpu else
                 peak_table.PEAKS["TPU v5 lite"])
    except KeyError as e:
        raise NoChip(str(e)) from None
    compiles = CompileCounter()
    mesh = make_mesh(cell.traffic, devs)
    served = cell.family.build(cell.config, cell.traffic, mesh)
    served.load(seed)
    adapter = driver.TimedAdapter(served.adapter())
    warm_up(adapter, served.warm_payloads(), cell.traffic["slots"], mesh)
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {cell.name}: {setup_s:.3f}s on {kind} x{len(devs)}; "
        f"compile cache: {compiles.hits} hits, {compiles.misses} misses")
    return Setup(cell=cell, devs=devs, peaks=peaks, mesh=mesh, served=served,
                 adapter=adapter, compiles=compiles, setup_s=setup_s)


def measure(st: Setup, *, seed: int, seconds: float, trace: bool,
            check: bool = True) -> dict:
    """One window over a fresh scheduler, the check, the result line."""
    import jax
    from repro.serve.runtime import Scheduler

    cell, adapter = st.cell, st.adapter
    adapter.reset(annotate=trace)
    preroll = cell.traffic.get("preroll_s", 0.0)
    offsets, payloads = st.served.requests(preroll + seconds, seed)
    sched = Scheduler(adapter, cell.traffic["slots"], mesh=st.mesh,
                      policy="continuous")
    entries = cell.per_layer if trace else cell.end_to_end
    readers = {m["name"]: cells.metric_module(m["name"]) for m in entries}
    tmp = tempfile.TemporaryDirectory() if trace else None
    tracer = None
    if trace:
        tracer = Tracer(max(0.0, (seconds - TRACE_S) / 2), TRACE_S, tmp.name)
    win = driver.serve(sched, adapter, payloads, offsets, seconds,
                       preroll_s=preroll,
                       drain_s=cell.traffic.get("drain_s", 0.0),
                       tracer=tracer, compile_counter=st.compiles)
    peak = memory_peak(st.devs)
    reduced = ev = None
    if trace:
        try:
            xp = trace_reduce.find_xplane(tmp.name)
        except FileNotFoundError:
            xp = None                      # the loop ended before the slice
        if xp is not None:
            ev = trace_reduce.extract(xp)
            reduced = trace_reduce.reduce(
                ev, [m.MODULE for m in readers.values() if hasattr(m, "MODULE")])
            log("[trace] " + json.dumps(
                {k: reduced[k] for k in ("busy_s", "window_s", "idle_share",
                                         "modules")}))
        tmp.cleanup()
    in_window = [r for r in win.due if win.in_window(r)]
    _report_window(cell, win, in_window, peak)

    # free the program's state (the KV cache) before the reference runs
    del sched
    st.last_window = win
    numbers = st.served.check(win, seed) if check else {}
    if numbers:
        log("[check] sample " + json.dumps(getattr(st.served, "last_check",
                                                   None)))
    correct = all(v is not None and v <= lim for v, lim in numbers.values())
    run = Run(config=cell.config, traffic=cell.traffic, window=win,
              setup_s=st.setup_s, peaks=st.peaks,
              chips=cell.workload["chips"], trace=reduced, events=ev)
    metrics = {}
    for m in entries:
        v = readers[m["name"]].read(run)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": st.devs[0].platform, "kind": st.devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(in_window),
           "failed": win.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["check"] = {k: {"value": v, "limit": lim}
                    for k, (v, lim) in numbers.items()}
    return out


def run_cell(cell: cells.Cell, *, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, t_start: float = T_START) -> dict:
    """Everything after argument parsing; returns the result line."""
    st = setup(cell, seed=seed, require_tpu=require_tpu, t_start=t_start)
    return measure(st, seed=seed, seconds=seconds, trace=trace)


def _report_window(cell, win, in_window, peak):
    import numpy as np
    late = np.asarray(win.lateness) if win.lateness else np.zeros(1)
    done_in = sum(1 for r in in_window if r in win.finished)
    firsts = [win.token_times[r][0] - win.due[r] for r in in_window
              if win.token_times.get(r)]
    ttft = (f"{np.percentile(firsts, 50) * 1e3:.1f}/"
            f"{np.percentile(firsts, 95) * 1e3:.1f} ms over {len(firsts)}"
            if firsts else "none")
    depth = win.queue_depth
    log(f"[window] {cell.name}: {win.seconds:.1f}s, {len(in_window)} due, "
        f"{done_in} finished, {win.failed} failed, {win.steps} steps, "
        f"queue depth {depth[0] if depth else 0} -> "
        f"{depth[-1] if depth else 0}, compiles in window {win.compiles}")
    log(f"[window] generator late: p50 {np.percentile(late, 50) * 1e3:.2f} "
        f"ms, p99 {np.percentile(late, 99) * 1e3:.2f} ms, max "
        f"{late.max() * 1e3:.2f} ms; time to first output p50/p95 {ttft}")
    log(f"[memory] peak_bytes_in_use {peak}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cells.load_cell(args.workload)
        out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace))
    except (NoChip, ImportError) as e:     # no chip, or no program here
        log(f"run: {type(e).__name__}: {e}")
        return 2
    for k, v in out["check"].items():
        log(f"{k} {v['value']} limit {v['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

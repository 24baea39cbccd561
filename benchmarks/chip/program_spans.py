"""The program's own spans (`repro.obs`), as the per-layer metrics read
them.

A reader that reads them calls `enable` when it is loaded. ``run.py``
loads per-layer readers only for a ``--trace 1`` run, just before its
window, so the program records spans in exactly the traced runs and in
none that the end-to-end metrics are read from. With
``xla_annotations`` each span is also a profiler annotation, so the
program's spans sit on the host plane of the slice's trace.

Spans come back on the host clock of `driver.Window` (seconds of
`time.perf_counter`), as ``(name, start, end, args)``. Every function
returns None when the program records no spans (a program without
`repro.obs.to_perf_counter` and `repro.obs.dropped`, or none of the
names asked for), or when its ring buffer dropped events of the window.

To put program spans on the trace's clock, `trace_offset_ns` pairs each
``bench.step`` event of the slice with the program's ``serve.step``
span that encloses it, in order: the first ``serve.step`` that starts
after the profiler's start call pairs with the first ``bench.step`` of
the trace. The offset is the median over the pairs; it holds only if at
least 90% of the pairs agree with it to within 0.5 ms. `idle_shares`
puts device 0's idle time in the slice down to the spans placed so.
"""
from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Optional, Tuple

from benchmarks.chip import trace_reduce as tr

Span = Tuple[str, float, float, dict]

AGREE_NS = 0.5e6          # a pair agrees within half a millisecond
AGREE_SHARE = 0.9         # ... and so must this share of the pairs
STEP_PHASES = ("serve.admit", "serve.feed", "serve.consume")


def _trace_module():
    try:
        from repro.obs import trace
    except ImportError:
        return None
    if not all(hasattr(trace, f) for f in ("to_perf_counter", "dropped")):
        return None
    return trace


def enable():
    """Turn the program's spans on, mirrored into the profiler's trace."""
    try:
        from repro import obs
    except ImportError:                  # no program here: nothing to read
        return
    obs.enable(xla_annotations=True)


def spans(run) -> Optional[List[Span]]:
    """Every complete span the program recorded, sorted by start, or None
    when there are none or the buffer lost events of the window."""
    trace = _trace_module()
    if trace is None or run.window is None:
        return None
    every = trace.events()
    evs = [e for e in every if e.get("ph") == "X"]
    if not evs:
        return None
    oldest = every[0]                    # events enter the buffer at exit
    if (trace.dropped()
            and trace.to_perf_counter(oldest["ts"] + oldest.get("dur", 0.0))
            >= run.window.t0):
        return None                      # spans of the window fell off
    out = [(e["name"], trace.to_perf_counter(e["ts"]),
            trace.to_perf_counter(e["ts"] + e["dur"]), e.get("args", {}))
           for e in evs]
    out.sort(key=lambda s: s[1])
    return out


def in_window(run, names: Iterable[str]) -> Optional[Dict[str, List[Span]]]:
    """The spans of each name that start in [t0, t_stop]: the window and
    its drain, the steps `driver.Window.steps` counts. None if no span
    of these names is there."""
    sp = spans(run)
    if sp is None:
        return None
    w = run.window
    names = tuple(names)
    out: Dict[str, List[Span]] = {n: [] for n in names}
    for s in sp:
        if s[0] in out and w.t0 <= s[1] <= w.t_stop:
            out[s[0]].append(s)
    return out if any(out.values()) else None


def per_step_ms(run, names: Iterable[str]) -> Optional[float]:
    """Host milliseconds of the named spans per scheduler step (one
    ``serve.admit`` a step), over the window's steps."""
    names = tuple(names)
    got = in_window(run, names + ("serve.admit",))
    if got is None or not got["serve.admit"]:
        return None
    total = sum(e - s for n in names for _, s, e, _ in got[n])
    return total / len(got["serve.admit"]) * 1e3


def trace_offset_ns(run, sp: Optional[List[Span]] = None
                    ) -> Optional[float]:
    """Nanoseconds to add to ``perf_counter`` seconds x 1e9 to land on
    the trace's clock, or None when the pairs do not agree."""
    if run.events is None or not run.window.paused:
        return None
    sp = spans(run) if sp is None else sp
    if sp is None:
        return None
    t_on = run.window.paused[0][1]
    bench = sorted(s for n, s, _ in run.events["host"] if n == "bench.step")
    prog = sorted(s for n, s, _, _ in sp if n == "serve.step" and s > t_on)
    n = min(len(bench), len(prog))
    if not n:
        return None
    offs = [b - p * 1e9 for b, p in zip(bench[:n], prog[:n])]
    med = statistics.median(offs)
    if sum(abs(o - med) <= AGREE_NS for o in offs) < AGREE_SHARE * n:
        return None
    return med


def placed(run) -> Optional[Tuple[List[Tuple[float, float]],
                                 Dict[str, List[List[float]]]]]:
    """Device 0's idle intervals in the traced slice, and the program's
    spans put on the trace's clock by `trace_offset_ns`, by name, each
    name's merged and clipped to the slice (ns). None when there is no
    slice or the spans cannot be placed."""
    ev = run.events
    if ev is None or not ev["devices"] or not ev["host"]:
        return None
    sp = spans(run)
    off = trace_offset_ns(run, sp)
    if off is None:
        return None
    lo, hi = tr.window_of(ev)
    ops = ev["devices"][sorted(ev["devices"])[0]]["ops"]
    idle = tr.gaps(tr.merge(tr.clip([(s, s + d) for _, s, d in ops],
                                    lo, hi)), lo, hi)
    by: Dict[str, list] = {}
    for n, s, e, _ in sp:
        by.setdefault(n, []).append((s * 1e9 + off, e * 1e9 + off))
    return idle, {n: tr.merge(tr.clip(iv, lo, hi)) for n, iv in by.items()}


def overlap(a, b) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        tot += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_shares(run, groups: Dict[str, Iterable[str]]
                ) -> Optional[Dict[str, float]]:
    """Percent of device 0's idle time in the traced slice that falls
    inside the union of each group's spans. A group of which the program
    recorded no span is left out; None when nothing can be placed or the
    device was never idle."""
    got = placed(run)
    if got is None:
        return None
    idle, by = got
    total = sum(e - s for s, e in idle)
    if not total:
        return None
    out = {}
    for label, names in groups.items():
        names = [n for n in names if n in by]
        if names:
            union = tr.merge(iv for n in names for iv in by[n])
            out[label] = 100.0 * overlap(idle, union) / total
    return out

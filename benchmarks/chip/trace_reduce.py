"""From a profiler trace to the numbers the per-layer metrics read.

`extract` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event lists (JSON-able, so a trimmed copy is kept as test data):

  {"devices": {plane: {"ops": [[name, start_ns, dur_ns], ...],
                       "modules": [[name, start_ns, dur_ns], ...]}},
   "host": [[name, start_ns, dur_ns], ...]}       # the harness's spans

`reduce` computes, over the traced window (from the first to the last of
the harness's host spans):

  busy_s      union of the op intervals of each device, mean over devices
  window_s    length of the window
  idle_share  1 - busy_s / window_s
  modules     {pattern: (seconds, count)}: XLA programs whose name
              contains the pattern, summed over devices
  device_ops  the 10 op names that took the most device time
  idle_gaps   the 10 longest gaps of device 0, each named by the host
              span that covered most of it ("host.other" if none did)

`custom_calls` lists the custom calls (Pallas kernels) inside the window
with the shapes of their output and operands, read from the HLO text
that names each op event: a kernel's reader matches them by shape, since
the calls carry no name of their own.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line) -> List[list]:
    return [[ev.name, float(ev.start_ns), float(ev.duration_ns)]
            for ev in line.events]


def extract(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            out["devices"][plane.name] = {
                "ops": _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                "modules": (_events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else [])}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                out["host"].extend(e for e in _events(ln)
                                   if e[0].startswith(HOST_PREFIX))
    out["host"].sort(key=lambda e: e[1])
    return out


# ------------------------------------------------------------ algebra ---

def merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> List[List[float]]:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(merged: List[List[float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi) around disjoint sorted ``merged``."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def label_gap(g0: float, g1: float, host: List[list]) -> str:
    """The host span that covers most of the gap [g0, g1)."""
    best, name = 0.0, "host.other"
    for n, s, d in host:
        if s >= g1:
            break
        ov = _overlap(g0, g1, s, s + d)
        if ov > best:
            best, name = ov, n
    return name


def short_name(op: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return op.split(" = ", 1)[0].lstrip("%")


def window_of(ev: dict) -> Tuple[float, float]:
    host = ev["host"]
    if not host:
        raise ValueError("the trace holds none of the harness's host spans")
    return (min(s for _, s, _ in host), max(s + d for _, s, d in host))


def reduce(ev: dict, module_patterns: Iterable[str] = ()) -> dict:
    lo, hi = window_of(ev)
    window_ns = hi - lo
    devices = sorted(ev["devices"])
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy = []
    by_op: Dict[str, float] = {}
    modules = {p: [0.0, 0] for p in module_patterns}
    merged0 = None
    for dev in devices:
        ops = [e for e in ev["devices"][dev]["ops"]
               if _overlap(lo, hi, e[1], e[1] + e[2]) > 0]
        merged = merge(clip([(s, s + d) for _, s, d in ops], lo, hi))
        if merged0 is None:
            merged0 = merged
        busy.append(sum(e - s for s, e in merged))
        for name, s, d in ops:
            by_op[name] = by_op.get(name, 0.0) + d
        for name, s, d in ev["devices"][dev]["modules"]:
            if _overlap(lo, hi, s, s + d) <= 0:
                continue
            for p in modules:
                if p in name:
                    modules[p][0] += d
                    modules[p][1] += 1
    busy_ns = sum(busy) / len(busy)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps(merged0, lo, hi), key=lambda g: g[0] - g[1])[:10]
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": window_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "devices": len(devices),
        "modules": {p: (v[0] * 1e-9, v[1]) for p, v in modules.items()},
        "device_ops": [[short_name(n), d * 1e-9 / len(devices)]
                       for n, d in top],
        "idle_gaps": [[label_gap(g0, g1, ev["host"]), (g1 - g0) * 1e-9]
                      for g0, g1 in idle],
    }


_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]*)\]")


def _dims(text: str) -> List[int]:
    return [int(v) for v in text.split(",") if v]


def custom_calls(ev: dict, target: str = "tpu_custom_call") -> List[dict]:
    """Every op of the window that is a custom call to ``target``, as
    {"out": dims, "operands": [dims, ...], "seconds": s}; summed over
    devices by the caller. An op whose output is a tuple has ``out``
    None."""
    lo, hi = window_of(ev)
    marker = f'custom_call_target="{target}"'
    out = []
    for dev in sorted(ev["devices"]):
        for name, s, d in ev["devices"][dev]["ops"]:
            if marker not in name or _overlap(lo, hi, s, s + d) <= 0:
                continue
            head, _, rest = name.partition(" = ")
            res, _, args = rest.partition("custom-call(")
            m = _SHAPE.match(res.strip())
            out.append({
                "out": _dims(m.group(1)) if m else None,
                "operands": [_dims(g) for g in
                             _SHAPE.findall(args.split(marker)[0])],
                "seconds": d * 1e-9})
    return out

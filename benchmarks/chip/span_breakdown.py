"""Put a traced run's idle device time down to the program's spans.

    python3 benchmarks/chip/span_breakdown.py OUT.json \\
        --workload <cell> --seed <n> --seconds 45

Runs the cell in this process as ``run.py --trace 1`` does and prints the
same result line, then writes OUT.json:

  idle_share_by_span  percent of device 0's idle time in the traced slice
                      inside each program span. The leaf spans are
                      disjoint; ``serve.step (rest)`` is ``serve.step``
                      less the ``lm.*`` / ``vision.*`` spans inside it, and
                      ``no program span`` is what no span covers.
  longest_idle_gaps   the 10 longest idle gaps of device 0, in ms, each
                      named by the leaf span that covers most of it.
  ms_per_step         host ms of each leaf span per scheduler step over
                      the window and its drain.
  copy_gb_per_s       the LM's copy of logits to the host: the
                      ``lm.bytes_to_host`` counter over the summed time of
                      every ``lm.logits_to_host`` span, all steps the spans
                      were on for. None if the ring buffer dropped events.

The spans are put on the trace's clock as `idle_in_copy.lm` puts them
(`program_spans.trace_offset_ns`). A number is None where the program
recorded nothing to read.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import cell as cells  # noqa: E402
from benchmarks.chip import program_spans, run as bench, \
    trace_reduce  # noqa: E402

LEAVES = ("serve.submit", "serve.admit", "serve.feed", "serve.consume",
          "lm.dispatch", "lm.device_wait", "lm.logits_to_host",
          "vision.dispatch", "vision.device_wait", "vision.logits_to_host")
INNER = tuple(n for n in LEAVES if not n.startswith("serve."))


def idle_by_span(run):
    """Percent of device 0's idle time per leaf span, ``serve.step``'s
    own rest and the part no span covers; None if nothing is placed."""
    groups = {n: (n,) for n in LEAVES}
    groups.update({"serve.step": ("serve.step",), "inner": INNER,
                   "any": LEAVES + ("serve.step",)})
    got = program_spans.idle_shares(run, groups)
    if got is None:
        return None
    out = {n: got[n] for n in LEAVES if n in got}
    out["serve.step (rest)"] = got.get("serve.step", 0.0) - got.get(
        "inner", 0.0)
    out["no program span"] = 100.0 - got.get("any", 0.0)
    return out


def longest_gaps(run, n=10):
    got = program_spans.placed(run)
    if got is None:
        return None
    idle, by = got
    out = []
    for g in sorted(idle, key=lambda g: g[0] - g[1])[:n]:
        cover = {s: program_spans.overlap([g], by.get(s, []))
                 for s in LEAVES}
        name = max(cover, key=cover.get)
        out.append([name if cover[name] > 0 else "none",
                    (g[1] - g[0]) * 1e-6])
    return out


def ms_per_step(run):
    got = program_spans.in_window(run, LEAVES)
    if got is None or not got["serve.admit"]:
        return None
    steps = len(got["serve.admit"])
    return {n: sum(e - s for _, s, e, _ in got[n]) / steps * 1e3
            for n in LEAVES if got[n]}


def copy_gb_per_s():
    """``lm.bytes_to_host`` over the time of every ``lm.logits_to_host``
    span: both count from the moment the spans were turned on."""
    try:
        from repro.obs import trace as obs
    except ImportError:
        return None
    if not hasattr(obs, "dropped") or obs.dropped():
        return None
    nbytes = obs.counter_values().get("lm.bytes_to_host")
    took_us = sum(e["dur"] for e in obs.spans("lm.logits_to_host"))
    return nbytes / took_us * 1e-3 if nbytes and took_us else None


def traced_run(cell, *, seed: int, seconds: float, require_tpu=True):
    """The result line of a ``--trace 1`` run of ``cell``, and the
    summary above."""
    # `run.measure` keeps no copy of the slice's events: keep the one it
    # extracts
    kept = {}
    extract = trace_reduce.extract

    def keep(xp):
        kept["events"] = extract(xp)
        return kept["events"]

    trace_reduce.extract = keep
    try:
        st = bench.setup(cell, seed=seed, require_tpu=require_tpu)
        line = bench.measure(st, seed=seed, seconds=seconds, trace=True)
    finally:
        trace_reduce.extract = extract
    run = bench.Run(config=cell.config, traffic=cell.traffic,
                    window=st.last_window, setup_s=st.setup_s,
                    peaks=st.peaks, chips=cell.workload["chips"],
                    events=kept.get("events"))
    return line, {"idle_share_by_span": idle_by_span(run),
                  "longest_idle_gaps": longest_gaps(run),
                  "ms_per_step": ms_per_step(run),
                  "copy_gb_per_s": copy_gb_per_s()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("out")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    line, summary = traced_run(cells.load_cell(args.workload),
                               seed=args.seed, seconds=args.seconds)
    print(json.dumps(line), flush=True)
    with open(args.out, "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

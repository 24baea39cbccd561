"""The readings a cell's correctness limit is set from: the program's
number over many seeds, and the control's on the same samples.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \
        --seeds 1,2,3 --variant '{"a_bits": 4}' [--variant ...] \
        [--serving '{"kv_dtype": "int8"}']

One process (one set-up): for each seed it makes that seed's weights,
runs a window at the cell's own load, checks it as a run does, and then
computes each control on the same sample: the plain reference put in the
program's place at the next lower precision (``variant``). With
``--serving`` the program itself runs a lower precision of its own (the
configuration's ``serving`` entries replaced), and its check readings
are that control's. The largest program reading is the limit's lower
reading, the smallest control reading its upper one (PERF.md gives both
for every limit). The benchmark's own runs never run a control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import run as bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant", action="append", default=[],
                    help="JSON of the lower precision, e.g. {\"a_bits\": 4}")
    ap.add_argument("--serving", default=None,
                    help="JSON of serving entries the program runs at")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    variants = [json.loads(v) for v in args.variant]
    cell = bench.cells.load_cell(args.workload)
    if args.serving:
        stated = dict(cell.config["serving"])
        cell.config["serving"].update(json.loads(args.serving))
    st = bench.setup(cell, seed=seeds[0])
    if args.serving:
        # the program is built; its check holds it to the configuration
        cell.config["serving"].update(stated)
    for i, seed in enumerate(seeds):
        if i:
            st.served.load(seed)
            st.adapter.inner = st.served.rebind(st.adapter.inner)
            bench.warm_up(st.adapter, st.served.warm_payloads(),
                          cell.traffic["slots"], st.mesh)
        out = bench.measure(st, seed=seed, seconds=args.seconds, trace=False)
        sample = getattr(st.served, "last_check", None)
        controls = {json.dumps(v, sort_keys=True):
                    st.served.control(st.last_window, seed, v)
                    for v in variants}
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "check": out["check"], "controls": controls,
                          "sample": sample,
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

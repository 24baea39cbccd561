"""Find a cell's knee: windows at several offered rates in one process
(one set-up), each reporting its throughput and how its queue grew.

    python3 benchmarks/chip/sweep.py --workload <name> --seed <n> \
        --seconds <s> --rates 10,15,20

The knee is the highest rate whose queue stays flat and whose
throughput keeps up with the offered load. The sweep waits for no first
output after a window (no drain): it reads rates and queues, not
latencies. The cells' fixed rates in
``traffic/*.json`` were set from one such sweep (see PERF.md); the
benchmark's runs never search for a rate.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    args = ap.parse_args(argv)
    cell = bench.cells.load_cell(args.workload)
    cell.traffic["drain_s"] = 0
    st = bench.setup(cell, seed=args.seed)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        out = bench.measure(st, seed=args.seed + i, seconds=args.seconds,
                            trace=False, check=False)
        w = st.last_window
        outputs = sum(1 for ts in w.token_times.values() for t in ts
                      if w.t0 <= t < w.t1)
        depth = w.queue_depth or [0]
        print(json.dumps({"rate_per_s": rate,
                          "outputs_per_s": outputs / w.seconds,
                          "attempted": out["attempted"],
                          "queue_depth": [depth[0], depth[-1]]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

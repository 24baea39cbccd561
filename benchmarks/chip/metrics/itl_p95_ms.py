"""95th percentile of every gap between consecutive output tokens of
every request, both tokens inside the window."""
import numpy as np


def read(run):
    w = run.window
    gaps = [b - a for ts in w.token_times.values()
            for a, b in zip(ts, ts[1:]) if w.t0 <= a and b < w.t1]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None

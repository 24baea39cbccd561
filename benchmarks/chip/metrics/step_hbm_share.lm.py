"""Bytes one decode step must stream (every parameter at its served
width, plus the KV entries of the positions the batch attends to, from
`costs.lm_step_bytes`), over the device time of one `jit(decode)` program
in the trace, as a share of the chip's HBM bandwidth."""
from benchmarks.chip import costs

MODULE = "decode"


def read(run):
    if run.trace is None:
        return None
    secs, count = run.trace["modules"].get(MODULE, (0.0, 0))
    w = run.window
    if not count or not w.steps:
        return None
    per_step = secs / count
    nbytes = costs.lm_step_bytes(run.config, w.live_positions / w.steps)
    return 100.0 * nbytes / per_step / run.peaks["hbm_bytes_per_s"]

"""Operations per image (2 x the MACs of every conv and the head,
`costs.cnn_ops_per_image`) times the images served per second in the
window, over the chip's int8 peak. The seconds are the window's, less
the profiler's start and stop, which stall the loop in a traced run."""
from benchmarks.chip import costs


def read(run):
    w = run.window
    n = sum(1 for ts in w.token_times.values() for t in ts
            if w.t0 <= t < w.t1)
    if not n:
        return None
    ops = costs.cnn_ops_per_image(run.config) * n / w.serving_seconds(
        w.t0, w.t1)
    return 100.0 * ops / (run.peaks["int8_ops"] * run.chips)

"""Active operations the served tokens need (2 x the MACs of the
projections, conv taps, router, the routed top-k experts, head and
attention at the mean context, `moe_costs.ops_per_token`), times the
tokens the steps processed per second (prompt and output tokens alike,
one per occupied slot per step), over the chip's int8 peak. The seconds
are the window's and its drain's, less the profiler's start and stop,
which stall the loop in a traced run."""
from benchmarks.chip import moe_costs


def read(run):
    w = run.window
    if not w.active_slot_steps:
        return None
    context = w.live_positions / w.active_slot_steps
    rate = w.active_slot_steps / w.serving_seconds(w.t0, w.t_stop)
    ops = moe_costs.ops_per_token(run.config, context) * rate
    return 100.0 * ops / (run.peaks["int8_ops"] * run.chips)

"""Bytes one decode step must stream (every non-expert parameter at its
served width, the experts its tokens hit, and the KV entries of the
positions the batch attends to, from `moe_costs.step_bytes`), over the
device time of one `jit(decode)` program in the trace, as a share of the
chip's HBM bandwidth.

The experts hit per step are the program's counter ``moe.experts_hit``
(summed over the MoE layers) over its ``lm.dispatch`` spans, both
counted from when the traced run turned the program's spans on, so over
the preroll, the window and its drain. None when the program counts no
experts or its ring buffer dropped spans."""
import sys

from benchmarks.chip import moe_costs, program_spans

MODULE = "decode"

program_spans.enable()


def experts_hit_per_step():
    try:
        from repro.obs import trace as obs
    except ImportError:
        return None
    if not hasattr(obs, "dropped") or obs.dropped():
        return None
    hits = obs.counter_values().get("moe.experts_hit")
    steps = len(obs.spans("lm.dispatch"))
    return hits / steps if hits and steps else None


def read(run):
    if run.trace is None:
        return None
    secs, count = run.trace["modules"].get(MODULE, (0.0, 0))
    w = run.window
    hit = experts_hit_per_step()
    if not count or not w.steps or hit is None:
        return None
    print(f"[moe] experts hit a step {hit:.2f}, summed over the MoE layers",
          file=sys.stderr, flush=True)
    nbytes = moe_costs.step_bytes(run.config, w.live_positions / w.steps,
                                  hit)
    return 100.0 * nbytes / (secs / count) / run.peaks["hbm_bytes_per_s"]

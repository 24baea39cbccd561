"""The fused conv kernels' share of their roofline: over the traced
slice, the least time the chip needs for the conv calls it ran (per call
the larger of its ops over the int8 peak and its bytes over the HBM
peak, at the layer's true widths, `costs.qconv_least_seconds`), over the
device time those calls took.

The calls are the slice's custom calls whose output is a batch of
feature maps, (batch, h, w, channels), with (h, w) the output size of a
conv layer of the configuration; layers of one output size are told
apart by nothing in the trace, so each such call is given their mean."""
from benchmarks.chip import costs, trace_reduce


def read(run):
    if run.events is None:
        return None
    sizes = {}
    took = least = 0.0
    for call in trace_reduce.custom_calls(run.events):
        out = call["out"]
        if out is None or len(out) != 4:
            continue
        batch = out[0]
        if batch not in sizes:
            sizes[batch] = costs.qconv_least_seconds(run.config, batch,
                                                     run.peaks)
        t = sizes[batch].get(tuple(out[1:3]))
        if t is None:
            continue
        took += call["seconds"]
        least += t
    return 100.0 * least / took if took else None

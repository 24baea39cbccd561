"""95th percentile, over the requests due inside the window, of the
program's ``serve.queue`` span: from `Scheduler.submit` to admission
into a slot. A request submitted and never admitted counts from the end
of its ``serve.submit`` to the end of the run's drain."""
import numpy as np

from benchmarks.chip import program_spans

program_spans.enable()


def read(run):
    sp = program_spans.spans(run)
    if sp is None:
        return None
    w = run.window
    queued, submitted = {}, {}
    for name, s, e, args in sp:
        if name == "serve.queue":
            queued[args.get("rid")] = e - s
        elif name == "serve.submit":
            submitted[args.get("rid")] = e
    if not queued:
        return None
    waits = []
    for r in w.due:
        if not w.in_window(r):
            continue
        if r in queued:
            waits.append(queued[r])
        elif r in submitted:
            waits.append(w.t_stop - submitted[r])
    return float(np.percentile(waits, 95)) * 1e3 if waits else None

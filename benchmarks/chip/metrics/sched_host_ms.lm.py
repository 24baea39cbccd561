"""Mean host milliseconds per `Scheduler.step` outside the adapter's
`step`: admission, feed rows, the per-slot `consume` with its host
argmax over the logits."""


def read(run):
    w = run.window
    if not w.steps:
        return None
    return (w.sched_s - w.adapter_step_s) / w.steps * 1e3

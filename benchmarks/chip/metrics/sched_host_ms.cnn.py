"""Mean host milliseconds per `Scheduler.step` outside the adapter's
`step`, plus the per-image `VisionAdapter.begin` (input quantize and its
device round trip) spread over the steps."""


def read(run):
    w = run.window
    if not w.steps:
        return None
    return (w.sched_s - w.adapter_step_s + w.begin_s) / w.steps * 1e3

"""Mean host milliseconds per `Scheduler.step` in the program's own
spans: ``serve.admit`` + ``serve.feed`` + ``serve.consume``, plus every
``serve.submit`` of the window (the per-image `VisionAdapter.begin` and
the fit check) spread over the steps. The inside twin of
`sched_host_ms.cnn`."""
from benchmarks.chip import program_spans

program_spans.enable()


def read(run):
    return program_spans.per_step_ms(
        run, program_spans.STEP_PHASES + ("serve.submit",))

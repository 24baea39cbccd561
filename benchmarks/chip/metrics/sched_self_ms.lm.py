"""Mean host milliseconds per `Scheduler.step` in the program's own
spans: ``serve.admit`` + ``serve.feed`` + ``serve.consume`` (admission,
feed rows, the per-slot consume with its host argmax), over the steps of
the window and its drain. The inside twin of `sched_host_ms.lm`."""
from benchmarks.chip import program_spans

program_spans.enable()


def read(run):
    return program_spans.per_step_ms(run, program_spans.STEP_PHASES)

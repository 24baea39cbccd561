"""Share of device 0's idle time in the traced slice that falls inside
the program's ``lm.logits_to_host`` spans (the step's logits copied to
the host), the spans put on the trace's clock by
`program_spans.trace_offset_ns`."""
from benchmarks.chip import program_spans

program_spans.enable()

COPY = "lm.logits_to_host"


def read(run):
    got = program_spans.idle_shares(run, {COPY: (COPY,)})
    return None if got is None else got.get(COPY)

"""Mean milliseconds of the program's ``lm.logits_to_host`` span per
step over the window and its drain: the step's last-position float32
logits copied into host memory, after the program has finished."""
from benchmarks.chip import program_spans

program_spans.enable()


def read(run):
    got = program_spans.in_window(run, ("lm.logits_to_host",))
    if got is None:
        return None
    took = [e - s for _, s, e, _ in got["lm.logits_to_host"]]
    return sum(took) / len(took) * 1e3

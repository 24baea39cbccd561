"""repro.obs — the observability layer: spans, software performance
counters, dispatch decision log, Chrome-trace export.

The software analogue of the paper's hardware performance-counter
methodology (Sec. V). Disabled by default; ``REPRO_OBS=1`` (or
`enable()`) turns recording on, ``REPRO_OBS_TRACE=path.json`` makes the
instrumented CLIs/benchmarks export a Chrome trace-event artifact that
``python -m repro.obs.report`` renders as MAC/µs-per-bit-width,
dispatch-summary, and top-span tables.

This package stays import-light: neither this module, `obs.env`, nor
`obs.trace` imports jax at module level, so `launch/dryrun.py` can read
env knobs before jax initialises.
"""
from repro.obs import env  # noqa: F401
from repro.obs.trace import (TRACE_SCHEMA_VERSION, chrome_trace,  # noqa: F401
                             complete, counter, counter_values, disable,
                             dispatch_event, dispatch_log, dropped, enable,
                             enabled, enabled_scope, events,
                             export_chrome_trace, export_if_configured,
                             now_us, span, spans, summary, time_call,
                             to_perf_counter)


def reset() -> None:
    """Drop every recorded event, generic counter, and op counter."""
    from repro.obs import counters, trace
    trace.reset()
    counters.reset()

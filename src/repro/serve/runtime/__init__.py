"""Continuous-batching serving runtime.

One scheduler + slot manager + stats stack serves every workload through
a pluggable adapter (`adapters.WorkloadAdapter`): LM token decode and
quantized-CNN image classification ship here. The launch CLIs, the
examples and the chip benchmark all serve through this one loop.

The design mirrors the paper's cluster-utilization argument at request
granularity: a slot that idles behind a straggler is an idle cluster
core, so the scheduler re-admits queued requests into freed slots at
the next step and the slot array — and with ``mesh=`` every
data-parallel device behind it — stays busy.
"""
from repro.serve.runtime.adapters import (LMDecodeAdapter, Request,
                                          VisionAdapter, WorkloadAdapter)
from repro.serve.runtime.scheduler import Backpressure, Scheduler
from repro.serve.runtime.slots import SlotManager

__all__ = ["Backpressure", "LMDecodeAdapter", "Request", "Scheduler",
           "SlotManager", "VisionAdapter", "WorkloadAdapter"]

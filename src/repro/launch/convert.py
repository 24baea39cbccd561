"""Checkpoint -> packed integer deployment artifact (the Dory-style flow
the paper integrates into, §V-B): walk an fp parameter tree, quantize and
chunk-planar-pack every dense weight, and emit the int-mode parameter tree
the serving path consumes.

The uniform single-bit-width entry point below is a thin wrapper over the
mixed-precision converter (`repro.deploy.apply.apply_plan`), which walks
with parameter paths and resolves per-dense bit-widths from a
`PrecisionPlan` — `plan=None` degenerates to uniform `w_bits` everywhere.
"""
from __future__ import annotations

from repro.deploy.apply import apply_plan
from repro.nn.module import param_bytes


def convert_params(q_tree, fp_tree, w_bits: int):
    """Fill an int-mode parameter tree (zeros-initialized `w_packed` /
    `w_scale` leaves) from the fp checkpoint tree at one uniform bit-width.
    Stacked (scanned) layer weights pack along their own K axis."""
    return apply_plan(q_tree, fp_tree, None, w_bits)


def artifact_bytes(params) -> int:
    """Total bytes of a (packed or fp) parameter tree — one accounting
    (`nn/module.py::param_bytes`) shared by the converter and the CLIs."""
    return param_bytes(params)

"""Compat wrappers around the unified quantized-op API (`repro.kernels.api`).

`qlinear_apply`/`qlinear_apply_packed` are thin shims over `api.qdot` /
`api.qdot_packed`: backend selection, block lookup, padding, and packing
all live in the registry layer now. The deprecated ``use_kernel`` /
``interpret`` booleans map onto named backends (True -> 'pallas_interpret'
— the old default silently ran interpret mode; True + interpret=False ->
'pallas'; False -> 'xla') with a DeprecationWarning.

`qmatmul_jnp` keeps its raw-argument signature (tests/benchmarks build
operands directly) but is now a wrapper over the one shared XLA int-GEMM
implementation (`api.xla_int_gemm`) — the same code path the nn dense int
mode runs.
"""
from __future__ import annotations

from typing import Optional

from repro.core import packing
from repro.core.quantize import QuantizedLinearParams
from repro.kernels import api


def qmatmul_jnp(x_packed, w_packed, kappa, lam, m_mul, *,
                a_bits, a_signed, w_bits, d, out_bits,
                epilogue="int", scale=1.0):
    """Pure-XLA path, bit-identical to the kernel (shared requant helper)."""
    x = packing.unpack(x_packed, a_bits, a_signed, axis=-1)
    return api.xla_int_gemm(x, w_packed, w_bits=w_bits, kappa=kappa,
                            lam=lam, m_mul=m_mul, d=d, out_bits=out_bits,
                            epilogue=epilogue, scale=scale)


def qlinear_apply(params: QuantizedLinearParams, x_hat, *,
                  epilogue: str = "int", scale: float = 1.0,
                  backend: Optional[str] = None,
                  block: Optional[tuple] = None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None):
    """Apply a quantized linear layer to integer-image activations.

    Thin compat wrapper over `repro.kernels.api.qdot`; prefer calling that
    directly. ``use_kernel``/``interpret`` are deprecated aliases.
    """
    backend = api.resolve_legacy_backend(backend, use_kernel, interpret)
    return api.qdot(params, x_hat, epilogue=epilogue, scale=scale,
                    backend=backend, block=block)


def qlinear_apply_packed(params: QuantizedLinearParams, x_packed, *,
                         epilogue: str = "int", scale: float = 1.0,
                         backend: Optional[str] = None,
                         block: Optional[tuple] = None,
                         use_kernel: Optional[bool] = None,
                         interpret: Optional[bool] = None):
    """`qlinear_apply` over already-packed activations (compat wrapper over
    `repro.kernels.api.qdot_packed`)."""
    backend = api.resolve_legacy_backend(backend, use_kernel, interpret)
    return api.qdot_packed(params, x_packed, epilogue=epilogue,
                           scale=scale, backend=backend, block=block)

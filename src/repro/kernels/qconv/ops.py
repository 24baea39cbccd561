"""Quantized HWC convolution (paper §III-C) — backends via the registry.

The conv is the implicit GEMM (N*Ho*Wo, fh*fw*Cin) @ (fh*fw*Cin, Cout).
The `pallas`/`pallas_interpret` backends run
`repro.kernels.qconv.kernel.qconv2d_fused`: the PULP-NN execution model
inside one Pallas kernel — receptive fields are gathered from the packed
HWC image straight into a VMEM scratch buffer (the NN-RF/im2col-buffer
analogue), then MatMul + BN + QNT/ACT run on the tile with no HBM-resident
im2col tensor, so the gather loads hide behind the MXU the way Mac&Load
hides loads behind MACs.

The `xla` backend keeps the original explicit route: an XLA im2col
(`im2col_hwc`) materializes the column tensor, then the XLA packed GEMM
consumes it. All backends share the quantization artifact and are
bit-identical; `xla` also covers images too large for the fused kernel's
whole-image VMEM block. `qconv2d_apply` below is a thin compat wrapper
over `repro.kernels.api.qconv` (the deprecated ``use_kernel``/
``interpret`` booleans map onto named backends).

Weights are packed twice at quantization time (a few KB each at IoT scale):
the flat im2col layout (K = fh*fw*cin padded once at the tail) for the
fallback, and the per-tap layout (each tap's Cin padded to a CHUNK multiple
independently, K = fh*fw*cin_pad, tap-major) the fused gather needs so every
receptive-field slice stays chunk-planar aligned.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.quantize import (QuantSpec, QuantizedLinearParams,
                                 fold_bn_requant, quantize)


def im2col_hwc(x, fh: int, fw: int, stride: int = 1, padding: int = 0):
    """(N, H, W, C) -> (N, Ho, Wo, fh*fw*C); receptive field flattened in
    (dy, dx, c) order, matching the paper's HWC im2col buffer."""
    n, h, w, c = x.shape
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding),
                        (0, 0)))
    ho = (h + 2 * padding - fh) // stride + 1
    wo = (w + 2 * padding - fw) // stride + 1
    cols = []
    for dy in range(fh):
        for dx in range(fw):
            sl = x[:, dy:dy + stride * ho:stride, dx:dx + stride * wo:stride]
            cols.append(sl)
    return jnp.concatenate(cols, axis=-1), ho, wo


@dataclasses.dataclass(frozen=True)
class QuantizedConvParams:
    """Deployable artifact for one quantized conv layer."""

    gemm: QuantizedLinearParams   # packed (fh*fw*cin -> cout) GEMM
    fh: int
    fw: int
    stride: int
    padding: int
    cin: int
    cout: int
    # fused implicit-GEMM layout: per-tap Cin padded to cin_pad, tap-major
    # K = fh*fw*cin_pad, packed chunk-planar along K.
    w_packed_fused: jnp.ndarray = None
    cin_pad: int = 0
    # filter groups (grouped/depthwise conv: cin is the *per-group* channel
    # count, cout the total). No registered backend runs groups > 1 today —
    # the registry rejects such params cleanly (see repro.kernels.api) and
    # repro.vision.layers.QDepthwiseConv2D lowers depthwise onto the
    # supported ops (per-group qconv, or block-diagonal im2col + qdot).
    groups: int = 1


def quantize_conv(w, spec_w: QuantSpec, bn_scale, bn_bias,
                  spec_x: QuantSpec, spec_y: QuantSpec,
                  stride: int = 1, padding: int = 1) -> QuantizedConvParams:
    """w: (fh, fw, cin, cout) real weights -> packed integer artifact.

    Builds both weight layouts from one quantization pass so the fused and
    fallback routes consume bit-identical integer weights.
    """
    fh, fw, cin, cout = w.shape
    w_hat = quantize(w.reshape(fh * fw * cin, cout), spec_w)
    k_logical = w_hat.shape[0]
    # im2col layout: one tail pad on the flat K axis
    w_flat = packing.pad_to_chunk(w_hat, axis=0)
    w_packed = packing.pack(w_flat, spec_w.bits, axis=0)
    # fused layout: pad each tap's channel run independently
    cin_pad = packing.padded_size(cin)
    w_tap = w_hat.reshape(fh * fw, cin, cout)
    w_tap = jnp.pad(w_tap, ((0, 0), (0, cin_pad - cin), (0, 0)))
    w_packed_fused = packing.pack(
        w_tap.reshape(fh * fw * cin_pad, cout), spec_w.bits, axis=0)
    kappa, lam, m, d = fold_bn_requant(
        spec_w.eps, spec_x.eps, spec_y.eps, bn_scale, bn_bias, spec_y.bits)
    gemm = QuantizedLinearParams(
        w_packed=w_packed, w_bits=spec_w.bits, a_bits=spec_x.bits,
        a_signed=spec_x.signed, kappa=kappa, lam=lam, m=m, d=d,
        out_bits=spec_y.bits, k_logical=k_logical)
    return QuantizedConvParams(gemm=gemm, fh=fh, fw=fw, stride=stride,
                               padding=padding, cin=cin, cout=cout,
                               w_packed_fused=w_packed_fused,
                               cin_pad=cin_pad)


def qconv2d_apply(params: QuantizedConvParams, x_hat, *,
                  backend: Optional[str] = None,
                  block: Optional[tuple] = None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None):
    """x_hat: (N, H, W, Cin) int8 integer images -> (N, Ho, Wo, Cout) int8.

    Thin compat wrapper over `repro.kernels.api.qconv`; prefer calling
    that directly. ``backend`` selects a registered conv backend (block =
    (bho, bn) conv tile override for the fused kernel); ``use_kernel``/
    ``interpret`` are deprecated aliases mapped by
    `api.resolve_legacy_backend`.
    """
    from repro.kernels import api

    backend = api.resolve_legacy_backend(backend, use_kernel, interpret)
    return api.qconv(params, x_hat, backend=backend, block=block)

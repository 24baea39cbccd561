"""Integer-image quantization algebra — paper §III-A, eqs. (1)-(4), bit-exact.

Every QNN tensor t has a real range [alpha, beta) discretized on 2^N levels:

    t = alpha + eps * t_hat,      eps = (beta - alpha) / (2^N - 1)      (1)

with alpha_x = alpha_y = 0 for activations/outputs (so activation integer
images are unsigned). The three QNN operators act on integer images:

    LIN:      phi_hat   = sum_n w_hat[m,n] * x_hat[n]        (int32 accum) (2)
    BN:       phi'_hat  = kappa_hat * phi_hat + lambda_hat   (int32)       (3)
    QNT/ACT:  y_hat     = clip((m * phi'_hat) >> d, 0, 2^N-1)              (4)
              m = round(eps_phi' * 2^d / eps_y)

The requantization product m * phi' needs ~47 bits; the paper's RISC-V core
computes it with 32-bit ops. We reproduce (4) **exactly in int32** with a
high/low split valid for d >= 16 (see :func:`requantize_shift`); calibration
always produces d >= 16 because eps_phi'/eps_y << 1 in any sane QNN. The same
helper is used inside the Pallas kernel epilogue, so kernel and pure-jnp
paths are bit-identical; tests/hypothesis cross-check against a numpy int64
oracle.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core import packing

M_BITS = 15  # requant multiplier m in [0, 2^15): keeps every split term in int32
D_MIN, D_MAX = 16, 31


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """Affine quantization grid for one tensor (eq. 1)."""

    bits: int
    signed: bool
    alpha: float  # range lower bound (0 for activations, per paper)
    beta: float

    @property
    def levels(self) -> int:
        return (1 << self.bits) - 1

    @property
    def eps(self) -> float:
        if self.signed:
            # symmetric grid: t = eps * t_hat, t_hat in [-int_max, int_max]
            # (most-negative code dropped; 2-bit signed => ternary {-1,0,1})
            return self.beta / self.int_max
        # unsigned: grid covers [alpha, beta] with int_max steps (int_max is
        # container-capped at 127 for 8-bit, see packing._INT_INFO)
        return (self.beta - self.alpha) / self.int_max

    @property
    def int_min(self) -> int:
        if self.signed:
            return -self.int_max  # symmetric
        return packing.int_range(self.bits, self.signed)[0]

    @property
    def int_max(self) -> int:
        return packing.int_range(self.bits, self.signed)[1]

    @staticmethod
    def activation(bits: int, beta: float) -> "QuantSpec":
        return QuantSpec(bits=bits, signed=False, alpha=0.0, beta=beta)

    @staticmethod
    def weight(bits: int, absmax: float) -> "QuantSpec":
        # symmetric signed grid for weights (paper's kernels are symmetric)
        return QuantSpec(bits=bits, signed=True, alpha=-absmax, beta=absmax)


def quantize(t, spec: QuantSpec, eps=None):
    """Real tensor -> integer image (int8 container), eq. (1) inverted.

    ``eps`` (a float32 array holding ``spec.eps``) makes the divisor an
    operand of a jitted caller: XLA turns a division by a constant into
    a multiply by its float32 reciprocal, which rounds a near-tie the
    other way from the division an eager call makes."""
    zero = 0.0 if spec.signed else spec.alpha
    t_hat = jnp.round((t - zero) / (spec.eps if eps is None else eps))
    t_hat = jnp.clip(t_hat, spec.int_min, spec.int_max)
    return t_hat.astype(jnp.int8)


def dequantize(t_hat, spec: QuantSpec):
    zero = 0.0 if spec.signed else spec.alpha
    return zero + spec.eps * t_hat.astype(jnp.float32)


def fake_quantize(t, spec: QuantSpec):
    """Quantize-dequantize with straight-through estimator (QAT forward).

    Gradient is identity inside the representable range, zero outside
    (PACT-style clipped STE)."""
    import jax

    q = dequantize(quantize(t, spec), spec)
    lo = spec.alpha + spec.eps * spec.int_min if spec.signed else spec.alpha
    hi = spec.alpha + spec.eps * spec.int_max
    t_clip = jnp.clip(t, lo, hi)
    return t_clip + jax.lax.stop_gradient(q - t_clip)


# --- shared int8 side-channel codecs (optimizer state, gradient wire) ---
# Symmetric absmax int8 — the same grid family as `QuantSpec.weight` but
# jit-traced (scales are tensors, not floats) and shaped for streaming
# state, not packed serving artifacts. Two layouts:
#   rowwise   — scale per last-axis row; codes keep the param shape, so
#               ZeRO/GSPMD shardings propagate untouched (optimizer m)
#   blockwise — flat BLOCK-sized runs with one scale each; shape-agnostic
#               (the gradient compression wire format)

BLOCK = 256          # blockwise run length (gradient wire)


def quantize_int8_rowwise(x):
    """Per-row (last axis) symmetric int8: {"codes", "scale"}."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    codes = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return {"codes": codes, "scale": scale[..., 0]}


def dequantize_int8_rowwise(s, shape=None):
    """Inverse of `quantize_int8_rowwise` (``shape`` accepted for
    signature-compatibility with the log-scale codec; codes already
    carry it)."""
    return s["codes"].astype(jnp.float32) * s["scale"][..., None]


def quantize_int8_blockwise(x):
    """Flat BLOCK-run symmetric int8 -> (codes (n/BLOCK, BLOCK), scale)."""
    n = x.size
    pad = (-n) % BLOCK
    xb = jnp.pad(x.reshape(-1), (0, pad)).reshape(-1, BLOCK)
    scale = jnp.maximum(
        jnp.max(jnp.abs(xb), axis=1, keepdims=True) / 127.0, 1e-12)
    codes = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return codes, scale


def dequantize_int8_blockwise(codes, scale, shape):
    """Inverse of `quantize_int8_blockwise`, cropping the pad."""
    import math
    x = codes.astype(jnp.float32) * scale
    return x.reshape(-1)[: math.prod(shape)].reshape(shape)


def lin(w_hat, x_hat):
    """Eq. (2): integer dot product with int32 accumulation."""
    return jnp.matmul(
        x_hat.astype(jnp.int8), w_hat.astype(jnp.int8),
        preferred_element_type=jnp.int32)


def batchnorm_int(phi, kappa, lam):
    """Eq. (3): per-output-channel integer batch-norm (int32 wraparound
    semantics, matching 32-bit RISC-V MAC)."""
    return phi * kappa.astype(jnp.int32) + lam.astype(jnp.int32)


def requantize_shift(phi, m, d):
    """Exact ``(m * phi) >> d`` (floor) in pure int32, for d in [16, 31].

    Split the 47-bit product: with hi = phi >> 16, lo = phi & 0xFFFF,
        m*phi = A * 2^16 + B,   A = m*hi + ((m*lo) >> 16),  B = (m*lo) & 0xFFFF
    and for s = d - 16 >= 0:  floor(m*phi / 2^d) = A >> s  exactly, because
    the discarded ``r*2^16 + B`` remainder is < 2^(s+16). Every intermediate
    fits int32 given m < 2^15. Used verbatim in the Pallas kernel epilogue.
    """
    phi = phi.astype(jnp.int32)
    m = m.astype(jnp.int32)
    hi = phi >> 16
    lo = phi & 0xFFFF
    mlo = m * lo
    a = m * hi + (mlo >> 16)
    return a >> (d - 16)


def requantize_shift_i64(phi, m, d):
    """numpy int64 oracle for :func:`requantize_shift` (tests only)."""
    phi = np.asarray(phi, dtype=np.int64)
    m = np.asarray(m, dtype=np.int64)
    return ((m * phi) >> d).astype(np.int64)


def qnt_act(phi_prime, m, d, out_bits: int):
    """Eq. (4): requantize + clip to the unsigned N-bit activation grid.

    The clip-at-zero implements the ReLU-style activation semantic the paper
    folds into QNT/ACT (alpha_y = 0).
    """
    y = requantize_shift(phi_prime, m, d)
    hi = packing.int_range(out_bits, False)[1]
    return jnp.clip(y, 0, hi).astype(jnp.int8)


def pick_requant_md(ratio: float, d_min: int = D_MIN) -> tuple:
    """Largest-precision ``(m, d)`` with ``m = round(ratio * 2^d) < 2^15``.

    ``ratio`` is the real requantization factor (eps_in / eps_out terms);
    ``d_min`` is the smallest admissible shift — `D_MIN` (16) when the
    requant runs through :func:`requantize_shift` (the int32 hi/lo split
    needs it), 0 for small-operand requants (e.g. residual add, where
    ``m * x`` fits int32 directly). Shared by `fold_bn_requant` and the
    vision-layer folds (avg-pool, residual add).
    """
    ratio = float(ratio)
    if ratio <= 0:
        raise ValueError("invalid quanta")
    d = min(D_MAX, int(np.floor(np.log2((1 << M_BITS) - 1) - np.log2(ratio))))
    if d < d_min:
        raise ValueError(
            f"requant ratio {ratio} too large for int32 requant "
            f"(d={d} < {d_min}); re-calibrate output quantum")
    return int(np.round(ratio * (1 << d))), d


def fold_bn_requant(eps_w: float, eps_x: float, eps_y: float,
                    bn_scale, bn_bias,
                    bits_out: int,
                    kappa_bits: int = 8):
    """Calibrate integer BN + QNT/ACT parameters from real-valued BN.

    Real pipeline:  y = clip((bn_scale * phi_real + bn_bias) / eps_y)
    with phi_real = eps_w*eps_x*phi_hat. We pick the accumulator quantum
    eps_phi' and integer kappa_hat (kappa_bits) per channel, lambda_hat int32,
    and (m, d) with m < 2^15, d in [16, 31], maximizing precision.

    Returns (kappa_hat i32[n], lambda_hat i32[n], m i32[n], d int scalar).
    """
    bn_scale = np.asarray(bn_scale, dtype=np.float64)
    bn_bias = np.asarray(bn_bias, dtype=np.float64)
    eps_phi = float(eps_w) * float(eps_x)

    # kappa_hat = round(bn_scale / eps_kappa); choose per-layer eps_kappa so
    # the largest channel scale uses the full kappa_bits range.
    kmax = max(np.abs(bn_scale).max(), 1e-12)
    eps_kappa = kmax / ((1 << (kappa_bits - 1)) - 1)
    kappa_hat = np.round(bn_scale / eps_kappa).astype(np.int32)
    eps_phi_p = eps_phi * eps_kappa
    lambda_hat = np.round(bn_bias / eps_phi_p).astype(np.int32)

    ratio = eps_phi_p / float(eps_y)
    # largest d in [D_MIN, D_MAX] with m = round(ratio * 2^d) < 2^M_BITS
    m_scalar, d = pick_requant_md(ratio)
    m = np.broadcast_to(np.int32(m_scalar), bn_scale.shape).copy()
    return (jnp.asarray(kappa_hat), jnp.asarray(lambda_hat),
            jnp.asarray(m), d)


@dataclasses.dataclass(frozen=True)
class QuantizedLinearParams:
    """Everything the integer GEMM needs — the deployable artifact."""

    w_packed: jnp.ndarray  # (K_pad/pf, N) int8 containers, chunk-planar
    w_bits: int
    a_bits: int
    a_signed: bool
    kappa: jnp.ndarray     # (N,) int32
    lam: jnp.ndarray       # (N,) int32
    m: jnp.ndarray         # (N,) int32
    d: int
    out_bits: int
    k_logical: int         # pre-padding K


@dataclasses.dataclass(frozen=True)
class SegmentedLinearParams:
    """Mixed-width deployable artifact: per-output-channel-run containers.

    ``w_flat`` is a `packing.pack_segmented` buffer whose runs over the
    output-feature axis are named by ``segmap`` (fine-grain mixed
    precision, Nadalini et al. 2307.01056). Epilogue vectors span the full
    N. `segment_params` views one run as a uniform
    `QuantizedLinearParams` — running each segment through the uniform
    kernel and concatenating along N is the mixed-operand kernel's
    bit-exactness oracle (and the segment-looping xla/eager backends).
    """

    w_flat: jnp.ndarray    # (total_bytes,) int8, panel-major segmented
    segmap: "packing.SegmentMap"
    a_bits: int
    a_signed: bool
    kappa: jnp.ndarray     # (N,) int32
    lam: jnp.ndarray       # (N,) int32
    m: jnp.ndarray         # (N,) int32
    d: int
    out_bits: int
    k_logical: int         # pre-padding K

    @property
    def n(self) -> int:
        return self.segmap.n

    def segment_params(self, index: int) -> QuantizedLinearParams:
        s, e, b = self.segmap.runs[index]
        return QuantizedLinearParams(
            w_packed=packing.segment_packed(self.w_flat, self.segmap,
                                            index, self.k_logical),
            w_bits=b, a_bits=self.a_bits, a_signed=self.a_signed,
            kappa=self.kappa[s:e], lam=self.lam[s:e], m=self.m[s:e],
            d=self.d, out_bits=self.out_bits, k_logical=self.k_logical)


def quantize_linear_segmented(w_hat, segmap, kappa, lam, m, *,
                              a_bits: int, a_signed: bool, d: int,
                              out_bits: int,
                              assert_range: bool = False
                              ) -> SegmentedLinearParams:
    """Pack already-quantized int8 weight values (K, N) at per-run widths.

    The integer-side companion of `quantize_linear` for segmented
    containers: values must already sit on each run's ``w_bits`` grid
    (``assert_range=True`` arms the truncation guard per run).
    """
    k_logical = int(w_hat.shape[-2])
    return SegmentedLinearParams(
        w_flat=packing.pack_segmented(w_hat, segmap,
                                      assert_range=assert_range),
        segmap=segmap, a_bits=a_bits, a_signed=a_signed,
        kappa=jnp.asarray(kappa, jnp.int32), lam=jnp.asarray(lam, jnp.int32),
        m=jnp.asarray(m, jnp.int32), d=d, out_bits=out_bits,
        k_logical=k_logical)


def quantize_linear(w, spec_w: QuantSpec, bn_scale, bn_bias,
                    spec_x: QuantSpec, spec_y: QuantSpec) -> QuantizedLinearParams:
    """Full deployment quantization of one linear layer (paper's pipeline)."""
    w_hat = quantize(w, spec_w)                       # (K, N) int8
    k_logical = w_hat.shape[0]
    w_hat = packing.pad_to_chunk(w_hat, axis=0)
    w_packed = packing.pack(w_hat, spec_w.bits, axis=0)
    kappa, lam, m, d = fold_bn_requant(
        spec_w.eps, spec_x.eps, spec_y.eps, bn_scale, bn_bias, spec_y.bits)
    return QuantizedLinearParams(
        w_packed=w_packed, w_bits=spec_w.bits, a_bits=spec_x.bits,
        a_signed=spec_x.signed, kappa=kappa, lam=lam, m=m, d=d,
        out_bits=spec_y.bits, k_logical=k_logical)

"""Core layers: quantization-aware Dense, embeddings, norms, RoPE.

Dense is where the paper's technique plugs into every architecture: a
`QuantConfig` selects fp / fake-quant (QAT) / integer deployment mode, the
latter holding chunk-planar *packed* sub-byte weights in HBM and running the
int8 MXU GEMM with a dequant epilogue (W{8,4,2}A8 serving) — the XpulpNN
pipeline adapted to TPU (see DESIGN.md §2).
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.quantize import QuantSpec, fake_quantize
from repro.nn.module import ParamDef


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    mode: str = "off"        # off | fake | int
    w_bits: int = 8
    a_bits: int = 8
    # static activation scale (absmax) used in int mode; per-tensor dynamic
    # quantization when None (max computed on the fly; costs a reduction)
    a_absmax: Optional[float] = 4.0
    # named kernel backend for the quantized-op registry
    # (repro.kernels.api: pallas | pallas_interpret | xla | eager_ref);
    # None -> capability-ordered default resolution. Honored by the op
    # entry points (api.qdot / api.qconv); dense_apply's int path runs the
    # shared `xla` implementation (the production lowering) — the field is
    # carried through deployment plans for call sites that route kernels.
    backend: Optional[str] = None
    # kernel software-pipeline mode ('off' | 'double_buffer', the Mac&Load
    # knob — repro.kernels.common.PIPELINE_MODES); None -> runtime
    # resolution (REPRO_QPIPELINE env -> tune-cache winner -> 'off').
    # Like `backend`, honored by call sites routing through the op
    # registry and carried through deployment plans (PlanRule.pipeline).
    pipeline: Optional[str] = None
    # Fine-grain mixed precision (plan schema v4): ordered
    # (n_start, n_end, w_bits) runs over the output-feature axis — one
    # dense layer serves different channel groups at different widths
    # (Nadalini et al. 2307.01056). None -> uniform w_bits. Normalized to
    # a tuple-of-int-tuples (hashable) and validated through
    # `packing.SegmentMap` in __post_init__.
    segments: Optional[tuple] = None
    # DEPRECATION SHIM: pre-registry boolean. Normalized to None in
    # __post_init__ after mapping True -> 'pallas_interpret' (the old
    # default silently ran interpret mode), False -> 'xla'.
    use_kernel: Optional[bool] = None

    def __post_init__(self):
        if self.pipeline is not None:
            from repro.kernels.common import check_pipeline
            check_pipeline(self.pipeline)
        if self.segments is not None:
            sm = packing.SegmentMap(tuple(tuple(r) for r in self.segments))
            object.__setattr__(self, "segments", sm.runs)
        if self.use_kernel is not None:
            if self.backend is not None:
                raise ValueError(
                    "pass either backend= or the deprecated use_kernel=, "
                    "not both")
            warnings.warn(
                "QuantConfig(use_kernel=...) is deprecated; pass "
                "backend='pallas'|'pallas_interpret'|'xla'|'eager_ref' "
                "(see repro.kernels.api)", DeprecationWarning, stacklevel=3)
            object.__setattr__(
                self, "backend",
                "pallas_interpret" if self.use_kernel else "xla")
            object.__setattr__(self, "use_kernel", None)

    @property
    def enabled(self):
        return self.mode != "off"


QOFF = QuantConfig()


# Calibration tap: when set, dense_apply calls it with (params, x) before
# the matmul. The deploy calibrator uses this to record per-dense activation
# absmax and bit-width sensitivity during an *eager* replay — callbacks get
# concrete arrays only when no jit/scan tracing is active, so taps are for
# host-side calibration passes, never inside compiled training/serving.
_DENSE_TAP: Optional[Callable] = None


@contextlib.contextmanager
def dense_tap(fn: Callable):
    """Install ``fn(params_dict, x)`` as the dense-apply observer."""
    global _DENSE_TAP
    prev = _DENSE_TAP
    _DENSE_TAP = fn
    try:
        yield
    finally:
        _DENSE_TAP = prev


# ---------------------------------------------------------------- dense ---

def dense_def(d_in: int, d_out: int, axes=("embed", "mlp"), *,
              bias: bool = False, qcfg: QuantConfig = QOFF,
              dtype=jnp.float32, scale: float = 1.0):
    if qcfg.mode == "int" and qcfg.segments is not None:
        segmap = packing.SegmentMap(qcfg.segments)
        if segmap.n != d_out:
            raise ValueError(
                f"segment map covers N={segmap.n} but d_out={d_out}")
        # flat segmented container (panel-major, exact bytes); the sharding
        # axis collapses away — segmented denses are not TP-sharded today
        p = {"w_packed": ParamDef((segmap.packed_bytes(d_in),), (None,),
                                  "zeros", jnp.int8),
             "w_scale": ParamDef((d_out,), (axes[1],), "ones", jnp.float32)}
    elif qcfg.mode == "int":
        kp = packing.padded_size(d_in) // packing.pack_factor(qcfg.w_bits)
        p = {"w_packed": ParamDef((kp, d_out), (axes[0], axes[1]),
                                  "zeros", jnp.int8),
             "w_scale": ParamDef((d_out,), (axes[1],), "ones", jnp.float32)}
    else:
        p = {"w": ParamDef((d_in, d_out), axes, "normal", dtype, scale)}
    if bias:
        p["b"] = ParamDef((d_out,), (axes[1],), "zeros", dtype)
    return p


def dense_apply(p, x, *, qcfg: QuantConfig = QOFF, precision=None):
    """x: (..., d_in) bf16/f32 -> (..., d_out)."""
    if _DENSE_TAP is not None:
        _DENSE_TAP(p, x)
    if qcfg.mode == "int":
        y = _int_matmul(p, x, qcfg)
    elif qcfg.mode == "fake":
        w = p["w"]
        sw = QuantSpec.weight(qcfg.w_bits, 3.0 / (w.shape[0] ** 0.5))
        sa = QuantSpec(qcfg.a_bits, True, -qcfg.a_absmax, qcfg.a_absmax)
        y = jnp.matmul(fake_quantize(x, sa).astype(x.dtype),
                       fake_quantize(w, sw).astype(x.dtype))
    else:
        y = jnp.matmul(x, p["w"].astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def quantize_activations(x, qcfg: QuantConfig):
    """x (..., K) -> (int8 codes on the symmetric a_bits grid of the static
    range ``a_absmax``, K padded to the packing chunk; the grid's step).
    A8 caps at 127 (int8 containers)."""
    absmax = qcfg.a_absmax or 4.0
    a_max = packing.int_range(qcfg.a_bits, True)[1]
    a_scale = absmax / a_max
    x_q = jnp.clip(jnp.round(x.astype(jnp.float32) / a_scale), -a_max, a_max
                   ).astype(jnp.int8)
    return packing.pad_to_chunk(x_q, axis=-1), a_scale


def _int_matmul(p, x, qcfg: QuantConfig):
    """W{8,4,2}A{8,4,2} integer GEMM with dequant epilogue.

    Activations are symmetrically quantized onto the a_bits grid (int8
    containers, so A8 caps at ±127) with a static scale; the GEMM +
    per-channel dequant epilogue is the shared `xla` implementation of the
    quantized-op registry (`repro.kernels.api.xla_int_gemm`) — the same
    code path the `xla` qdot backend runs, so dense serving and the packed
    kernel wrappers no longer maintain divergent copies. HBM traffic for
    weights is 1/pf of the bf16 baseline — the paper's sub-byte gain
    mapped to the TPU memory roofline term.
    """
    from repro.kernels.api import xla_int_gemm

    k_logical = x.shape[-1]
    x_q, a_scale = quantize_activations(x, qcfg)
    if qcfg.segments is not None:
        # fine-grain mixed precision: each N-run is a uniform container
        # view of the flat segmented buffer — a static Python loop over
        # runs, so the path stays jit/scan-safe (segment maps are config,
        # not data)
        segmap = packing.SegmentMap(qcfg.segments)
        outs = []
        for i, (s, e, b) in enumerate(segmap.runs):
            wp = packing.segment_packed(p["w_packed"], segmap, i, k_logical)
            sc = (p["w_scale"][s:e] * a_scale).astype(jnp.float32)
            outs.append(xla_int_gemm(x_q, wp, w_bits=b, epilogue="dequant",
                                     scale=sc, out_dtype=x.dtype))
        return jnp.concatenate(outs, axis=-1)
    scale = (p["w_scale"] * a_scale).astype(jnp.float32)
    return xla_int_gemm(x_q, p["w_packed"], w_bits=qcfg.w_bits,
                        epilogue="dequant", scale=scale, out_dtype=x.dtype)


def quantize_dense_weights(w, w_bits: int):
    """fp weights (..., K, N) -> (w_hat int8 in-range, w_scale (..., N))
    on per-output-channel symmetric grids. Leading dims (a stacked layer
    axis) broadcast — no vmap needed, so host paths can range-check the
    whole stack before packing."""
    red = w.ndim - 2  # K axis
    absmax = jnp.maximum(jnp.max(jnp.abs(w), axis=red), 1e-8)
    int_max = packing.int_range(w_bits, True)[1]
    w_scale = absmax / int_max
    w_hat = jnp.clip(jnp.round(w / jnp.expand_dims(w_scale, red)),
                     -int_max, int_max).astype(jnp.int8)
    return w_hat, w_scale


def pack_dense_weights(w, w_bits: int, *, assert_range: bool = False):
    """fp weights (K,N) or stacked (L,K,N) -> (w_packed, w_scale) for
    int-mode params. ``assert_range`` enables the host-side truncation
    guard (eager only)."""
    w_hat, w_scale = quantize_dense_weights(w, w_bits)
    red = w.ndim - 2
    w_hat = packing.pad_to_chunk(w_hat, axis=red)
    return packing.pack(w_hat, w_bits, axis=red,
                        assert_range=assert_range), w_scale


def pack_dense_weights_segmented(w, segments, *, assert_range: bool = False):
    """fp weights (K,N) or stacked (L,K,N) -> (w_flat, w_scale) at
    per-run widths: each output-channel run quantizes on its own
    per-channel symmetric grid at its own w_bits, then the runs pack into
    one flat segmented container (`packing.pack_segmented`). w_scale
    spans the full N regardless of widths."""
    segmap = (segments if isinstance(segments, packing.SegmentMap)
              else packing.SegmentMap(tuple(tuple(r) for r in segments)))
    if w.shape[-1] != segmap.n:
        raise ValueError(
            f"segment map covers N={segmap.n} but weights have "
            f"d_out={w.shape[-1]}")
    hats, scales = [], []
    for s, e, b in segmap.runs:
        h, sc = quantize_dense_weights(w[..., s:e], b)
        hats.append(h)
        scales.append(sc)
    w_hat = jnp.concatenate(hats, axis=-1)
    w_scale = jnp.concatenate(scales, axis=-1)
    return packing.pack_segmented(w_hat, segmap,
                                  assert_range=assert_range), w_scale


# ------------------------------------------------------------ embedding ---

VOCAB_PAD = 256  # pad vocab so logits/vocab-sharded ops divide the mesh
# (odd vocabs — mamba2 50280, seamless 256206 — otherwise replicate the
# (tokens x vocab) logits per device: +52 GB/dev f32 at mamba2 train_4k)


def padded_vocab(vocab: int) -> int:
    return vocab + (-vocab) % VOCAB_PAD


def embedding_def(vocab: int, d: int, dtype=jnp.float32):
    return {"table": ParamDef((padded_vocab(vocab), d), ("vocab", "embed"),
                              "embed", dtype, scale=1.0)}


def embedding_apply(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def embedding_logits(p, x, vocab: int = 0):
    """Tied output head: (..., d) @ (vocab_pad, d)^T. Padded rows are
    masked to -inf so the softmax ignores them."""
    lg = jnp.matmul(x, p["table"].astype(x.dtype).T)
    vp = p["table"].shape[0]
    if vocab and vp != vocab:
        mask = (jnp.arange(vp) < vocab)
        lg = jnp.where(mask, lg, jnp.asarray(-1e9, lg.dtype))
    return lg


# ---------------------------------------------------------------- norms ---

def norm_def(d: int, kind: str = "rmsnorm", dtype=jnp.float32):
    if kind == "nonparam_ln":   # OLMo: non-parametric LayerNorm
        return {}
    if kind == "layernorm":
        return {"scale": ParamDef((d,), ("embed",), "ones", dtype),
                "bias": ParamDef((d,), ("embed",), "zeros", dtype)}
    # rmsnorm / gemma_rmsnorm ((1+scale) form)
    return {"scale": ParamDef((d,), ("embed",),
                              "zeros" if kind == "gemma_rmsnorm" else "ones",
                              dtype)}


def norm_apply(p, x, kind: str = "rmsnorm", eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind in ("layernorm", "nonparam_ln"):
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        if kind == "layernorm":
            y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(
                jnp.float32)
        return y.astype(x.dtype)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    scale = p["scale"].astype(jnp.float32)
    if kind == "gemma_rmsnorm":
        scale = 1.0 + scale
    return (y * scale).astype(x.dtype)


# ----------------------------------------------------------------- rope ---

def rope_tables(seq_len: int, head_dim: int, theta: float = 10000.0,
                dtype=jnp.float32):
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    t = jnp.arange(seq_len, dtype=jnp.float32)
    ang = jnp.outer(t, freqs)            # (S, half)
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def rope_apply(x, cos, sin):
    """x: (..., S, H, Dh); tables (S, Dh/2) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, None, :]
    s = sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rope_apply_at(x, cos, sin, positions):
    """Decode-time RoPE: positions (B,) int32 index the tables."""
    c = jnp.take(cos, positions, axis=0)[:, None, None, :]  # (B,1,1,half)
    s = jnp.take(sin, positions, axis=0)[:, None, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rope_single(x, position, theta):
    """Table-free decode RoPE: x (B,1,H,Dh); position a scalar (wave
    decode: every row at the same step) or a (B,) vector (continuous
    batching: each slot at its own true position). The per-element math
    is identical in both forms, so an all-equal vector is bit-exact vs
    the scalar path.

    `theta` may be a traced scalar (per-layer dual-theta schedules). Avoids
    materializing (max_len, Dh/2) tables in decode — at 512k context the
    tables alone would cost hundreds of MB.
    """
    half = x.shape[-1] // 2
    theta = jnp.asarray(theta, jnp.float32)
    freqs = jnp.power(theta, -jnp.arange(0, half, dtype=jnp.float32) / half)
    position = jnp.asarray(position)
    if position.ndim == 0:
        ang = position.astype(jnp.float32) * freqs          # (half,)
        c = jnp.cos(ang).astype(x.dtype)[None, None, None, :]
        s = jnp.sin(ang).astype(x.dtype)[None, None, None, :]
    else:
        ang = position.astype(jnp.float32)[:, None] * freqs  # (B, half)
        c = jnp.cos(ang).astype(x.dtype)[:, None, None, :]
        s = jnp.sin(ang).astype(x.dtype)[:, None, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)

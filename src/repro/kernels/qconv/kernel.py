"""Fused implicit-GEMM quantized conv — the PULP-NN execution model in one
Pallas kernel (paper §III-C; PULP-NN, arXiv:1908.11263).

PULP-NN convolves by interleaving an im2col of each output tile's receptive
fields into the NN register file with the MatMul + BN + QNT/ACT pipeline,
so the loads ride behind the MACs (Mac&Load) and no HBM-resident im2col
tensor ever exists. This kernel reproduces that structure on TPU:

  * the packed HWC input image is the only activation tensor in HBM,
    split by the wrapper into its stride phases (phase (py, px) holds
    pixels (py + s*i, px + s*j)), so every tap reads a unit-stride window;
  * per grid step the kernel *gathers* the receptive fields of a
    (bho output rows x Wo columns) tile directly out of the image block —
    one window per filter tap (dy, dx) — into a VMEM scratch buffer
    that plays the NN-RF/im2col-buffer role;
  * the planar sub-byte dot product (repro.kernels.common.matmul_planes)
    then contracts the whole fh*fw*Cin_pad axis against the packed weight
    panel on the MXU, and the eq.(3)/(4) integer BN + requant epilogue is
    applied before the tile is written back.

Because the gather happens between pipelined MXU invocations of adjacent
grid steps, the Pallas grid pipeliner overlaps it with compute exactly the
way Mac&Load hides the pointer-walk loads of the RISC-V core.

``pipeline='double_buffer'`` makes that overlap explicit *inside* one grid
step (the Mac&Load analogue at filter-row granularity): the packed image
stays in HBM, the kernel owns two VMEM band slots, and while filter row
dy's per-tap partial dots run on the MXU, the DMA of row dy+1's band is
already in flight. The contraction becomes a sum of per-tap partial dots
— integer accumulation is order-invariant, so the result is bit-exact
against the one-pass 'off' mode and the eager oracle
(tests/test_kernel_pipeline.py).

Layout: the implicit GEMM is (N*Ho*Wo, fh*fw*Cin_pad) @ (fh*fw*Cin_pad,
Cout). Cin is padded per-tap to a CHUNK multiple so every tap's channel
run is chunk-planar packable on its own (zero padding == zero MACs); the
weight panel uses the matching per-tap layout built by
`quantize_conv` (`w_packed_fused`). The grid is (N, ceil(Ho/bho),
Cout_pad/bn) — each step owns its full contraction; the cout dim is
innermost and 'arbitrary' so the gathered scratch is reused across cout
panels instead of re-gathered.

Sizing: the whole packed image is one VMEM block (IoT-scale images — the
paper's layers are 16x16/32x32 — fit trivially); `conv_default_block`
checks the budget and raises for images that would not fit, in which case
the HBM im2col fallback (the `xla` backend of `repro.kernels.api.qconv`)
applies.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.kernels.common import (LANE, EPILOGUE_DTYPES, apply_epilogue,
                                  check_pipeline, conv_default_block,
                                  matmul_planes, round_up)


def _tap_window(dy: int, dx: int, stride: int):
    """Where filter tap (dy, dx) reads in the stride-phase image: the
    phase index, and the row and column offset inside that phase. Output
    pixel (oy, ox) reads input (oy*s + dy, ox*s + dx), which is pixel
    (oy + dy//s, ox + dx//s) of phase (dy % s, dx % s)."""
    return (dy % stride) * stride + dx % stride, dy // stride, dx // stride


def _qconv_kernel(x_ref, w_ref, kappa_ref, lam_ref, m_ref, o_ref, col_ref,
                  *, fh: int, fw: int, stride: int, bho: int, wo: int,
                  cp: int, a_bits: int, a_signed: bool, w_bits: int,
                  d: int, out_bits: int, epilogue: str, scale: float):
    """One grid step: implicit-GEMM for (bho x wo) output pixels.

    x_ref:   (s*s, Hs, Ws, cp) whole packed image split into its stride
             phases (cp = cin_pad/pf_a; batch dim squeezed by the
             BlockSpec).
    w_ref:   (fh*fw*cin_pad/pf_w, bn) packed weight panel, tap-major K.
    col_ref: (bho*wo, fh*fw*cp) VMEM scratch — the NN-RF/im2col buffer.
    o_ref:   (bho, wo, bn) output tile (batch dim squeezed).
    """
    r0 = pl.program_id(1) * bho  # first phase row of this tile

    # im2col gather: one unit-stride window per filter tap, written to the
    # tap's chunk-aligned column run of the scratch buffer. The scratch
    # depends only on (b, i); with the cout dim innermost ('arbitrary', so
    # the scratch persists across j steps) the gather runs once per output
    # tile, not once per cout panel.
    @pl.when(pl.program_id(2) == 0)
    def _gather():
        for dy in range(fh):
            for dx in range(fw):
                ph, oy, ox = _tap_window(dy, dx, stride)
                patch = x_ref[ph, pl.ds(r0 + oy, bho), ox:ox + wo, :]
                t = dy * fw + dx
                col_ref[:, t * cp:(t + 1) * cp] = patch.reshape(
                    bho * wo, cp)

    # MatMul + BN + QNT/ACT on the gathered tile (full K, one pass).
    acc = matmul_planes(col_ref[...], w_ref[...], a_bits, a_signed, w_bits)
    y = apply_epilogue(
        acc, kappa_ref[...], lam_ref[...], m_ref[...],
        d=d, out_bits=out_bits, epilogue=epilogue, scale=scale,
        out_dtype=o_ref.dtype)
    o_ref[...] = y.reshape(bho, wo, -1)


def _qconv_kernel_db(x_hbm, w_ref, kappa_ref, lam_ref, m_ref, o_ref,
                     buf, sems, *, fh: int, fw: int, stride: int, bho: int,
                     wo: int, cp: int, kpt: int, a_bits: int,
                     a_signed: bool, w_bits: int, d: int, out_bits: int,
                     epilogue: str, scale: float):
    """Double-buffered gather: per filter row dy, the next row's band DMA
    overlaps the current row's per-tap partial sub-byte dots.

    x_hbm: (N, s*s, Hs, Ws, cp) stride-phase packed image, in HBM.
    buf:   (2, s, bho, Ws, lanes) int8 slots: the s column phases of one
           phase-row band. The copy slices only the leading (untiled)
           axes, so it is tile-aligned for any dy; the column offset of a
           tap is a static slice of the slot.
    kpt:   packed weight rows per tap (cin_pad / pf_w); tap t's panel rows
           are w_ref[t*kpt:(t+1)*kpt] (tap-major K, static slices).
    """
    b = pl.program_id(0)
    r0 = pl.program_id(1) * bho

    def band_dma(slot, dy):
        ph, oy, _ = _tap_window(dy, 0, stride)
        return pltpu.make_async_copy(
            x_hbm.at[b, pl.ds(ph, stride), pl.ds(r0 + oy, bho)],
            buf.at[slot], sems.at[slot])

    band_dma(0, 0).start()
    acc = jnp.zeros((bho * wo, o_ref.shape[-1]), jnp.int32)
    # static Python loop: filter rows are compile-time, so slot indices and
    # the per-tap weight-panel slices stay static while the DMA of row
    # dy+1 rides behind row dy's MXU contractions
    for dy in range(fh):
        if dy + 1 < fh:
            band_dma((dy + 1) % 2, dy + 1).start()
        band_dma(dy % 2, dy).wait()
        for dx in range(fw):
            _, _, ox = _tap_window(dy, dx, stride)
            patch = buf[dy % 2, dx % stride, :, ox:ox + wo, :cp]
            t = dy * fw + dx
            acc += matmul_planes(patch.reshape(bho * wo, cp),
                                 w_ref[t * kpt:(t + 1) * kpt, :],
                                 a_bits, a_signed, w_bits)
    y = apply_epilogue(
        acc, kappa_ref[...], lam_ref[...], m_ref[...],
        d=d, out_bits=out_bits, epilogue=epilogue, scale=scale,
        out_dtype=o_ref.dtype)
    o_ref[...] = y.reshape(bho, wo, -1)


def qconv2d_fused(x_hat, w_packed_fused, kappa, lam, m_mul, *,
                  fh: int, fw: int, stride: int, padding: int,
                  cin_pad: int, cout: int,
                  a_bits: int, a_signed: bool, w_bits: int,
                  d: int, out_bits: int, epilogue: str = "int",
                  scale: float = 1.0,
                  block: Optional[tuple] = None,
                  out_dtype=None,
                  pipeline: str = "off",
                  interpret: bool = False):
    """Fused implicit-GEMM conv on integer images.

    x_hat: (N, H, W, Cin) int8 integer images (unpacked). Spatial and
    channel padding plus sub-byte packing happen here; the Pallas kernel
    sees only the packed image. w_packed_fused is the per-tap-padded
    packed weight panel from `quantize_conv` (K = fh*fw*cin_pad,
    tap-major). ``pipeline`` selects the execution mode (module
    docstring): 'off' gathers the whole receptive field into the im2col
    scratch once per tile, 'double_buffer' keeps the image in HBM and
    double-buffers the per-tap patch copies behind per-tap partial dots.
    Returns (N, Ho, Wo, Cout).
    """
    check_pipeline(pipeline)
    n, h, w_, cin = x_hat.shape
    assert cin <= cin_pad and cin_pad % packing.CHUNK == 0, (cin, cin_pad)
    ho = (h + 2 * padding - fh) // stride + 1
    wo = (w_ + 2 * padding - fw) // stride + 1
    assert ho > 0 and wo > 0, (ho, wo)
    pf_a = packing.pack_factor(a_bits)
    pf_w = packing.pack_factor(w_bits)
    cp = cin_pad // pf_a
    kp = fh * fw * cin_pad // pf_w
    assert w_packed_fused.shape[0] == kp, (w_packed_fused.shape, kp)

    if block is None:
        block = conv_default_block(n, ho, wo, cout, fh, fw, cin_pad,
                                   stride, a_bits, w_bits)
    bho, bn = block
    bho = min(bho, ho)
    n_ho = -(-ho // bho)
    ho_pad = n_ho * bho

    # Spatial pad: `padding` zeros on top/left, and enough rows/cols below
    # so even the ragged last row tile's receptive field stays in bounds
    # (the extra rows are zeros; their outputs are sliced off). Both
    # extents round up to the stride so the image splits into phases.
    # The phase width also rounds up to 8 rows, the sublane tile of an
    # int8 array in HBM, so a DMA of a whole phase row is tile-aligned.
    s = stride
    hp = round_up(max(h + 2 * padding, (ho_pad - 1) * s + fh), s)
    wp = round_up(max(w_ + 2 * padding, (wo - 1) * s + fw), 8 * s)
    x = jnp.pad(x_hat, ((0, 0),
                        (padding, hp - h - padding),
                        (padding, wp - w_ - padding),
                        (0, cin_pad - cin)))
    xp = packing.pack(x, a_bits, axis=-1)  # (N, hp, wp, cp)
    # Stride phases: phase (py, px) is xp[:, py::s, px::s], so every tap
    # reads a unit-stride window (Mosaic has no strided int8 load).
    hs, ws = hp // s, wp // s
    xp = xp.reshape(n, hs, s, ws, s, cp).transpose(0, 2, 4, 1, 3, 5)
    xp = xp.reshape(n, s * s, hs, ws, cp)

    cout_pad = round_up(cout, bn)
    wpk = jnp.pad(w_packed_fused, ((0, 0), (0, cout_pad - cout)))
    kappa2 = jnp.pad(kappa.reshape(1, -1), ((0, 0), (0, cout_pad - cout)))
    lam2 = jnp.pad(lam.reshape(1, -1), ((0, 0), (0, cout_pad - cout)))
    mm2 = jnp.pad(m_mul.reshape(1, -1), ((0, 0), (0, cout_pad - cout)))

    if out_dtype is None:
        out_dtype = EPILOGUE_DTYPES[epilogue]

    grid = (n, n_ho, cout_pad // bn)
    if pipeline == "double_buffer":
        # The band copy moves whole (Ws, lanes) tiles, so the container
        # axis is padded to the lane width; the chip's HBM tiling pads a
        # narrower int8 minor axis to 128 lanes anyway.
        cpl = round_up(cp, LANE)
        xp = jnp.pad(xp, ((0, 0),) * 4 + ((0, cpl - cp),))
        kernel = functools.partial(
            _qconv_kernel_db, fh=fh, fw=fw, stride=stride, bho=bho, wo=wo,
            cp=cp, kpt=cin_pad // pf_w, a_bits=a_bits, a_signed=a_signed,
            w_bits=w_bits, d=d, out_bits=out_bits, epilogue=epilogue,
            scale=scale)
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.ANY),
                pl.BlockSpec((kp, bn), lambda b, i, j: (0, j)),
                pl.BlockSpec((1, bn), lambda b, i, j: (0, j)),
                pl.BlockSpec((1, bn), lambda b, i, j: (0, j)),
                pl.BlockSpec((1, bn), lambda b, i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((None, bho, wo, bn),
                                   lambda b, i, j: (b, i, 0, j)),
            out_shape=jax.ShapeDtypeStruct((n, ho_pad, wo, cout_pad),
                                           out_dtype),
            scratch_shapes=[
                pltpu.VMEM((2, s, bho, ws, cpl), jnp.int8),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="qconv_fused_db",
        )(xp, wpk, kappa2, lam2, mm2)
        return out[:, :ho, :, :cout]

    kernel = functools.partial(
        _qconv_kernel, fh=fh, fw=fw, stride=stride, bho=bho, wo=wo, cp=cp,
        a_bits=a_bits, a_signed=a_signed, w_bits=w_bits, d=d,
        out_bits=out_bits, epilogue=epilogue, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, s * s, hs, ws, cp),
                         lambda b, i, j: (b, 0, 0, 0, 0)),
            pl.BlockSpec((kp, bn), lambda b, i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda b, i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda b, i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda b, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((None, bho, wo, bn),
                               lambda b, i, j: (b, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, ho_pad, wo, cout_pad), out_dtype),
        scratch_shapes=[pltpu.VMEM((bho * wo, fh * fw * cp), jnp.int8)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="qconv_fused",
    )(xp, wpk, kappa2, lam2, mm2)
    return out[:, :ho, :, :cout]

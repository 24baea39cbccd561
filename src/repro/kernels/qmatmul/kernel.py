"""Packed sub-byte integer GEMM — the XpulpNN `sdotp`/`mac&load` analogue.

One Pallas TPU kernel implements the whole paper pipeline per output tile
(see repro.kernels.common for the shared unpack/dot/epilogue machinery):

    unpack(W, X) -> int8        (the nibble/crumb SIMD operands, Table II)
    int8 x int8 -> int32 MXU    (pv.sdotp: sum-of-dot-product, eq. 2)
    kappa*acc + lambda          (integer batch-norm, eq. 3)
    (m * .) >> d, clip          (QNT/ACT, eq. 4)  [epilogue='int']

Mac&Load mapping — two pipeline modes (``pipeline=``):

  'off'            `pallas_call` grid pipelining double-buffers every
                   HBM->VMEM block copy, so the DMA of tile k+1 overlaps
                   the MXU work on tile k implicitly.
  'double_buffer'  the explicit Mac&Load analogue: the packed operands
                   stay in HBM (`memory_space=ANY`), the kernel owns two
                   VMEM slots per operand and issues manual async copies —
                   tile k+1's DMA starts before tile k's unpack+dot runs,
                   exactly how the paper's fused mac&load issues the next
                   load in the MAC's issue slot. The K grid dimension
                   disappears (the kernel loops K itself), so one grid
                   step owns the whole contraction.

Either way VMEM scratch plays the NN-RF role and the fused load never costs
an issue slot. OPEF -> 1 becomes "DMA fully hidden behind the MXU". Both
modes consume identical packed operands and accumulate in the same int32
order, so they are bit-exact against each other and the eager oracle
(tests/test_kernel_pipeline.py is the differential harness).

Tiling ("4x2 -> 4x4 MatMul layout" analogue): block sizes (bm, bn, bk) are
chosen so the double-buffered working set fits VMEM, with bm/bn multiples of
the MXU tile and bk a multiple of packing.CHUNK so chunk-planar unpacking
uses only static contiguous slices (no lane shuffles).

Grid is (M/bm, N/bn, K/bk) with K innermost ("arbitrary" semantics); the
int32 accumulator lives in a VMEM scratch buffer across K steps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.kernels.common import (LANE, SUBLANE_I8, EPILOGUE_DTYPES,
                                  apply_epilogue, check_pipeline,
                                  default_block, matmul_planes,
                                  segmented_bk, segmented_default_block)


def _qmatmul_kernel(x_ref, w_ref, kappa_ref, lam_ref, m_ref, o_ref, acc_ref,
                    *, nk: int, a_bits: int, a_signed: bool, w_bits: int,
                    d: int, out_bits: int, epilogue: str, scale: float):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += matmul_planes(
        x_ref[...], w_ref[...], a_bits, a_signed, w_bits)

    @pl.when(k_idx == nk - 1)
    def _epilogue():
        o_ref[...] = apply_epilogue(
            acc_ref[...], kappa_ref[...], lam_ref[...], m_ref[...],
            d=d, out_bits=out_bits, epilogue=epilogue, scale=scale,
            out_dtype=o_ref.dtype)


def _qmatmul_kernel_db(x_hbm, w_hbm, kappa_ref, lam_ref, m_ref, o_ref,
                       x_buf, w_buf, sems, acc_ref,
                       *, nk: int, bm: int, bn: int, bka: int, bkw: int,
                       a_bits: int, a_signed: bool, w_bits: int,
                       d: int, out_bits: int, epilogue: str, scale: float):
    """Double-buffered variant: x/w stay in HBM; two VMEM slots per
    operand; the DMA of K tile kk+1 is issued before tile kk's dot runs.

    x_buf: (2, bm, bka) int8 slots; w_buf: (2, bkw, bn) int8 slots;
    sems: (2, 2) DMA semaphores ([slot, operand]).
    """
    i = pl.program_id(0)
    j = pl.program_id(1)

    def x_dma(slot, kk):
        return pltpu.make_async_copy(
            x_hbm.at[pl.dslice(i * bm, bm), pl.dslice(kk * bka, bka)],
            x_buf.at[slot], sems.at[slot, 0])

    def w_dma(slot, kk):
        return pltpu.make_async_copy(
            w_hbm.at[pl.dslice(kk * bkw, bkw), pl.dslice(j * bn, bn)],
            w_buf.at[slot], sems.at[slot, 1])

    # warm-up: tile 0's copies are in flight before the loop starts
    x_dma(0, 0).start()
    w_dma(0, 0).start()
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(kk, carry):
        cur = jax.lax.rem(kk, 2)
        nxt = jax.lax.rem(kk + 1, 2)

        @pl.when(kk + 1 < nk)
        def _prefetch():        # next tile's DMA rides behind this dot
            x_dma(nxt, kk + 1).start()
            w_dma(nxt, kk + 1).start()

        x_dma(cur, kk).wait()
        w_dma(cur, kk).wait()
        acc_ref[...] += matmul_planes(
            x_buf[cur], w_buf[cur], a_bits, a_signed, w_bits)
        return carry

    jax.lax.fori_loop(0, nk, body, 0)
    o_ref[...] = apply_epilogue(
        acc_ref[...], kappa_ref[...], lam_ref[...], m_ref[...],
        d=d, out_bits=out_bits, epilogue=epilogue, scale=scale,
        out_dtype=o_ref.dtype)


def qmatmul_packed(x, w_packed, kappa, lam, m_mul, *,
                   a_bits: int, a_signed: bool, w_bits: int,
                   d: int, out_bits: int, epilogue: str = "int",
                   scale: float = 1.0,
                   block: Optional[tuple] = None,
                   out_dtype=None,
                   pipeline: str = "off",
                   interpret: bool = False):
    """Packed GEMM: x (M, K/pf_a) @ w (K/pf_w, N) with fused epilogue.

    K is the padded logical contraction dim (multiple of CHUNK); both
    operands are chunk-planar packed along K (bits==8 means unpacked).
    kappa/lam/m_mul are (N,) int32 epilogue params (ignored unless
    epilogue=='int'). ``pipeline`` selects the execution mode (module
    docstring): 'off' grids over K, 'double_buffer' loops K inside the
    kernel with manual two-slot DMA prefetch.

    ``interpret`` defaults to False (real Mosaic lowering); interpreter
    runs go through the explicit ``pallas_interpret`` backend of
    `repro.kernels.api` (tests pass interpret=True directly).
    """
    check_pipeline(pipeline)
    mdim = x.shape[0]
    pf_a, pf_w = packing.pack_factor(a_bits), packing.pack_factor(w_bits)
    k = x.shape[1] * pf_a
    assert w_packed.shape[0] * pf_w == k, (
        x.shape, w_packed.shape, a_bits, w_bits)
    n = w_packed.shape[1]
    if block is None:
        block = default_block(mdim, n, k, a_bits, w_bits)
    bm, bn, bk = block
    assert bk % packing.CHUNK == 0, (k, bk)
    assert mdim % bm == 0 and n % bn == 0, (mdim, n, bm, bn)
    if k % bk:
        # Ragged final K tile: zero-pad both packed operands to the next
        # bk multiple. Zero containers hold zero in every plane (signed or
        # not), so the extra MACs contribute nothing — exact in both
        # pipeline modes, and tuned bk choices aren't limited to divisors.
        k_fit = k + bk - k % bk
        x = jnp.pad(x, ((0, 0), (0, (k_fit - k) // pf_a)))
        w_packed = jnp.pad(w_packed, ((0, (k_fit - k) // pf_w), (0, 0)))
        k = k_fit
    nk = k // bk

    if out_dtype is None:
        out_dtype = EPILOGUE_DTYPES[epilogue]

    if pipeline == "double_buffer":
        kernel = functools.partial(
            _qmatmul_kernel_db, nk=nk, bm=bm, bn=bn, bka=bk // pf_a,
            bkw=bk // pf_w, a_bits=a_bits, a_signed=a_signed,
            w_bits=w_bits, d=d, out_bits=out_bits, epilogue=epilogue,
            scale=scale)
        return pl.pallas_call(
            kernel,
            grid=(mdim // bm, n // bn),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.ANY),
                pl.BlockSpec(memory_space=pltpu.ANY),
                pl.BlockSpec((1, bn), lambda i, j: (0, j)),
                pl.BlockSpec((1, bn), lambda i, j: (0, j)),
                pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((mdim, n), out_dtype),
            scratch_shapes=[
                pltpu.VMEM((2, bm, bk // pf_a), jnp.int8),
                pltpu.VMEM((2, bk // pf_w, bn), jnp.int8),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((bm, bn), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="qmatmul_db",
        )(x, w_packed, kappa.reshape(1, -1), lam.reshape(1, -1),
          m_mul.reshape(1, -1))

    kernel = functools.partial(
        _qmatmul_kernel, nk=nk, a_bits=a_bits, a_signed=a_signed,
        w_bits=w_bits, d=d, out_bits=out_bits, epilogue=epilogue, scale=scale)

    grid = (mdim // bm, n // bn, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk // pf_a), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // pf_w, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mdim, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="qmatmul",
    )(x, w_packed, kappa.reshape(1, -1), lam.reshape(1, -1),
      m_mul.reshape(1, -1))


def _qmatmul_segmented_kernel(code_ref, row_ref, x_ref, kappa_ref, lam_ref,
                              m_ref, w_hbm, o_ref, w_buf, sems, acc_ref,
                              *, nk: int, bk: int, widths, a_bits: int,
                              a_signed: bool, d: int, out_bits: int,
                              epilogue: str, scale: float, pipeline: str):
    """Mixed-operand GEMM tile (fine-grain mixed precision, 2307.01056).

    One grid step owns one (bm, LANE) output tile. The flat segmented
    buffer arrives as rows of LANE bytes; the weight panel for N-tile j
    starts at row ``row_ref[j]`` and is packed at width
    ``widths[code_ref[j]]`` — both scalars arrive via prefetch, so the
    kernel picks its DMA size and planar unpack width per tile with a
    `jax.lax.switch` over the (static) width set. K loops inside the
    kernel: panel-major layout makes tile kk of the panel the contiguous
    rows [row + kk*r, row + (kk+1)*r), r = bk / pf.
    """
    j = pl.program_id(1)
    code = code_ref[j]
    base = row_ref[j]
    pf_a = packing.pack_factor(a_bits)
    bka = bk // pf_a
    rows = [bk // packing.pack_factor(b) for b in widths]

    def dma(slot, kk, wi):
        r = rows[wi]
        # every panel and K tile spans a multiple of 32 rows (CHUNK / 4)
        start = pl.multiple_of(base + kk * r, SUBLANE_I8)
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(start, r)],
            w_buf.at[slot, pl.ds(0, r)], sems.at[slot])

    def start(slot, kk):
        jax.lax.switch(code, [
            (lambda wi=wi: dma(slot, kk, wi).start())
            for wi in range(len(widths))])

    def wait(slot, kk):
        jax.lax.switch(code, [
            (lambda wi=wi: dma(slot, kk, wi).wait())
            for wi in range(len(widths))])

    def tile_dot(slot, kk):
        xb = x_ref[:, pl.ds(pl.multiple_of(kk * bka, bka), bka)]

        def dot_at(wi):
            wb = w_buf[slot, :rows[wi], :]
            return matmul_planes(xb, wb, a_bits, a_signed, widths[wi])

        return jax.lax.switch(code, [
            (lambda wi=wi: dot_at(wi)) for wi in range(len(widths))])

    acc_ref[...] = jnp.zeros_like(acc_ref)
    if pipeline == "double_buffer":
        start(0, 0)

        def body(kk, carry):
            cur = jax.lax.rem(kk, 2)
            nxt = jax.lax.rem(kk + 1, 2)

            @pl.when(kk + 1 < nk)
            def _prefetch():    # next K tile's DMA rides behind this dot
                start(nxt, kk + 1)

            wait(cur, kk)
            acc_ref[...] += tile_dot(cur, kk)
            return carry
    else:

        def body(kk, carry):
            start(0, kk)
            wait(0, kk)
            acc_ref[...] += tile_dot(0, kk)
            return carry

    jax.lax.fori_loop(0, nk, body, 0)
    o_ref[...] = apply_epilogue(
        acc_ref[...], kappa_ref[...], lam_ref[...], m_ref[...],
        d=d, out_bits=out_bits, epilogue=epilogue, scale=scale,
        out_dtype=o_ref.dtype)


def qmatmul_segmented(x, w_flat, segmap, kappa, lam, m_mul, *,
                      k_logical: int, a_bits: int, a_signed: bool,
                      d: int, out_bits: int, epilogue: str = "int",
                      scale: float = 1.0,
                      block: Optional[tuple] = None,
                      out_dtype=None,
                      pipeline: str = "off",
                      interpret: bool = False):
    """Mixed-operand packed GEMM over a segmented weight container.

    x: (M, K_pad/pf_a) packed activations; w_flat: a flat
    `packing.pack_segmented` buffer (panel-major) whose N must be a
    CHUNK/LANE multiple — callers `packing.pad_segmented` first. The grid
    is (M/bm, N/LANE): each N tile is exactly one CHUNK-wide column
    panel, so a tile never straddles a segment boundary and its unpack
    width + panel offset come from the prefetched per-tile descriptor
    (`segmap.tile_table`). K loops inside the kernel with manual DMA from
    the flat buffer — 'off' copies/waits/dots serially per K tile,
    'double_buffer' rotates two slots with the next tile's copy issued
    behind the current dot. Both orders accumulate identically in int32,
    so they are bit-exact vs each other and vs running each segment
    through the uniform kernel and concatenating (the composition
    oracle, tests/test_mixed_operand_kernel.py).
    """
    check_pipeline(pipeline)
    mdim = x.shape[0]
    pf_a = packing.pack_factor(a_bits)
    k_pad = x.shape[1] * pf_a
    assert k_pad == packing.padded_size(k_logical), (k_pad, k_logical)
    n = segmap.n
    assert n % LANE == 0, n
    assert w_flat.ndim == 1 and w_flat.shape[0] == segmap.packed_bytes(
        k_logical), (w_flat.shape, segmap.runs)
    widths = segmap.widths()
    if block is None:
        bm, bk = segmented_default_block(mdim, k_pad, a_bits, widths)
    else:
        bm, _, bk = block
        bk = segmented_bk(k_pad, bk)
    assert mdim % bm == 0, (mdim, bm)
    nk = k_pad // bk
    nslots = 2 if pipeline == "double_buffer" else 1
    slot_rows = bk // min(packing.pack_factor(b) for b in widths)

    codes, offs = segmap.tile_table(k_logical)
    if out_dtype is None:
        out_dtype = EPILOGUE_DTYPES[epilogue]

    kernel = functools.partial(
        _qmatmul_segmented_kernel, nk=nk, bk=bk, widths=widths,
        a_bits=a_bits, a_signed=a_signed, d=d, out_bits=out_bits,
        epilogue=epilogue, scale=scale, pipeline=pipeline)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(mdim // bm, n // LANE),
        in_specs=[
            pl.BlockSpec((bm, k_pad // pf_a), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((1, LANE), lambda i, j, *_: (0, j)),
            pl.BlockSpec((1, LANE), lambda i, j, *_: (0, j)),
            pl.BlockSpec((1, LANE), lambda i, j, *_: (0, j)),
            pl.BlockSpec(memory_space=pltpu.ANY),
        ],
        out_specs=pl.BlockSpec((bm, LANE), lambda i, j, *_: (i, j)),
        scratch_shapes=[
            pltpu.VMEM((nslots, slot_rows, LANE), jnp.int8),
            pltpu.SemaphoreType.DMA((nslots,)),
            pltpu.VMEM((bm, LANE), jnp.int32),
        ])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mdim, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="qmatmul_segmented",
    )(jnp.asarray(codes, jnp.int32), jnp.asarray(offs // LANE, jnp.int32),
      x, kappa.reshape(1, -1), lam.reshape(1, -1), m_mul.reshape(1, -1),
      w_flat.reshape(-1, LANE))

"""Shared machinery for the packed sub-byte Pallas kernels.

Both integer kernels (qmatmul: packed GEMM, qconv: fused implicit-GEMM
conv) run the same per-tile pipeline from the paper:

    unpack(W, X) -> int8        (nibble/crumb SIMD operands, Table II)
    int8 x int8 -> int32 MXU    (pv.sdotp: sum-of-dot-product, eq. 2)
    kappa*acc + lambda          (integer batch-norm, eq. 3)
    (m * .) >> d, clip          (QNT/ACT, eq. 4)  [epilogue='int']

This module holds the pieces they share: the chunk-planar plane re-cut
(`recut_rows`), the planar sub-byte dot product (`matmul_planes`), the
three epilogues (`apply_epilogue`, int / dequant / raw), and block-shape
selection for both the GEMM grid (`default_block`) and the conv grid
(`conv_default_block`).

Field extraction is elementwise (shift+mask on the containers, widened to
int32), so a plane of a packed block keeps the block's shape; planes of X
pair with planes of W because both sides use the same chunk-planar
logical K order and integer accumulation is order-invariant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.core.quantize import requantize_shift

# int8 MXU-friendly minimum tile: (32, 128); accumulate in int32.
LANE = 128
SUBLANE_I8 = 32

EPILOGUES = ("int", "dequant", "raw")
EPILOGUE_DTYPES = {"int": jnp.int8, "dequant": jnp.bfloat16, "raw": jnp.int32}

# Software-pipeline execution modes for the Pallas kernels — the Mac&Load
# analogue knob. 'off' leans on the pallas_call grid pipeliner alone;
# 'double_buffer' keeps the packed operands in HBM and issues manual
# double-buffered async copies so the next K tile's (or receptive-field
# tap's) DMA overlaps the current tile's unpack+dot explicitly.
PIPELINE_MODES = ("off", "double_buffer")


def check_pipeline(mode: str) -> str:
    if mode not in PIPELINE_MODES:
        raise ValueError(
            f"unknown pipeline mode {mode!r}; expected one of "
            f"{PIPELINE_MODES}")
    return mode


def round_up(x: int, mult: int) -> int:
    return x + (-x) % mult


def recut_rows(planes, pf_to: int):
    """Re-cut the chunk-planar planes of a K-leading operand into ``pf_to``.

    Plane p of a pf-packed operand holds, for chunk c, the logical run
    ``c*CHUNK + p*R + [0, R)`` (R = CHUNK // pf) at rows ``c*R + [0, R)``.
    The re-cut planes hold the same runs at R' = CHUNK // pf_to. It is
    built from static row slices and a concatenate along rows: every piece
    is at least 32 rows (one int8 sublane tile), which Mosaic lowers, where
    a reshape that splits the lane axis is refused.
    """
    pf_from = len(planes)
    if pf_from == pf_to:
        return planes
    run_from, run_to = packing.CHUNK // pf_from, packing.CHUNK // pf_to
    piece = min(run_from, run_to)
    n_chunks = planes[0].shape[0] // run_from
    out = []
    for q in range(pf_to):
        parts = []
        for c in range(n_chunks):
            for t in range(q * run_to, (q + 1) * run_to, piece):
                p, j = divmod(t, run_from)
                r = c * run_from + j
                parts.append(planes[p][r:r + piece])
        out.append(jnp.concatenate(parts, axis=0))
    return out


def matmul_planes(x_block, w_block, a_bits, a_signed, w_bits):
    """Planar sub-byte dot product -> (bm, bn) int32 partial sum.

    x_block: (bm, bk/pf_a) packed containers, K along axis 1.
    w_block: (bk/pf_w, bn) packed containers, K along axis 0.
    Both sides must share the chunk-planar logical K order. The
    activation planes are used as unpacked (K on lanes); the weight planes
    (K on sublanes) are re-cut to the activation's plane layout.
    """
    x_planes = packing.unpack_planes(x_block, a_bits, a_signed)
    w_planes = recut_rows(
        packing.unpack_planes(w_block, w_bits, True),  # weights signed
        len(x_planes))

    acc = None
    for xp, wp in zip(x_planes, w_planes):
        part = jax.lax.dot_general(
            xp, wp, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = part if acc is None else acc + part
    return acc


def apply_epilogue(acc, kappa, lam, m_mul, *, d: int, out_bits: int,
                   epilogue: str, scale: float, out_dtype):
    """Fused epilogue on an int32 accumulator tile.

    'int':     eq.(3) integer BN (per out-channel) then eq.(4) requant+clip.
    'dequant': float rescale (QAT-style inspection path).
    'raw':     int32 accumulators, no epilogue.
    kappa/lam/m_mul broadcast against acc along the lane (out-channel) dim.
    """
    if epilogue == "int":
        phi_p = acc * kappa + lam
        y = requantize_shift(phi_p, m_mul, d)
        hi = packing.int_range(out_bits, False)[1]
        return jnp.clip(y, 0, hi).astype(out_dtype)
    if epilogue == "dequant":
        return (acc.astype(jnp.float32) * scale).astype(out_dtype)
    return acc.astype(out_dtype)  # 'raw'


def gemm_working_set(bm, bn, bk, a_bits, w_bits) -> int:
    """VMEM bytes a (bm, bn, bk) GEMM tile needs with every copy
    double-buffered.

    Counts 2x residency for *all* pipelined blocks — the packed activation
    and weight K tiles (grid pipeliner in 'off' mode, the manual DMA slots
    in 'double_buffer' mode: same two-buffer footprint either way), the
    output tile, and the three epilogue-parameter blocks — plus the
    single int32 accumulator scratch that persists across K steps. The
    pre-fix check under-counted (single-buffered out block, no epilogue
    params), so an autotuned pipelined tile at the budget edge could
    overflow VMEM.
    """
    pf_a, pf_w = packing.pack_factor(a_bits), packing.pack_factor(w_bits)
    x_b = bm * (bk // pf_a)
    w_b = (bk // pf_w) * bn
    params = 3 * bn * 4                # kappa/lam/m blocks
    out = bm * bn * 4                  # out tile (<= int32)
    acc = bm * bn * 4                  # int32 accumulator scratch
    return 2 * (x_b + w_b + params + out) + acc


def default_block(m, n, k, a_bits, w_bits,
                  vmem_budget: int = 8 * 1024 * 1024):
    """Pick GEMM (bm, bn, bk): MXU-aligned, chunk-aligned, VMEM-bounded.

    The paper's 4x2 -> 4x4 register-tiling exploration becomes this block
    shape selection; benchmarks/fig8 measures the ladder. The fit check
    (`gemm_working_set`) counts both buffers of every double-buffered
    copy, so the same tile is safe in either pipeline mode.
    """
    def align(v, unit):
        return max(unit, (v // unit) * unit)

    bm = align(min(m, 256), SUBLANE_I8)
    bn = align(min(n, 512), LANE)
    bk = align(min(k, 1024), packing.CHUNK)

    def fits(bm, bn, bk):
        return gemm_working_set(bm, bn, bk, a_bits, w_bits) <= vmem_budget

    while not fits(bm, bn, bk) and bk > packing.CHUNK:
        bk = align(bk // 2, packing.CHUNK)
    while not fits(bm, bn, bk) and bn > LANE:
        bn = align(bn // 2, LANE)
    while not fits(bm, bn, bk) and bm > SUBLANE_I8:
        bm = align(bm // 2, SUBLANE_I8)
    return bm, bn, bk


def segmented_bk(k_pad: int, target: int) -> int:
    """Largest CHUNK-multiple divisor of ``k_pad`` that is <= ``target``.

    The mixed-operand kernel loops K inside the grid step with manual DMA
    at per-width static sizes, so its K tile must divide the padded
    contraction exactly (no ragged tail inside the kernel — raggedness is
    handled by container zero-padding at the wrapper).
    """
    if k_pad % packing.CHUNK:
        raise ValueError(f"k_pad={k_pad} not a CHUNK multiple")
    c = k_pad // packing.CHUNK
    best = 1
    for t in range(1, c + 1):
        if c % t == 0 and t * packing.CHUNK <= target:
            best = t
    return best * packing.CHUNK


def segmented_working_set(bm, k_pad, bk, a_bits, widths) -> int:
    """VMEM bytes of one mixed-operand GEMM tile.

    The activation block holds the full packed K row panel (K loops inside
    the kernel); the weight side is two manual-DMA slots sized for the
    widest width present (widest => most container bytes per K tile);
    epilogue params and the out tile are grid-pipelined (2x); the int32
    accumulator persists across the K loop.
    """
    pf_a = packing.pack_factor(a_bits)
    pf_min = min(packing.pack_factor(b) for b in widths)
    x_b = bm * (k_pad // pf_a)
    w_slots = 2 * (bk // pf_min) * LANE
    params = 3 * LANE * 4
    out = bm * LANE * 4
    acc = bm * LANE * 4
    return 2 * (x_b + params + out) + w_slots + acc


def segmented_default_block(m, k_pad, a_bits, widths,
                            vmem_budget: int = 8 * 1024 * 1024):
    """Pick (bm, bk) for the mixed-operand kernel (bn is pinned to LANE:
    one N tile == one CHUNK column panel, so a tile never straddles a
    segment boundary)."""
    def align(v, unit):
        return max(unit, (v // unit) * unit)

    bm = align(min(m, 256), SUBLANE_I8)
    bk = segmented_bk(k_pad, min(k_pad, 1024))

    def fits(bm, bk):
        return segmented_working_set(
            bm, k_pad, bk, a_bits, widths) <= vmem_budget

    while not fits(bm, bk) and bk > packing.CHUNK:
        bk = segmented_bk(k_pad, bk // 2)
    while not fits(bm, bk) and bm > SUBLANE_I8:
        bm //= 2
    return bm, bk


def conv_working_set(bho, bn, *, ho, wo, cout, fh, fw, cin_pad, stride,
                     a_bits, w_bits):
    """VMEM bytes the fused conv kernel needs for a (bho, bn) tile.

    Counts the double-buffered pipeline blocks (full packed image, weight
    panel, epilogue params, output tile) plus the single-buffered im2col
    scratch and the int32 accumulator. Uses a safe upper bound for the
    padded image extent (the wrapper pads rows so every tile's receptive
    field is in-bounds).
    """
    pf_a = packing.pack_factor(a_bits)
    pf_w = packing.pack_factor(w_bits)
    cp = cin_pad // pf_a
    kp = fh * fw * cin_pad // pf_w
    n_tiles = -(-ho // bho)
    # upper bounds of the wrapper's stride-phase image extents
    hp = round_up(n_tiles * bho * stride + fh, stride)
    wp = round_up(wo * stride + fw, 8 * stride)
    bm = bho * wo
    img = hp * wp * cp                        # packed int8 image block
    w_b = kp * bn                             # packed weight panel
    params = 3 * bn * 4                       # kappa/lam/m blocks
    out = bm * bn * 4                         # out tile (<= int32)
    col = bm * fh * fw * cp                   # im2col VMEM scratch (NN-RF)
    acc = bm * bn * 4                         # int32 accumulator
    return 2 * (img + w_b + params + out) + col + acc


def conv_default_block(n, ho, wo, cout, fh, fw, cin_pad, stride,
                       a_bits, w_bits, vmem_budget: int = 8 * 1024 * 1024):
    """Pick the fused conv tile (bho, bn): the M dim of the implicit GEMM
    is the flattened output-pixel axis N*Ho*Wo, tiled as (batch image) x
    (bho output rows x all Wo columns); the N dim is Cout tiled by bn.

    Invariants (property-tested): bn is a LANE multiple, the per-tap
    contraction run cin_pad is a CHUNK multiple (so every tap of the
    im2col scratch stays chunk-planar aligned), ceil(ho/bho) tiles cover a
    ragged Ho, and the whole working set fits `vmem_budget`.
    """
    if cin_pad % packing.CHUNK:
        raise ValueError(f"cin_pad={cin_pad} not a CHUNK multiple")
    bn = max(LANE, min(round_up(cout, LANE), 4 * LANE))
    # target bm = bho*wo around 256 output pixels, at least one row
    bho = max(1, min(ho, 256 // max(wo, 1)))

    def fits(bho, bn):
        return conv_working_set(
            bho, bn, ho=ho, wo=wo, cout=cout, fh=fh, fw=fw,
            cin_pad=cin_pad, stride=stride, a_bits=a_bits,
            w_bits=w_bits) <= vmem_budget

    while not fits(bho, bn) and bho > 1:
        bho = max(1, bho // 2)
    while not fits(bho, bn) and bn > LANE:
        bn = max(LANE, bn // 2 // LANE * LANE)     # stays a LANE multiple
    if not fits(bho, bn):
        raise ValueError(
            f"fused conv tile (bho=1, bn={LANE}) exceeds the VMEM budget "
            f"for image ho={ho} wo={wo} cin_pad={cin_pad}; use the im2col "
            f"fallback (backend='xla') for images this large")
    return bho, bn

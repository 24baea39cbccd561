"""Sub-byte pack/unpack — the storage layer of the XpulpNN reproduction.

The paper's `nibble` (4-bit) and `crumb` (2-bit) SIMD operands live packed in
32-bit registers; on TPU we store them packed in int8 *containers* in HBM and
unpack inside the Pallas kernel (VREG-level shifts), mirroring the paper's
"no unpack overhead when the ISA supports it natively" argument: unpacking
costs shift+mask ALU work overlapped with the MXU, not extra memory traffic.

Layout: **chunk-planar packing** along the reduction (K) axis.  Within each
chunk of ``CHUNK = 128`` logical elements, the packed byte ``j`` of the chunk
holds logical elements ``j, j+64`` (4-bit) or ``j, j+32, j+64, j+96`` (2-bit)
in its low→high bit-fields.  Planar layout means the kernel unpacks a packed
tile into ``pack_factor`` *contiguous* sub-tiles (cheap static slices — no
lane interleave), and because integer accumulation is order-invariant the
matmul can consume the sub-tiles in planar order as long as the *other*
operand is sliced with the same chunk-planar order.  This is the TPU analogue
of Marlin-style permuted weight packing.

**Segmented containers** (fine-grain mixed precision — Nadalini et al.
2307.01056 on the same cluster family): a `SegmentMap` partitions the
*output-feature* (N) axis into ordered runs, each packed at its own w_bits.
`pack_segmented` lays the runs out in one contiguous int8 buffer,
column-panel-major within each run (panels of CHUNK output channels, each
panel's packed K rows contiguous), so a kernel N-tile of CHUNK channels is
one contiguous byte range addressed by the per-segment offset table
(`SegmentMap.seg_offsets` / `SegmentMap.tile_table`). Interior run
boundaries must be CHUNK-aligned so no kernel N-tile ever straddles two
widths; only the final run may end ragged.

All functions are pure jnp and usable both on host (packing checkpoints) and
inside kernels (unpacking blocks).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
import numpy as np

# Logical elements per packing chunk. The packed chunk is CHUNK // pack_factor
# containers: 64 bytes for 4-bit, 32 bytes for 2-bit — both sublane-aligned
# for int8 TPU tiles. K dims must be padded to a multiple of CHUNK.
CHUNK = 128

# NOTE: unsigned 8-bit caps at 127, not 255 — containers are int8 and
# XLA's dot_general has no mixed-signedness mode (unlike pv.sdotusp on the
# paper's ISA), so byte activations sacrifice 1 bit of range. The paper's
# focus (nibble/crumb) is unaffected. See DESIGN.md assumption changes.
_INT_INFO = {
    8: (-128, 127, 0, 127),
    4: (-8, 7, 0, 15),
    2: (-2, 1, 0, 3),
}


def pack_factor(bits: int) -> int:
    if bits not in (8, 4, 2):
        raise ValueError(f"unsupported bitwidth {bits}")
    return 8 // bits


def int_range(bits: int, signed: bool) -> tuple[int, int]:
    lo_s, hi_s, lo_u, hi_u = _INT_INFO[bits]
    return (lo_s, hi_s) if signed else (lo_u, hi_u)


def _check_last_axis(x, bits):
    if x.shape[-1] % CHUNK != 0:
        raise ValueError(
            f"packing axis ({x.shape[-1]}) must be a multiple of CHUNK={CHUNK}"
        )


def check_range(x, bits: int, signed: bool = True):
    """Assert every value of ``x`` fits the ``bits``-wide integer grid.

    ``pack`` keeps only the low ``bits`` bits, so an out-of-range value is
    silently truncated into a *different* in-range value — a corrupt
    artifact with no error. Host-side packing paths call this first; it
    forces concrete values (``np.asarray``) and therefore must not be used
    under jit/vmap tracing.
    """
    lo, hi = int_range(bits, signed)
    xv = np.asarray(x)
    if xv.size == 0:
        return
    saw_lo, saw_hi = int(xv.min()), int(xv.max())
    if saw_lo < lo or saw_hi > hi:
        raise ValueError(
            f"pack: values outside the {'signed' if signed else 'unsigned'} "
            f"{bits}-bit range [{lo}, {hi}] (saw min={saw_lo}, "
            f"max={saw_hi}); packing would silently truncate — "
            "quantize/clip first")


def pack(x, bits: int, axis: int = -1, *, assert_range: bool = False,
         signed: bool = True):
    """Pack sub-byte integer values (stored as int8) into int8 containers.

    ``x`` values must already be in the signed/unsigned range of ``bits``
    (packing only keeps the low ``bits`` bits, so signed and unsigned share
    one packer).  Packing is chunk-planar along ``axis``.

    ``assert_range=True`` raises instead of truncating out-of-range values
    (``signed`` selects the grid checked). Host/eager paths only — the check
    needs concrete values.
    """
    if assert_range:
        check_range(x, bits, signed)
    if bits == 8:
        return x.astype(jnp.int8)
    pf = pack_factor(bits)
    x = jnp.moveaxis(x, axis, -1)
    _check_last_axis(x, bits)
    *lead, k = x.shape
    sub = CHUNK // pf  # packed bytes per chunk
    # (..., n_chunks, pf, sub): plane p holds logical j = p*sub + j_in_plane
    planes = x.reshape(*lead, k // CHUNK, pf, sub).astype(jnp.int32)
    mask = (1 << bits) - 1
    out = jnp.zeros((*lead, k // CHUNK, sub), dtype=jnp.int32)
    for p in range(pf):
        out = out | ((planes[..., p, :] & mask) << (bits * p))
    out = out.reshape(*lead, k // pf).astype(jnp.int8)
    return jnp.moveaxis(out, -1, axis)


def unpack(p, bits: int, signed: bool, axis: int = -1):
    """Inverse of :func:`pack`; returns int8 values in the sub-byte range."""
    if bits == 8:
        return p.astype(jnp.int8)
    pf = pack_factor(bits)
    p = jnp.moveaxis(p, axis, -1)
    *lead, kp = p.shape
    sub = CHUNK // pf
    if kp % sub != 0:
        raise ValueError(f"packed axis ({kp}) not a multiple of {sub}")
    chunks = p.reshape(*lead, kp // sub, sub)
    planes = []
    for pl in range(pf):
        planes.append(_extract_field(chunks, bits, pl, signed))
    out = jnp.stack(planes, axis=-2)  # (..., n_chunks, pf, sub)
    out = out.reshape(*lead, kp * pf)
    return jnp.moveaxis(out, -1, axis)


def _extract_field(container, bits: int, plane: int, signed: bool):
    """Extract bit-field ``plane`` from int8 containers, with sign/zero ext.

    The shifts run on int32: Mosaic (TPU v5e) cannot lower int8 shifts, so
    the container is sign-extended to 32 bits first and the field is
    narrowed back to int8 at the end.
    """
    c = container.astype(jnp.int8).astype(jnp.int32)
    shift = bits * plane
    if signed:
        # left-align the field then arithmetic-shift right to sign-extend
        left = 32 - bits - shift
        return ((c << left) >> (32 - bits)).astype(jnp.int8)
    mask = (1 << bits) - 1
    return ((c >> shift) & mask).astype(jnp.int8)


def unpack_planes(p_block, bits: int, signed: bool):
    """Kernel-side unpack: split a packed block into ``pf`` planar sub-blocks.

    ``p_block`` has its *packed* K dim as the leading axis and must cover a
    whole number of chunks.  Returns a list of ``pf`` arrays, each with
    leading dim ``p_block.shape[0]`` (one plane), such that plane ``p`` holds
    logical elements ``chunk*CHUNK + p*sub + j``.  Consuming the planes in
    order with the matching planar slices of the other operand reproduces the
    exact integer matmul (accumulation order is irrelevant for ints).
    """
    if bits == 8:
        return [p_block.astype(jnp.int8)]
    pf = pack_factor(bits)
    return [_extract_field(p_block, bits, pl, signed) for pl in range(pf)]


def planar_perm(k: int, bits: int) -> np.ndarray:
    """Permutation mapping *planar order* position -> logical K index.

    After unpacking with :func:`unpack_planes`, concatenating the planes of
    every chunk yields elements in planar order: for chunk c and plane p the
    run ``c*CHUNK + p*sub + [0..sub)``. The *other* (unpacked) matmul operand
    must be gathered with this permutation so both sides agree. When both
    operands are packed with the same chunk-planar scheme no permutation is
    needed anywhere — planes pair up one-to-one.
    """
    if bits == 8:
        return np.arange(k)
    pf = pack_factor(bits)
    sub = CHUNK // pf
    idx = np.arange(k).reshape(k // CHUNK, pf, sub)
    return idx.reshape(-1)


def pad_to_chunk(x, axis: int = -1, value: int = 0):
    """Pad ``axis`` up to a CHUNK multiple (zero padding == zero MACs)."""
    size = x.shape[axis]
    pad = (-size) % CHUNK
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def padded_size(k: int) -> int:
    return k + ((-k) % CHUNK)


# ------------------------------------------------- segmented containers ---

# Candidate container widths, widest first — the canonical order
# `SegmentMap.widths()` and the mixed-operand kernel's branch table use.
WIDTHS = (8, 4, 2)


@dataclasses.dataclass(frozen=True)
class SegmentMap:
    """Ordered ``(n_start, n_end, w_bits)`` runs over the output-feature axis.

    Invariants (validated loudly — a malformed map would silently corrupt a
    packed artifact):

    * runs are non-empty, start at 0, and tile N contiguously (no gaps, no
      overlaps: each run starts where the previous ended);
    * every *interior* boundary is a multiple of CHUNK, so a kernel N-tile
      of CHUNK output channels never straddles two widths (only the final
      run may end ragged);
    * widths come from `WIDTHS` ({8, 4, 2}).

    The map is hashable (rides inside frozen plan rules / QuantConfigs) and
    JSON-serializable via `to_json_obj`/`from_json_obj`.
    """

    runs: Tuple[Tuple[int, int, int], ...]

    def __post_init__(self):
        runs = tuple((int(s), int(e), int(b)) for s, e, b in self.runs)
        object.__setattr__(self, "runs", runs)
        if not runs:
            raise ValueError("SegmentMap: empty run list")
        pos = 0
        for i, (s, e, b) in enumerate(runs):
            if b not in WIDTHS:
                raise ValueError(
                    f"SegmentMap: run {i} has unsupported width {b}; "
                    f"expected one of {WIDTHS}")
            if s != pos:
                kind = "overlaps" if s < pos else "leaves a gap after"
                raise ValueError(
                    f"SegmentMap: run {i} [{s}, {e}) {kind} the previous "
                    f"run (expected n_start={pos}); runs must tile N "
                    "contiguously in order")
            if e <= s:
                raise ValueError(
                    f"SegmentMap: run {i} [{s}, {e}) is empty or reversed")
            if i + 1 < len(runs) and e % CHUNK:
                raise ValueError(
                    f"SegmentMap: interior boundary {e} (run {i}) is not a "
                    f"multiple of CHUNK={CHUNK}; a kernel N-tile would "
                    "straddle two container widths (only the final run may "
                    "end ragged)")
            pos = e

    # ------------------------------------------------------- structure ---

    @staticmethod
    def uniform(n: int, bits: int) -> "SegmentMap":
        return SegmentMap(((0, int(n), int(bits)),))

    @property
    def n(self) -> int:
        return self.runs[-1][1]

    @property
    def is_uniform(self) -> bool:
        return len(self.runs) == 1

    def widths(self) -> Tuple[int, ...]:
        """Distinct run widths, widest first (the kernel's branch order)."""
        present = {b for _, _, b in self.runs}
        return tuple(b for b in WIDTHS if b in present)

    def run_lengths(self) -> Tuple[int, ...]:
        return tuple(e - s for s, e, _ in self.runs)

    # ------------------------------------------------- byte accounting ---

    def _run_bytes(self, run, k: int) -> int:
        s, e, b = run
        return (padded_size(k) // pack_factor(b)) * (e - s)

    def packed_bytes(self, k: int) -> int:
        """Total container bytes for a (K=k, N=self.n) weight matrix —
        exactly ``sum(run_len * K_pad * bits / 8)``."""
        return sum(self._run_bytes(r, k) for r in self.runs)

    def seg_offsets(self, k: int) -> Tuple[int, ...]:
        """Byte offset of each run's container block in the flat buffer."""
        offs, off = [], 0
        for r in self.runs:
            offs.append(off)
            off += self._run_bytes(r, k)
        return tuple(offs)

    def tile_table(self, k: int):
        """Per-N-tile kernel descriptors: ``(codes, offsets)`` int32 arrays,
        one entry per CHUNK-wide output-channel tile.

        ``codes[j]`` indexes `widths()` (the tile's unpack-width branch);
        ``offsets[j]`` is the byte offset of the tile's contiguous column
        panel in the flat buffer. Requires an N already padded to CHUNK
        (`pad_segmented`) — a ragged tail panel has no full-width tile.
        """
        if self.n % CHUNK:
            raise ValueError(
                f"tile_table: N={self.n} is not a CHUNK multiple; pad the "
                "container first (pad_segmented)")
        widths = self.widths()
        kp = padded_size(k)
        codes, offs = [], []
        off = 0
        for s, e, b in self.runs:
            rows = kp // pack_factor(b)
            for _ in range(s, e, CHUNK):
                codes.append(widths.index(b))
                offs.append(off)
                off += rows * CHUNK
        return (np.asarray(codes, np.int32), np.asarray(offs, np.int32))

    def pad_to(self, n_pad: int) -> "SegmentMap":
        """Extend the final run to ``n_pad`` (zero-channel padding)."""
        if n_pad < self.n:
            raise ValueError(f"pad_to: {n_pad} < N={self.n}")
        if n_pad == self.n:
            return self
        s, _, b = self.runs[-1]
        return SegmentMap(self.runs[:-1] + ((s, int(n_pad), b),))

    # ------------------------------------------------------------ json ---

    def to_json_obj(self):
        return [[s, e, b] for s, e, b in self.runs]

    @staticmethod
    def from_json_obj(obj) -> "SegmentMap":
        return SegmentMap(tuple((int(s), int(e), int(b))
                                for s, e, b in obj))


def _iter_panels(length: int):
    """(panel_start, panel_width) pairs tiling ``length`` by CHUNK."""
    for p0 in range(0, length, CHUNK):
        yield p0, min(CHUNK, length - p0)


def pack_segmented(w_hat, segmap: SegmentMap, *, assert_range: bool = False):
    """Pack int8 weight values (..., K, N) into one flat segmented buffer.

    Each run ``(s, e, b)`` of ``segmap`` packs columns [s, e) chunk-planar
    along K at width ``b`` (K zero-padded to CHUNK), then flattens
    column-panel-major: panels of CHUNK output channels, each panel's
    packed rows contiguous. Returns an int8 array (..., total_bytes) with
    ``total_bytes == segmap.packed_bytes(K)``; per-run offsets are
    `segmap.seg_offsets(K)`.
    """
    n = w_hat.shape[-1]
    if n != segmap.n:
        raise ValueError(
            f"pack_segmented: weight N={n} != SegmentMap N={segmap.n}")
    lead = w_hat.shape[:-2]
    parts = []
    for s, e, b in segmap.runs:
        seg = w_hat[..., s:e]
        if assert_range:
            check_range(seg, b, True)
        packed = pack(pad_to_chunk(seg, axis=-2), b, axis=-2,
                      signed=True)                     # (..., kp/pf, e-s)
        rows = packed.shape[-2]
        for p0, pw in _iter_panels(e - s):
            parts.append(packed[..., p0:p0 + pw].reshape(*lead, rows * pw))
    return jnp.concatenate(parts, axis=-1).astype(jnp.int8)


def segment_packed(buf, segmap: SegmentMap, index: int, k: int):
    """Run ``index``'s uniform container view: (..., K_pad/pf_b, run_len).

    The exact array `pack` would have produced for that column range —
    the composition oracle and the segment-looping backends consume these.
    """
    s, e, b = segmap.runs[index]
    rows = padded_size(k) // pack_factor(b)
    off = segmap.seg_offsets(k)[index]
    lead = buf.shape[:-1]
    parts, pos = [], off
    for _, pw in _iter_panels(e - s):
        blk = buf[..., pos:pos + rows * pw]
        parts.append(blk.reshape(*lead, rows, pw))
        pos += rows * pw
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate(parts, axis=-1)


def unpack_segmented(buf, segmap: SegmentMap, k: int):
    """Inverse of :func:`pack_segmented`: (..., K_pad, N) int8 values.

    Returns the CHUNK-padded K extent (slice ``[..., :k, :]`` for the
    logical matrix), matching `pack`'s padding convention.
    """
    outs = [unpack(segment_packed(buf, segmap, i, k), b, True, axis=-2)
            for i, (_, _, b) in enumerate(segmap.runs)]
    return jnp.concatenate(outs, axis=-1)


def pad_segmented(buf, segmap: SegmentMap, k: int):
    """Zero-pad the ragged tail panel to a full CHUNK of output channels.

    Kernel callers only: the artifact stays exact-bytes; the mixed-operand
    kernel needs every N-tile to be a full contiguous CHUNK-wide panel.
    Returns ``(buf_padded, segmap_padded)`` (identity when N is aligned).
    """
    n = segmap.n
    n_pad = padded_size(n)
    if n_pad == n:
        return buf, segmap
    _, _, b = segmap.runs[-1]
    rows = padded_size(k) // pack_factor(b)
    rem = n - (n // CHUNK) * CHUNK          # ragged tail panel width
    tail_bytes = rows * rem
    lead = buf.shape[:-1]
    head = buf[..., :buf.shape[-1] - tail_bytes]
    tail = buf[..., buf.shape[-1] - tail_bytes:].reshape(*lead, rows, rem)
    widths = [(0, 0)] * tail.ndim
    widths[-1] = (0, CHUNK - rem)
    tail = jnp.pad(tail, widths).reshape(*lead, rows * CHUNK)
    return (jnp.concatenate([head, tail], axis=-1),
            segmap.pad_to(n_pad))

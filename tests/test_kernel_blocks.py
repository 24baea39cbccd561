"""Property tests for the kernels' BlockSpec selection: the chosen tile
always fits the VMEM budget and is MXU/chunk aligned (the paper's 4x4-
layout feasibility question at the VMEM level). Covers both the GEMM
selector (`default_block`) and the fused-conv selector
(`conv_default_block`), whose grid must also cover ragged Ho edges."""
import pytest

from conftest import hypothesis_api

# guarded: property tests skip (not hard-fail) without hypothesis
given, settings, st = hypothesis_api()

from repro.core import packing
from repro.kernels.common import (LANE, SUBLANE_I8, conv_default_block,
                                  conv_working_set, gemm_working_set)
from repro.kernels.qmatmul import default_block

BUDGET = 8 * 1024 * 1024


@given(m=st.integers(32, 8192), n=st.integers(128, 16384),
       k=st.integers(128, 32768),
       a_bits=st.sampled_from([8, 4, 2]), w_bits=st.sampled_from([8, 4, 2]))
@settings(max_examples=100, deadline=None)
def test_default_block_fits_vmem(m, n, k, a_bits, w_bits):
    bm, bn, bk = default_block(m, n, k, a_bits, w_bits, BUDGET)
    assert gemm_working_set(bm, bn, bk, a_bits, w_bits) <= BUDGET
    assert bk % packing.CHUNK == 0
    assert bm >= 32 and bn >= 128


def test_gemm_working_set_counts_double_buffered_copies():
    """Regression: the fit check must count 2x residency for every
    pipelined block (x/w K tiles, out tile, epilogue params), not just
    the operand tiles — the pre-fix formula under-counted by the second
    out-block buffer plus both param-block buffers, so a tile at the
    budget edge could overflow VMEM once double-buffered."""
    bm, bn, bk, a_bits, w_bits = 256, 512, 1024, 8, 8
    work = gemm_working_set(bm, bn, bk, a_bits, w_bits)
    under = (2 * (bm * bk + bk * bn)      # operands only, double-buffered
             + 2 * bm * bn * 4)           # old formula: acc + single out
    assert work > under
    missed = work - under                 # second out buffer + 2x params
    assert missed == bm * bn * 4 + 2 * 3 * bn * 4


def test_default_block_boundary_at_budget():
    """At a budget exactly equal to the chosen tile's working set the
    selector keeps the tile; one byte less forces a strictly smaller tile
    (the fit check is the working set, with no hidden slack)."""
    m, n, k, a_bits, w_bits = 256, 512, 2048, 4, 4
    blk = default_block(m, n, k, a_bits, w_bits, BUDGET)
    exact = gemm_working_set(*blk, a_bits, w_bits)
    assert default_block(m, n, k, a_bits, w_bits, exact) == blk
    smaller = default_block(m, n, k, a_bits, w_bits, exact - 1)
    assert smaller != blk
    assert gemm_working_set(*smaller, a_bits, w_bits) <= exact - 1
    # the floor tile is never shrunk below MXU alignment
    assert smaller[0] >= SUBLANE_I8 and smaller[1] >= LANE
    assert smaller[2] % packing.CHUNK == 0


def _check_conv_block(ho, wo, cout, fh, fw, cin_pad, stride, a_bits, w_bits):
    bho, bn = conv_default_block(1, ho, wo, cout, fh, fw, cin_pad, stride,
                                 a_bits, w_bits, BUDGET)
    # MXU/chunk alignment: lane dim a LANE multiple, per-tap contraction
    # run (and hence every im2col scratch column run) CHUNK-aligned
    assert bn % LANE == 0 and bn >= LANE
    assert cin_pad % packing.CHUNK == 0
    # ragged Ho coverage: ceil(ho/bho) tiles cover every output row with
    # less than one tile of overshoot
    assert 1 <= bho <= ho
    n_tiles = -(-ho // bho)
    assert n_tiles * bho >= ho
    assert n_tiles * bho - ho < bho
    # the working set the wrapper will actually allocate fits the budget
    assert conv_working_set(
        bho, bn, ho=ho, wo=wo, cout=cout, fh=fh, fw=fw, cin_pad=cin_pad,
        stride=stride, a_bits=a_bits, w_bits=w_bits) <= BUDGET
    return bho, bn


@given(ho=st.integers(1, 64), wo=st.integers(1, 64),
       cout=st.integers(1, 1024),
       fh=st.sampled_from([1, 3, 5, 7]), fw=st.sampled_from([1, 3, 5, 7]),
       n_chunks=st.integers(1, 3), stride=st.sampled_from([1, 2]),
       a_bits=st.sampled_from([8, 4, 2]), w_bits=st.sampled_from([8, 4, 2]))
@settings(max_examples=100, deadline=None)
def test_conv_default_block_fits_vmem(ho, wo, cout, fh, fw, n_chunks,
                                      stride, a_bits, w_bits):
    cin_pad = n_chunks * packing.CHUNK
    try:
        _check_conv_block(ho, wo, cout, fh, fw, cin_pad, stride, a_bits,
                          w_bits)
    except ValueError:
        # the selector refuses (im2col fallback) only an image whose
        # smallest tile, one row by one lane group, busts the budget
        assert conv_working_set(
            1, LANE, ho=ho, wo=wo, cout=cout, fh=fh, fw=fw,
            cin_pad=cin_pad, stride=stride, a_bits=a_bits,
            w_bits=w_bits) > BUDGET


# deterministic edge cases — these run even without hypothesis installed
@pytest.mark.parametrize("ho,wo", [(1, 1), (7, 5), (33, 1), (1, 63),
                                   (16, 16), (64, 64)])
@pytest.mark.parametrize("stride", [1, 2])
def test_conv_block_ragged_edges(ho, wo, stride):
    bho, bn = _check_conv_block(ho, wo, cout=40, fh=3, fw=3,
                                cin_pad=packing.CHUNK, stride=stride,
                                a_bits=4, w_bits=4)
    assert -(-ho // bho) * bho >= ho


@pytest.mark.parametrize("stride", [1, 2])
def test_conv_block_halves_bn_in_lane_steps(stride):
    """Regression (a Hypothesis find): a 5x7 filter over 3 chunks with
    cout 257 starts at bn=384 and must shrink it; halving to 192 broke
    the LANE-multiple invariant, shrinking in LANE steps keeps it."""
    bho, bn = _check_conv_block(1, 1, cout=257, fh=5, fw=7,
                                cin_pad=3 * packing.CHUNK, stride=stride,
                                a_bits=8, w_bits=8)
    assert bn < 4 * LANE


def test_conv_block_paper_layers():
    """The paper's fig.11 layers (16x16x32, 32x32x32 -> 64ch 3x3) pick a
    single-tile block: the whole output in one VMEM-resident pass."""
    for hw in (16, 32):
        bho, bn = _check_conv_block(hw, hw, cout=64, fh=3, fw=3,
                                    cin_pad=packing.CHUNK, stride=1,
                                    a_bits=4, w_bits=4)
        assert bn == LANE


def test_conv_block_rejects_oversized_image():
    """Images whose packed whole-image block cannot fit VMEM must raise
    (callers then use the im2col fallback) rather than return a tile that
    would OOM the kernel."""
    with pytest.raises(ValueError):
        conv_default_block(1, 4096, 4096, 64, 3, 3, 8 * packing.CHUNK,
                           1, 8, 8, BUDGET)

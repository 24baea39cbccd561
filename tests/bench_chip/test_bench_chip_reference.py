"""The references' own arithmetic against the program's formats and
against hand-made cases (the references import nothing of the program;
these tests may)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.families import cnn, lm
from benchmarks.chip.reference import cnn as cnn_ref
from benchmarks.chip.reference import lm as lm_ref


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_lm_reference_unpacks_the_programs_containers(bits):
    from repro.core import packing
    lo, hi = packing.int_range(bits, True)
    codes = np.random.default_rng(bits).integers(lo, hi + 1, (256, 24),
                                                 dtype=np.int8)
    packed = packing.pack(jnp.asarray(codes), bits, axis=0)
    np.testing.assert_array_equal(np.asarray(lm_ref.unpack(packed, bits)),
                                  codes.astype(np.float32))


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_drawn_codes_are_zero_mean_on_the_symmetric_grid(bits):
    w = lm.random_packed(jax.random.PRNGKey(bits), (512, 64), bits)
    c = np.asarray(lm_ref.unpack(w, bits))
    top = 2 ** (bits - 1) - 1
    assert c.min() == -top and c.max() == top
    assert abs(c.mean()) < 0.05 * top


def test_cnn_reference_conv_and_requant_by_hand():
    x = np.arange(2 * 4 * 4 * 1).reshape(2, 4, 4, 1) % 5
    w = np.zeros((3, 3, 1, 2), np.int64)
    w[1, 1, 0, 0] = 3                      # centre tap: 3 * x
    w[:, :, 0, 1] = 1                      # 3x3 box sum
    acc = cnn_ref.conv(x, w, 1, 1)
    np.testing.assert_array_equal(acc[..., 0], 3 * x[..., 0])
    assert acc[0, 1, 1, 1] == x[0, 0:3, 0:3, 0].sum()
    # floor shift of negative products, clip to the 8-bit grid
    phi = np.array([-5, 3, 1000])
    np.testing.assert_array_equal(cnn_ref.requant(phi, 3, 1, 8),
                                  [0, 4, 127])


def test_cnn_requant_constants_fit_the_programs_int32_split():
    import json
    from bench_chip_smoke import ROOT
    cfg = json.loads((ROOT / "benchmarks" / "chip" / "configs"
                      / "resnet8-w842.json").read_text())
    for path, c in cnn.requant_constants(cfg, 8).items():
        if "m1" in c:
            assert c["m1"] < 2**15 and 0 <= c["d"] <= 31
        else:
            assert 0 < c["m"] < 2**15 and 16 <= c["d"] <= 31, path
    assert cnn.input_eps(cfg, 8) == 1 / 128 and cnn.input_eps(cfg, 4) == 1 / 16

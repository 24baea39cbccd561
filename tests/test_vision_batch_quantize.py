"""The vision adapter quantizes its feed inside the step's program.

* served logits are bit-identical to the eager `quantize_input` followed
  by `forward_int`, under the calibrated input spec and under a
  power-of-two grid (2^-7 at A8, as the benchmark's CNN configuration
  sets) fed every value of a 1/1024 pixel grid (exact rounding ties
  included) and values that clip below 0 and above beta, and under the
  calibrated spec fed the float32 values nearest its rounding ties,
  over slot counts that leave pad rows beside real ones;
* `begin` launches nothing on the device and keeps the float32 image,
  `input_spec()` is float32, and the step compiles once whatever the
  number of active slots.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.quantize import QuantSpec
from repro.serve.runtime import Scheduler, VisionAdapter
from repro.vision.models import forward_int, quantize_input


@pytest.fixture(scope="module")
def calibrated():
    from repro.deploy.calibrate import calibrate_vision
    from repro.vision.configs import get_vision_config
    from repro.vision.models import init_fp, quantize_net

    cfg = get_vision_config("resnet8", smoke=True)
    params = init_fp(cfg, seed=0)
    rng = np.random.default_rng(0)
    cal = rng.uniform(0, 1, (4, *cfg.in_hw, cfg.in_ch)).astype(np.float32)
    _, absmax = calibrate_vision(cfg, params, [cal])
    qnet = quantize_net(cfg, params, absmax)
    spec = qnet.input_spec
    shape = (5, *cfg.in_hw, cfg.in_ch)
    flat = rng.uniform(-0.1 * spec.beta, 1.1 * spec.beta,
                       int(np.prod(shape))).astype(np.float32)
    # the float32 values nearest each half step of the grid, and two
    # ulps either side: dividing by eps and multiplying by its float32
    # reciprocal round some of them to different integers
    mid = ((np.arange(spec.int_max) + 0.5) * spec.eps).astype(np.float32)
    near = [mid]
    for side in (-np.inf, np.inf):
        x = mid
        for _ in range(2):
            x = np.nextafter(x, np.float32(side))
            near.append(x)
    near = np.concatenate(near)
    flat[:len(near)] = near
    return qnet, rng.permutation(flat).reshape(shape)


@pytest.fixture(scope="module")
def power_of_two(calibrated):
    """The calibrated net on the input grid 2^-7 (beta = 127/128), fed
    the whole 1/1024 pixel grid: x / eps = k / 8, so every k = 4 mod 8
    is an exact tie that rounds to even."""
    qnet, _ = calibrated
    qnet = dataclasses.replace(
        qnet, input_spec=QuantSpec.activation(8, 127 / 128))
    shape = (*qnet.cfg.in_hw, qnet.cfg.in_ch)
    grid = np.arange(1024, dtype=np.float32) / 1024
    clipped = np.float32([-1.0, -0.5, -1 / 1024, 127 / 128, 1.0, 1.5, 4.0])
    values = np.concatenate([grid, clipped])
    n = 5
    reps = -(-n * int(np.prod(shape)) // len(values))
    flat = np.random.default_rng(1).permutation(np.tile(values, reps))
    images = flat[:n * int(np.prod(shape))].reshape(n, *shape)
    return qnet, images


def test_power_of_two_grid_has_ties_and_clips(power_of_two):
    qnet, images = power_of_two
    x_hat = np.asarray(quantize_input(qnet, images))
    want = np.clip(np.round(images * 128), 0, 127).astype(np.int8)
    assert np.array_equal(x_hat, want)
    scaled = images * 128
    assert np.any((scaled % 1 == 0.5) & (scaled < 127))   # exact ties
    assert np.any(images < 0) and np.any(images > 127 / 128)


@pytest.mark.parametrize("slots", [1, 3, 8])
@pytest.mark.parametrize("grid", ["calibrated", "power_of_two"])
def test_batched_quantize_is_bit_exact(request, grid, slots):
    qnet, images = request.getfixturevalue(grid)
    want = np.asarray(forward_int(qnet, quantize_input(qnet, images),
                                  backend="xla"))
    got = Scheduler(VisionAdapter(qnet, backend="xla"), slots).serve(
        list(images))
    assert np.array_equal(np.stack(got), want)


def test_begin_touches_no_device(calibrated):
    qnet, images = calibrated
    adapter = VisionAdapter(qnet, backend="xla")
    img = images[0]
    with jax.transfer_guard("disallow"):
        cur = adapter.begin(img, rid=0)
    assert cur.payload.dtype == np.float32
    assert np.shares_memory(cur.payload, img)
    assert np.array_equal(cur.payload, img)
    shape, dtype = adapter.input_spec()
    assert shape == (*qnet.cfg.in_hw, qnet.cfg.in_ch)
    assert dtype == np.float32


def test_step_compiles_once_over_active_counts(calibrated):
    qnet, images = calibrated
    adapter = VisionAdapter(qnet, backend="xla")
    sched = Scheduler(adapter, 4)
    for n in (1, 4, 2, 3):
        for img in images[:n]:
            sched.submit(img)
        sched.drain()
    assert len(sched.results) == 10
    assert adapter._forward._cache_size() == 1

"""v5e compile rehearsals of the packed sub-byte kernels at real widths.

The TPU compiler is installed even where no chip is attached: it compiles
for a described v5e chip. These tests compile the real (non-interpret)
Pallas kernels at Qwen2.5-3B MLP widths and ResNet-8 conv geometries and
check that Mosaic accepted them (a ``tpu_custom_call`` in the program).
Interpret mode cannot catch what Mosaic refuses: int8 shifts, lane-
splitting reshapes, strided int8 loads, unaligned DMA windows.

The kernel functions are called directly: the registry's ``pallas``
backend asks `jax.default_backend()`, which is the CPU here. The topology
is described inside a fixture (never at import), so every test worker
collects the same tests and only the one that runs them loads the TPU
library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import packing
from repro.kernels.qconv.kernel import qconv2d_fused
from repro.kernels.qmatmul.kernel import qmatmul_packed, qmatmul_segmented

K, N, M = 2048, 11008, 256      # Qwen2.5-3B MLP up-projection
N_PAD = 11264                   # N padded to the 512-wide default block,
#                                 as the registry's pallas backend does


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip, so keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiles_to_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _epilogue_specs(sharding, n):
    return [_spec(sharding, (n,), jnp.int32) for _ in range(3)]


@pytest.mark.parametrize("w_bits,a_bits,pipeline", [
    (8, 8, "off"), (4, 8, "double_buffer"), (2, 8, "off"),
    (2, 2, "double_buffer")])
def test_qmatmul_packed_compiles_for_v5e(one_chip, w_bits, a_bits,
                                         pipeline):
    def fn(x, w, kappa, lam, m):
        return qmatmul_packed(x, w, kappa, lam, m, a_bits=a_bits,
                              a_signed=False, w_bits=w_bits, d=18,
                              out_bits=8, pipeline=pipeline)

    _compiles_to_kernel(
        fn, _spec(one_chip, (M, K // packing.pack_factor(a_bits)), jnp.int8),
        _spec(one_chip, (K // packing.pack_factor(w_bits), N_PAD), jnp.int8),
        *_epilogue_specs(one_chip, N_PAD))


def test_qmatmul_segmented_compiles_for_v5e(one_chip):
    third = N // 3 // packing.CHUNK * packing.CHUNK
    segmap = packing.SegmentMap(
        ((0, third, 8), (third, 2 * third, 4), (2 * third, N, 2)))

    def fn(x, w_flat, kappa, lam, m):
        return qmatmul_segmented(x, w_flat, segmap, kappa, lam, m,
                                 k_logical=K, a_bits=8, a_signed=False,
                                 d=18, out_bits=8, pipeline="double_buffer")

    _compiles_to_kernel(
        fn, _spec(one_chip, (M, K), jnp.int8),
        _spec(one_chip, (segmap.packed_bytes(K),), jnp.int8),
        *_epilogue_specs(one_chip, N))


# ResNet-8 layers at 32x32 input: (h, cin, cout, stride)
@pytest.mark.parametrize("h,cin,cout,stride,w_bits,a_bits,pipeline", [
    (16, 32, 32, 1, 4, 8, "off"),             # s2/c2
    (16, 32, 64, 2, 2, 8, "double_buffer"),   # s3/c1
    (32, 16, 16, 1, 4, 4, "double_buffer"),   # s1/c at A4
])
def test_qconv_fused_compiles_for_v5e(one_chip, h, cin, cout, stride,
                                      w_bits, a_bits, pipeline):
    cin_pad = packing.padded_size(cin)

    def fn(x, w, kappa, lam, m):
        return qconv2d_fused(x, w, kappa, lam, m, fh=3, fw=3,
                             stride=stride, padding=1, cin_pad=cin_pad,
                             cout=cout, a_bits=a_bits, a_signed=False,
                             w_bits=w_bits, d=18, out_bits=8,
                             pipeline=pipeline)

    kp = 9 * cin_pad // packing.pack_factor(w_bits)
    _compiles_to_kernel(
        fn, _spec(one_chip, (8, h, h, cin), jnp.int8),
        _spec(one_chip, (kp, cout), jnp.int8),
        *_epilogue_specs(one_chip, cout))

"""Compile-only rehearsals of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed next to jax, so the kernels of the main
path compile here at the real widths of `vgg16-cifar` with N=20 clients
— for a chip that is described, not attached.  Nothing runs: each test
checks that the compiler accepts the kernel (``tpu_custom_call`` in the
executable) and that the program fits a v5e's 16 GB of device memory.

This is the only test file that describes a topology.  The description
loads the TPU library, which one process at a time may hold, so it
happens in a module fixture and never at import, in a ``skipif`` or in
a ``parametrize`` argument: every worker collects the same tests, and
only the worker that runs this file loads the library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import batched_conv as BC
from repro.kernels.clip_sgd import clip_sgd_update

V5E_HBM_BYTES = 16 * 10**9

# (N, b, H=W, Cin, Cout): VGG-16's first 32x32 block and its last 4x4 one
CONV_SHAPES = [(20, 16, 32, 64, 64), (20, 16, 4, 512, 512)]
# [N, D]: the largest VGG-16 leaf (3x3x512x512 conv weight) and the head bias
CLIP_SHAPES = [(20, 3 * 3 * 512 * 512), (20, 10)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
    return compiled


def _conv_operands(shape, sharding):
    n, b, h, cin, cout = shape

    def sds(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)

    return sds(n, b, h, h, cin), sds(n, 3, 3, cin, cout), sds(n, cout)


@pytest.mark.parametrize("shape", CONV_SHAPES)
def test_batched_conv_forward_compiles(one_chip, shape):
    conv = BC.conv_vjp(1, "pallas", False)
    _compile(conv, *_conv_operands(shape, one_chip))


@pytest.mark.parametrize("shape", CONV_SHAPES)
def test_batched_conv_backward_compiles(one_chip, shape):
    conv = BC.conv_vjp(1, "pallas", False)

    def loss(x, w, b):
        return conv(x, w, b).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)),
             *_conv_operands(shape, one_chip))


@pytest.mark.parametrize("shape", CLIP_SHAPES)
def test_clip_sgd_compiles(one_chip, shape):
    """Both variants: the in-register client mean and the external
    (mesh-mode) mean with its scalar use-common flag."""
    n, d = shape

    def sds(s, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    p, g, scale, keep = sds((n, d)), sds((n, d)), sds((n,)), sds((n,))

    def internal(p, g, scale, keep):
        return clip_sgd_update(p, g, scale, keep, gamma=0.05,
                               interpret=False)

    def external(p, g, scale, keep, common, use_common):
        return clip_sgd_update(p, g, scale, keep, gamma=0.05,
                               interpret=False, common=common,
                               use_common=use_common)

    _compile(internal, p, g, scale, keep)
    _compile(external, p, g, scale, keep, sds((d,)), sds((), jnp.bool_))

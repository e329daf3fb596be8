"""Ahead-of-time compiles of the main-path kernels for a described v5e chip.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes whose last two dims neither tile (8, 128) nor equal
the array's, strided in-kernel subselects, packed-int8 relayouts.  These
tests compile each kernel of the sparse ResNet-50 serving path at its real
widths for one chip of a described (not attached) ``v5e:2x2`` topology and
check that the executable holds the Pallas kernel (``tpu_custom_call``).
Nothing runs, so they say nothing about results or speed.

The topology is described only inside the module-scoped fixtures: only one
process at a time may load the TPU library, so it must never happen while
a module is being imported or collected.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.vector_sparse import VectorSparse
from repro.kernels import ops
from repro.models.graph import conv_tile_geometry, fc_tile_geometry, strip_steps

DENSITY = 0.235  # vscnn-resnet50's published operating point


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU stack"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile_conv(one_chip, x_shape, cout, *, kh, kw, stride=1, groups=1,
                  dtype=jnp.float32, residual=False):
    """Compile `ops.vsconv` (interpret off) for one encoded conv layer at
    the tile geometry `sparsify` would give it; returns the HLO text."""
    n, h, w, cin = x_shape
    g = conv_tile_geometry(kh, kw, cin // groups, cout, groups=groups)
    assert g.cin_pad == 0, "pass the encoded (padded) input width"
    s_steps = strip_steps(g.kb, DENSITY, prune=cin // groups >= 32 or groups > 1)
    k = kh * kw if g.depthwise else kh * kw * cin // groups
    int8 = dtype == jnp.int8
    ho, wo = -(-h // stride), -(-w // stride)

    def fn(x, vals, idx, bias, scale, res):
        vs = VectorSparse(vals=vals, idx=idx, shape=(k, cout))
        return ops.vsconv(x, vs, kh=kh, kw=kw, stride=stride, groups=groups,
                          bias=bias, scale=scale, residual=res,
                          fuse_relu=True, interpret=False)

    args = [
        _spec(x_shape, dtype, one_chip),
        _spec((g.nb, s_steps, g.vk, g.vn), dtype, one_chip),
        _spec((g.nb, s_steps), jnp.int32, one_chip),
        _spec((cout,), jnp.float32, one_chip),
        _spec((cout,), jnp.float32, one_chip) if int8 else None,
        _spec((n, ho, wo, cout), jnp.float32, one_chip) if residual else None,
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _compile_fc(one_chip, m, din, dout, *, dtype=jnp.float32):
    fg = fc_tile_geometry(din, dout)
    s_steps = strip_steps(fg.kb, DENSITY)
    n_enc = dout + fg.pad

    def fn(x, vals, idx, bias, scale):
        vs = VectorSparse(vals=vals, idx=idx, shape=(din, n_enc))
        return ops.vsmm(x, vs, bias=bias, scale=scale, interpret=False)

    args = [
        _spec((m, din), dtype, one_chip),
        _spec((fg.nb, s_steps, fg.vk, fg.vn), dtype, one_chip),
        _spec((fg.nb, s_steps), jnp.int32, one_chip),
        _spec((n_enc,), jnp.float32, one_chip),
        _spec((n_enc,), jnp.float32, one_chip) if dtype == jnp.int8 else None,
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


# (label, NHWC input (encoded), cout, kh, kw, stride, groups, dtype, residual)
CONV_CASES = [
    # layer1 expand 1x1 at 56px: vsmm with fused bias + residual
    ("vsmm_1x1_56px_bias_residual", (4, 56, 56, 64), 256, 1, 1, 1, 1,
     jnp.float32, True),
    # layer2 projection 1x1/s2 (subsample fused into the layout pass)
    ("vsmm_1x1_s2_projection", (4, 56, 56, 256), 512, 1, 1, 2, 1,
     jnp.float32, False),
    ("halo_3x3_s1_56px", (4, 56, 56, 64), 64, 3, 3, 1, 1, jnp.float32, False),
    ("halo_3x3_s2_56px", (4, 56, 56, 128), 128, 3, 3, 2, 1, jnp.float32,
     False),
    # layer4 at 224px: Wout = 7, not a sublane multiple
    ("halo_3x3_s1_7px", (4, 7, 7, 512), 512, 3, 3, 1, 1, jnp.float32, False),
    # the stem: 3 channels padded to one vk=8 tile
    ("halo_7x7_s2_stem_224px", (4, 224, 224, 8), 64, 7, 7, 2, 1,
     jnp.float32, False),
    ("resident_halo_hout3", (4, 3, 3, 512), 512, 3, 3, 1, 1, jnp.float32,
     False),
    ("mobilenet_depthwise_3x3_s2", (4, 112, 112, 64), 64, 3, 3, 2, 64,
     jnp.float32, False),
    ("int8_vsmm_1x1_56px", (4, 56, 56, 64), 256, 1, 1, 1, 1, jnp.int8,
     True),
    ("int8_halo_3x3_s2_28px", (4, 28, 28, 256), 256, 3, 3, 2, 1, jnp.int8,
     False),
]


@pytest.mark.parametrize(
    "x_shape,cout,kh,kw,stride,groups,dtype,residual",
    [c[1:] for c in CONV_CASES], ids=[c[0] for c in CONV_CASES])
def test_conv_compiles_for_v5e(one_chip, x_shape, cout, kh, kw, stride,
                               groups, dtype, residual):
    text = _compile_conv(one_chip, x_shape, cout, kh=kh, kw=kw,
                         stride=stride, groups=groups, dtype=dtype,
                         residual=residual)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8],
                         ids=["f32", "int8"])
def test_fc_head_compiles_for_v5e(one_chip, dtype):
    """The 2048 -> 1000 classifier: a remainder strip pads 1000 to 1024."""
    text = _compile_fc(one_chip, 4, 2048, 1000, dtype=dtype)
    assert "tpu_custom_call" in text

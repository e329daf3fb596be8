"""Dense narrow-Cin stems run through XLA at HIGHEST, not on the kernels.

`sparsify` keeps an ungrouped conv whose Cin is below the K-tile dense
(`graph.keeps_dense`); a float one then keeps its folded weight and
`apply_sparse_conv` runs it as one dot over the space-to-depth patches of
the unpadded input (`core.sparse_ops.s2d_im2col`).  Every layer vector pruning reaches, grouped and depthwise
layers, int8 entries and the oracle impls keep the encoded path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.sparse_ops import (
    dense_conv2d, im2col, s2d_im2col, s2d_weight_matrix,
)
from repro.launch import spans
from repro.launch.serve import CNNServer, ImageRequest
from repro.models import graph as G
from repro.models.layers import init_params

SIDE = 16
HIGHEST = "precision = [HIGHEST, HIGHEST]"

# (label, Conv, rows of its space-to-depth weight matrix) at the geometry
# of each served net's first conv: 7x7/s2 -> 4x4 blocks of 2x2x3
STEMS = [
    ("resnet50_conv1", G.Conv("conv1", 3, 64, 7, 7, stride=2, bn=True), 192),
    ("vgg16_conv1", G.Conv("conv1_1", 3, 64, 3, 3), 27),
]
# layers that keep their kernel: pruned, grouped, depthwise
KERNEL_LAYERS = [
    ("pruned_cin_ge_vk", G.Conv("c", 32, 64, 3, 3)),
    ("pruned_1x1", G.Conv("c", 64, 128, 1, 1)),
    ("grouped", G.Conv("c", 32, 64, 3, 3, groups=2)),
    ("depthwise", G.Conv("c", 32, 32, 3, 3, groups=32)),
]


def _one_layer(conv, dtype=None, density=0.5):
    net = G.SparseNet("one", (conv,))
    params = init_params(net.schema(), jax.random.PRNGKey(0), jnp.float32)
    if conv.bn:  # non-identity BN, so the fold is exercised
        rng = np.random.default_rng(1)
        p = params[conv.name]
        p["scale"] = jnp.asarray(rng.uniform(0.5, 1.5, conv.cout), jnp.float32)
        p["offset"] = jnp.asarray(rng.normal(size=conv.cout), jnp.float32)
    sparse, pruned = G.sparsify(net, params, density, dtype=dtype)
    return net, params, sparse, pruned


def _x(cin, n=2):
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.standard_normal((n, SIDE, SIDE, cin)), jnp.float32)


@pytest.mark.parametrize("impl", ["auto", "pallas"])
@pytest.mark.parametrize("conv,rows", [s[1:] for s in STEMS],
                         ids=[s[0] for s in STEMS])
def test_dense_narrow_stem_dispatches_to_xla_conv(conv, rows, impl):
    net, params, sparse, _ = _one_layer(conv)
    spec = sparse[conv.name]
    assert G.keeps_dense(conv.groups, conv.cin, 32)
    assert spec.cin_pad == 5 and spec.dense_w.shape == (rows, conv.cout)
    assert G.runs_xla_conv(spec, impl)
    text = jax.jit(lambda x: net.apply(params, x, sparse=sparse, impl=impl)
                   ).lower(_x(conv.cin)).as_text()
    # one dot over the 3-channel input's patches: no conv, no kernel and
    # no channel pad
    assert text.count("stablehlo.dot_general") == 1
    assert "stablehlo.convolution" not in text
    assert "pallas" not in text and "custom_call" not in text
    assert f"tensor<2x{SIDE}x{SIDE}x3xf32>" in text
    assert f"x{SIDE}x{SIDE}x8xf32>" not in text


@pytest.mark.parametrize("conv", [c for _, c in KERNEL_LAYERS],
                         ids=[n for n, _ in KERNEL_LAYERS])
def test_vector_pruned_layers_keep_their_kernel(conv):
    _, _, sparse, _ = _one_layer(conv)
    spec = sparse[conv.name]
    assert not G.keeps_dense(conv.groups, conv.cin // conv.groups, 32)
    assert spec.dense_w is None
    assert not G.runs_xla_conv(spec, "auto")


@pytest.mark.parametrize("conv", [s[1] for s in STEMS],
                         ids=[s[0] for s in STEMS])
def test_int8_stem_and_oracle_impls_keep_the_encoded_path(conv):
    _, _, sparse8, _ = _one_layer(conv, dtype="int8")
    assert sparse8[conv.name].dense_w is None
    assert not G.runs_xla_conv(sparse8[conv.name], "auto")
    _, _, sparse, _ = _one_layer(conv)
    for impl in G.ORACLE_IMPLS:
        assert not G.runs_xla_conv(sparse[conv.name], impl)
    # a bare encoding carries no dense weight
    assert not G.runs_xla_conv(sparse[conv.name].vs, "auto")


@pytest.mark.parametrize("k,stride,dilation,side", [
    (7, 2, 1, 16), (7, 2, 1, 15), (3, 1, 1, 16), (3, 2, 1, 13),
    (1, 2, 1, 9), (5, 3, 1, 17), (3, 2, 2, 14), (3, 1, 2, 11), (4, 2, 1, 12),
])
def test_s2d_patches_times_weight_matrix_is_the_conv(k, stride, dilation,
                                                     side):
    rng = np.random.default_rng(k * 100 + stride * 10 + dilation)
    x = jnp.asarray(rng.standard_normal((2, side, side, 3)), jnp.float32)
    w = rng.standard_normal((k, k, 3, 8)).astype(np.float32)
    got = jnp.dot(s2d_im2col(x, kh=k, kw=k, stride=stride,
                             dilation=dilation),
                  s2d_weight_matrix(w, stride=stride, dilation=dilation),
                  precision=jax.lax.Precision.HIGHEST)
    want = dense_conv2d(x, jnp.asarray(w), stride=stride, dilation=dilation)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    if stride == 1 and dilation == 1:  # the plain im2col, exactly
        np.testing.assert_array_equal(
            s2d_im2col(x, kh=k, kw=k), im2col(x, kh=k, kw=k))


@pytest.mark.parametrize("residual", [False, True])
@pytest.mark.parametrize("conv", [s[1] for s in STEMS],
                         ids=[s[0] for s in STEMS])
def test_xla_stem_matches_pruned_dense_oracle(conv, residual):
    net, params, sparse, pruned = _one_layer(conv)
    x = _x(conv.cin)
    spec = sparse[conv.name]
    ho = -(-SIDE // conv.stride)
    res = (jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, ho, ho, conv.cout)), jnp.float32) if residual else None)
    got = G.apply_sparse_conv(x, spec, bias=spec.bias, residual=res)
    want = G._dense_conv(conv, pruned[conv.name], x, res)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the encoded path computes the same function
    enc = G.apply_sparse_conv(x, spec, bias=spec.bias, residual=res,
                              impl="jnp")
    np.testing.assert_allclose(got, enc, rtol=1e-5, atol=1e-5)
    # and the whole net against its pruned dense tree
    np.testing.assert_allclose(net.apply(params, x, sparse=sparse),
                               net.apply(pruned, x), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("conv", [s[1] for s in STEMS],
                         ids=[s[0] for s in STEMS])
def test_lowered_stem_conv_is_highest_on_both_operands(conv):
    net, params, sparse, _ = _one_layer(conv)
    text = jax.jit(lambda x: net.apply(params, x, sparse=sparse)
                   ).lower(_x(conv.cin)).as_text()
    line = next(l for l in text.splitlines() if "stablehlo.dot_general" in l)
    assert HIGHEST in line


def test_resnet50_launch_records_one_xla_conv(tmp_path):
    srv = CNNServer(get_config("vscnn-resnet50").reduce(), batch=1, seed=0)
    assert srv.backend.apply.xla_convs == 1
    assert sum(G.runs_xla_conv(e, "auto") for e in srv.sparse.values()) == 1
    s = srv.cfg.image_size
    req = ImageRequest(rid=0, image=np.zeros((s, s, 3), np.float32))
    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        srv.serve([req])
    launches = [sp for sp in spans.recorded() if sp.name == "backend.launch"]
    spans.clear()
    assert srv.outcomes[0].status == "delivered"
    assert [sp.attrs["xla_convs"] for sp in launches] == [1]

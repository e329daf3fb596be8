"""Grouped / depthwise / dilated vector-sparse conv: parity + traffic.

The acceptance sweep for the grouped-geometry extension: every
(groups, dilation, stride) combination must agree across all four
implementations — halo kernel, row-tap-stack kernel, the structural jnp
path, and the densified `kernels/ref.py` oracle — and the DRAM traffic
model's per-group bytes must equal the kernels' own `pl.CostEstimate`
formulas (per-group fetch, not full-cin).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import encode, prune_vectors_balanced
from repro.core.accel_model import (
    PE_4_14_3, conv_layer_cycles, conv_layer_traffic,
)
from repro.core.sparse_ops import same_pads, vs_conv2d
from repro.kernels import vsconv
from repro.kernels.ref import conv_ref, vsconv_ref
from repro.models.graph import apply_sparse_conv, sparse_conv_from_dense


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)


# (groups, dilation, stride) — the acceptance grid: groups in {2, 4, cin},
# dilation in {1, 2}, stride 1/2.  cin = 64 throughout, 3x3 taps.
ACCEPTANCE_GRID = [
    (g, d, s)
    for g in (2, 4, 64)
    for d in (1, 2)
    for s in (1, 2)
]


class TestGroupedParity:
    @pytest.mark.parametrize("groups,dilation,stride", ACCEPTANCE_GRID)
    def test_halo_stack_jnp_vs_ref(self, groups, dilation, stride, rng):
        kh = kw = 3
        c, co = 64, 64 if groups == 64 else 128
        cin_g = c // groups
        w = rng.standard_normal((kh, kw, cin_g, co)).astype(np.float32)
        spec, wp = sparse_conv_from_dense(
            w, 0.5, vk=16, vn=32, stride=stride, groups=groups,
            dilation=dilation)
        x = jnp.asarray(
            np.maximum(rng.standard_normal((2, 11, 10, c)), 0), jnp.float32)
        ref = vsconv_ref(x, spec.vs, kh=kh, kw=kw, stride=stride,
                         groups=groups, dilation=dilation)
        # the densified sparse weight equals the pruned dense weight
        dense = conv_ref(x, jnp.asarray(wp), stride=stride, groups=groups,
                         dilation=dilation)
        assert _rel(ref, dense) < 1e-5
        for impl in ("pallas-halo", "pallas-stack", "jnp"):
            out = apply_sparse_conv(x, spec, fuse_relu=False, impl=impl)
            assert out.shape == ref.shape, (impl, out.shape, ref.shape)
            assert _rel(out, ref) < 1e-5, impl

    @pytest.mark.parametrize("stride,dilation", [(1, 1), (2, 1), (1, 2)])
    @pytest.mark.parametrize("bias,residual,relu", [
        (True, False, True), (True, True, True),
    ])
    def test_depthwise_fused_epilogue(self, stride, dilation, bias, residual,
                                      relu, rng):
        """Depthwise per-channel tap kernels run the same fused epilogue
        (bias + residual-before-ReLU) as the full kernels."""
        kh = kw = 3
        c, vc = 64, 32
        wm = prune_vectors_balanced(
            rng.standard_normal((kh * kw, c)).astype(np.float32),
            0.6, 1, vc)[0]
        vs = encode(jnp.asarray(wm), 1, vc)
        x = jnp.asarray(
            np.maximum(rng.standard_normal((1, 9, 12, c)), 0), jnp.float32)
        b = (jnp.asarray(rng.standard_normal((c,)), jnp.float32)
             if bias else None)
        ho, _, _ = same_pads(9, kh, stride, dilation)
        wo, _, _ = same_pads(12, kw, stride, dilation)
        res = (jnp.asarray(rng.standard_normal((1, ho, wo, c)), jnp.float32)
               if residual else None)
        kw_args = dict(kh=kh, kw=kw, stride=stride, groups=c,
                       dilation=dilation, bias=b, residual=res,
                       fuse_relu=relu)
        ref = vsconv_ref(x, vs, **kw_args)
        for impl in ("halo", "stack"):
            out = vsconv(x, vs, impl=impl, **kw_args)
            assert _rel(out, ref) < 1e-5, impl
        outj = vs_conv2d(x, vs, impl="jnp", **kw_args)
        assert _rel(outj, ref) < 1e-5

    def test_grouped_1x1(self, rng):
        """Grouped pointwise convs (block-diagonal matmul) run through the
        general kernels, not the full-cin vsmm route."""
        c, co, groups = 64, 128, 4
        w = rng.standard_normal((1, 1, c // groups, co)).astype(np.float32)
        spec, _ = sparse_conv_from_dense(w, 0.5, vk=16, vn=32, groups=groups)
        x = jnp.asarray(
            np.maximum(rng.standard_normal((2, 8, 8, c)), 0), jnp.float32)
        ref = vsconv_ref(x, spec.vs, kh=1, kw=1, groups=groups)
        for impl in ("pallas-halo", "pallas-stack", "jnp"):
            out = apply_sparse_conv(x, spec, fuse_relu=False, impl=impl)
            assert _rel(out, ref) < 1e-5, impl


class TestGroupedEncoding:
    def test_grouped_strips_stay_in_group(self, rng):
        """No output strip straddles a group: vn shrinks to a divisor of
        Cout/groups, and the K axis is Cin/groups."""
        w = rng.standard_normal((3, 3, 16, 128)).astype(np.float32)
        spec, _ = sparse_conv_from_dense(w, 0.5, vk=32, vn=128, groups=4)
        assert spec.groups == 4
        assert spec.vs.shape == (3 * 3 * 16, 128)
        assert spec.vs.vn <= 128 // 4
        assert (128 // 4) % spec.vs.vn == 0
        assert spec.vs.vk <= 16 and 16 % spec.vs.vk == 0
        assert spec.cin_pad == 0

    def test_depthwise_encoding_is_tap_matrix(self, rng):
        w = rng.standard_normal((3, 3, 1, 256)).astype(np.float32)
        spec, wp = sparse_conv_from_dense(w, 0.5, vk=32, vn=128, groups=256)
        assert spec.groups == 256
        assert spec.vs.vk == 1 and spec.vs.vn == 128
        assert spec.vs.shape == (9, 256)
        # balanced: ceil-rounded tap quota per channel tile
        assert spec.vs.nnz_per_strip == max(1, round(9 * 0.5))
        assert wp.shape == (3, 3, 1, 256)

    def test_grouped_cin_major_order(self, rng):
        """Grouped tile ids are group-relative; the cin-major reorder keys
        on the per-group tile count, so the per-strip cin-tile stream is
        still non-decreasing (the halo revisit contract)."""
        w = rng.standard_normal((3, 3, 32, 64)).astype(np.float32)
        spec, _ = sparse_conv_from_dense(w, 0.5, vk=16, vn=32, groups=2)
        cbg = 32 // spec.vs.vk
        idx = np.asarray(spec.vs.idx)
        assert (np.diff(idx % cbg, axis=1) >= 0).all()


class TestGroupedTraffic:
    def test_per_group_bytes_match_kernel_cost(self):
        """Acceptance: the traffic model's per-group input fetch equals the
        halo kernel's CostEstimate with cb = Cin/(groups*vk) — NOT the
        full-cin count."""
        from repro.kernels.vsconv import halo_kernel_cost

        n, h, c, co, vk, vn, groups, s = 1, 16, 64, 128, 16, 32, 4, 12
        tr = conv_layer_traffic((n, h, h, c), kh=3, kw=3, stride=1,
                                groups=groups, cout=co, s_steps=s, vk=vk,
                                vn=vn, impl="halo")
        cbg = (c // vk) // groups
        # halo phase-plane width bwp = Wout + (kw-1)//stride = 16 + 2
        est = halo_kernel_cost(
            n=n, hop=16, w_out=16, kh=3, stride=1, bwp=18, bh=8,
            nb=co // vn, s_steps=s, cb=cbg, vk=vk, vn=vn)
        assert (tr.input_bytes + tr.weight_bytes + tr.output_bytes
                == est.bytes_accessed)
        # full-cin accounting would fetch 4x the tiles per strip
        est_full = halo_kernel_cost(
            n=n, hop=16, w_out=16, kh=3, stride=1, bwp=18, bh=8,
            nb=co // vn, s_steps=s, cb=c // vk, vk=vk, vn=vn)
        assert est.bytes_accessed < est_full.bytes_accessed

    def test_depthwise_bytes_match_dw_kernel_cost(self):
        from repro.kernels.vsconv import (
            dw_halo_kernel_cost, dw_stack_kernel_cost,
        )

        n, h, c, vc, s = 1, 16, 256, 128, 5
        tr_h = conv_layer_traffic((n, h, h, c), kh=3, kw=3, stride=2,
                                  groups=c, cout=c, s_steps=s, vk=1, vn=vc,
                                  impl="halo")
        # phase-plane width bwp = Wout + (kw-1)//stride = 8 + 1
        est_h = dw_halo_kernel_cost(
            n=n, hop=8, w_out=8, kh=3, stride=2, bwp=9, bh=8, nb=c // vc,
            s_steps=s, vc=vc)
        assert (tr_h.input_bytes + tr_h.weight_bytes + tr_h.output_bytes
                == est_h.bytes_accessed)
        tr_s = conv_layer_traffic((n, h, h, c), kh=3, kw=3, stride=2,
                                  groups=c, cout=c, s_steps=s, vk=1, vn=vc,
                                  impl="stack")
        # stack bw = round_up(wo + (kw-1)//stride, 8) = round_up(9, 8)
        est_s = dw_stack_kernel_cost(
            n=n, hop=8, w_out=8, bw=16, bh=8, nb=c // vc, s_steps=s, vc=vc)
        assert (tr_s.input_bytes + tr_s.weight_bytes + tr_s.output_bytes
                == est_s.bytes_accessed)

    def test_depthwise_halo_below_stack(self):
        """The mobilenet dw 3x3/s2 gate geometry: halo fetches the block
        once per (strip, row-block); the stack re-fetches per stored tap."""
        for h in (14, 28):
            tr = {impl: conv_layer_traffic(
                      (1, h, h, 512), kh=3, kw=3, stride=2, groups=512,
                      cout=512, s_steps=5, vk=1, vn=128, impl=impl)
                  for impl in ("halo", "stack")}
            assert (tr["halo"].bytes_accessed
                    < tr["stack"].bytes_accessed), h


class TestGroupedCycles:
    def test_grouped_cycles_sum_of_group_slices(self, rng):
        """A grouped conv's cycle report is the per-group sum on the
        channel slices — dense cycles scale with Cout/groups per input
        vector, not full Cout."""
        x = np.maximum(rng.standard_normal((8, 8, 16)), 0)
        w = rng.standard_normal((3, 3, 8, 32))
        rep_g = conv_layer_cycles(x, w, PE_4_14_3, groups=2)
        rep_a = conv_layer_cycles(x[..., :8], w[..., :16], PE_4_14_3)
        rep_b = conv_layer_cycles(x[..., 8:], w[..., 16:], PE_4_14_3)
        assert rep_g.dense == rep_a.dense + rep_b.dense
        assert rep_g.vscnn == rep_a.vscnn + rep_b.vscnn
        assert rep_g.macs_nonzero == rep_a.macs_nonzero + rep_b.macs_nonzero

    def test_dilated_macs_match_dense_conv(self, rng):
        """`macs_dense` and the nonzero-MAC count stay consistent with the
        dilated SAME geometry (Hout = ceil(H/stride) regardless of
        dilation; boundary taps read zero padding, so even an all-ones
        input issues fewer nonzero MACs than the dense slot count)."""
        x = np.maximum(rng.standard_normal((9, 9, 4)), 0)
        w = rng.standard_normal((3, 3, 4, 8))
        rep = conv_layer_cycles(x, w, PE_4_14_3, stride=2, dilation=2)
        assert rep.macs_dense == 5 * 5 * 3 * 3 * 4 * 8
        dense_macs = conv_layer_cycles(
            np.ones_like(x), np.ones_like(w), PE_4_14_3, stride=2,
            dilation=2).macs_nonzero
        assert 0 < dense_macs <= rep.macs_dense
        assert rep.macs_nonzero <= dense_macs


class TestNewNetsEndToEnd:
    @pytest.mark.parametrize("builder", ["build_resnet34", "build_resnet50",
                                         "build_mobilenet_v1"])
    def test_sparse_apply_matches_pruned_dense(self, builder, rng):
        """Acceptance: ResNet-34/50 and MobileNetV1 run end-to-end sparse
        through `SparseNet.apply` and match the BN-folded pruned dense
        oracle."""
        from repro.models import graph as G
        from repro.models.layers import init_params

        net = getattr(G, builder)(16, image_size=32)
        params = init_params(net.schema(), jax.random.PRNGKey(0),
                             jnp.float32)
        x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
        sparse, pruned = G.sparsify(net, params, 0.5)
        # every conv and FC runs sparse
        assert set(sparse) == {l.name for l in net.conv_layers()} \
            | {l.name for l in net.fc_layers()}
        out = net.apply(params, x, sparse=sparse, impl="jnp")
        oracle = net.apply(pruned, x)
        assert out.shape == (2, 16)
        assert _rel(out, oracle) < 1e-5

    def test_mobilenet_depthwise_layers_are_depthwise(self):
        from repro.models.graph import build_mobilenet_v1

        net = build_mobilenet_v1(10)
        dw = [l for l in net.conv_layers() if l.groups > 1]
        assert len(dw) == 13
        assert all(l.groups == l.cin == l.cout for l in dw)

    def test_resnet50_bottleneck_shapes(self):
        from repro.models.graph import build_resnet50

        net = build_resnet50(10)
        convs = net.conv_layers()
        assert len(convs) == 1 + 16 * 3 + 4  # stem + blocks + projections
        assert convs[-1].cout == 2048

    def test_resnet34_basic_block_shapes(self):
        from repro.models.graph import build_resnet34

        net = build_resnet34(10)
        convs = net.conv_layers()
        # stem + 2 convs per basic block (3+4+6+3 blocks) + 3 projections
        assert len(convs) == 1 + 16 * 2 + 3
        assert convs[-1].cout == 512  # basic blocks: no 4x expansion

    @pytest.mark.parametrize("arch", ["vscnn-resnet34", "vscnn-resnet50",
                                      "vscnn-mobilenet-v1"])
    def test_servable_configs(self, arch):
        from repro.configs import get_config, list_cnn_archs

        assert arch in list_cnn_archs()
        cfg = get_config(arch).reduce()
        net = cfg.build()
        assert net.conv_layers()

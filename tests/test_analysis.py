"""vscheck analyzer tests: IR walker, abstract contract proofs, lint.

The property tests sweep randomized conv geometries (kernel x stride x
dilation x groups x tiny maps) and assert the three claims the analyzer
makes hold together: the abstract interval proof accepts the layer, the
byte derivation matches the kernel cost contract exactly (a VSC202/203
error would surface as a report error), and a *real* sparsified encoding
stays inside the abstract bounds with a faithful DMA count no larger
than the contract's budget.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.analysis import Report, VSCheckError
from repro.analysis.contracts import (
    _bounds_violations, _contract_fetches, _faithful_fetches, _offsets,
    canonical_conv_idx, check_contracts,
)
from repro.analysis.ir import check_net
from repro.analysis.lint import IMPL_VOCAB, lint_source
from repro.kernels.plan import conv_plan
from repro.models.graph import (
    Conv, FC, Flatten, Pool, ResidualAdd, Save, SparseNet,
    sparse_conv_from_dense,
)


def _single_conv_net(cin, cout, kh, kw, stride, groups, dilation,
                     allow_fallback=False):
    return SparseNet("prop", (
        Conv("c0", cin, cout, kh, kw, stride=stride, groups=groups,
             dilation=dilation, allow_fallback=allow_fallback),
    ))


@st.composite
def conv_geometries(draw):
    kind = draw(st.sampled_from(["dense", "dense", "grouped", "depthwise"]))
    kh = draw(st.sampled_from([1, 3, 5]))
    kw = draw(st.sampled_from([1, 3]))
    stride = draw(st.sampled_from([1, 2]))
    dilation = draw(st.sampled_from([1, 2]))
    h = draw(st.integers(min_value=6, max_value=14))
    w = draw(st.integers(min_value=6, max_value=14))
    density = draw(st.sampled_from([0.125, 0.25, 0.5, 1.0]))
    if kind == "dense":
        cin, cout, groups = draw(st.sampled_from(
            [(16, 64), (32, 128), (24, 64)])) + (1,)
    elif kind == "grouped":
        cin, cout, groups = draw(st.sampled_from(
            [(32, 64, 2), (64, 128, 4)]))
        if kh == 1 and kw == 1:
            kh = 3  # 1x1 grouped still runs the direct kernels; keep taps
    else:
        c = draw(st.sampled_from([32, 64]))
        cin = cout = groups = c
        if kh == 1 and kw == 1:
            kh = 3
    return (cin, cout, kh, kw, stride, groups, dilation, h, w, density)


class TestIRWalker:
    @pytest.mark.parametrize("name", [
        "vgg16", "resnet18", "resnet34", "resnet50", "mobilenet_v1"])
    def test_registered_nets_clean(self, name):
        from repro.analysis.__main__ import NETS
        net = NETS[name](image_size=32)
        nc = check_net(net, (1, 32, 32, 3))
        assert not nc.report.errors, nc.report.render()
        assert nc.conv_sites and nc.fc_sites

    def test_channel_mismatch_vsc101(self):
        net = SparseNet("bad", (Conv("c0", 3, 64, 3, 3),
                                Conv("c1", 32, 64, 3, 3)))
        rep = check_net(net, (1, 16, 16, 3)).report
        assert any(d.rule == "VSC101" for d in rep.errors)

    def test_undefined_slot_vsc104(self):
        net = SparseNet("bad", (Conv("c0", 3, 64, 3, 3),
                                ResidualAdd("nowhere")))
        rep = check_net(net, (1, 16, 16, 3)).report
        assert any(d.rule == "VSC104" for d in rep.errors)

    def test_residual_shape_mismatch_vsc105(self):
        net = SparseNet("bad", (
            Save("skip"),
            Conv("c0", 3, 64, 3, 3, stride=2),
            ResidualAdd("skip"),
        ))
        rep = check_net(net, (1, 16, 16, 3)).report
        assert any(d.rule == "VSC105" for d in rep.errors)

    def test_fc_fanin_mismatch_vsc106(self):
        net = SparseNet("bad", (
            Conv("c0", 3, 64, 3, 3),
            Pool(kind="gap"), Flatten(),
            FC("fc", 128, 10),
        ))
        rep = check_net(net, (1, 16, 16, 3)).report
        assert any(d.rule == "VSC106" for d in rep.errors)

    def test_channel_multiplier_vsc109(self):
        # multiplier-2 depthwise without allow_fallback is refused…
        net = _single_conv_net(32, 64, 3, 3, 1, 32, 1)
        rep = check_net(net, (1, 16, 16, 32)).report
        assert any(d.rule == "VSC109" for d in rep.errors)
        # …and downgraded to a warning (with a usable geometry) with it
        net = _single_conv_net(32, 64, 3, 3, 1, 32, 1, allow_fallback=True)
        nc = check_net(net, (1, 16, 16, 32))
        assert not nc.report.errors, nc.report.render()
        assert any(d.rule == "VSC109" for d in nc.report.warnings)
        assert nc.conv_sites[0].geom is not None


class TestContracts:
    @given(conv_geometries())
    @settings(max_examples=40, deadline=None)
    def test_random_geometry_proves_clean(self, geo):
        cin, cout, kh, kw, stride, groups, dilation, h, w, density = geo
        net = _single_conv_net(cin, cout, kh, kw, stride, groups, dilation)
        nc = check_net(net, (1, h, w, cin), density=density)
        assert not nc.report.errors, nc.report.render()
        rep, rows = check_contracts(nc)
        # zero errors here asserts: in-bounds proof (VSC201), exact byte
        # equality with the kernel CostEstimate (VSC202), traffic-model
        # agreement (VSC203), elision soundness (VSC204), FLOPs (VSC205)
        assert not rep.errors, rep.render()
        # halo + stack variants, each proved under both dtype contracts —
        # but a layer sparsify keeps dense (ungrouped, cin below vk) runs
        # its float path as one XLA dot: no f32 kernel plan
        site = nc.conv_sites[0]
        assert site.xla_float == (groups == 1 and cin < 32)
        tags = (":int8",) if site.xla_float else ("", ":int8")
        assert len(rows) == 2 * len(tags)
        assert sorted(r.path for r in rows) == sorted(
            f"{site.path}[{impl}{tag}]"
            for impl in ("halo", "stack") for tag in tags)

    @given(conv_geometries())
    @settings(max_examples=15, deadline=None)
    def test_real_encoding_within_abstract_bounds(self, geo):
        cin, cout, kh, kw, stride, groups, dilation, h, w, density = geo
        net = _single_conv_net(cin, cout, kh, kw, stride, groups, dilation)
        nc = check_net(net, (1, h, w, cin), density=density)
        site = nc.conv_sites[0]
        g = site.geom
        rng = np.random.default_rng(abs(hash(geo)) % 2**32)
        wd = rng.standard_normal(
            (kh, kw, cin // groups, cout)).astype(np.float32)
        spec, _ = sparse_conv_from_dense(
            wd, density, vk=g.vk if not g.depthwise else 32, vn=g.vn,
            stride=stride, groups=groups, dilation=dilation)
        real_idx = np.asarray(spec.vs.idx, np.int64)
        for impl in ("halo", "stack"):
            plan = conv_plan(
                site.x_shape, kh=kh, kw=kw, stride=stride, groups=groups,
                dilation=dilation, cout=cout, s_steps=real_idx.shape[1],
                vk=g.vk, vn=g.vn, impl=impl, has_bias=True,
                has_residual=False, itemsize=4)
            cbg = 1 if g.depthwise else (site.x_shape[3] // g.vk) // groups
            canon = canonical_conv_idx(plan.nb, plan.s_steps, cbg) \
                if plan.kind != "vsmm" else real_idx
            for buf in plan.buffers:
                # the interval proof is idx-independent: it must hold for
                # the real encoding because it held for AbstractIdx
                assert not _bounds_violations(plan, buf), (impl, buf.name)
                if buf.policy == "excluded":
                    continue
                offs = _offsets(plan, buf, real_idx)
                assert offs.min() >= 0
                budget = _contract_fetches(
                    plan, buf, _offsets(plan, buf, canon))
                if buf.name == "input":
                    # the cin-major store order keeps the faithful DMA
                    # count of ANY balanced encoding within the budget the
                    # canonical worst case sets
                    assert _faithful_fetches(offs) <= budget, \
                        (impl, buf.name)

    def test_canonical_idx_matches_real_full_density(self):
        # at density 1 the stored set is all kb tiles, so the real
        # cin-major order must equal canonical_conv_idx exactly
        kh, kw, cin, cout = 3, 3, 32, 128
        wd = np.random.default_rng(0).standard_normal(
            (kh, kw, cin, cout)).astype(np.float32)
        spec, _ = sparse_conv_from_dense(wd, 1.0, vk=32, vn=128)
        real = np.asarray(spec.vs.idx, np.int64)
        canon = canonical_conv_idx(real.shape[0], real.shape[1], cin // 32)
        np.testing.assert_array_equal(real, canon)

    @given(conv_geometries())
    @settings(max_examples=6, deadline=None)
    def test_executed_kernel_matches_planned_shape(self, geo):
        # the plan's geometry must describe the kernel that actually runs:
        # execute the real sparsified conv (interpret mode) and check the
        # output extents the IR walker predicted
        import jax.numpy as jnp

        from repro.kernels import vsconv

        cin, cout, kh, kw, stride, groups, dilation, h, w, density = geo
        net = _single_conv_net(cin, cout, kh, kw, stride, groups, dilation)
        nc = check_net(net, (1, h, w, cin), density=density)
        g = nc.conv_sites[0].geom
        rng = np.random.default_rng(abs(hash(geo)) % 2**32)
        wd = rng.standard_normal(
            (kh, kw, cin // groups, cout)).astype(np.float32)
        spec, _ = sparse_conv_from_dense(
            wd, density, vk=g.vk if not g.depthwise else 32, vn=g.vn,
            stride=stride, groups=groups, dilation=dilation)
        x = jnp.asarray(rng.standard_normal((1, h, w, cin)), jnp.float32)
        out = vsconv(x, spec.vs, kh=kh, kw=kw, stride=stride, groups=groups,
                     dilation=dilation, interpret=True)
        assert out.shape == (1, -(-h // stride), -(-w // stride), cout)

    def test_selftest_catches_every_seed(self, capsys):
        from repro.analysis.__main__ import run_selftest
        assert run_selftest(), capsys.readouterr().out


class TestLint:
    def test_impl_typo_vsc301(self):
        rep = Report()
        lint_source("y = vsconv(x, vs, impl='hallo')\n", "f.py", rep=rep)
        assert any(d.rule == "VSC301" for d in rep.errors)
        rep = Report()
        for good in sorted(IMPL_VOCAB):
            lint_source(f"y = vsconv(x, vs, impl='{good}')\n", "f.py",
                        rep=rep)
        assert not rep.errors

    def test_clock_in_scheduler_branch_vsc302(self):
        src = ("import time\n"
               "while time.monotonic() < deadline:\n    pass\n")
        rep = Report()
        lint_source(src, "replica_scheduler.py", rep=rep)
        assert any(d.rule == "VSC302" for d in rep.errors)
        rep = Report()  # same pattern outside scheduler files is fine
        lint_source(src, "bench.py", rep=rep)
        assert not rep.errors

    def test_env_mutation_vsc303_scoping(self):
        rep = Report()
        lint_source("import os\nos.environ['A'] = '1'\n", "f.py", rep=rep)
        assert any(d.rule == "VSC303" for d in rep.errors)
        # inside a function or the __main__ guard it's allowed
        rep = Report()
        lint_source(
            "import os\n"
            "def main():\n    os.environ['A'] = '1'\n"
            "if __name__ == '__main__':\n    os.environ['B'] = '2'\n",
            "f.py", rep=rep)
        assert not rep.errors
        # …but a module-scope try/if body still runs at import time
        rep = Report()
        lint_source(
            "import os\ntry:\n    os.environ['A'] = '1'\n"
            "except KeyError:\n    pass\n", "f.py", rep=rep)
        assert any(d.rule == "VSC303" for d in rep.errors)

    def test_blanket_except_in_launch_vsc304(self):
        src = ("try:\n    run.dispatch()\n"
               "except Exception:\n    pass\n")
        rep = Report()
        lint_source(src, "src/repro/launch/scheduler.py", rep=rep)
        assert any(d.rule == "VSC304" for d in rep.errors)
        # bare except and tuple-smuggled blankets are caught too
        for body in ("except:", "except (ValueError, BaseException):"):
            rep = Report()
            lint_source(f"try:\n    f()\n{body}\n    pass\n",
                        "src/repro/launch/serve.py", rep=rep)
            assert any(d.rule == "VSC304" for d in rep.errors), body
        # typed handlers in launch are fine
        rep = Report()
        lint_source("try:\n    f()\nexcept (ValueError, KeyError):\n"
                    "    pass\n", "src/repro/launch/serve.py", rep=rep)
        assert not rep.errors
        # the same blanket outside launch/ is out of scope
        rep = Report()
        lint_source(src, "src/repro/kernels/ops.py", rep=rep)
        assert not rep.errors
        # waivers work for VSC304 like the other lint rules
        rep = Report()
        lint_source("try:\n    f()\n"
                    "# vscheck: ignore[VSC304] - sweep driver\n"
                    "except Exception:\n    pass\n",
                    "src/repro/launch/dryrun.py", rep=rep)
        assert not rep.errors

    def test_inline_waiver_covers_next_line(self):
        rep = Report()
        lint_source(
            "import os\n"
            "# vscheck: ignore[VSC303] - must precede the jax import\n"
            "os.environ['XLA_FLAGS'] = '-x'\n", "f.py", rep=rep)
        assert not rep.errors


class TestServeGate:
    def test_validate_net_refuses_malformed(self):
        from repro.launch.serve import validate_net
        net = SparseNet("bad", (Conv("c0", 3, 64, 3, 3),
                                Conv("c1", 32, 64, 3, 3)))
        with pytest.raises(VSCheckError) as ei:
            validate_net(net, 32)
        assert any(d.rule == "VSC101" for d in ei.value.diagnostics)

    def test_validate_net_accepts_registered(self):
        from repro.analysis.__main__ import NETS
        from repro.launch.serve import validate_net
        validate_net(NETS["resnet18"](image_size=32), 32)

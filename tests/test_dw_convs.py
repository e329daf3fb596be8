"""`BatchedApply.dw_convs`: the depthwise conv layers each served
executable runs, one `vsconv_dw_halo_pallas` launch each on a TPU,
recorded on the ``backend.launch`` span beside ``xla_convs``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import spans
from repro.launch.serve import CNNServer, ImageRequest
from repro.models import graph as G
from repro.models.layers import init_params

# (arch, depthwise convs, convs run through XLA) at the reduced size
NETS = [("vscnn-mobilenet-v1", 13, 1), ("vscnn-resnet50", 0, 1)]


@pytest.mark.parametrize("conv,depthwise", [
    (G.Conv("dw", 32, 32, 3, 3, groups=32), True),
    (G.Conv("dw_s2", 64, 64, 3, 3, stride=2, groups=64, bn=True), True),
    (G.Conv("grouped", 32, 64, 3, 3, groups=2), False),
    (G.Conv("pruned", 32, 64, 3, 3), False),
    (G.Conv("pointwise", 64, 128, 1, 1), False),
    (G.Conv("stem", 3, 32, 3, 3, stride=2, bn=True), False),
], ids=lambda v: getattr(v, "name", str(v)))
def test_is_depthwise_conv(conv, depthwise):
    net = G.SparseNet("one", (conv,))
    params = init_params(net.schema(), jax.random.PRNGKey(0), jnp.float32)
    sparse, _ = G.sparsify(net, params, 0.5)
    assert G.is_depthwise_conv(sparse[conv.name]) is depthwise


@pytest.fixture(scope="module")
def servers():
    return {arch: CNNServer(get_config(arch).reduce(), batch=1, seed=0)
            for arch, _, _ in NETS}


@pytest.mark.parametrize("arch,dw,xla", NETS, ids=[n[0] for n in NETS])
def test_counts_fixed_at_build_whatever_the_impl(servers, arch, dw, xla):
    srv = servers[arch]
    assert srv.backend.apply.dw_convs == dw
    assert srv.backend.apply.xla_convs == xla
    for impl in ("jnp", "pallas", "pallas-stack"):
        apply = G.BatchedApply(srv.net, srv.params, sparse=srv.sparse,
                               impl=impl)
        assert apply.dw_convs == dw


@pytest.mark.parametrize("arch,dw,xla", NETS, ids=[n[0] for n in NETS])
def test_launch_span_records_dw_convs(servers, arch, dw, xla, tmp_path):
    srv = servers[arch]
    s = srv.cfg.image_size
    req = ImageRequest(rid=0, image=np.zeros((s, s, 3), np.float32))
    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        srv.serve([req])
    launches = [sp for sp in spans.recorded() if sp.name == "backend.launch"]
    spans.clear()
    assert srv.outcomes[0].status == "delivered"
    assert [(sp.attrs["dw_convs"], sp.attrs["xla_convs"])
            for sp in launches] == [(dw, xla)]

"""The MobileNetV1 configuration and the depthwise per-layer metrics.

* ``dw_roofline`` and ``dw_ms_per_image`` on a synthetic trace summary,
  against a hand count of the depthwise layers' work;
* neither reads a number when no depthwise kernel ran, and ``dw_roofline``
  none when the layers counted differ from the program's ``dw_convs``;
* the committed configuration, cut to 32 px and 16 classes: the program
  is within its limit of the reference at highest precision, and the
  bf16x3 control is not.
"""
import json
import types

import numpy as np
import pytest

from harness import check, trace, work
from harness.reference import Reference, network
from harness.spec import ROOT, load_cell, load_config, metric_reader
from repro.launch import spans

SEED = 2**31 + 321
CONFIG = "mobilenet-v1-f32"
KERNEL = "vsconv_dw_halo_pallas"
PEAKS = (197e12, 819e9)         # v5e: bf16 FLOP/s, HBM bytes/s
WAVES = [32, 32, 32, 32, 5]     # images per traced wave
CALLS = [(0.0, 1.0, 128), (1.0, 1.5, 5)]  # (start s, end s, requests)


def _summary(layers: dict) -> trace.TraceSummary:
    pallas = sum(lt.pallas_ns for lt in layers.values())
    other = sum(lt.other_ns for lt in layers.values())
    return trace.TraceSummary(
        window_ns=2e9, busy_ns=pallas + other, pallas_ns=pallas,
        other_ns=other, pallas_events=0, device_ops=[], idle_gaps=[],
        layers=layers)


def _layers(dw_kernel: str = KERNEL) -> dict:
    lt = trace.LayerTime
    return {
        "conv0": lt(other_ns=3e6),
        "dw1": lt(pallas_ns=20e6, other_ns=5e6, kernels={dw_kernel}),
        "pw1": lt(pallas_ns=9e6, other_ns=1e6, kernels={"vsmm_pallas"}),
        "dw2": lt(pallas_ns=12e6, other_ns=4e6, kernels={dw_kernel}),
        "pw2": lt(pallas_ns=7e6, kernels={"vsmm_pallas"}),
        None: lt(other_ns=2e6),
    }


def _ctx(summary, config):
    calls = list(CALLS)
    return types.SimpleNamespace(
        traced=True, summary=summary, work=work.network_work(
            network(config), config),
        record=types.SimpleNamespace(calls=calls),
        traced_calls=lambda: calls, traced_images=133,
        traced_waves=lambda: list(WAVES), peaks=lambda: PEAKS)


def _span(sid, name, start_s, end_s, parent=None, **attrs):
    return types.SimpleNamespace(
        id=sid, parent=parent, name=name, start_ns=int(start_s * 1e9),
        end_ns=int(end_s * 1e9), attrs=attrs)


@pytest.fixture
def launches(monkeypatch):
    """Records one ``backend.launch`` span per wave of each call in
    `CALLS`, each carrying the given attrs."""
    def record(**attrs):
        out, sid = [], 1
        for t0, t1, _ in CALLS:
            top = sid
            for k in range(2):
                out.append(_span(sid + 1 + k, "backend.launch", t0, t0,
                                 top, **attrs))
            out.append(_span(top, "scheduler.serve", t0, t1, requests=1))
            sid += 3
        monkeypatch.setattr(spans, "recorded", lambda: list(out))
    return record


def _hand_roofline_s(config) -> float:
    """dw1: 32 channels, 112x112 -> 112x112; dw2: 64 channels, 112x112 ->
    56x56 (stride 2); each keeps round(9 * 0.5) = 4 taps per channel."""
    out = 0.0
    for c, hi, ho in ((32, 112, 112), (64, 112, 56)):
        flops = 2 * 4 * c * ho * ho
        weight_bytes = 4 * c * 4
        act_bytes = (hi * hi * c + ho * ho * c) * 4
        out += sum(max(flops * n / PEAKS[0],
                       (weight_bytes + act_bytes * n) / PEAKS[1])
                   for n in WAVES)
    return out


@pytest.fixture(scope="module")
def config():
    return load_config(CONFIG)


def test_dw_roofline_hand_count(config, launches):
    launches(dw_convs=2, xla_convs=1)
    got = metric_reader("dw_roofline")(_ctx(_summary(_layers()), config))
    # both layers are bound by bandwidth; their kernels took 32 ms
    assert got == pytest.approx(100 * _hand_roofline_s(config) / 32e-3,
                                rel=1e-12)
    assert 0 < got < 100


def test_dw_ms_per_image_hand_count(config):
    got = metric_reader("dw_ms_per_image")(_ctx(_summary(_layers()), config))
    # dw1 20 + 5 ms, dw2 12 + 4 ms, over 133 images
    assert got == pytest.approx(41 / 133, rel=1e-12)


def test_nothing_read_without_a_depthwise_kernel(config, launches):
    launches(dw_convs=2, xla_convs=1)
    ctx = _ctx(_summary(_layers(dw_kernel="vsconv_halo_pallas")), config)
    assert metric_reader("dw_roofline")(ctx) is None
    assert metric_reader("dw_ms_per_image")(ctx) is None


@pytest.mark.parametrize("attrs", [
    dict(dw_convs=13, xla_convs=1),   # layers the trace missed
    dict(dw_convs=1, xla_convs=1),
    dict(xla_convs=1),                # a program that records no count
], ids=["13", "1", "unrecorded"])
def test_dw_roofline_reads_nothing_when_the_count_disagrees(
        config, launches, attrs):
    launches(**attrs)
    ctx = _ctx(_summary(_layers()), config)
    assert metric_reader("dw_roofline")(ctx) is None
    assert metric_reader("dw_ms_per_image")(ctx) is not None


def test_untraced_run_reads_nothing(config):
    ctx = _ctx(None, config)
    ctx.traced = False
    for name in ("dw_roofline", "dw_ms_per_image"):
        assert metric_reader(name)(ctx) is None


def test_cell_and_configuration(config):
    cell = load_cell("mobilenet-v1-offline")
    assert cell.chips == 1 and cell.config == config
    assert {"dw_roofline", "dw_ms_per_image"} <= \
        {m["name"] for m in cell.per_layer}
    assert [m["name"] for m in cell.end_to_end] == ["images_per_s",
                                                    "setup_s"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == []
    # every depthwise layer of the reference is one the metrics can name
    dw = [l["name"] for l in network(config) if l.get("groups", 1) > 1]
    assert dw == [f"dw{i}" for i in range(1, 14)]


def test_served_parameters_match_the_file(config):
    from repro.configs import get_config
    from repro.models import graph as G

    net = get_config(config["arch"]).build()
    counted = sum(int(np.prod(p.shape))
                  for e in G.net_schema(net).values()
                  for k, p in e.items() if k not in ("mean", "var"))
    assert counted == config["parameters"]


def test_program_within_the_limit_and_control_beyond_it(config):
    import dataclasses

    from repro.configs import get_config
    from repro.launch.serve import CNNServer, ImageRequest

    small = dict(config, image_size=32, num_classes=16)
    images = np.random.default_rng(1).standard_normal(
        (6, 32, 32, 3)).astype(np.float32)
    ref = Reference(small, SEED, block=4)
    want = ref.logits(images, "highest")
    cfg = dataclasses.replace(get_config(small["arch"]), image_size=32,
                              num_classes=16)
    srv = CNNServer(cfg, batch=4, seed=SEED)
    reqs = [ImageRequest(rid=i, image=im) for i, im in enumerate(images)]
    srv.serve(reqs)
    limit = small["limits"]["logit_gap"]
    assert max(check.relative_gap(r.logits, w)
               for r, w in zip(reqs, want)) <= limit
    ctl = ref.logits(images, "bf16x3")
    assert max(check.relative_gap(c, w) for c, w in zip(ctl, want)) > limit

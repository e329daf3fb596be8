"""The work count against a hand count, the real nets' totals, and the
peak table."""
import pytest

from harness import reference, work
from harness.layers import conv, fc, flatten, pool, save

TOY = [
    conv("c1", 3, 32, 3),                 # cin < vk: kept dense
    conv("c2", 32, 64, 3, 2),             # pruned, stride 2
    save("s"),
    conv("c3", 64, 64, 1, residual="s"),  # 1x1 with a residual
    pool("gap"), flatten(),
    fc("f", 64, 10, relu=False),          # one strip of 10 real columns
]
TOY_CONFIG = dict(image_size=32, num_classes=10, weight_density=0.25, vk=32,
                  vn=128, sparse=True, dtype="float32")


def test_toy_net_hand_count():
    got = {lw.name: lw for lw in work.network_work(TOY, TOY_CONFIG)}
    # c1: 3*3*3*32 weights, all kept; 32x32 outputs
    assert got["c1"].flops == 2 * 864 * 32 * 32
    assert got["c1"].weight_bytes == 864 * 4
    assert got["c1"].act_bytes == (32 * 32 * 3 + 32 * 32 * 32) * 4
    # c2: K = 9*32 = 288 -> 9 tiles of 32 rows, round(9 * 0.25) = 2 kept
    # per strip; one 64-wide strip; 16x16 outputs
    assert got["c2"].flops == 2 * (2 * 32 * 64) * 16 * 16
    assert got["c2"].act_bytes == (32 * 32 * 32 + 16 * 16 * 64) * 4
    # c3: K = 64 -> 2 tiles, round(0.5) = 0 -> at least 1 kept; residual
    assert got["c3"].flops == 2 * (1 * 32 * 64) * 16 * 16
    assert got["c3"].act_bytes == (16 * 16 * 64 + 2 * 16 * 16 * 64) * 4
    # f: K = 64 -> 1 tile kept, 10 real columns of its strip
    assert got["f"].flops == 2 * 32 * 10
    assert got["f"].act_bytes == (64 + 10) * 4
    dense = work.network_work(TOY, dict(TOY_CONFIG, sparse=False))
    assert [lw.flops for lw in dense] == [
        2 * 864 * 1024, 2 * 9 * 32 * 64 * 256, 2 * 64 * 64 * 256, 2 * 640]


TOY_DW = [
    conv("c0", 3, 32, 3, 2),                 # 32 -> 16, kept dense
    conv("dw1", 32, 32, 3, groups=32),       # depthwise, stride 1
    conv("pw1", 32, 64, 1),                  # pointwise
    conv("dw2", 64, 64, 3, 2, groups=64),    # depthwise, 16 -> 8
    pool("gap"), flatten(),
    fc("f", 64, 10, relu=False),
]


@pytest.mark.parametrize("density,taps", [(0.5, 4), (0.25, 2), (0.05, 1)])
def test_toy_depthwise_separable_hand_count(density, taps):
    cfg = dict(TOY_CONFIG, weight_density=density)
    got = {lw.name: lw for lw in work.network_work(TOY_DW, cfg)}
    # dw1: the (9, 32) tap matrix, round(9 * density) taps kept per
    # channel (at least 1; round(4.5) is 4); 16x16 outputs
    assert got["dw1"].weight_bytes == taps * 32 * 4
    assert got["dw1"].flops == 2 * taps * 32 * 16 * 16
    assert got["dw1"].act_bytes == (16 * 16 * 32 + 16 * 16 * 32) * 4
    # pw1: K = 32 -> one tile, kept whatever the density
    assert got["pw1"].flops == 2 * 32 * 64 * 16 * 16
    # dw2: 64 channels, stride 2: reads 16x16, writes 8x8
    assert got["dw2"].flops == 2 * taps * 64 * 8 * 8
    assert got["dw2"].act_bytes == (16 * 16 * 64 + 8 * 8 * 64) * 4
    dense = {lw.name: lw for lw in
             work.network_work(TOY_DW, dict(cfg, sparse=False))}
    assert dense["dw1"].weight_bytes == 9 * 32 * 4
    assert dense["dw2"].flops == 2 * 9 * 64 * 8 * 8


def test_grouped_conv_is_refused():
    net = [conv("g", 32, 32, 3, groups=4)]
    with pytest.raises(ValueError, match="depthwise"):
        work.network_work(net, TOY_CONFIG)
    with pytest.raises(ValueError, match="depthwise"):
        reference.pruned_weights(net, TOY_CONFIG, 0)


# MobileNetV1 at 224 by hand: (channels in, channels out, output side) of
# each depthwise-separable block after the 3x3/s2 stem of 32 channels
MOBILENET_BLOCKS = ([(32, 64, 112), (64, 128, 56), (128, 128, 56),
                     (128, 256, 28), (256, 256, 28), (256, 512, 14)]
                    + [(512, 512, 14)] * 5
                    + [(512, 1024, 7), (1024, 1024, 7)])


def _mobilenet_hand_count(density: float | None) -> tuple[int, int]:
    """(weights, FLOPs) per image; ``density`` None is the dense net.  At
    0.5: the stem keeps all 864 weights (3 channels < vk), a depthwise
    conv round(4.5) = 4 of its 9 taps, a pointwise conv max(1,
    round(cin/32 * 0.5)) K-tiles of 32 rows, the fc 16 of 32 tiles."""
    def rows(k):  # kept rows of a (k, cout) matrix in 32-row tiles
        return k if density is None else max(1, round(k / 32 * density)) * 32
    taps = 9 if density is None else round(9 * density)
    weights = flops = 27 * 32
    flops *= 112 * 112
    for cin, cout, side in MOBILENET_BLOCKS:
        w = taps * cin + rows(cin) * cout
        weights += w
        flops += w * side * side
    weights += rows(1024) * 1000
    flops += rows(1024) * 1000
    return weights, 2 * flops


def test_mobilenet_hand_count():
    assert _mobilenet_hand_count(None) == (4_209_088, 1_137_480_704)
    assert _mobilenet_hand_count(0.5)[1] == 603_336_704


@pytest.mark.parametrize("arch,params,dense_gflop,sparse_gflop", [
    ("resnet50", 25_502_912, 8.178, 2.254),
    ("vgg16", 138_344_128, 30.941, 7.283),
    ("mobilenet_v1", 4_209_088, 1.137, 0.603),  # _mobilenet_hand_count
])
def test_published_nets(arch, params, dense_gflop, sparse_gflop):
    density = 0.5 if arch == "mobilenet_v1" else 0.235
    cfg = dict(reference=arch, image_size=224, num_classes=1000,
               weight_density=density, vk=32, vn=128, sparse=False,
               dtype="float32")
    layers = reference.network(cfg)
    dense = work.network_work(layers, cfg)
    assert sum(lw.weight_bytes for lw in dense) // 4 == params  # no biases
    assert sum(lw.flops for lw in dense) / 1e9 == pytest.approx(
        dense_gflop, abs=1e-3)
    sparse = work.network_work(layers, dict(cfg, sparse=True))
    assert sum(lw.flops for lw in sparse) / 1e9 == pytest.approx(
        sparse_gflop, abs=1e-3)


def test_peaks_and_roofline():
    flops, bw = work.load_peaks("TPU v5 lite", "float32")
    assert (flops, bw) == (197e12, 819e9)
    assert work.load_peaks("TPU v5 lite", "int8")[0] == 393e12
    with pytest.raises(KeyError, match="no peaks"):
        work.load_peaks("TPU v9 imaginary", "float32")
    lw = work.LayerWork("x", flops=100, weight_bytes=10, act_bytes=1)
    # compute-bound wave of 4: 400 FLOP at 100/s; memory 14 B at 10 B/s
    assert work.roofline_seconds([lw], [4], 100.0, 10.0) == pytest.approx(4)
    assert work.roofline_seconds([lw], [4, 0], 1000.0, 10.0) == \
        pytest.approx(1.4)

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


@pytest.mark.parametrize("arch,params,dense_gflop,sparse_gflop", [
    ("resnet50", 25_502_912, 8.178, 2.254),
    ("vgg16", 138_344_128, 30.941, 7.283),
])
def test_published_nets(arch, params, dense_gflop, sparse_gflop):
    cfg = dict(reference=arch, image_size=224, num_classes=1000,
               weight_density=0.235, vk=32, vn=128, sparse=False,
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

"""MobileNetV1 1.0 (Howard et al. 2017, arXiv:1704.04861, Table 1): a 3x3/s2
stem of 32 channels, then 13 depthwise-separable blocks, each a 3x3
depthwise conv and a 1x1 pointwise conv with BN and ReLU after both, a
global average pool and a 1024 -> classes classifier.  Table 1 lists the
last depthwise conv as s2 on a 7x7 map whose output stays 7x7: it runs at
stride 1.  Padding is SAME throughout, as the served model states."""
from __future__ import annotations

from harness.layers import conv, fc, flatten, pool

# (pointwise output channels, depthwise stride) of each block
BLOCKS = ((64, 1), (128, 2), (128, 1), (256, 2), (256, 1), (512, 2),
          (512, 1), (512, 1), (512, 1), (512, 1), (512, 1), (1024, 2),
          (1024, 1))


def layers(num_classes: int, image_size: int) -> list[dict]:
    del image_size  # global average pool: any input size
    out, cin = [conv("conv0", 3, 32, 3, 2, bn=True)], 32
    for i, (c, stride) in enumerate(BLOCKS, 1):
        out += [conv(f"dw{i}", cin, cin, 3, stride, bn=True, groups=cin),
                conv(f"pw{i}", cin, c, 1, bn=True)]
        cin = c
    return out + [pool("gap"), flatten(),
                  fc("fc", 1024, num_classes, relu=False)]

"""VGG-16 (Simonyan & Zisserman 2014, configuration D): thirteen 3x3 convs
in five stages with 2x2 max-pools, then three fully connected layers over
the flattened 512 x (image_size/32)^2 features.  No BN."""
from __future__ import annotations

from harness.layers import conv, fc, flatten, pool

PLAN = (64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
        512, 512, 512, "M", 512, 512, 512, "M")


def layers(num_classes: int, image_size: int) -> list[dict]:
    out, cin, i = [], 3, 1
    for c in PLAN:
        if c == "M":
            out.append(pool("max", 2))
        else:
            out.append(conv(f"conv{i}", cin, c, 3))
            cin, i = c, i + 1
    return out + [flatten(),
                  fc("fc1", 512 * (image_size // 32) ** 2, 4096),
                  fc("fc2", 4096, 4096),
                  fc("fc3", 4096, num_classes, relu=False)]

"""ResNet-50 (He et al. 2015; torchvision ``resnet50``): 7x7/s2 stem,
3x3/s2 max-pool, bottleneck stages of (3, 4, 6, 3) blocks (1x1 -> 3x3 ->
1x1, 4x expansion, stride on the 3x3, a 1x1 projection shortcut where the
shape changes), global average pool, classifier; BN after every conv.
Padding is SAME throughout, as the served model states."""
from __future__ import annotations

from harness.layers import conv, fc, flatten, pool, save

STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))


def layers(num_classes: int, image_size: int) -> list[dict]:
    del image_size  # global average pool: any input size
    out = [conv("conv1", 3, 64, 7, 2, bn=True), pool("max", 3, 2, "SAME")]
    cin = 64
    for si, (c, blocks) in enumerate(STAGES):
        for bi in range(blocks):
            stride = 2 if si > 0 and bi == 0 else 1
            p = f"layer{si + 1}_{bi}"
            out.append(save(f"{p}_in"))
            idkey = f"{p}_in"
            if stride != 1 or cin != 4 * c:
                idkey = f"{p}_id"
                out.append(conv(f"{p}_down", cin, 4 * c, 1, stride, bn=True,
                                relu=False, src=f"{p}_in", dst=idkey))
            out += [conv(f"{p}_conv1", cin, c, 1, bn=True),
                    conv(f"{p}_conv2", c, c, 3, stride, bn=True),
                    conv(f"{p}_conv3", c, 4 * c, 1, bn=True, residual=idkey)]
            cin = 4 * c
    return out + [pool("gap"), flatten(),
                  fc("fc", 2048, num_classes, relu=False)]

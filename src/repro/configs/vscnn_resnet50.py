"""ResNet-50 on the vector-sparse datapath — the headline benchmark shared
with SCNN (Parashar et al.) and the structured-sparse FPGA accelerator
(Zhu et al.).

The bottleneck block (1x1 reduce -> 3x3 -> 1x1 expand, 4x expansion) was
already expressible in the kernel family; `models.graph.build_resnet50`
wires it.  Same pruning recipe and PE configurations as the paper's VGG-16
setup; BN folds into the conv weights/bias at sparsify time and residual
adds ride the kernels' fused epilogue, so every conv and FC layer but the
dense 3-channel stem (one XLA dot, `graph.keeps_dense`) runs the single
sparse datapath end-to-end.
"""
from __future__ import annotations

import dataclasses

from repro.core.accel_model import PEConfig, PE_4_14_3, PE_8_7_3


@dataclasses.dataclass(frozen=True)
class VSCNNResNet50Config:
    name: str = "vscnn-resnet50"
    modality: str = "cnn"           # servable arch: image requests, not tokens
    image_size: int = 224
    num_classes: int = 1000
    weight_density: float = 0.235   # the paper's vector-pruning operating point
    vk: int = 32                    # TPU kernel vector length (K-tile)
    vn: int = 128                   # output strip width
    # GAP head: geometry is size-agnostic, so serving buckets pad images to
    # the nearest shape bucket instead of one fixed size
    fixed_image_size: bool = False
    pe_configs: tuple[PEConfig, ...] = (PE_4_14_3, PE_8_7_3)

    def reduce(self) -> "VSCNNResNet50Config":
        # num_classes=200 keeps a non-tileable head (200 % 128 != 0): the
        # FC remainder strip stays exercised even in the reduced config.
        return dataclasses.replace(self, image_size=32, num_classes=200)

    def build(self):
        """The servable network: `models.graph.SparseNet` for this config."""
        from repro.models.graph import build_resnet50
        return build_resnet50(self.num_classes, image_size=self.image_size)


CONFIG = VSCNNResNet50Config()

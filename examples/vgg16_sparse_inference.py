"""End-to-end driver (the paper's kind: sparse CNN *inference*).

Pipeline: build VGG-16 -> vector-prune to the paper's 23.5% density ->
serve batched image requests through the vector-sparse path (structural op
or Pallas kernel) -> report agreement with the dense oracle and the
simulated accelerator cycle counts for the same traffic (Figs 12/13).

Run:  PYTHONPATH=src python examples/vgg16_sparse_inference.py [--size 64]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.vscnn_vgg16 import CONFIG
from repro.core.accel_model import PE_4_14_3, PE_8_7_3, aggregate
from repro.data import SyntheticImages
from repro.models.cnn import sparsify_vgg16, vgg16_apply, vgg16_schema
from repro.models.graph import runs_xla_conv
from repro.models.layers import init_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=64,
                    help="image resolution (224 = paper scale)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--impl", choices=("jnp", "pallas"), default="jnp")
    args = ap.parse_args()

    print(f"== VGG-16 vector-sparse inference @ {args.size}px, "
          f"density {CONFIG.weight_density} ==")
    params = init_params(vgg16_schema(1000, image_size=args.size),
                         jax.random.PRNGKey(0), jnp.float32)
    sparse, pruned = sparsify_vgg16(params, CONFIG.weight_density,
                                    vk=CONFIG.vk, vn=CONFIG.vn)
    n_conv = sum(1 for k in sparse if k.startswith("conv"))
    n_xla = sum(runs_xla_conv(e, args.impl) for e in sparse.values())
    print(f"sparsified {len(sparse)} layers — {n_conv - n_xla}/13 convs + FC "
          f"run the vector-sparse path; XLA runs {n_xla} (the dense "
          f"3-channel stem)")

    data = SyntheticImages(args.batch, size=args.size)
    imgs = jnp.asarray(data.batch_at(0)["images"])

    dense_fn = jax.jit(lambda x: vgg16_apply(pruned, x))
    sparse_fn = jax.jit(lambda x: vgg16_apply(params, x, sparse=sparse,
                                              impl=args.impl))
    y_dense = dense_fn(imgs)
    t0 = time.time()
    y_sparse = sparse_fn(imgs)
    y_sparse.block_until_ready()
    dt = time.time() - t0
    rel = float(jnp.abs(y_sparse - y_dense).max() / jnp.abs(y_dense).max())
    print(f"sparse ({args.impl}) vs pruned-dense: rel err {rel:.2e}  "
          f"({dt*1e3:.0f} ms for batch {args.batch})")

    # accelerator cycle accounting for the same traffic — the per-layer
    # graph walk shared with ResNet-18 (see resnet18_sparse_inference.py)
    from repro.core.accel_model import network_cycle_reports
    from repro.models.graph import build_vgg16, collect_conv_traffic
    traffic = collect_conv_traffic(build_vgg16(), pruned, imgs[:1])
    for pe in (PE_4_14_3, PE_8_7_3):
        reports = network_cycle_reports(traffic, pe)
        agg = aggregate([r for _, r in reports])
        print(f"PE [{pe.blocks},{pe.rows},{pe.cols}]: "
              f"{agg.speedup:.2f}x speedup over dense "
              f"({agg.vscnn:,} vs {agg.dense:,} cycles; paper: 1.87-1.93x)")


if __name__ == "__main__":
    main()

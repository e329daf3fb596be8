"""End-to-end ResNet-18 on the vector-sparse datapath.

Pipeline: build ResNet-18 from the graph IR -> fold BN into the conv
weights/bias and vector-prune to the paper's density -> run every conv and
FC layer (residual adds fused in the kernel epilogue) through the sparse
path -> report agreement with the folded-pruned dense oracle and the
simulated accelerator per-layer cycle counts, the same analysis walk VGG-16
uses.

Run:  PYTHONPATH=src python examples/resnet18_sparse_inference.py [--size 64]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.vscnn_resnet18 import CONFIG
from repro.core.accel_model import aggregate, network_cycle_reports
from repro.data import SyntheticImages
from repro.models.graph import (
    build_resnet18, collect_conv_traffic, net_apply, runs_xla_conv, sparsify,
)
from repro.models.layers import init_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=64,
                    help="image resolution (224 = ImageNet scale)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--impl", choices=("jnp", "pallas"), default="jnp")
    ap.add_argument("--classes", type=int, default=CONFIG.num_classes)
    args = ap.parse_args()

    print(f"== ResNet-18 vector-sparse inference @ {args.size}px, "
          f"density {CONFIG.weight_density} ==")
    net = build_resnet18(args.classes, image_size=args.size)
    params = init_params(net.schema(), jax.random.PRNGKey(0), jnp.float32)
    sparse, pruned = sparsify(net, params, CONFIG.weight_density,
                              vk=CONFIG.vk, vn=CONFIG.vn)
    n_conv = len(net.conv_layers())
    n_xla = sum(runs_xla_conv(e, args.impl) for e in sparse.values())
    print(f"sparsified {len(sparse)} layers — {n_conv - n_xla}/{n_conv} convs "
          f"(BN folded, residuals fused in-epilogue) + the {args.classes}-class "
          f"head (remainder strip) run the vector-sparse path; XLA runs "
          f"{n_xla} (the dense 3-channel stem)")

    data = SyntheticImages(args.batch, size=args.size)
    imgs = jnp.asarray(data.batch_at(0)["images"])

    dense_fn = jax.jit(lambda x: net_apply(net, pruned, x))
    sparse_fn = jax.jit(lambda x: net_apply(net, params, x, sparse=sparse,
                                            impl=args.impl))
    y_dense = dense_fn(imgs)
    t0 = time.time()
    y_sparse = sparse_fn(imgs)
    y_sparse.block_until_ready()
    dt = time.time() - t0
    rel = float(jnp.abs(y_sparse - y_dense).max() / jnp.abs(y_dense).max())
    print(f"sparse ({args.impl}) vs folded-pruned dense: rel err {rel:.2e}  "
          f"({dt*1e3:.0f} ms for batch {args.batch})")

    # per-layer accelerator cycle accounting for the same traffic — the
    # graph walk VGG-16 shares
    traffic = collect_conv_traffic(net, pruned, imgs[:1])
    for pe in CONFIG.pe_configs:
        reports = network_cycle_reports(traffic, pe)
        agg = aggregate([r for _, r in reports])
        worst = min(reports, key=lambda nr: nr[1].speedup)
        best = max(reports, key=lambda nr: nr[1].speedup)
        print(f"PE [{pe.blocks},{pe.rows},{pe.cols}]: "
              f"{agg.speedup:.2f}x speedup over dense "
              f"({agg.vscnn:,} vs {agg.dense:,} cycles; "
              f"best layer {best[0]} {best[1].speedup:.2f}x, "
              f"worst {worst[0]} {worst[1].speedup:.2f}x)")


if __name__ == "__main__":
    main()

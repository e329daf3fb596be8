"""Batched serving on CPU (reduced configs) through the scheduler loop.

LM mode (default): the production prefill/decode jits with continuous
batching — EOS/budget retirement and in-run slot backfill — the same step
functions the decode_32k / long_500k dry-run cells lower on the 512-chip
mesh.

CNN mode (--cnn): image requests through `SparseNet.apply` on the
vector-sparse datapath, batches padded/bucketed on image shape, freed slots
backfilled from the queue so the compiled batch shape is reused wave after
wave.

Multi-device: ``--replicas N`` serves a data-parallel replica fleet (one
device-placed weight copy per replica, per-replica wave dispatch, work
stealing); ``--shard-fc`` additionally cout-shards the FC heads over each
replica's leftover devices.  On a CPU-only box fake a mesh with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Run:  PYTHONPATH=src python examples/serve_batched.py --arch rwkv6-3b
      PYTHONPATH=src python examples/serve_batched.py --cnn vscnn-vgg16
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/serve_batched.py --cnn vscnn-vgg16 \\
          --replicas 4 --shard-fc
"""
import argparse

import numpy as np

from repro.configs import get_config, list_archs, list_cnn_archs
from repro.launch.serve import CNNServer, ImageRequest, Request, Server


def serve_cnn(args) -> None:
    cfg = get_config(args.cnn).reduce()
    rng = np.random.default_rng(0)
    s = cfg.image_size
    # mixed sizes exercise the shape bucketing; fixed-input nets (VGG) pad
    # everything up to image_size, size-agnostic nets (ResNet) get one
    # bucket per padded shape
    sizes = [s if i % 3 else max(8, s // 2) for i in range(args.requests)]
    reqs = [ImageRequest(rid=i,
                         image=rng.standard_normal((sz, sz, 3))
                                  .astype(np.float32))
            for i, sz in enumerate(sizes)]
    srv = CNNServer(cfg, batch=args.batch, replicas=args.replicas,
                    shard_fc=args.shard_fc)
    stats = srv.serve(reqs)
    total = sum(st["images"] for st in stats)
    run_s = sum(st["run_s"] for st in stats)
    backfills = sum(st["backfills"] for st in stats)
    used = sorted({st.get("replica", 0) for st in stats})
    print(f"served {total} images in {len(stats)} runs "
          f"({backfills} backfills), {total / max(run_s, 1e-9):.1f} img/s "
          f"(density {srv.density}, replicas used {used}, "
          f"shard_fc={args.shard_fc}; CPU, reduced config)")
    print("first request prediction:", reqs[0].out)


def serve_lm(args) -> None:
    cfg = get_config(args.arch).reduce()
    if not cfg.embed_inputs or cfg.encoder_only:
        raise SystemExit(f"{cfg.name}: choose a token-input decoder arch")
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(8, 40)),
                                        dtype=np.int32),
                    max_new=args.tokens,
                    temperature=args.temperature, top_k=args.top_k)
            for i in range(args.requests)]
    srv = Server(cfg, batch=args.batch, capacity=80)
    stats = srv.serve(reqs)
    total = sum(s["new_tokens"] for s in stats)
    dec_s = sum(s["decode_s"] for s in stats)
    backfills = sum(s["backfills"] for s in stats)
    print(f"served {args.requests} requests in {len(stats)} runs "
          f"({backfills} backfills)")
    print(f"{total} tokens generated, decode throughput "
          f"{total / max(dec_s, 1e-9):.1f} tok/s (CPU, reduced config)")
    print("first request output:", reqs[0].out[:12], "...")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-3b", choices=list_archs())
    ap.add_argument("--cnn", default=None, choices=list_cnn_archs(),
                    help="serve a CNN arch through SparseNet.apply instead "
                         "of the LM stack")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--replicas", type=int, default=1,
                    help="CNN mode: data-parallel replica fleet size")
    ap.add_argument("--shard-fc", action="store_true",
                    help="CNN mode: cout-shard FC heads over each "
                         "replica's model devices")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="LM mode: sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="LM mode: top-k cutoff (0 = full vocab)")
    args = ap.parse_args()
    if args.cnn:
        serve_cnn(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()

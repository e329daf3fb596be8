#!/usr/bin/env python3
"""Chip smoke run: sparse ResNet-50 served at its published size on a TPU.

Drives the serving path a user calls — `CNNServer` -> `LockstepScheduler`
-> `BatchedApply` / `net_apply` -> the Pallas vector-sparse kernels — with
``vscnn-resnet50`` at its published size (224x224x3 images, 1000 classes,
weight density 0.235, f32), random weights and images from ``--seed``, and
checks what comes out.  One process; it starts no children.

    python chip_smoke.py                # one chip: f32 phase, int8 phase
    python chip_smoke.py --replicas 4   # only the replica-fleet phase

f32 phase: 8 requests through ``CNNServer(cfg, batch=4)``.  Every outcome
must be ``delivered`` with finite (1000,) logits; the served executable
must hold one ``tpu_custom_call`` per sparse layer the kernels run (all
but the dense 3-channel stem, which runs as one XLA dot; no other layer
fell back to XLA); and every request's logits must match the pruned-dense
reference ``net_apply(net, pruned, x)`` run under
``default_matmul_precision("highest")``: same top-1, and
max|logit - ref| / max|ref| <= F32_RTOL.

int8 phase: the same requests through ``CNNServer(cfg, dtype="int8")``.
Its logits must match the int8 structural reference (the same int8 weights
and per-tensor activation quantization run by the jnp path, which the
Pallas kernels reproduce bit for bit on the CPU, over the same batches of
BATCH requests) within INT8_RTOL, and its top-1 must agree with the f32
sparse logits on every request.

``--replicas N``: 16 requests through ``CNNServer(cfg, replicas=N)``
compared with a one-replica server in the same process: each replica's
mesh and weights sit on a distinct device, every replica serves, every
request is delivered, and the logits match within REPLICA_RTOL (reported
bit-identical or not).

Any failed check exits non-zero.  Seconds printed are from this one smoke
run, compilation where labelled — not measurements.  The last line of
stdout is ``{"ok": true, "device": {...}}``, printed only when every check
passed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# The kernels accumulate in f32 at HIGHEST MXU precision and the reference
# runs XLA's convs at HIGHEST too; they differ only in summation order and
# where BN is folded — about 1e-6 of the logit range after 54 layers on the
# CPU.  A wrong tap, phase, strip or epilogue moves the logits by O(1) of
# their range.  1e-2 separates the two with room for TPU rounding.
F32_RTOL = 1e-2
# int8: both sides sum exact integer products in f32 and dequantize with
# power-of-two scales, so they agree bit for bit unless an activation lands
# on a rounding boundary; 1e-4 of the range admits a stray boundary flip.
INT8_RTOL = 1e-4
# replicas run the same program on chips of one kind: identical up to the
# fusion choices of each replica's compile
REPLICA_RTOL = 1e-4

# Random weights leave the 1000 logits nearly tied (the top-1 margin of
# 1000 near-Gaussian logits is ~0.27 of their spread), so top-1 is only a
# test on inputs where it is decisive: the served requests are the
# REQUESTS of CANDIDATES seeded images whose reference top-1 margin is
# widest.  The choice reads only the f32 reference, never a served output.
REQUESTS = 8
CANDIDATES = 256
CANDIDATE_CHUNK = 32
FLEET_REQUESTS = 16
BATCH = 4


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


def rel_err(a, b) -> float:
    import numpy as np
    return float(np.abs(a - b).max() / max(float(np.abs(b).max()), 1e-30))


def make_requests(images):
    from repro.launch.serve import ImageRequest
    return [ImageRequest(rid=i, image=im) for i, im in enumerate(images)]


def serve(srv, images, label: str):
    """Serve ``images`` through ``srv``; check every outcome is delivered
    with finite logits of the config's class count; return (N, classes)."""
    import numpy as np
    reqs = make_requests(images)
    t0 = time.perf_counter()
    srv.serve(reqs)
    secs = time.perf_counter() - t0
    # JAX reuses an executable an earlier `check_kernels` compiled, so
    # whether this includes a compile depends on the phase
    print(f"{label}: served {len(reqs)} requests in {secs:.2f} s "
          f"(one smoke run; not a measurement)")
    delivered = sum(srv.outcomes[r.rid].status == "delivered" for r in reqs)
    check(delivered == len(reqs),
          f"{label}: {delivered} of {len(reqs)} outcomes delivered")
    logits = np.stack([r.logits for r in reqs])
    check(logits.shape == (len(reqs), srv.cfg.num_classes),
          f"{label}: logits shape {logits.shape}")
    check(bool(np.isfinite(logits).all()), f"{label}: logits finite")
    return logits


def reference_logits(net, params, x, *, sparse=None, impl="auto",
                     chunk=BATCH):
    """``net_apply`` under highest matmul/conv precision over ``x`` in
    chunks of ``chunk`` images (one compile), as numpy."""
    import jax
    import numpy as np
    from repro.models.graph import net_apply
    fn = jax.jit(lambda xx: net_apply(net, params, xx, sparse=sparse,
                                      impl=impl))
    with jax.default_matmul_precision("highest"):
        return np.concatenate([jax.device_get(fn(x[i:i + chunk]))
                               for i in range(0, len(x), chunk)])


def check_kernels(srv, shape) -> None:
    """The served executable must run every sparse layer but those XLA runs
    (``BatchedApply.xla_convs``: the float stem) as a Pallas kernel: one
    ``tpu_custom_call`` per such entry of ``srv.sparse``."""
    import jax
    import jax.numpy as jnp
    t0 = time.perf_counter()
    compiled = srv.backend.apply.lower(
        jax.ShapeDtypeStruct(shape, jnp.float32)).compile()
    print(f"  compile of the served executable: "
          f"{time.perf_counter() - t0:.2f} s (one smoke run)")
    calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    xla = srv.backend.apply.xla_convs
    check(calls == len(srv.sparse) - xla,
          f"{calls} tpu_custom_call in the served executable for "
          f"{len(srv.sparse)} sparse layers, {xla} of them through XLA")


def pick_images(net, pruned, rng, shape):
    """The REQUESTS candidate images with the widest reference top-1
    margins, and the reference logits for them."""
    import numpy as np
    cands = rng.standard_normal((CANDIDATES, *shape)).astype(np.float32)
    ref = reference_logits(net, pruned, cands, chunk=CANDIDATE_CHUNK)
    top2 = np.sort(ref, axis=1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    keep = np.sort(np.argsort(-margin)[:REQUESTS])
    print(f"  reference top-1 margins of the served requests: "
          f"{np.round(margin[keep], 6).tolist()} "
          f"(logit range {float(np.abs(ref).max()):.6f})")
    return cands[keep], ref[keep]


def single_chip(cfg, seed: int) -> None:
    import numpy as np
    from repro.launch.serve import CNNServer

    s = cfg.image_size
    print(f"config {cfg.name}: {s}x{s}x3, {cfg.num_classes} classes, "
          f"density {cfg.weight_density}, batch {BATCH}")
    t0 = time.perf_counter()
    srv = CNNServer(cfg, batch=BATCH, seed=seed)
    print(f"f32 server built (sparsify included) in "
          f"{time.perf_counter() - t0:.2f} s; {len(srv.sparse)} sparse layers")
    images, ref = pick_images(srv.net, srv.pruned,
                              np.random.default_rng(seed), (s, s, 3))
    check_kernels(srv, (BATCH, s, s, 3))
    f32 = serve(srv, images, "f32")
    err = rel_err(f32, ref)
    print(f"  f32 vs pruned-dense reference (highest precision): "
          f"max|d|/max|ref| = {err:.3e} (limit {F32_RTOL:g})")
    check(err <= F32_RTOL, "f32 logits within tolerance of the reference")
    check(bool((f32.argmax(1) == ref.argmax(1)).all()),
          "f32 top-1 equals the reference top-1 on every request")

    t0 = time.perf_counter()
    srv8 = CNNServer(cfg, batch=BATCH, seed=seed, dtype="int8")
    print(f"int8 server built in {time.perf_counter() - t0:.2f} s")
    check_kernels(srv8, (BATCH, s, s, 3))
    q = serve(srv8, images, "int8")
    # activations quantize per tensor, i.e. per served batch: the
    # reference runs the same waves of BATCH requests
    ref8 = reference_logits(srv8.net, srv8.params, images,
                            sparse=srv8.sparse, impl="jnp")
    err8 = rel_err(q, ref8)
    print(f"  int8 vs int8 structural reference: max|d|/max|ref| = "
          f"{err8:.3e} (limit {INT8_RTOL:g}); bit-identical: "
          f"{bool((q == ref8).all())}")
    check(err8 <= INT8_RTOL, "int8 logits within tolerance of the int8 "
          "structural reference")
    print(f"  int8 vs f32 sparse: max|dlogit| = "
          f"{float(np.abs(q - f32).max()):.6e}")
    check(bool((q.argmax(1) == f32.argmax(1)).all()),
          "int8 top-1 equals the f32 sparse top-1 on every request")


def replica_fleet(cfg, seed: int, replicas: int) -> None:
    import jax
    import numpy as np
    from repro.launch.serve import CNNServer

    check(jax.device_count() >= replicas,
          f"{jax.device_count()} devices for {replicas} replicas")
    s = cfg.image_size
    images = np.random.default_rng(seed).standard_normal(
        (FLEET_REQUESTS, s, s, 3)).astype(np.float32)
    one = CNNServer(cfg, batch=BATCH, seed=seed)
    fleet = CNNServer(cfg, batch=BATCH, seed=seed, replicas=replicas)
    devs = []
    for mesh, be in zip(fleet.group.meshes, fleet.group.backends):
        ids = sorted(d.id for d in mesh.devices.flat)
        leaf = jax.tree.leaves(be.apply.params)[0]
        check(sorted(d.id for d in leaf.devices()) == ids,
              f"replica weights on its mesh devices {ids}")
        devs.extend(ids)
    check(len(devs) == replicas and len(set(devs)) == replicas,
          f"{replicas} replicas on distinct devices {devs}")
    ref = serve(one, images, "one replica")
    got = serve(fleet, images, f"{replicas} replicas")
    served_by = sorted({fleet.outcomes[i].replica
                        for i in range(FLEET_REQUESTS)})
    check(served_by == list(range(replicas)),
          f"every replica served requests ({served_by})")
    err = rel_err(got, ref)
    print(f"  fleet vs one replica: max|d|/max|ref| = {err:.3e} "
          f"(limit {REPLICA_RTOL:g}); bit-identical: "
          f"{bool((got == ref).all())}")
    check(err <= REPLICA_RTOL, "fleet logits within tolerance of one "
          "replica's")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--replicas", type=int, default=None,
                    help="run only the replica-fleet phase on this many "
                         "chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU backend, JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.utils.compile_cache import enable_compile_cache

    cache = pathlib.Path(enable_compile_cache())
    entries = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    print(f"compile cache: {cache} ({entries} entries before this run)")
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x {jax.device_count()}")
    cfg = get_config("vscnn-resnet50")
    try:
        if args.replicas is None:
            single_chip(cfg, args.seed)
        else:
            replica_fleet(cfg, args.seed, args.replicas)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""CNN serving-path benchmark: images/s vs density vs batch size.

Drives the batched CNN backend (`launch.serve.CNNServer`) end to end —
queue, bucketing, slot retirement, backfill, jit-cached `SparseNet.apply` —
and reports steady-state throughput for the dense-jnp baseline (plain XLA
convs) and the vector-sparse structural path at several densities.  CPU
numbers demonstrate work ∝ density and batch amortization on a real
backend, not the TPU claim (same caveat as bench_kernels).

Each (path, density, batch) cell serves a warmup wave first so the compile
cost of the batch bucket is off the clock — the steady state is what a
serving deployment sees.

Each sparse cell also carries the *modeled* per-image HBM bytes of the two
conv input layouts (halo direct input vs materialized row-tap stack) and
their arithmetic intensity — `core.accel_model.conv_layer_traffic`, the
same formulas the Pallas kernels hand XLA as CostEstimate — so the
serving artifact captures the bandwidth win next to images/s.  ``--impl``
selects the executed path (jnp | pallas | pallas-stack; the pallas paths
run interpret-mode on CPU and are slow — bench them on TPU).

Writes a ``BENCH_serving.json`` artifact (--out) with per-cell rows plus a
summary checking that batched sparse throughput >= batch-1 throughput at
equal density.

Replica scaling (``--replicas R1 R2 ...``): serves the same request set
through the data-parallel replica fleet (`launch.serve.ReplicaGroup` +
`launch.scheduler.FleetScheduler`) at each fleet size and reports images/s
plus scaling efficiency against the *achievable* ideal — min(replicas,
cores), overridable with ``VSCNN_SCALING_IDEAL``.  On a forced-host CPU
mesh (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) the replicas
share the physical cores XLA's intra-op parallelism already saturates, so
set ``VSCNN_SCALING_IDEAL=1`` there: the gate then bounds fleet-machinery
*overhead* (and pins scheduling determinism), not parallel speedup — real
replica speedup needs real devices (a TPU pod's data axis).  Scheduling
columns (waves/steps/steals/digest) are deterministic: the fleet loop is
synchronous and its control flow never reads the clock, so they gate
exactly against the committed ``BENCH_serving_replicas.json`` baseline
(``--compare-baseline``, modeled on bench_kernels).  ``--shard-fc``
additionally cout-shards FC heads over each replica's model-axis devices
and checks logits parity against the first fleet size.

Chaos / degraded mode (``--chaos``): serves the same request set under
seeded fault injection (`launch.faults.FaultPlan.random` over a
``--chaos-replicas`` fleet, one row per ``--chaos-seeds`` entry) and
reports planned vs fired faults, delivered/refused outcome counts by
reason, final replica health, degraded images/s vs the fault-free
reference, a delivered-bit-identical check, and a replay-determinism
check (the same plan must reproduce the exact outcome/fault/health
trajectory).  Exits non-zero if either check fails — the CI chaos smoke.

Run:  PYTHONPATH=src python benchmarks/bench_serving.py --arch vscnn-vgg16
(also: vscnn-resnet18 / vscnn-resnet50 / vscnn-mobilenet-v1 — any CNN
registry arch; MobileNet exercises the depthwise tap kernels' traffic
columns.)
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.faults import ChaosBackend, FaultPlan
from repro.launch.scheduler import FleetScheduler
from repro.launch.serve import CNNServer, ImageRequest
from repro.utils.compile_cache import enable_compile_cache


def _requests(rng, n: int, size: int) -> list[ImageRequest]:
    return [ImageRequest(rid=i,
                         image=rng.standard_normal((size, size, 3))
                                  .astype(np.float32))
            for i in range(n)]


def _model_bytes(srv: CNNServer, size: int) -> dict:
    """Modeled per-image conv HBM bytes + arithmetic intensity, both conv
    layouts, for this server's sparsified net at the served image size."""
    from repro.core.accel_model import network_traffic_reports
    from repro.models.graph import collect_conv_traffic

    if srv.sparse is None:
        return {}
    x = jnp.zeros((1, size, size, 3), jnp.float32)
    traffic = collect_conv_traffic(srv.net, srv.params, x)
    reps = network_traffic_reports(traffic, srv.sparse)
    out = {}
    for impl in ("halo", "stack"):
        total = sum(t[impl].bytes_accessed for _, t in reps)
        flops = sum(t[impl].flops for _, t in reps)
        out[f"model_bytes_per_image_{impl}"] = total
        out[f"model_ai_{impl}"] = round(flops / max(total, 1), 2)
    return out


def _throughput(srv: CNNServer, rng, n: int, size: int, batch: int) -> dict:
    srv.serve(_requests(rng, batch, size))          # warmup: compile bucket
    stats = srv.serve(_requests(rng, n, size))
    run_s = sum(s["run_s"] for s in stats)
    return {
        "images_per_s": round(n / max(run_s, 1e-9), 2),
        "run_s": round(run_s, 4),
        "runs": len(stats),
        "steps": sum(s["steps"] for s in stats),
        "backfills": sum(s["backfills"] for s in stats),
        "compiles": srv.backend.apply.compiles,
    }


def _int8_agreement(srv_f32: CNNServer, srv_int8: CNNServer, size: int,
                    batch: int) -> dict:
    """Serve one identical seeded request wave through both precision paths
    and compare logits: max |Δlogit| + top-1 match rate."""
    reqs_f = _requests(np.random.default_rng(42), batch, size)
    reqs_q = _requests(np.random.default_rng(42), batch, size)
    srv_f32.serve(reqs_f)
    srv_int8.serve(reqs_q)
    lf = np.stack([r.logits for r in sorted(reqs_f, key=lambda r: r.rid)])
    lq = np.stack([r.logits for r in sorted(reqs_q, key=lambda r: r.rid)])
    return {
        "max_abs_dlogit_vs_f32": round(float(np.abs(lq - lf).max()), 6),
        "top1_match_vs_f32": round(
            float((lq.argmax(-1) == lf.argmax(-1)).mean()), 4),
    }


def run(arch: str = "vscnn-vgg16", *, densities=(1.0, 0.5, 0.235),
        batches=(1, 4, 8), images: int = 24, size: int | None = None,
        impl: str = "jnp", dtype: str = "f32",
        out_path: str | None = None) -> dict:
    cfg = get_config(arch).reduce()
    size = size or cfg.image_size
    int8 = dtype == "int8"
    rng = np.random.default_rng(0)
    rows = []
    model_bytes: dict = {}  # per (density, dtype) — batch-size independent
    for batch in batches:
        srv = CNNServer(cfg, batch=batch, sparse=False)
        rows.append({"path": "dense-jnp", "density": 1.0, "batch": batch,
                     **_throughput(srv, rng, images, size, batch)})
        for density in densities:
            srv = CNNServer(cfg, batch=batch, density=density, impl=impl)
            if density not in model_bytes:
                model_bytes[density] = _model_bytes(srv, size)
            rows.append({"path": f"sparse-{impl}", "density": density,
                         "batch": batch,
                         **model_bytes[density],
                         **_throughput(srv, rng, images, size, batch)})
            if int8:
                # compound sparsity x precision cell: same density, int8
                # weights/activations, plus output-agreement columns vs
                # the sparse-f32 server on one identical seeded wave
                srv_q = CNNServer(cfg, batch=batch, density=density,
                                  impl=impl, dtype="int8")
                key = (density, "int8")
                if key not in model_bytes:
                    model_bytes[key] = _model_bytes(srv_q, size)
                rows.append({"path": f"sparse-{impl}-int8",
                             "density": density, "batch": batch,
                             **model_bytes[key],
                             **_throughput(srv_q, rng, images, size, batch),
                             **_int8_agreement(srv, srv_q, size, batch)})
    # batched throughput must beat (or match) batch-1 at equal density
    summary = {}
    max_batch = max(batches)
    for density in densities:
        cells = {r["batch"]: r["images_per_s"] for r in rows
                 if r["path"] == f"sparse-{impl}"
                 and r["density"] == density}
        summary[str(density)] = {
            "batch1_images_per_s": cells.get(1),
            "batched_images_per_s": cells.get(max_batch),
            "batched_ge_batch1": (cells.get(max_batch, 0.0)
                                  >= cells.get(1, float("inf"))),
        }
    artifact = {
        "bench": "cnn_serving",
        "arch": arch,
        "image_size": size,
        "images": images,
        "impl": impl,
        "dtype": dtype,
        "batches": list(batches),
        "densities": list(densities),
        "rows": rows,
        "summary": summary,
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
    return artifact


# --------------------------------------------------------------------------
# Replica-fleet scaling (--replicas) + regression gate (--compare-baseline)
# --------------------------------------------------------------------------

# scheduling columns gated exactly against the committed baseline: the
# fleet loop is synchronous Python whose control flow (placement, stealing,
# wave/step counts) never reads the clock, and the class digest pins the
# served outputs — wall-clock columns are reported, never gated.
REPLICA_DET_COLS = ("waves", "steps", "backfills", "finished", "steals",
                    "bit_identical_to_first", "class_digest")


def _ideal_parallelism(replicas: int) -> int:
    """Achievable ideal speedup at this fleet size: min(replicas, cores),
    overridable with VSCNN_SCALING_IDEAL (set it to 1 on forced-host CPU
    meshes, where XLA intra-op parallelism already saturates the cores)."""
    cap = int(os.environ.get("VSCNN_SCALING_IDEAL", os.cpu_count() or 1))
    return max(1, min(replicas, cap))


def _class_digest(reqs) -> str:
    h = hashlib.sha256()
    for r in sorted(reqs, key=lambda r: r.rid):
        h.update(np.int64(r.out[0]).tobytes())
    return h.hexdigest()[:16]


def run_replicas(arch: str = "vscnn-vgg16", *, replicas=(1, 2, 4, 8),
                 images: int = 32, batch: int = 4, density: float = 0.5,
                 size: int | None = None, impl: str = "jnp",
                 shard_fc: bool = False,
                 out_path: str | None = None) -> dict:
    """Serve one request set at each fleet size; images/s + scaling
    efficiency + deterministic scheduling columns per row."""
    cfg = get_config(arch).reduce()
    size = size or cfg.image_size
    rows = []
    ref_logits = None
    base_ips = None
    for nrep in replicas:
        srv = CNNServer(cfg, batch=batch, density=density, impl=impl,
                        replicas=nrep, shard_fc=shard_fc)
        # warmup one wave per replica so every replica's executable is
        # compiled off the clock
        srv.serve(_requests(np.random.default_rng(0), batch * nrep, size))
        reqs = _requests(np.random.default_rng(1), images, size)
        t0 = time.time()
        stats = srv.serve(reqs)
        wall = time.time() - t0
        logits = np.stack([r.logits
                           for r in sorted(reqs, key=lambda r: r.rid)])
        if ref_logits is None:
            ref_logits = logits
        ips = images / max(wall, 1e-9)
        if base_ips is None:
            base_ips = ips
        ideal = _ideal_parallelism(nrep)
        speedup = ips / base_ips
        rows.append({
            "replicas": nrep,
            "images_per_s": round(ips, 2),
            "wall_s": round(wall, 4),
            "speedup_vs_first": round(speedup, 3),
            "ideal_parallelism": ideal,
            "scaling_efficiency": round(speedup / ideal, 3),
            "waves": len(stats),
            "steps": sum(s["steps"] for s in stats),
            "backfills": sum(s["backfills"] for s in stats),
            "finished": sum(s["finished"] for s in stats),
            "steals": getattr(srv.scheduler, "steals", 0),
            "replicas_used": sorted({s.get("replica", 0) for s in stats}),
            "bit_identical_to_first": bool(np.array_equal(ref_logits,
                                                          logits)),
            "parity_max_abs_diff": float(np.abs(ref_logits - logits).max()),
            "class_digest": _class_digest(reqs),
        })
    artifact = {
        "bench": "cnn_serving_replicas",
        "arch": arch,
        "image_size": size,
        "images": images,
        "batch": batch,
        "density": density,
        "impl": impl,
        "shard_fc": shard_fc,
        "replicas": list(replicas),
        "rows": rows,
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
    return artifact


# --------------------------------------------------------------------------
# Degraded-mode chaos bench (--chaos): seeded fault injection over the fleet
# --------------------------------------------------------------------------

def _chaos_serve(backends, plan: FaultPlan, reqs, *, batch: int,
                 deadline_waves: int | None):
    """One chaos serve over fresh ChaosBackend wrappers of the shared
    (stateless) CNN backends; returns (scheduler, wall_s)."""
    bes = [ChaosBackend(b, plan, replica=i)
           for i, b in enumerate(backends)]
    sched = FleetScheduler(bes, batch=batch, deadline_waves=deadline_waves)
    t0 = time.time()
    sched.serve(reqs)
    return sched, time.time() - t0


def _outcome_trace(sched) -> dict:
    return {rid: (o.status, o.reason, o.replica, o.attempts, o.wave)
            for rid, o in sched.outcomes.items()}


def run_chaos(arch: str = "vscnn-vgg16", *, seeds=(0, 1, 2),
              replicas: int = 3, images: int = 24, batch: int = 4,
              density: float = 0.5, size: int | None = None,
              impl: str = "jnp", deadline_waves: int | None = None,
              out_path: str | None = None) -> dict:
    """Degraded-mode serving under seeded fault injection.

    One fault-free fleet serve pins the reference logits and throughput;
    each chaos seed then serves the same request set through the same
    (shared, stateless) backends wrapped in a fresh `ChaosBackend` fleet.
    Per-seed columns: planned/fired faults by kind, delivered/refused by
    reason, final health, deterministic scheduling counters, degraded
    images/s, a delivered-bit-identical check against the fault-free
    reference, and a replay check (the same plan served twice must
    reproduce the exact outcome/fault/health trajectory).
    """
    cfg = get_config(arch).reduce()
    size = size or cfg.image_size
    srv = CNNServer(cfg, batch=batch, density=density, impl=impl,
                    replicas=replicas)
    # warmup: compile every batch bucket off the clock
    srv.serve(_requests(np.random.default_rng(0), batch * replicas, size))
    reqs = _requests(np.random.default_rng(1), images, size)
    t0 = time.time()
    srv.serve(reqs)
    ref_wall = time.time() - t0
    ref_logits = {r.rid: r.logits.tobytes() for r in reqs}
    ref_ips = images / max(ref_wall, 1e-9)
    rows = []
    for seed in seeds:
        plan = FaultPlan.random(seed, replicas=replicas)
        reqs_c = _requests(np.random.default_rng(1), images, size)
        sched, wall = _chaos_serve(srv.backends, plan, reqs_c, batch=batch,
                                   deadline_waves=deadline_waves)
        outcomes = sched.outcomes
        delivered = [rid for rid, o in outcomes.items()
                     if o.status == "delivered"]
        refused: dict[str, int] = {}
        for o in outcomes.values():
            if o.status == "refused":
                refused[o.reason] = refused.get(o.reason, 0) + 1
        fired: dict[str, int] = {}
        for be in sched.backends:
            for _, kind in be.injected:
                fired[kind] = fired.get(kind, 0) + 1
        # delivered outputs must be bit-identical to the fault-free run
        bit_identical = all(
            r.logits is not None
            and r.logits.tobytes() == ref_logits[r.rid]
            for r in reqs_c
            if outcomes[r.rid].status == "delivered")
        # replay: the same plan on a fresh fleet reproduces the exact
        # outcome / fault-event / health / wave trajectory
        sched2, _ = _chaos_serve(
            srv.backends, plan, _requests(np.random.default_rng(1),
                                          images, size),
            batch=batch, deadline_waves=deadline_waves)
        replay_identical = (
            _outcome_trace(sched) == _outcome_trace(sched2)
            and sched.fault_events == sched2.fault_events
            and sched.health == sched2.health
            and sched.waves == sched2.waves)
        rows.append({
            "chaos_seed": seed,
            "faults_planned": plan.counts(),
            "faults_fired": fired,
            "fault_events": len(sched.fault_events),
            "delivered": len(delivered),
            "refused": refused,
            "health": list(sched.health),
            "waves": sched.waves,
            "steals": sched.steals,
            "images_per_s_degraded": round(
                len(delivered) / max(wall, 1e-9), 2),
            "throughput_vs_fault_free": round(
                (len(delivered) / max(wall, 1e-9)) / max(ref_ips, 1e-9), 3),
            "wall_s": round(wall, 4),
            "delivered_bit_identical": bool(bit_identical),
            "replay_identical": bool(replay_identical),
        })
    artifact = {
        "bench": "cnn_serving_chaos",
        "arch": arch,
        "image_size": size,
        "images": images,
        "batch": batch,
        "density": density,
        "impl": impl,
        "replicas": replicas,
        "deadline_waves": deadline_waves,
        "seeds": list(seeds),
        "reference": {"images_per_s": round(ref_ips, 2),
                      "wall_s": round(ref_wall, 4)},
        "rows": rows,
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
    return artifact


def compare_replicas_baseline(rows: list[dict], baseline: dict
                              ) -> tuple[list[str], list[str]]:
    """Exact comparison of the deterministic scheduling columns against the
    committed baseline; wall-clock columns are shown, not gated."""
    cur = {r["replicas"]: r for r in rows}
    failures: list[str] = []
    lines = [
        "| replicas | metric | baseline | current | status |",
        "|---|---|---|---|---|",
    ]
    for b in baseline["rows"]:
        c = cur.get(b["replicas"])
        if c is None:
            failures.append(f"replicas={b['replicas']}: row missing")
            lines.append(f"| {b['replicas']} | — | — | MISSING | FAIL |")
            continue
        for metric in REPLICA_DET_COLS:
            if metric not in b:
                continue
            bad = c.get(metric) != b[metric]
            if bad:
                failures.append(
                    f"replicas={b['replicas']}: {metric} "
                    f"{b[metric]!r} -> {c.get(metric)!r}")
            lines.append(
                f"| {b['replicas']} | {metric} | {b[metric]} "
                f"| {c.get(metric)} | {'FAIL' if bad else 'ok'} |")
        lines.append(
            f"| {b['replicas']} | images_per_s (not gated) "
            f"| {b.get('images_per_s')} | {c.get('images_per_s')} | — |")
    return failures, lines


def gate_replicas(baseline_path: str, *, min_efficiency: float | None = None,
                  out_path: str | None = None) -> int:
    """CI gate: re-run the replica bench at the committed baseline's
    settings, fail on any deterministic-column drift, and (when
    ``min_efficiency`` is set) on scaling efficiency below the bound at any
    fleet size.  The fresh rows double as the run's trajectory artifact."""
    with open(baseline_path) as f:
        baseline = json.load(f)
    art = run_replicas(
        baseline["arch"], replicas=tuple(baseline["replicas"]),
        images=baseline["images"], batch=baseline["batch"],
        density=baseline["density"], size=baseline["image_size"],
        impl=baseline["impl"], shard_fc=baseline.get("shard_fc", False),
        out_path=out_path)
    failures, lines = compare_replicas_baseline(art["rows"], baseline)
    if min_efficiency is not None:
        for r in art["rows"]:
            if r["scaling_efficiency"] < min_efficiency:
                failures.append(
                    f"replicas={r['replicas']}: scaling efficiency "
                    f"{r['scaling_efficiency']} < {min_efficiency} "
                    f"(ideal parallelism {r['ideal_parallelism']})")
    summary = "\n".join(
        [f"## Replica-scaling gate — `{baseline_path}` "
         f"({'FAIL' if failures else 'PASS'})", ""]
        + lines + [""]
        + [f"- {f}" for f in failures])
    step_summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if step_summary:
        with open(step_summary, "a") as f:
            f.write(summary + "\n")
    print(summary)
    if failures:
        print(f"replica gate: FAIL ({len(failures)} failure(s))")
        return 1
    print("replica gate: PASS")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vscnn-vgg16")
    ap.add_argument("--images", type=int, default=24)
    ap.add_argument("--size", type=int, default=None,
                    help="override the reduced config's image size")
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 4, 8])
    ap.add_argument("--densities", type=float, nargs="+",
                    default=[1.0, 0.5, 0.235])
    ap.add_argument("--impl", default="jnp",
                    choices=["jnp", "pallas", "pallas-halo", "pallas-stack"],
                    help="executed sparse path (pallas* = the TPU kernels; "
                         "interpret-mode and slow on CPU)")
    ap.add_argument("--dtype", default="f32", choices=["f32", "int8"],
                    help="int8 adds a sparse-<impl>-int8 row per cell "
                         "(compound sparsity x precision) with "
                         "output-agreement columns vs sparse-f32")
    ap.add_argument("--out", default=None,
                    help="write the artifact (e.g. BENCH_serving.json)")
    ap.add_argument("--replicas", type=int, nargs="+", default=None,
                    help="replica-fleet scaling mode: fleet sizes to bench")
    ap.add_argument("--batch", type=int, default=4,
                    help="wave width per replica (replica mode)")
    ap.add_argument("--density", type=float, default=0.5,
                    help="sparse density (replica mode)")
    ap.add_argument("--shard-fc", action="store_true",
                    help="cout-shard FC heads over each replica's model-"
                         "axis devices (replica mode)")
    ap.add_argument("--compare-baseline", default=None,
                    help="replica-gate mode: re-run at this committed "
                         "baseline's settings and fail on drift")
    ap.add_argument("--min-efficiency", type=float, default=None,
                    help="fail the gate below this scaling efficiency")
    ap.add_argument("--chaos", action="store_true",
                    help="degraded-mode bench: serve under seeded fault "
                         "injection and report refusals / degraded "
                         "throughput / replay determinism")
    ap.add_argument("--chaos-seeds", type=int, nargs="+", default=[0, 1, 2],
                    help="FaultPlan seeds for --chaos")
    ap.add_argument("--chaos-replicas", type=int, default=3,
                    help="fleet size for --chaos")
    ap.add_argument("--deadline-waves", type=int, default=None,
                    help="per-request deadline in fleet ticks (--chaos)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.chaos:
        art = run_chaos(args.arch, seeds=tuple(args.chaos_seeds),
                        replicas=args.chaos_replicas, images=args.images,
                        batch=args.batch, density=args.density,
                        size=args.size, impl=args.impl,
                        deadline_waves=args.deadline_waves,
                        out_path=args.out)
        print("reference:", art["reference"])
        bad = []
        for r in art["rows"]:
            print(r)
            if not r["delivered_bit_identical"]:
                bad.append(f"seed={r['chaos_seed']}: delivered logits "
                           f"diverge from the fault-free run")
            if not r["replay_identical"]:
                bad.append(f"seed={r['chaos_seed']}: chaos replay is not "
                           f"deterministic")
        for b in bad:
            print("FAIL:", b)
        sys.exit(1 if bad else 0)
    if args.compare_baseline:
        sys.exit(gate_replicas(args.compare_baseline,
                               min_efficiency=args.min_efficiency,
                               out_path=args.out))
    if args.replicas:
        art = run_replicas(args.arch, replicas=tuple(args.replicas),
                           images=args.images, batch=args.batch,
                           density=args.density, size=args.size,
                           impl=args.impl, shard_fc=args.shard_fc,
                           out_path=args.out)
        bad = []
        for r in art["rows"]:
            print(r)
            if args.shard_fc and r["parity_max_abs_diff"] > 1e-4:
                bad.append(f"replicas={r['replicas']}: sharded-FC logits "
                           f"diverge ({r['parity_max_abs_diff']:g})")
            if args.min_efficiency is not None \
                    and r["scaling_efficiency"] < args.min_efficiency:
                bad.append(f"replicas={r['replicas']}: efficiency "
                           f"{r['scaling_efficiency']} < "
                           f"{args.min_efficiency}")
        for b in bad:
            print("FAIL:", b)
        sys.exit(1 if bad else 0)
    art = run(args.arch, densities=tuple(args.densities),
              batches=tuple(args.batches), images=args.images,
              size=args.size, impl=args.impl, dtype=args.dtype,
              out_path=args.out)
    for r in art["rows"]:
        print(r)
    print("summary:", art["summary"])

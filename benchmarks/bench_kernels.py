"""Kernel-level benches: the TPU analogue of the paper's cycle savings.

1. Structural FLOP scaling: compiled HLO FLOPs of the vector-sparse matmul
   vs density — the zero weight vectors are absent from the compiled
   program exactly as they are absent from the paper's SRAM (compare with
   the dense baseline at density 1.0).
2. Wall-clock on CPU for the jnp structural path (CPU timing is NOT the TPU
   claim — it demonstrates the cycle model's work∝density on a real
   backend).
3. Pallas kernel allclose + grid-size-vs-density check (interpret mode).
4. Generalized conv geometry sweep: per-(kernel, stride, groups, dilation)
   speedup-vs-density rows for the vsconv kernel family (1x1 / 3x3 / 5x5 /
   7x7, stride 1-2, grouped / depthwise / dilated taps), reporting the
   structural FLOP ratio, jnp-path wall clock, interpret-mode parity for
   *both* conv input layouts (halo direct input vs row-tap stack), and the
   modeled HBM bytes of each layout — the bandwidth story is part of the
   benchmarked contract, not just the MAC skips.
5. Per-network per-layer speedup-vs-density (``--net vgg16 | resnet18 |
   resnet34 | resnet50 | mobilenet_v1``, ``--resnet18`` kept as an alias):
   the graph executor + cycle model walked over every conv (residual
   blocks, BN folded, depthwise stages), emitting a ``BENCH_<net>.json``
   artifact so CI tracks the perf trajectory — with per-layer bytes /
   arithmetic-intensity columns for the halo and stack layouts, and the
   measured-vs-modeled columns (wall clock, compiled-HLO FLOPs/bytes,
   calibrated ``predicted_us`` — see `repro.core.calibration`) next to
   them.
6. ``--gate-traffic``: CI smoke gate — runs both impls on the ResNet
   7x7/s2 stem geometry and a MobileNet depthwise 3x3/s2 layer (interpret
   parity) and fails unless the halo path's modeled ``bytes_accessed`` is
   strictly below the stack path's on both.
7. ``--compare-baseline PATH``: CI regression gate — re-runs the
   per-network bench at the committed baseline's settings and fails on a
   >10% per-layer regression of cycle speedup or modeled bytes, writing a
   per-layer delta table to ``$GITHUB_STEP_SUMMARY`` when set.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encode, prune_vectors_balanced, vs_conv2d, vs_matmul
from repro.kernels import vsconv, vsmm
from repro.kernels.ref import vsconv_ref, vsmm_ref
from repro.utils.compile_cache import enable_compile_cache


def _sparse(rng, k, n, vk, vn, density, dtype=jnp.float32):
    w = rng.standard_normal((k, n)).astype(np.float32)
    wp, _ = prune_vectors_balanced(w, density, vk, vn)
    return encode(jnp.asarray(wp, dtype), vk, vn)


def hlo_flops(fn, *args) -> float:
    # the structural path is a scan over S steps: XLA's cost_analysis counts
    # the body once, so use the trip-multiplying analyzer (utils.hlo)
    from repro.utils.hlo import analyze
    return analyze(jax.jit(fn).lower(*args).compile().as_text()).flops


def run() -> list[dict]:
    rng = np.random.default_rng(0)
    rows = []
    m, k, n, vk, vn = 256, 2048, 2048, 32, 128
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)

    dense_flops = None
    for density in (1.0, 0.5, 0.25, 0.125):
        vs = _sparse(rng, k, n, vk, vn, density)
        f = hlo_flops(lambda xx: vs_matmul(xx, vs), x)
        if dense_flops is None:
            dense_flops = f
        # wall time (CPU, jnp structural path)
        fn = jax.jit(lambda xx: vs_matmul(xx, vs))
        fn(x).block_until_ready()
        t0 = time.time()
        for _ in range(20):
            out = fn(x)
        out.block_until_ready()
        us = (time.time() - t0) / 20 * 1e6
        rows.append({
            "name": f"vsmm_structural_density_{density}",
            "us_per_call": round(us, 1),
            "hlo_flops": f,
            "flops_vs_dense": round(f / dense_flops, 4),
            "expected": density,
        })

    # Pallas kernel correctness + structural grid scaling
    for density in (1.0, 0.25):
        vs = _sparse(rng, 512, 512, 32, 128, density)
        xs = jnp.asarray(rng.standard_normal((64, 512)), jnp.float32)
        t0 = time.time()
        out = vsmm(xs, vs)
        us = (time.time() - t0) * 1e6
        ref = vsmm_ref(xs, vs)
        rel = float(np.abs(np.asarray(out) - np.asarray(ref)).max()
                    / np.abs(np.asarray(ref)).max())
        rows.append({
            "name": f"vsmm_pallas_density_{density}",
            "us_per_call": round(us, 1),
            "rel_err_vs_ref": rel,
            "grid_sparse_steps": vs.nnz_per_strip,
            "grid_dense_steps": vs.kb,
        })

    rows += run_conv_geometries()
    return rows


# (kh, kw, stride, groups, dilation, h, w, cin, cout, vk, vn) — the
# generalized kernel family: VGG's 3x3/s1 plus the ResNet vocabulary
# (7x7-s2 stem, 1x1 projection, stride-2 downsample), a 5x5 mid-size tap,
# grouped and depthwise (groups == cin) 3x3s, and dilated taps.
CONV_GEOMETRIES = [
    (1, 1, 1, 1, 1, 28, 28, 128, 128, 32, 128),
    (1, 1, 2, 1, 1, 28, 28, 128, 128, 32, 128),
    (3, 3, 1, 1, 1, 28, 28, 64, 128, 32, 128),
    (3, 3, 2, 1, 1, 28, 28, 64, 128, 32, 128),
    (5, 5, 1, 1, 1, 14, 14, 32, 128, 32, 128),
    (7, 7, 2, 1, 1, 28, 28, 8, 64, 8, 64),
    (3, 3, 1, 1, 2, 28, 28, 64, 128, 32, 128),   # dilated 3x3 d2
    (3, 3, 1, 4, 1, 28, 28, 64, 128, 16, 32),    # grouped 3x3 g4
    (3, 3, 2, 128, 1, 28, 28, 128, 128, 1, 128),  # depthwise 3x3/s2
]


def _geom_vs(rng, kh, kw, cin, cout, vk, vn, groups, density):
    """Encode one sweep geometry's sparse weight (grouped/dw aware)."""
    from repro.core import conv_cin_major

    cin_g = cin // groups
    wm = rng.standard_normal((kh * kw * cin_g, cout)).astype(np.float32)
    wp, _ = prune_vectors_balanced(wm, density, vk, vn)
    vs = encode(jnp.asarray(wp), vk, vn)
    if kh * kw > 1 and groups < cin:
        vs = conv_cin_major(vs, cin_g // vk)  # the serving tile order
    return vs


def _conv_bytes(kh, kw, stride, groups, dilation, h, w, cin, cout, vk, vn,
                s_steps, batch: int = 4) -> dict:
    """Modeled HBM bytes + arithmetic intensity for both conv layouts."""
    from repro.core.accel_model import conv_layer_traffic

    out = {}
    for impl in ("halo", "stack"):
        tr = conv_layer_traffic(
            (batch, h, w, cin), kh=kh, kw=kw, stride=stride, groups=groups,
            dilation=dilation, cout=cout,
            s_steps=s_steps, vk=vk, vn=vn, impl=impl)
        out[f"bytes_{impl}"] = tr.bytes_accessed
        out[f"ai_{impl}"] = round(tr.arithmetic_intensity, 2)
    return out


def run_conv_geometries(densities=(1.0, 0.5, 0.25)) -> list[dict]:
    """Per-geometry speedup-vs-density: structural FLOP ratio (the kernel's
    grid shrinks with density), jnp-path wall clock, modeled HBM bytes for
    the halo and stack layouts, and Pallas interpret parity of both impls
    vs the oracle — grouped, depthwise and dilated geometries included."""
    rng = np.random.default_rng(1)
    rows = []
    for (kh, kw, stride, groups, dilation, h, w, cin, cout, vk,
         vn) in CONV_GEOMETRIES:
        base_us = None
        for density in densities:
            vs = _geom_vs(rng, kh, kw, cin, cout, vk, vn, groups, density)
            x = jnp.asarray(
                np.maximum(rng.standard_normal((4, h, w, cin)), 0),
                jnp.float32)
            # structural work: sparse grid steps vs dense K-tiles
            flop_ratio = vs.density
            # jnp structural path wall clock (CPU; demonstrates work∝density)
            fn = jax.jit(lambda xx: vs_conv2d(
                xx, vs, kh=kh, kw=kw, stride=stride, groups=groups,
                dilation=dilation, impl="jnp"))
            fn(x).block_until_ready()
            t0 = time.time()
            for _ in range(5):
                out = fn(x)
            out.block_until_ready()
            us = (time.time() - t0) / 5 * 1e6
            if base_us is None:
                base_us = us  # density 1.0 reference
            tag = (f"vsconv_{kh}x{kw}_s{stride}"
                   + (f"_g{groups}" if groups > 1 else "")
                   + (f"_d{dilation}" if dilation > 1 else ""))
            row = {
                "name": f"{tag}_density_{density}",
                "us_per_call": round(us, 1),
                "speedup_vs_dense": round(base_us / us, 3),
                "structural_flops_vs_dense": round(flop_ratio, 4),
                "expected": density,
            }
            row.update(_conv_bytes(kh, kw, stride, groups, dilation, h, w,
                                   cin, cout, vk, vn, vs.nnz_per_strip))
            # Pallas interpret parity at the smallest density only (slow):
            # both input layouts against the oracle
            if density == densities[-1]:
                ref = vsconv_ref(x, vs, kh=kh, kw=kw, stride=stride,
                                 groups=groups, dilation=dilation)
                for impl in ("halo", "stack"):
                    out_p = vsconv(x, vs, kh=kh, kw=kw, stride=stride,
                                   groups=groups, dilation=dilation,
                                   impl=impl)
                    row[f"pallas_{impl}_rel_err_vs_ref"] = float(
                        np.abs(np.asarray(out_p) - np.asarray(ref)).max()
                        / np.abs(np.asarray(ref)).max())
            rows.append(row)
    return rows


def _net_builders() -> dict:
    from repro.models.graph import (
        build_mobilenet_v1, build_resnet18, build_resnet34, build_resnet50,
        build_vgg16,
    )
    return {"vgg16": build_vgg16, "resnet18": build_resnet18,
            "resnet34": build_resnet34, "resnet50": build_resnet50,
            "mobilenet_v1": build_mobilenet_v1}


MEASURED_COLS = ("measured_us", "hlo_flops", "hlo_bytes", "measured_ai",
                 "flops_model_ratio", "modeled_flops", "predicted_us")


def _measured_vs_modeled(net, params, x, density) -> dict:
    """Per-layer measured-vs-modeled columns (`repro.core.calibration`):
    median wall clock, compiled-HLO FLOPs/bytes, and the calibrated time
    model's ``predicted_us``.  Reported next to the modeled columns, never
    gated — only the deterministic metrics are stable enough for that."""
    from repro.core.accel_model import load_calibration
    from repro.core.calibration import (
        attach_predictions, measured_vs_modeled_records,
    )

    recs = measured_vs_modeled_records(net, params, x, density=density,
                                       repeats=3, warmup=1)
    attach_predictions(recs, load_calibration())
    keep = MEASURED_COLS + ("modeled_cycles", "modeled_bytes", "modeled_ai",
                            "kind")
    return {r["layer"]: {k: (round(r[k], 3) if k == "predicted_us" else r[k])
                         for k in keep if k in r} for r in recs}


def run_network(net_name: str = "resnet18", densities=(1.0, 0.5, 0.25), *,
                image_size: int = 32, num_classes: int = 200, batch: int = 1,
                out_path: str | None = None,
                measure: bool = True, dtype: str = "f32") -> list[dict]:
    """Per-network per-layer speedup-vs-density through the graph executor.

    For each density: sparsify the whole network (BN folded, residuals
    fused, depthwise stages on the per-channel tap path), time the jnp
    structural forward (whole-net wall clock; CPU demonstrates work ∝
    density, not the TPU claim), and walk the same graph through the
    accelerator cycle model for per-layer VSCNN-vs-dense cycle speedups
    plus the DRAM traffic model for per-layer bytes / arithmetic intensity
    under both conv input layouts (halo vs stack).  With ``measure`` (the
    default) each per-layer row also carries the measured-vs-modeled
    columns — standalone-jitted wall clock, compiled-HLO FLOPs/bytes, and
    the calibrated model's ``predicted_us`` — and the FC head gets its own
    (ungated) row.  ``out_path`` writes the rows as a JSON artifact
    (``BENCH_<net>.json`` in CI).

    ``dtype="int8"`` runs the compound sparsity x precision path: weights
    quantized per-cout at sparsify time (power-of-two scales), activations
    quantized per-tensor at apply time, int32 accumulation, dequant fused
    into the epilogue.  The traffic model keys itemsizes off the stored
    weight dtype (int8 activation/weight bytes, f32 output bytes), and
    every ``__net__`` row gains int8-vs-f32 output-agreement columns
    (``max_abs_dlogit_vs_f32``, ``top1_match_vs_f32``) against the
    sparse-f32 forward at the same density on the same seeded input.
    The calibrated measured-vs-modeled columns are f32-only and skipped.
    """
    from repro.core.accel_model import PE_4_14_3, aggregate, \
        network_cycle_reports, network_traffic_reports
    from repro.models.graph import collect_conv_traffic, net_apply, sparsify
    from repro.models.layers import init_params

    if dtype not in ("f32", "int8"):
        raise ValueError(f"dtype must be 'f32' or 'int8', got {dtype!r}")
    int8 = dtype == "int8"
    net = _net_builders()[net_name](num_classes, image_size=image_size)
    params = init_params(net.schema(), jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((batch, image_size, image_size, 3)),
                    jnp.float32)
    pe = PE_4_14_3
    rows = []
    base_us = None
    for density in densities:
        sparse, pruned = sparsify(net, params, density,
                                  dtype="int8" if int8 else None)
        fn = jax.jit(lambda xx: net_apply(net, params, xx, sparse=sparse,
                                          impl="jnp"))
        fn(x).block_until_ready()
        t0 = time.time()
        for _ in range(3):
            out = fn(x)
        out.block_until_ready()
        us = (time.time() - t0) / 3 * 1e6
        if base_us is None:
            base_us = us  # density 1.0 reference
        agreement = {}
        if int8:
            # output agreement vs the sparse-f32 forward at the same
            # density (the dense-f32 reference is the density-1.0 row)
            sparse_f, _ = sparsify(net, params, density)
            ref = np.asarray(net_apply(net, params, x, sparse=sparse_f,
                                       impl="jnp"))
            got = np.asarray(out)
            agreement = {
                "max_abs_dlogit_vs_f32": round(
                    float(np.abs(got - ref).max()), 6),
                "top1_match_vs_f32": round(
                    float((got.argmax(-1) == ref.argmax(-1)).mean()), 4),
            }
        # cycle model on the pruned weights + real forward-pass activations,
        # DRAM traffic model on the encoded geometry (itemsizes keyed off
        # the stored weight dtype — int8 in/weight bytes, f32 out bytes)
        traffic = collect_conv_traffic(net, pruned, x[:1])
        reports = network_cycle_reports(traffic, pe)
        byte_reports = dict(network_traffic_reports(traffic, sparse))
        measured = _measured_vs_modeled(net, params, x, density) \
            if measure and not int8 else {}
        for name, rep in reports:
            layer = next(l for l in net.conv_layers() if l.name == name)
            tr = byte_reports[name]
            geom = f"{layer.kh}x{layer.kw}_s{layer.stride}"
            if layer.groups > 1:
                geom += "_dw" if layer.groups == layer.cin \
                    else f"_g{layer.groups}"
            if layer.dilation > 1:
                geom += f"_d{layer.dilation}"
            row = {
                "name": f"{net_name}_{name}_density_{density}",
                "layer": name,
                "geometry": geom,
                "density": density,
                "cycle_speedup": round(rep.speedup, 3),
                "vscnn_cycles": rep.vscnn,
                "dense_cycles": rep.dense,
                "structural_flops_vs_dense": round(
                    sparse[name].vs.density, 4),
                "bytes_halo": tr["halo"].bytes_accessed,
                "bytes_stack": tr["stack"].bytes_accessed,
                "ai_halo": round(tr["halo"].arithmetic_intensity, 2),
                "ai_stack": round(tr["stack"].arithmetic_intensity, 2),
            }
            if name in measured:
                row.update({k: v for k, v in measured[name].items()
                            if k in MEASURED_COLS})
            rows.append(row)
        # FC layers have no cycle-model row; their measured-vs-modeled
        # record rides along as its own (ungated: no cycle/bytes metrics)
        conv_names = {name for name, _ in reports}
        for name, m in measured.items():
            if name not in conv_names:
                rows.append({
                    "name": f"{net_name}_{name}_density_{density}",
                    "layer": name,
                    "geometry": "fc",
                    "density": density,
                    **m,
                })
        agg = aggregate([r for _, r in reports])
        rows.append({
            "name": f"{net_name}_net_density_{density}",
            "layer": "__net__",
            "density": density,
            "cycle_speedup": round(agg.speedup, 3),
            "vscnn_cycles": agg.vscnn,
            "dense_cycles": agg.dense,
            "us_per_call": round(us, 1),
            "wallclock_speedup_vs_dense": round(base_us / us, 3),
            "bytes_halo": sum(t["halo"].bytes_accessed
                              for t in byte_reports.values()),
            "bytes_stack": sum(t["stack"].bytes_accessed
                               for t in byte_reports.values()),
            **agreement,
        })
    if out_path:
        artifact = {
            "bench": f"{net_name}_per_layer",
            "net": net_name,
            "dtype": dtype,
            "image_size": image_size,
            "num_classes": num_classes,
            "batch": batch,
            "pe": [pe.blocks, pe.rows, pe.cols],
            "densities": list(densities),
            "rows": rows,
        }
        with open(out_path, "w") as f:
            json.dump(artifact, f, indent=1)
    return rows


def run_resnet18(densities=(1.0, 0.5, 0.25), *, image_size: int = 32,
                 num_classes: int = 200, batch: int = 1,
                 out_path: str | None = None) -> list[dict]:
    """Back-compat alias for `run_network("resnet18", ...)`."""
    return run_network("resnet18", densities, image_size=image_size,
                       num_classes=num_classes, batch=batch,
                       out_path=out_path)


# --------------------------------------------------------------------------
# Benchmark-regression gate (--compare-baseline)
# --------------------------------------------------------------------------

# per-layer metrics gated against the committed baseline.  Wall-clock
# columns are deliberately absent: only deterministic model outputs (cycle
# counts from seeded weights/activations, modeled bytes from the encoded
# geometry) are stable enough to gate at 10%.
COMPARE_HIGHER_IS_BETTER = ("cycle_speedup",)
COMPARE_LOWER_IS_BETTER = ("bytes_halo", "bytes_stack")


def compare_baseline(rows: list[dict], baseline: dict, *,
                     tol: float = 0.10) -> tuple[list[str], list[str]]:
    """Compare fresh bench rows against a committed baseline artifact.

    Returns ``(failures, table_lines)``: a failure for every per-layer
    metric that regressed by more than ``tol`` (speedup down >10%, or
    modeled bytes up >10%) and for every baseline row that vanished; the
    table is a GitHub-flavoured markdown per-layer delta table for
    ``$GITHUB_STEP_SUMMARY``.  Rows new in this run (new layers/nets) pass
    — they have no baseline to regress against.
    """
    cur = {r["name"]: r for r in rows}
    failures: list[str] = []
    lines = [
        "| layer row | metric | baseline | current | delta | status |",
        "|---|---|---|---|---|---|",
    ]
    for b in baseline["rows"]:
        name = b["name"]
        c = cur.get(name)
        if c is None:
            failures.append(f"{name}: row missing from current bench")
            lines.append(f"| {name} | — | — | MISSING | — | FAIL |")
            continue
        for metric, better in (
            [(m, "higher") for m in COMPARE_HIGHER_IS_BETTER]
            + [(m, "lower") for m in COMPARE_LOWER_IS_BETTER]
        ):
            if metric not in b or metric not in c:
                continue
            bv, cv = float(b[metric]), float(c[metric])
            delta = (cv - bv) / max(abs(bv), 1e-12)
            if better == "higher":
                bad = cv < bv * (1.0 - tol)
            else:
                bad = cv > bv * (1.0 + tol)
            status = "FAIL" if bad else "ok"
            if bad:
                failures.append(
                    f"{name}: {metric} {bv:g} -> {cv:g} "
                    f"({delta:+.1%}, tol ±{tol:.0%})")
            lines.append(
                f"| {name} | {metric} | {bv:g} | {cv:g} | {delta:+.1%} "
                f"| {status} |")
    return failures, lines


def gate_baseline(baseline_path: str, *, tol: float = 0.10,
                  out_path: str | None = None) -> int:
    """CI regression gate: re-run the per-network bench at the committed
    baseline's settings and fail on any >tol per-layer regression.  Writes
    the per-layer delta table to ``$GITHUB_STEP_SUMMARY`` when set;
    ``out_path`` writes the fresh rows as the run's bench artifact (so the
    gate run doubles as the trajectory artifact — no second bench pass)."""
    with open(baseline_path) as f:
        baseline = json.load(f)
    rows = run_network(
        baseline.get("net", "resnet18"),
        tuple(baseline["densities"]),
        image_size=baseline["image_size"],
        num_classes=baseline["num_classes"],
        batch=baseline.get("batch", 1),
        dtype=baseline.get("dtype", "f32"),
        out_path=out_path,
    )
    failures, lines = compare_baseline(rows, baseline, tol=tol)
    summary = "\n".join(
        [f"## Benchmark regression gate — `{baseline_path}` "
         f"({'FAIL' if failures else 'PASS'})", ""]
        + lines + [""]
        + [f"- {f}" for f in failures])
    step_summary = os.environ.get("GITHUB_STEP_SUMMARY")
    if step_summary:
        with open(step_summary, "a") as f:
            f.write(summary + "\n")
    print(summary)
    if failures:
        print(f"baseline gate: FAIL ({len(failures)} regression(s))")
        return 1
    print("baseline gate: PASS")
    return 0


def gate_int8_traffic(*, ratio_max: float = 0.55) -> bool:
    """Per-layer dtype half of the traffic gate: on every weight-carrying
    layer of resnet18 (every conv, both input layouts, plus the FC head)
    the int8 contract's modeled HBM bytes must be strictly below the f32
    contract's — and at most ``ratio_max`` of it (int8 activations+weights
    at 1 byte, the f32 output stream unchanged)."""
    from repro.core.accel_model import network_traffic_reports
    from repro.kernels.plan import fc_plan
    from repro.models.graph import collect_conv_traffic, sparsify
    from repro.models.layers import init_params

    net = _net_builders()["resnet18"](200, image_size=32)
    params = init_params(net.schema(), jax.random.PRNGKey(0), jnp.float32)
    x = jnp.asarray(
        np.random.default_rng(3).standard_normal((1, 32, 32, 3)),
        jnp.float32)
    ok = True
    worst = 0.0
    per_layer: dict[str, dict[str, int]] = {}
    for dt in ("f32", "int8"):
        sparse, pruned = sparsify(net, params, 0.5,
                                  dtype="int8" if dt == "int8" else None)
        traffic = collect_conv_traffic(net, pruned, x)
        for name, tr in network_traffic_reports(traffic, sparse):
            for impl in ("halo", "stack"):
                per_layer.setdefault(f"{name}[{impl}]", {})[dt] = \
                    tr[impl].bytes_accessed
        # FC head: quote the vsmm plan's cost under this dtype contract
        fc = sparse.get("fc")
        if fc is not None:
            a_i, w_i, o_i = (1, 1, 4) if dt == "int8" else (4, 4, 4)
            plan = fc_plan(
                m=1, k=fc.vs.shape[0], s_steps=fc.vs.nnz_per_strip,
                vk=fc.vs.vk, vn=fc.vs.vn, nb=fc.vs.vals.shape[0],
                has_bias=True, has_scale=dt == "int8", itemsize=a_i,
                w_itemsize=w_i, out_itemsize=o_i)
            per_layer.setdefault("fc[vsmm]", {})[dt] = \
                plan.cost.bytes_accessed
    for name, b in sorted(per_layer.items()):
        r = b["int8"] / b["f32"]
        worst = max(worst, r)
        bad = not (b["int8"] < b["f32"] and r <= ratio_max)
        if bad:
            print(f"FAIL: {name}: int8 {b['int8']:,} B vs f32 "
                  f"{b['f32']:,} B (ratio {r:.3f} > {ratio_max})")
            ok = False
    print(f"int8 traffic gate: {len(per_layer)} weight-carrying layer "
          f"rows, worst int8/f32 byte ratio {worst:.3f} "
          f"(bound {ratio_max})")
    return ok


def gate_traffic() -> int:
    """CI smoke gate for the halo layout's bandwidth claim.

    Runs both conv impls in interpret mode (allclose vs the oracle) and
    checks the modeled HBM bytes — the halo path must be *strictly below*
    the stack path — on two geometries: the ResNet 7x7/s2 stem and a
    MobileNetV1 depthwise 3x3/s2 layer (512 channels, the stage-4
    downsample), each at the ImageNet size and the reduced CI size.
    Also asserts the int8 dtype contract's modeled bytes are strictly
    below (and at most 0.55x) the f32 contract's on every weight-carrying
    resnet18 layer (`gate_int8_traffic`).  Returns a process exit code.
    """
    from repro.core import conv_cin_major
    from repro.core.accel_model import conv_layer_traffic

    rng = np.random.default_rng(7)
    ok = True

    # --- ResNet 7x7/s2 stem -------------------------------------------------
    kh, kw, stride, cin, cout, vk, vn = 7, 7, 2, 8, 64, 8, 64
    wm = rng.standard_normal((kh * kw * cin, cout)).astype(np.float32)
    vs = conv_cin_major(encode(jnp.asarray(wm), vk, vn), cin // vk)
    x = jnp.asarray(
        np.maximum(rng.standard_normal((1, 28, 28, cin)), 0), jnp.float32)
    ref = vsconv_ref(x, vs, kh=kh, kw=kw, stride=stride)
    for impl in ("halo", "stack"):
        out = vsconv(x, vs, kh=kh, kw=kw, stride=stride, impl=impl)
        rel = float(np.abs(np.asarray(out) - np.asarray(ref)).max()
                    / np.abs(np.asarray(ref)).max())
        print(f"stem 7x7/s2 {impl}: rel err vs ref {rel:.2e}")
        ok &= rel < 1e-5
    for h in (28, 224):
        tr = {impl: conv_layer_traffic(
                  (1, h, h, cin), kh=kh, kw=kw, stride=stride, cout=cout,
                  s_steps=vs.nnz_per_strip, vk=vk, vn=vn, impl=impl)
              for impl in ("halo", "stack")}
        ratio = tr["stack"].bytes_accessed / max(tr["halo"].bytes_accessed, 1)
        print(f"stem 7x7/s2 @{h}: halo {tr['halo'].bytes_accessed:,} B, "
              f"stack {tr['stack'].bytes_accessed:,} B "
              f"(stack/halo {ratio:.2f}x)")
        if not tr["halo"].bytes_accessed < tr["stack"].bytes_accessed:
            print("FAIL: halo modeled bytes not strictly below stack")
            ok = False

    # --- MobileNetV1 depthwise 3x3/s2 (512ch stage-4 downsample) ------------
    kh, kw, stride, c, vc = 3, 3, 2, 512, 128
    wm = rng.standard_normal((kh * kw, c)).astype(np.float32)
    dvs = encode(jnp.asarray(
        prune_vectors_balanced(wm, 0.5, 1, vc)[0]), 1, vc)
    x = jnp.asarray(
        np.maximum(rng.standard_normal((1, 14, 14, c)), 0), jnp.float32)
    ref = vsconv_ref(x, dvs, kh=kh, kw=kw, stride=stride, groups=c)
    for impl in ("halo", "stack"):
        out = vsconv(x, dvs, kh=kh, kw=kw, stride=stride, groups=c,
                     impl=impl)
        rel = float(np.abs(np.asarray(out) - np.asarray(ref)).max()
                    / np.abs(np.asarray(ref)).max())
        print(f"dw 3x3/s2 {impl}: rel err vs ref {rel:.2e}")
        ok &= rel < 1e-5
    for h in (14, 28):
        tr = {impl: conv_layer_traffic(
                  (1, h, h, c), kh=kh, kw=kw, stride=stride, groups=c,
                  cout=c, s_steps=dvs.nnz_per_strip, vk=1, vn=vc, impl=impl)
              for impl in ("halo", "stack")}
        ratio = tr["stack"].bytes_accessed / max(tr["halo"].bytes_accessed, 1)
        print(f"dw 3x3/s2 @{h}: halo {tr['halo'].bytes_accessed:,} B, "
              f"stack {tr['stack'].bytes_accessed:,} B "
              f"(stack/halo {ratio:.2f}x)")
        if not tr["halo"].bytes_accessed < tr["stack"].bytes_accessed:
            print("FAIL: halo modeled bytes not strictly below stack (dw)")
            ok = False

    ok &= gate_int8_traffic()

    print("traffic gate:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default=None,
                    choices=["vgg16", "resnet18", "resnet34", "resnet50",
                             "mobilenet_v1"],
                    help="run a per-layer network table instead of the "
                         "kernel micro-benches")
    ap.add_argument("--resnet18", action="store_true",
                    help="alias for --net resnet18")
    ap.add_argument("--gate-traffic", action="store_true",
                    help="CI gate: both conv impls on the 7x7/s2 stem and a "
                         "depthwise 3x3/s2 MobileNet layer; fail unless the "
                         "halo path's modeled bytes_accessed is strictly "
                         "below the stack path's")
    ap.add_argument("--compare-baseline", default=None, metavar="PATH",
                    help="CI gate: re-run the per-network bench at the "
                         "committed baseline's settings and fail on a >10%% "
                         "per-layer cycle-speedup or modeled-bytes "
                         "regression (delta table to $GITHUB_STEP_SUMMARY)")
    ap.add_argument("--tol", type=float, default=0.10,
                    help="regression tolerance for --compare-baseline")
    ap.add_argument("--size", type=int, default=32)
    ap.add_argument("--classes", type=int, default=200)
    ap.add_argument("--dtype", default="f32", choices=["f32", "int8"],
                    help="weight/activation precision for the per-network "
                         "bench: int8 runs the compound sparsity x "
                         "precision path with output-agreement columns "
                         "vs the sparse-f32 forward")
    ap.add_argument("--out", default=None,
                    help="write rows as a JSON artifact "
                         "(e.g. BENCH_resnet18.json)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.gate_traffic:
        raise SystemExit(gate_traffic())
    if args.compare_baseline:
        raise SystemExit(gate_baseline(args.compare_baseline, tol=args.tol,
                                       out_path=args.out))
    net = args.net or ("resnet18" if args.resnet18 else None)
    if net:
        for r in run_network(net, image_size=args.size,
                             num_classes=args.classes, dtype=args.dtype,
                             out_path=args.out):
            print(r)
    else:
        for r in run():
            print(r)

"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads`` entry) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  Set-up builds ``CNNServer`` with
weights drawn from ``--seed`` and warms every wave width the mix runs; the
window then drives ``CNNServer.serve`` for ``--seconds``; after it, every
delivered request's logits are compared with the plain reference
(``harness/reference.py``).  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
by ``bench/metrics/<name>.py`` from a device trace of the window and the
harness's own spans.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and with ``--trace 1`` ``breakdown``;
``checks`` last).  The numbers compared are also the last lines of stderr.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.

Options for measuring the benchmark itself, not used by its checks:
``--config`` serves another configuration file under the cell's traffic;
``--control 1`` puts the control (the reference one precision step down)
in the program's place: after the window, each delivered request's logits
are replaced by the control's for its image and go through the same
comparison, so the result line reads ``correct: false``.  The program's own
reading is printed on an earlier line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent


def _paths() -> None:
    for p in (BENCH, BENCH.parent / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


class CompileCounter:
    """Programs XLA compiled, and programs loaded from the persistent
    compile cache: JAX times both as one compile event and counts the
    loads as cache hits."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.events = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, name: str, *_args, **_kw) -> None:
        self.events += name == self.EVENT

    def _event(self, name: str, *_args, **_kw) -> None:
        self.hits += name == self.HIT

    def take(self) -> str:
        """What happened since the last call."""
        out = (f"{self.events - self.hits} programs compiled by XLA, "
               f"{self.hits} loaded from the compile cache")
        self.events = self.hits = 0
        return out


def device_info(jax) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             control: bool = False, require_tpu: bool = True,
             t_start: float = T_START) -> int:
    _paths()
    import jax

    if require_tpu and (jax.default_backend() != "tpu"
                        or jax.device_count() < cell.chips):
        print(f"run.py: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {jax.device_count()} {jax.default_backend()} "
              f"device(s)", file=sys.stderr)
        return 2
    from harness import work
    from harness.drive import Driver, make_server
    from harness.reference import network
    from harness.spec import metric_reader
    from harness.traffic import Traffic
    from repro.utils.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cache = enable_compile_cache()
    device = device_info(jax)
    config = cell.config
    traffic = Traffic(cell.traffic, seed)
    counter = CompileCounter()
    t_build = time.perf_counter()
    server = make_server(config, traffic, seed)
    t_warm = time.perf_counter()
    driver = Driver(server, traffic)
    # The served programs hold the weights as constants, so each seed is a
    # new program; writing them (43-130 MB each) to the compile cache only
    # evicts the programs that do repeat: the init ops and the reference.
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 2**62)
    driver.warm()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    setup_s = time.perf_counter() - t_start
    print(f"set-up {setup_s:.2f} s: start {t_build - t_start:.2f} s, server "
          f"build {t_warm - t_build:.2f} s, warm-up "
          f"{t_start + setup_s - t_warm:.2f} s; {counter.take()}",
          flush=True)
    program_compiles = server.backend.apply.compiles

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
        if trace:
            _start_trace(jax, tmp, driver)
        counter.take()
        record = driver.run(seconds)
        in_window = counter.take()
        if trace:
            jax.profiler.stop_trace()
        print(f"compiles in the window: {in_window}; "
              f"{server.backend.apply.compiles - program_compiles} new "
              f"BatchedApply programs; compile cache {cache}", flush=True)
        stats = jax.devices()[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        spans = driver.spans
        del driver, server
        gc.collect()
        layer_work = work.network_work(network(config), config)
        summary = _read_trace(f"{tmp}/window", spans, record, device,
                              {lw.name for lw in layer_work}) \
            if trace else None

    from harness.latency import percentile_ms
    print("latency ms over " + str(record.attempted) + " requests: " + ", ".join(
        f"p{q} {percentile_ms(record, q)}" for q in (50, 90, 95, 99)),
        flush=True)
    checks = _correctness(config, traffic, record, seed, control)
    print(f"after the window (reference): {counter.take()}", flush=True)
    ctx = Context(record=record, setup_s=setup_s, summary=summary,
                  work=layer_work,
                  peaks=lambda: work.load_peaks(device["kind"],
                                                config["dtype"]),
                  traffic=traffic)
    if ctx.traced:
        ran = summary.pallas_layers() or set()
        least = [work.roofline_seconds(lws, ctx.traced_waves(), *ctx.peaks())
                 for lws in ([lw for lw in layer_work if lw.name in ran],
                             layer_work)]
        print(f"Pallas device time {summary.pallas_ns / 1e9!r} s; roofline "
              f"of the layers it ran {least[0]!r} s, of every layer "
              f"{least[1]!r} s", flush=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    from harness.check import passed
    result = {"correct": passed(checks), "attempted": record.attempted,
              "failed": int(record.attempted - record.delivered.sum()),
              "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def _start_trace(jax, tmp: str, driver) -> None:
    """Trace device ops only: the host tracer records ~10^6 host events per
    second of this workload and slows the host it measures, so the harness
    logs its own spans.  The first profiler session of a process runs the
    host slow: a throwaway one over a warm-up call goes first."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    jax.profiler.start_trace(f"{tmp}/warm", profiler_options=opts)
    driver.warm()
    jax.profiler.stop_trace()
    jax.profiler.start_trace(f"{tmp}/window", profiler_options=opts)


def _read_trace(log_dir: str, spans: list, record, device: dict,
                layer_names: set):
    """Reduce the window's trace; the harness spans move onto its clock."""
    from harness import trace as tr

    ops, t0 = tr.load_trace(log_dir)
    spans = [(n, s - t0, e - t0) for n, s, e in spans]
    window = next((s, e) for n, s, e in spans if n == "window")
    summary = tr.reduce(ops, spans, tr.covered(ops, spans, window),
                        layer_names=layer_names)
    device["busy_s"] = summary.busy_ns / 1e9
    device["window_s"] = summary.window_ns / 1e9
    ran = summary.pallas_layers()
    scopes = ("a Pallas kernel ran outside every layer scope" if ran is None
              else f"Pallas kernels ran under {len(ran)} of "
                   f"{len(layer_names)} layer scopes, under none of "
                   f"{sorted(layer_names - ran)}")
    print(f"trace: {len(ops)} device ops; {summary.window_ns / 1e9:.3f} s "
          f"of the {record.elapsed:.3f} s window traced; {scopes}",
          flush=True)
    return summary


def _correctness(config: dict, traffic, record, seed: int,
                 control: bool) -> dict:
    """Compare every delivered request with the reference.  With
    ``control``, the control's logits stand in for the program's."""
    from harness import check
    from harness.reference import Reference

    t = time.perf_counter()
    reference = Reference(config, seed)
    keys = {traffic.image(i) for i in record.logits}
    ref = check.reference_logits(reference, traffic, keys, "highest")
    checks = check.compare(record, traffic, ref, config["limits"])
    print(f"reference: {len(keys)} images in {time.perf_counter() - t:.2f} "
          f"s", flush=True)
    if control:
        print(f"program: logit_gap {checks['logit_gap']['value']!r}",
              flush=True)
        ctl = check.reference_logits(reference, traffic, keys, "bf16x3")
        record.logits = {i: ctl[traffic.image(i)] for i in record.logits}
        checks = check.compare(record, traffic, ref, config["limits"])
    return checks


class Context:
    """What a metric reader (``bench/metrics/<name>.py``) may read."""

    def __init__(self, *, record, setup_s, summary, work, peaks, traffic):
        self.record = record            # harness.drive.Record
        self.setup_s = setup_s
        self.summary = summary          # harness.trace.TraceSummary | None
        self.work = work                # [harness.work.LayerWork]
        self.peaks = peaks              # () -> (FLOP/s, HBM bytes/s)
        self.traffic = traffic

    @property
    def delivered(self) -> int:
        return int(self.record.delivered.sum())

    @property
    def traced(self) -> bool:
        """A trace with device ops in it (none on a host without a TPU)."""
        return self.summary is not None and self.summary.busy_ns > 0

    def traced_calls(self) -> list:
        """The window's ``serve`` calls that ended inside the trace."""
        end = self.summary.window_ns / 1e9 + 1e-3
        return [c for c in self.record.calls if c[1] <= end]

    @property
    def traced_images(self) -> int:
        return sum(n for _, _, n in self.traced_calls())

    def traced_waves(self) -> list[int]:
        """Real images in each lockstep wave inside the trace."""
        wave = self.traffic.wave
        out = []
        for _, _, n in self.traced_calls():
            full, rest = divmod(n, wave)
            out += [wave] * full + ([rest] if rest else [])
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--config", default=None,
                    help="serve this configuration file instead")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _paths()
    from harness.spec import load_cell, load_config

    cell = load_cell(args.workload)
    if args.config:
        cell.config = load_config(args.config)
    return run_cell(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), control=bool(args.control))


if __name__ == "__main__":
    sys.exit(main())

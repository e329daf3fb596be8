"""From a profiler trace to device numbers.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
`load_trace` reads it (``harness.xplane``) and keeps the operations the
first TPU ran: the ``XLA Ops`` line of the ``/device:TPU:0`` plane, as
``(hlo text, start_ns, dur_ns, tf_op)`` with times from the profile's
start (the ``Async XLA Ops`` line holds DMA starts and ends that overlap
compute, and is not busy time).  A Pallas kernel is a ``custom-call``
whose HLO text holds ``custom_call_target="tpu_custom_call"``; it is named
by the instruction before its numeric suffix (``vsconv_halo_pallas.17`` ->
``vsconv_halo_pallas``).  ``tf_op`` is the name scope stack the
instruction was traced under; the served model runs each layer under
``jax.named_scope(<layer name>)``, so an op's layer is the first scope in
it that names a layer of the network (`layer_of`).

Host spans come from the harness (``harness.drive.Driver.spans``, wall
clock), shifted onto the trace's clock by the profile's start time.

`reduce` turns ops and spans into busy time (the union of op intervals),
device time in Pallas kernels and in other ops, the same per layer scope,
the ops that took most time, and the idle time under each harness span.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib

PALLAS_MARK = 'custom_call_target="tpu_custom_call"'
DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
OUTSIDE = "outside harness spans"


def op_name(hlo: str) -> str:
    """``%vsmm_pallas.3 = f32[...] custom-call(...)`` -> ``vsmm_pallas``."""
    head = hlo.split(" = ", 1)[0].strip().lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def is_pallas(hlo: str) -> bool:
    return PALLAS_MARK in hlo


def layer_of(tf_op: str, layer_names) -> str | None:
    """The first scope of ``tf_op`` (``jit(f)/conv1/jit(_pad)/pad``) that
    is one of ``layer_names``; None for an op run outside every layer."""
    return next((p for p in tf_op.split("/") if p in layer_names), None)


def load_trace(log_dir: str | pathlib.Path) -> tuple[list[tuple], int]:
    """(device ops, the profile's start in wall-clock ns) of the one trace
    under ``log_dir``."""
    from harness import xplane

    files = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(files)}")
    space = xplane.parse(files[0].read_bytes())
    ops, start = [], None
    for plane in space.planes:
        if plane.name == DEVICE_PLANE:
            meta = {}  # metadata id -> (hlo text, tf_op)
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    m = meta.get(e.metadata_id)
                    if m is None:
                        md = plane.event_metadata[e.metadata_id]
                        m = meta[e.metadata_id] = (
                            md.name,
                            xplane.stats(plane, md.stats).get("tf_op") or "")
                    ops.append((
                        m[0],
                        float((line.timestamp_ns * 1000 + e.offset_ps)
                              // 1000),
                        float(e.duration_ps // 1000), m[1]))
        stats = xplane.stats(plane, plane.stats)
        if "profile_start_time" in stats:
            start = int(stats["profile_start_time"])
    if start is None:
        raise RuntimeError("trace has no profile_start_time")
    return ops, start


@dataclasses.dataclass
class LayerTime:
    """Device time of the ops under one layer's scope."""

    pallas_ns: float = 0.0
    other_ns: float = 0.0
    kernels: set = dataclasses.field(default_factory=set)  # Pallas op names


@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    busy_ns: float
    pallas_ns: float
    other_ns: float
    pallas_events: int
    device_ops: list       # [[op name, seconds], ...] most time first
    idle_gaps: list        # [[harness span, seconds], ...] most first
    layers: dict           # layer name (None: no layer scope) -> LayerTime

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def pallas_layers(self) -> set | None:
        """The layers under whose scope a Pallas kernel ran; None when a
        Pallas kernel ran outside every layer's scope, so its time belongs
        to no layer the harness can name."""
        if self.layers.get(None, LayerTime()).kernels:
            return None
        return {n for n, lt in self.layers.items() if lt.kernels}


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _segments(spans: list[tuple]) -> list[tuple]:
    """(start, end, innermost span) pieces of the timeline; ``spans`` are
    (name, start, end), nested or disjoint."""
    points = sorted({t for _, s, e in spans for t in (s, e)})
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    out, active, k = [], [], 0
    for a, b in zip(points, points[1:]):
        while k < len(spans) and spans[k][1] <= a:
            active.append(spans[k])
            k += 1
        active = [sp for sp in active if sp[2] > a]
        out.append((a, b, active[-1][0] if active else OUTSIDE))
    return out


def _attribute(idle: list[tuple], segments: list[tuple]) -> dict:
    """Time of the (sorted, disjoint) idle intervals under each span."""
    acc: collections.Counter = collections.Counter()
    k = 0
    for s, e in idle:
        covered = 0.0
        while k < len(segments) and segments[k][1] <= s:
            k += 1
        j = k
        while j < len(segments) and segments[j][0] < e:
            a, b, label = segments[j]
            d = min(b, e) - max(a, s)
            if d > 0:
                acc[label] += d
                covered += d
            j += 1
        if e - s - covered > 0:
            acc[OUTSIDE] += e - s - covered
    return acc


def covered(ops: list[tuple], spans: list[tuple],
            window: tuple[float, float]) -> tuple[float, float]:
    """The part of ``window`` the trace holds.  The profiler keeps a bounded
    number of device events and drops later ones: when a ``serve`` span
    starts after the last op kept, the trace ends where the ``serve`` span
    that op belongs to began."""
    w0, w1 = window
    if not ops:
        return w0, w1
    last = max(op[1] + op[2] for op in ops)
    starts = [s for n, s, _ in spans if n == "serve"]
    if any(s > last for s in starts):
        w1 = max((s for s in starts if s <= last), default=w1)
    return w0, w1


def reduce(ops: list[tuple], spans: list[tuple], window: tuple[float, float],
           top: int = 10, layer_names=frozenset()) -> TraceSummary:
    """Device numbers over ``window`` (start, end).  ``ops`` are (hlo,
    start, duration, tf_op), the ``tf_op`` optional, and ``spans`` (name,
    start, end), all in ns on the trace's clock.  Ops are clipped to the
    window.  An op's layer is found among ``layer_names`` (`layer_of`)."""
    w0, w1 = window
    kept = []
    for name, s, d, *tf_op in ops:
        s0, e0 = max(s, w0), min(s + d, w1)
        if e0 > s0:
            kept.append((name, s0, e0, tf_op[0] if tf_op else ""))
    busy = _union([(s, e) for _, s, e, _ in kept])
    per_op: collections.Counter = collections.Counter()
    layers: dict = collections.defaultdict(LayerTime)
    pallas_ns = other_ns = 0.0
    pallas_events = 0
    for name, s, e, tf_op in kept:
        per_op[op_name(name)] += e - s
        lt = layers[layer_of(tf_op, layer_names)]
        if is_pallas(name):
            pallas_ns += e - s
            pallas_events += 1
            lt.pallas_ns += e - s
            lt.kernels.add(op_name(name))
        else:
            other_ns += e - s
            lt.other_ns += e - s
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps = _attribute(idle, _segments(spans))
    return TraceSummary(
        window_ns=w1 - w0,
        busy_ns=sum(e - s for s, e in busy),
        pallas_ns=pallas_ns, other_ns=other_ns, pallas_events=pallas_events,
        device_ops=[[n, t / 1e9] for n, t in per_op.most_common(top)],
        idle_gaps=[[n, t / 1e9] for n, t in gaps.most_common(top)],
        layers=dict(layers))

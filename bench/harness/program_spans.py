"""Per-layer numbers from the program's own spans (``repro.launch.spans``).

The program records its spans while a profiler session is on, so a traced
run holds them for every ``serve`` call of the window.  A reader sees
neither the harness's spans nor the trace, so it finds the window by
count: the window's calls are the last ``len(ctx.record.calls)`` top-level
``scheduler.serve`` spans (the throwaway profiler session before the
window records warm-up calls too; nothing calls the server after the
window), and the traced calls are the first ``len(ctx.traced_calls())`` of
those.  Each matched span's length must agree with the harness's own
timing of its call within `TOLERANCE_S`, and no span of the window may
have left the buffer: otherwise the match is wrong and the readers raise
rather than report a number.
"""
from __future__ import annotations

import collections

TOLERANCE_S = 1e-3
SERVE = "scheduler.serve"


def _seconds(span) -> float:
    return (span.end_ns - span.start_ns) / 1e9


def match(recorded: list, calls: list, traced: int,
          capacity: int) -> list[tuple]:
    """``(serve span, [spans under it])`` for the first ``traced`` of the
    window's ``calls`` ((start, end, requests) each, harness seconds), from
    the program's ``recorded`` spans (a buffer of ``capacity``)."""
    tops = sorted((s for s in recorded if s.parent is None
                   and s.name == SERVE), key=lambda s: s.start_ns)
    if len(tops) < len(calls):
        raise ValueError(f"{len(tops)} {SERVE} spans recorded for the "
                         f"window's {len(calls)} calls")
    tops = tops[len(tops) - len(calls):]
    if len(recorded) >= capacity and recorded[0].end_ns >= tops[0].start_ns:
        raise ValueError(f"the span buffer ({capacity}) wrapped inside the "
                         f"window")
    for k, (s, (t0, t1, _)) in enumerate(zip(tops, calls)):
        if abs(_seconds(s) - (t1 - t0)) > TOLERANCE_S:
            raise ValueError(f"call {k} of the window took {t1 - t0:.6f} s "
                             f"but its {SERVE} span {_seconds(s):.6f} s")
    tops = tops[:traced]
    by_id = {s.id: s for s in recorded}
    under: dict = {s.id: [] for s in tops}
    for s in recorded:
        p = s.parent
        while p is not None and p not in under:
            p = by_id[p].parent if p in by_id else None
        if p is not None:
            under[p].append(s)
    return [(s, under[s.id]) for s in tops]


def totals(ctx) -> collections.Counter | None:
    """Over the traced calls: seconds under each span name, ``requests``
    (from ``scheduler.serve``) and ``images`` (from ``backend.wave``).
    None for an untraced run or a program that records no spans."""
    if not ctx.traced:
        return None
    try:
        from repro.launch import spans
    except ImportError:
        return None
    out: collections.Counter = collections.Counter()
    for top, under in match(spans.recorded(), ctx.record.calls,
                            len(ctx.traced_calls()), spans.CAPACITY):
        out[SERVE] += _seconds(top)
        out["requests"] += top.attrs["requests"]
        for s in under:
            out[s.name] += _seconds(s)
            out["images"] += s.attrs.get("images", 0)
    return out

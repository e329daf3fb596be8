"""Spans of the served path, on the wall clock the profiler's trace uses.

A span is ``(id, parent, name, start_ns, end_ns, attrs)``: times from
`time.time_ns` (the clock a JAX profile's ``profile_start_time`` is on, so
a reduction can shift these spans onto the device trace), ``parent`` the id
of the span open around it, ``attrs`` its counters.  Finished spans go into
a bounded in-memory buffer (the newest `CAPACITY`), read with `recorded`;
nothing is written out.

Recording follows the JAX profiler: `top` opens the span of one call into
the served path (a scheduler's ``serve``) and is the one place that asks
whether a profiler session is active.  If none is, every span opened under
that call is the shared no-op `OFF`: one flag check, no object.  So a
``jax.profiler.trace(...)`` around serving records these spans with no
other switch.  The recorder, like the profiler session it follows, is one
per process and assumes one serving thread.

Two ways to open a span:

  ``with span(name, **attrs):``       ends when the block does;
  ``s = begin(name, start_ns, ...)``  ends at ``s.end(end_ns)``; each
                                      ``with s:`` makes it the parent of the
                                      spans opened in that block, so work
                                      that interleaves with other spans (a
                                      fleet replica's run, a wave between its
                                      dispatch and its collect) keeps one span.
"""
from __future__ import annotations

import collections
import itertools
import time

from jax._src import profiler as _jax_profiler

__all__ = ["CAPACITY", "OFF", "Span", "annotate", "begin", "clear",
           "profiler_active", "recorded", "span", "top"]

CAPACITY = 65536

_buffer: collections.deque = collections.deque(maxlen=CAPACITY)
_open: list = []             # the spans whose ``with`` block is open
_ids = itertools.count(1)
_on = False


def profiler_active() -> bool:
    """True while a JAX profiler session is on (`jax.profiler.start_trace`
    or `jax.profiler.trace`).  JAX has no public query for it, so this reads
    the session JAX keeps privately; ``tests/test_spans.py`` fails if a JAX
    upgrade moves it."""
    return _jax_profiler._profile_state.profile_session is not None


class _Off:
    """What every opener returns while recording is off: does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass

    def end(self, end_ns: int | None = None) -> None:
        pass


OFF = _Off()


class Span:
    __slots__ = ("id", "parent", "name", "start_ns", "end_ns", "attrs",
                 "_closes")

    def __init__(self, name: str, start_ns: int, attrs: dict,
                 closes: bool):
        self.id = next(_ids)
        self.parent = _open[-1].id if _open else None
        self.name = name
        self.start_ns = start_ns
        self.end_ns = None
        self.attrs = attrs
        self._closes = closes

    def __enter__(self):
        _open.append(self)
        return self

    def __exit__(self, *exc):
        _open.pop()
        if self._closes:
            self.end()
        return False

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def end(self, end_ns: int | None = None) -> None:
        self.end_ns = time.time_ns() if end_ns is None else end_ns
        _buffer.append(self)

    def __repr__(self) -> str:
        return (f"Span({self.id}, {self.parent}, {self.name!r}, "
                f"{self.start_ns}, {self.end_ns}, {self.attrs})")


class _Top(Span):
    """A `top` span: recording is on while its block runs."""

    def __enter__(self):
        global _on
        _on = True
        return super().__enter__()

    def __exit__(self, *exc):
        global _on
        super().__exit__(*exc)
        _on = False
        return False


def top(name: str, **attrs):
    """The span of one call into the served path: records this call's spans
    if a profiler session is active now, else returns `OFF`."""
    if _on:                  # called from inside a recorded call
        return span(name, **attrs)
    if not profiler_active():
        return OFF
    return _Top(name, time.time_ns(), attrs, True)


def span(name: str, **attrs):
    """A span over a ``with`` block, child of the span open around it."""
    if not _on:
        return OFF
    return Span(name, time.time_ns(), attrs, True)


def begin(name: str, start_ns: int | None = None, **attrs):
    """A span that ends at its ``end()``; ``start_ns`` is a clock reading
    the caller already took, else now."""
    if not _on:
        return OFF
    return Span(name, time.time_ns() if start_ns is None else start_ns,
                attrs, False)


def annotate(**attrs) -> None:
    """Add counters to the innermost span whose ``with`` block is open."""
    if _on and _open:
        _open[-1].attrs.update(attrs)


def recorded() -> list:
    """The finished spans in the buffer, in the order they ended."""
    return list(_buffer)


def clear() -> None:
    _buffer.clear()

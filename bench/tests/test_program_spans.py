"""The readers of the program's spans, on synthetic spans and a fake
context: finding the window's calls, the traced subset, each metric's
arithmetic, and refusing a match that cannot be right."""
import types

import pytest

from harness import program_spans
from harness.spec import metric_reader
from repro.launch import spans

MS = 1_000_000


class _Spans:
    """Builds spans as the program records them: ids in opening order,
    the buffer in the order they end."""

    def __init__(self):
        self.out, self.next_id = [], 1

    def add(self, name, start_ms, end_ms, parent=None, **attrs):
        s = types.SimpleNamespace(
            id=self.next_id, parent=parent and parent.id, name=name,
            start_ns=int(start_ms * MS), end_ns=int(end_ms * MS),
            attrs=attrs)
        self.next_id += 1
        self.out.append(s)
        return s

    def call(self, t0_ms, waves, wait_ms=4.0, stack_ms=1.0, put_ms=0.5):
        """One serve call at ``t0_ms``: a wave of ``images`` per entry of
        ``waves``, each 10 ms long, then 1 ms of delivery."""
        top = self.add("scheduler.serve", t0_ms, 0, requests=sum(waves))
        run = self.add("scheduler.run", t0_ms + 1, 0, top, replica=0)
        t = t0_ms + 1
        for images in waves:
            w = self.add("backend.wave", t, t + 10, run, wave=0, replica=0,
                         images=images, rows=images)
            self.add("backend.stack", t, t + stack_ms, w)
            self.add("backend.put", t + stack_ms, t + stack_ms + put_ms, w)
            self.add("backend.wait", t + 10 - wait_ms, t + 10, w)
            t += 10
        run.end_ns = int(t * MS)
        top.end_ns = int((t + 1) * MS)
        # a parent ends after its children: move it behind them
        self.out.remove(run)
        self.out.append(run)
        self.out.remove(top)
        self.out.append(top)
        return (t0_ms / 1e3, (t + 1) / 1e3, sum(waves))


def _ctx(calls, traced=None, is_traced=True):
    traced = len(calls) if traced is None else traced
    return types.SimpleNamespace(
        traced=is_traced, record=types.SimpleNamespace(calls=calls),
        traced_calls=lambda: calls[:traced])


@pytest.fixture
def recorded(monkeypatch):
    rec = _Spans()
    monkeypatch.setattr(spans, "recorded", lambda: list(rec.out))
    return rec


def test_window_is_the_last_calls(recorded):
    """Warm-up calls before the window are skipped; the window's calls
    are matched in order."""
    recorded.call(0, [2])                      # warm-up: 1 + 10 + 1 ms
    calls = [recorded.call(100, [4, 4]), recorded.call(200, [4, 1])]
    found = program_spans.match(recorded.out, calls, 2, spans.CAPACITY)
    assert [top.start_ns for top, _ in found] == [100 * MS, 200 * MS]
    for top, under in found:
        assert len(under) == 1 + 4 * 2         # the run, 4 spans per wave
        assert all(top.start_ns <= s.start_ns for s in under)


def test_traced_calls_are_the_first_of_the_window(recorded):
    calls = [recorded.call(100 * k, [4]) for k in range(5)]
    t = program_spans.totals(_ctx(calls, traced=2))
    assert t["requests"] == 8
    assert t["scheduler.serve"] == pytest.approx(2 * 0.012)


def test_offline_metrics(recorded):
    calls = [recorded.call(100, [32, 32, 32, 32]),
             recorded.call(200, [32, 32, 32, 32])]
    ctx = _ctx(calls)
    # per call 42 ms of serve, 4 waves x 4 ms of wait: 26 ms of host
    host = metric_reader("host_ms_per_image.offline")(ctx)
    assert host == pytest.approx(2 * 26 / 256)
    # per wave 1 ms of stack + 0.5 ms of put
    inp = metric_reader("input_ms_per_image.offline")(ctx)
    assert inp == pytest.approx(8 * 1.5 / 256)


def test_single_stream_metric(recorded):
    calls = [recorded.call(10 * k, [1], wait_ms=2.5) for k in range(3)]
    host = metric_reader("host_ms_per_request.single")(_ctx(calls))
    assert host == pytest.approx(12 - 2.5)


def test_untraced_run_reads_nothing(recorded):
    calls = [recorded.call(0, [4])]
    for name in ("host_ms_per_image.offline", "input_ms_per_image.offline",
                 "host_ms_per_request.single"):
        assert metric_reader(name)(_ctx(calls, is_traced=False)) is None


def test_duration_mismatch_raises(recorded):
    t0, t1, n = recorded.call(100, [4])
    with pytest.raises(ValueError, match="took"):
        program_spans.totals(_ctx([(t0, t1 + 0.002, n)]))
    # within a millisecond is the same call
    assert program_spans.totals(_ctx([(t0, t1 + 0.0005, n)]))


def test_too_few_spans_raises(recorded):
    calls = [recorded.call(100, [4])]
    with pytest.raises(ValueError, match="recorded for the window"):
        program_spans.totals(_ctx(calls + [(1.0, 1.012, 4)]))


def test_wrapped_buffer_raises(recorded, monkeypatch):
    """A full buffer whose oldest span ended inside the window may have
    lost the window's first spans."""
    calls = [recorded.call(100, [4]), recorded.call(200, [4])]
    monkeypatch.setattr(spans, "CAPACITY", len(recorded.out))
    del recorded.out[0]                        # the first stack span, gone
    recorded.call(300, [4])
    calls.append((0.3, 0.312, 4))
    with pytest.raises(ValueError, match="wrapped"):
        program_spans.totals(_ctx(calls))
    # the same count of spans with a warm-up call in front is complete
    fresh = _Spans()
    fresh.call(0, [4])
    calls = [fresh.call(100, [4])]
    monkeypatch.setattr(spans, "CAPACITY", len(fresh.out))
    assert program_spans.match(fresh.out, calls, 1, spans.CAPACITY)

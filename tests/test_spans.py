"""The served path's spans (`launch.spans`): recorded only while a JAX
profiler session is on, one tree per ``serve`` call, counters where the
work happens, and the scheduler's run times read from the same clock."""
import pathlib

import jax
import numpy as np
import pytest

from repro.analysis.diagnostics import Report
from repro.analysis.lint import lint_source
from repro.configs import get_config
from repro.launch import spans
from repro.launch.scheduler import FleetScheduler
from repro.launch.serve import CNNServer, ImageRequest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"

TREE = {                      # span name -> the name of its parent
    "scheduler.serve": None,
    "scheduler.admit": "scheduler.serve",
    "scheduler.run": "scheduler.serve",
    "scheduler.deliver": "scheduler.run",
    "backend.wave": "scheduler.run",
    "backend.stack": "backend.wave",
    "backend.put": "backend.wave",
    "backend.launch": "backend.wave",
    "backend.wait": "backend.wave",
    "backend.fetch": "backend.wave",
}


@pytest.fixture(scope="module")
def server():
    return CNNServer(get_config("vscnn-vgg16").reduce(), batch=8, seed=0)


def _requests(server, n, first=0):
    s = server.cfg.image_size
    rng = np.random.default_rng(first)
    return [ImageRequest(rid=first + i,
                         image=rng.standard_normal((s, s, 3))
                                  .astype(np.float32))
            for i in range(n)]


def _traced(tmp_path, serve, *args):
    """Run ``serve(*args)`` inside a profiler session; its spans."""
    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        out = serve(*args)
    rec = spans.recorded()
    spans.clear()
    return out, rec


def test_no_profiler_session_records_nothing(server):
    spans.clear()
    assert not spans.profiler_active()
    server.serve(_requests(server, 3))
    assert spans.recorded() == []
    assert spans.span("backend.stack") is spans.OFF
    assert spans.top("scheduler.serve") is spans.OFF


def test_profiler_check_follows_the_session(tmp_path):
    assert spans.profiler_active() is False
    with jax.profiler.trace(str(tmp_path / "a")):
        assert spans.profiler_active() is True
    assert spans.profiler_active() is False
    jax.profiler.start_trace(str(tmp_path / "b"))
    try:
        assert spans.profiler_active() is True
    finally:
        jax.profiler.stop_trace()
    assert spans.profiler_active() is False


def test_traced_serve_records_the_span_tree(server, tmp_path):
    """11 requests at width 8: one run, a full wave and a backfilled
    partial one; every span sits inside its parent's interval."""
    stats, rec = _traced(tmp_path, server.serve, _requests(server, 11, 100))
    by_id = {s.id: s for s in rec}
    assert {s.name for s in rec} == set(TREE)
    for s in rec:
        parent = by_id.get(s.parent)
        assert (parent.name if parent else None) == TREE[s.name], s
        if parent:
            assert parent.start_ns <= s.start_ns <= s.end_ns \
                <= parent.end_ns, (parent, s)
    names = [s.name for s in rec]
    assert names.count("scheduler.serve") == 1
    assert names.count("backend.wave") == 2
    assert names.count("scheduler.deliver") == 3   # after start, each wave
    serve = next(s for s in rec if s.name == "scheduler.serve")
    assert serve.attrs == {"requests": 11}
    admit = next(s for s in rec if s.name == "scheduler.admit")
    assert admit.attrs == {"refused": 0}
    put = [s for s in rec if s.name == "backend.put"]
    assert [p.attrs["bytes"] for p in put] == [8 * 32 * 32 * 3 * 4,
                                                4 * 32 * 32 * 3 * 4]
    # start_s / run_s come from the run span's own clock readings
    (run,) = [s for s in rec if s.name == "scheduler.run"]
    (st,) = stats
    assert run.attrs == {"replica": 0}
    assert st["start_s"] + st["run_s"] == pytest.approx(
        (run.end_ns - run.start_ns) / 1e9, abs=1e-9)
    assert "images_per_s" not in st
    # each request's outcome names the wave that computed it
    waves = {s.attrs["wave"] for s in rec if s.name == "backend.wave"}
    assert {o.wave for o in server.outcomes.values()} == waves


def test_partial_wave_counts_images_and_rows(server, tmp_path):
    _, rec = _traced(tmp_path, server.serve, _requests(server, 3, 200))
    (wave,) = [s for s in rec if s.name == "backend.wave"]
    assert wave.attrs["images"] == 3
    assert wave.attrs["rows"] == 4
    assert wave.attrs["replica"] == 0


@pytest.mark.parametrize("n,ahead", [(20, [0, 1, 1]), (1, [0])])
def test_ahead_marks_waves_dispatched_in_flight(server, tmp_path, n, ahead):
    """Width 8: each wave after a run's first is dispatched while the one
    before it is in flight (``ahead`` 1), and each wave's own stack, put,
    launch, wait and fetch lie inside its span, though the spans of
    successive waves overlap."""
    _, rec = _traced(tmp_path, server.serve, _requests(server, n, 500))
    waves = sorted((s for s in rec if s.name == "backend.wave"),
                   key=lambda s: s.attrs["wave"])
    assert [w.attrs["ahead"] for w in waves] == ahead
    by_id = {s.id: s for s in rec}
    for w in waves:
        kids = [s.name for s in rec if s.parent == w.id]
        assert kids == ["backend.stack", "backend.put", "backend.launch",
                        "backend.wait", "backend.fetch"]
    for w, nxt in zip(waves, waves[1:]):
        assert nxt.start_ns < w.end_ns
    for s in rec:
        if s.name.startswith("backend.") and s.name != "backend.wave":
            w = by_id[s.parent]
            assert w.start_ns <= s.start_ns <= s.end_ns <= w.end_ns


def test_miss_only_on_the_first_call_of_a_shape(server, tmp_path):
    """Two requests run on a width-2 batch, which nothing else here
    serves: the first call compiles it, the second does not."""
    misses = []
    for k in range(2):
        _, rec = _traced(tmp_path / str(k), server.serve,
                         _requests(server, 2, 300 + 10 * k))
        (launch,) = [s for s in rec if s.name == "backend.launch"]
        misses.append(launch.attrs["miss"])
    assert misses == [True, False]


def test_fleet_tags_waves_and_runs_by_replica(server, tmp_path):
    """Two replicas (the same stateless backend twice): each replica's run
    and waves carry its index, and each wave's ``wave`` is the fleet tick
    its requests' outcomes name."""
    fleet = FleetScheduler([server.backend, server.backend], batch=4)
    _, rec = _traced(tmp_path, fleet.serve, _requests(server, 8, 400))
    by_id = {s.id: s for s in rec}
    waves = [s for s in rec if s.name == "backend.wave"]
    runs = [s for s in rec if s.name == "scheduler.run"]
    assert sorted(s.attrs["replica"] for s in runs) == [0, 1]
    assert sorted(s.attrs["replica"] for s in waves) == [0, 1]
    for w in waves:
        assert by_id[w.parent].attrs["replica"] == w.attrs["replica"]
        assert w.attrs["images"] == w.attrs["rows"] == 4
    for o in fleet.outcomes.values():
        assert (o.wave, o.replica) in {
            (w.attrs["wave"], w.attrs["replica"]) for w in waves}
    for s in rec:
        assert (by_id[s.parent].name if s.parent else None) == TREE[s.name]


def test_buffer_keeps_the_newest_spans(monkeypatch):
    monkeypatch.setattr(spans, "profiler_active", lambda: True)
    spans.clear()
    extra = 10
    with spans.top("scheduler.serve"):
        for _ in range(spans.CAPACITY + extra):
            with spans.span("backend.stack"):
                pass
    rec = spans.recorded()
    spans.clear()
    assert len(rec) == spans.CAPACITY
    assert rec[-1].name == "scheduler.serve"
    assert rec[0].name == "backend.stack"
    assert rec[-2].id - rec[0].id == spans.CAPACITY - 2


def test_changed_serving_files_pass_the_lint():
    """VSC302 (no clock read in a scheduler condition) and the other
    repo rules hold over the files that record spans."""
    rep = Report()
    for rel in ("launch/scheduler.py", "launch/serve.py", "launch/spans.py",
                "models/graph.py"):
        lint_source((SRC / rel).read_text(), f"src/repro/{rel}", rep=rep)
    assert not rep.errors, [str(d) for d in rep.errors]

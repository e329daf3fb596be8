"""Trace reduction on a trace recorded on the chip, on synthetic events,
and on a trace recorded here on the CPU."""
import json
import pathlib

import pytest

from harness import trace as tr

# One width-1 ResNet-50 call recorded on a v5e.  It was recorded in an
# open-loop trial whose harness waited for each arrival in a span of its
# own, ``wait_arrival``; the reduction reads any span name.
DATA = pathlib.Path(__file__).parent / "data" / "trace_resnet50_width1.json"


@pytest.fixture(scope="module")
def chip_trace():
    d = json.loads(DATA.read_text())
    spans = [tuple(s) for s in d["spans"]]
    window = next((s, e) for n, s, e in spans if n == "window")
    return [tuple(o) for o in d["ops"]], spans, window


def test_op_name():
    assert tr.op_name("%vsconv_halo_pallas.17 = f32[32,112,112,64]{3,2,1,0} "
                      "custom-call(...)") == "vsconv_halo_pallas"
    assert tr.op_name("%copy-done.115 = f32[1,49]") == "copy-done"
    assert tr.op_name("%fusion = f32[8]") == "fusion"


def test_recorded_width1_call(chip_trace):
    ops, spans, window = chip_trace
    s = tr.reduce(ops, spans, window, top=1000)
    # one kernel per sparse layer of ResNet-50: 53 convs and the fc
    assert s.pallas_events == 54
    names = [n for n, _ in s.device_ops]
    assert names[:2] == ["vsconv_halo_pallas", "vsmm_pallas"]
    # ops on the XLA Ops line never overlap: busy is their sum
    assert s.busy_ns == pytest.approx(s.pallas_ns + s.other_ns)
    assert s.busy_ns == pytest.approx(2429613.0)
    assert s.pallas_ns == pytest.approx(2088689.0)
    assert s.window_ns == pytest.approx(18447120.0)
    assert s.idle_share == pytest.approx(1 - 2429613.0 / 18447120.0)
    gaps = dict(s.idle_gaps)
    assert set(gaps) <= {"serve", "wait_arrival", "window"}
    assert gaps["wait_arrival"] == pytest.approx(
        (3005352035.0 - 2999984415.0) / 1e9)
    assert sum(gaps.values()) == pytest.approx(
        (s.window_ns - s.busy_ns) / 1e9)


def test_union_clip_and_attribution():
    spans = [("window", 0, 100), ("serve", 0, 60), ("build_requests", 0, 5),
             ("wait_arrival", 60, 100)]
    ops = [
        ('%k.1 = f32[1] custom-call(), custom_call_target="tpu_custom_call"',
         10, 20),
        ("%copy.2 = f32[1] copy()", 25, 10),    # overlaps the kernel
        ("%fusion.3 = f32[1] fusion()", 90, 30),  # clipped at 100
        ("%fusion.4 = f32[1] fusion()", -20, 10),  # before the window
    ]
    s = tr.reduce(ops, spans, (0, 100))
    assert s.window_ns == 100
    assert s.busy_ns == 25 + 10          # [10, 35) and [90, 100)
    assert s.pallas_ns == 20 and s.pallas_events == 1
    assert s.other_ns == 10 + 10
    # idle [0, 5) building, [5, 10) and [35, 60) serving, [60, 90) waiting
    assert dict(s.idle_gaps) == {"build_requests": 5e-9, "serve": 30e-9,
                                 "wait_arrival": 30e-9}
    s = tr.reduce(ops, [], (0, 100))
    assert dict(s.idle_gaps) == {tr.OUTSIDE: 65e-9}


def test_covered_window_ends_where_a_cut_trace_ends():
    spans = [("window", 0, 100), ("serve", 0, 30), ("serve", 30, 60),
             ("serve", 60, 90)]
    whole = [("%a.1 = f32[1] fusion()", 5, 10), ("%b.2 = f32[1] fusion()",
                                                  35, 10),
             ("%c.3 = f32[1] fusion()", 65, 10)]
    assert tr.covered(whole, spans, (0, 100)) == (0, 100)
    # the profiler dropped the third call's op: the trace ends where the
    # second call, which holds the last op kept, began
    assert tr.covered(whole[:2], spans, (0, 100)) == (0, 30)
    assert tr.covered([], spans, (0, 100)) == (0, 100)


def test_load_trace_from_a_cpu_trace(tmp_path):
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    t = time.time_ns()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, start = tr.load_trace(tmp_path)
    assert ops == []  # no TPU plane on a CPU
    assert abs(start - t) < 5e9  # the profile's start on the wall clock

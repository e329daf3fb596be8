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


# One width-1 ResNet-50 call of the singlestream cell recorded on a v5e
# with each op's ``tf_op`` name scope stack, after the stem moved to XLA.
SCOPES = pathlib.Path(__file__).parent / "data" / \
    "trace_resnet50_width1_scopes.json"


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


PALLAS = ('%k.{i} = f32[1] custom-call(), '
          'custom_call_target="tpu_custom_call"')


def test_layer_of():
    names = {"conv1", "layer1_0_conv2", "fc"}
    assert tr.layer_of("jit(apply)/layer1_0_conv2/jit(_pad)/pad:",
                       names) == "layer1_0_conv2"
    assert tr.layer_of("jit(apply)/conv1/dot_general:", names) == "conv1"
    assert tr.layer_of("jit(apply)/Pool/reduce_window_max:", names) is None
    assert tr.layer_of("", names) is None


def test_time_per_layer_scope():
    names = {"a", "b", "c"}
    ops = [(PALLAS.format(i=1), 0, 10, "jit(f)/a/pallas_call:"),
           ("%copy.2 = f32[1] copy()", 10, 5, "jit(f)/a/transpose:"),
           (PALLAS.format(i=3), 15, 20, "jit(f)/b/pallas_call:"),
           ("%fusion.4 = f32[1] fusion()", 35, 7, "jit(f)/c/dot_general:"),
           ("%copy.5 = f32[1] copy()", 42, 3, "")]
    s = tr.reduce(ops, [], (0, 100), layer_names=names)
    assert s.layers["a"].pallas_ns == 10 and s.layers["a"].other_ns == 5
    assert s.layers["a"].kernels == {"k"}
    assert s.layers["b"].pallas_ns == 20 and s.layers["b"].other_ns == 0
    assert s.layers["c"].kernels == set() and s.layers["c"].other_ns == 7
    assert s.layers[None].other_ns == 3
    assert s.pallas_layers() == {"a", "b"}
    # a kernel outside every layer's scope: its time belongs to no layer
    s = tr.reduce(ops + [(PALLAS.format(i=6), 50, 4, "jit(f)/g:")], [],
                  (0, 100), layer_names=names)
    assert s.pallas_layers() is None
    # ops without a tf_op (the older recording) carry no scope
    s = tr.reduce([op[:3] for op in ops], [], (0, 100), layer_names=names)
    assert s.pallas_layers() is None and s.pallas_ns == 30


@pytest.fixture(scope="module")
def scoped_call():
    """(ops, spans, window, ResNet-50's layer work at 224 px, f32)."""
    from harness import work
    from harness.reference import network
    from harness.spec import load_config

    d = json.loads(SCOPES.read_text())
    spans = [tuple(s) for s in d["spans"]]
    window = next((s, e) for n, s, e in spans if n == "window")
    config = load_config("resnet50-f32")
    return ([tuple(o) for o in d["ops"]], spans, window,
            work.network_work(network(config), config))


def _roofline_ctx(summary, layer_work):
    from types import SimpleNamespace

    from harness import work

    return SimpleNamespace(
        traced=True, summary=summary, work=layer_work,
        traced_waves=lambda: [1],
        peaks=lambda: work.load_peaks("TPU v5 lite", "float32"))


def test_recorded_call_layer_scopes(scoped_call):
    ops, spans, window, layer_work = scoped_call
    names = {lw.name for lw in layer_work}
    assert len(names) == 54  # 53 convs and the fc
    s = tr.reduce(ops, spans, window, layer_names=names)
    assert s.pallas_events == 53
    # the dense stem runs through XLA: its scope holds device time but no
    # Pallas op; every other layer's scope holds one kernel
    assert s.layers["conv1"].kernels == set()
    assert s.layers["conv1"].other_ns > 0
    assert s.pallas_layers() == names - {"conv1"}
    assert all(len(s.layers[n].kernels) == 1 for n in names - {"conv1"})
    assert s.layers["layer1_0_conv2"].kernels == {"vsconv_halo_pallas"}
    assert s.layers["fc"].kernels == {"vsmm_pallas"}
    assert sum(lt.pallas_ns for lt in s.layers.values()) == s.pallas_ns
    assert sum(lt.other_ns for lt in s.layers.values()) == \
        pytest.approx(s.other_ns)


def test_pallas_roofline_leaves_out_the_stem(scoped_call):
    from harness import work
    from harness.spec import metric_reader

    ops, spans, window, layer_work = scoped_call
    s = tr.reduce(ops, spans, window,
                  layer_names={lw.name for lw in layer_work})
    ctx = _roofline_ctx(s, layer_work)
    pallas_s = s.pallas_ns / 1e9
    peaks = ctx.peaks()
    every = 100 * work.roofline_seconds(layer_work, [1], *peaks) / pallas_s
    stem = 100 * work.roofline_seconds(
        [lw for lw in layer_work if lw.name == "conv1"], [1], *peaks) / pallas_s
    got = metric_reader("pallas_roofline")(ctx)
    assert got == pytest.approx(every - stem, rel=1e-12)
    assert got < every


def test_pallas_roofline_reads_nothing_for_a_kernel_without_a_scope(
        scoped_call, chip_trace):
    from harness.spec import metric_reader

    ops, spans, window, layer_work = scoped_call
    names = {lw.name for lw in layer_work}
    read = metric_reader("pallas_roofline")
    first = next(i for i, o in enumerate(ops) if tr.is_pallas(o[0]))
    unscoped = list(ops)
    unscoped[first] = ops[first][:3] + ("",)
    s = tr.reduce(unscoped, spans, window, layer_names=names)
    assert s.pallas_layers() is None
    assert read(_roofline_ctx(s, layer_work)) is None
    # the older recording, read without scopes
    ops12, spans12, window12 = chip_trace
    s = tr.reduce(ops12, spans12, window12, layer_names=names)
    assert read(_roofline_ctx(s, layer_work)) is None

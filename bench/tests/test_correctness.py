"""The comparison that decides ``correct``, at a size the CPU can hold.

* the reference draws the served model's weights again from the seed and
  matches the program's logits;
* the control (the reference at bf16x3, one precision step below float32
  at highest) fails the limit, alone and in the program's place in a whole
  run;
* a whole run on the CPU (the look for a chip skipped) comes out correct,
  and comes out not correct when the timed path alters an answer or drops a
  request.
"""
import dataclasses
import json
import subprocess
import sys

import numpy as np
import pytest

import run
from harness import check
from harness.reference import Reference
from harness.spec import BENCH_DIR, Cell, load_config

SEED = 2**31 + 123  # seeds go past 32 signed bits
SMALL = dict(image_size=32, num_classes=16)


def small_config(name: str) -> dict:
    return dict(load_config(name), **SMALL)


def program_logits(config: dict, images: np.ndarray) -> np.ndarray:
    from repro.configs import get_config
    from repro.launch.serve import CNNServer, ImageRequest

    cfg = dataclasses.replace(get_config(config["arch"]), **SMALL)
    srv = CNNServer(cfg, batch=4, seed=SEED)
    reqs = [ImageRequest(rid=i, image=im) for i, im in enumerate(images)]
    srv.serve(reqs)
    return np.stack([r.logits for r in reqs])


# MobileNetV1 has no configuration file yet: its 13 depthwise convs are
# held to the limit of the f32 ResNet-50 configuration
MOBILENET = dict(name="mobilenet-v1-f32", arch="vscnn-mobilenet-v1",
                 reference="mobilenet_v1", weight_density=0.5, vk=32, vn=128,
                 sparse=True, dtype="float32", limits={"logit_gap": 3e-6},
                 **SMALL)


def test_reference_prunes_depthwise_taps_per_channel_strip():
    from harness.reference import network, pruned_weights

    layers = network(MOBILENET)
    weights = pruned_weights(layers, MOBILENET, SEED)
    for l in layers:
        if l["op"] == "conv" and l["groups"] > 1:
            w = weights[l["name"]][0]
            c = l["cin"]
            assert w.shape == (3, 3, 1, c)
            # one strip of min(C, 128) channels shares its 4 kept taps
            kept = (w.reshape(9, c) != 0).reshape(9, -1, min(c, 128))
            assert (kept.all(axis=2) == kept.any(axis=2)).all()
            assert (kept.any(axis=2).sum(axis=0) == 4).all()


@pytest.mark.parametrize("name", ["resnet50-f32", "vgg16-f32",
                                  "mobilenet_v1"])
def test_reference_matches_program_and_control_fails(name):
    config = MOBILENET if name == "mobilenet_v1" else small_config(name)
    images = np.random.default_rng(0).standard_normal(
        (6, 32, 32, 3)).astype(np.float32)
    ref = Reference(config, SEED, block=4)
    want = ref.logits(images, "highest")
    got = program_logits(config, images)
    limit = config["limits"]["logit_gap"]
    gaps = [check.relative_gap(g, w) for g, w in zip(got, want)]
    assert max(gaps) <= limit
    ctl = ref.logits(images, "bf16x3")
    assert max(check.relative_gap(c, w) for c, w in zip(ctl, want)) > limit


def tiny_cell() -> Cell:
    return Cell(
        name="tiny-offline", chips=1, config=small_config("vgg16-f32"),
        traffic={"wave": 4, "per_call": 8, "image_side": 32, "pool": 8},
        end_to_end=[{"name": "images_per_s", "unit": "images/s"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[])


def run_tiny(capsys, control: bool = False) -> dict:
    rc = run.run_cell(tiny_cell(), seed=SEED, seconds=1.0, trace=False,
                      control=control, require_tpu=False)
    out, err = capsys.readouterr()
    assert rc == 0
    assert "compiles in the window: 0 programs compiled by XLA, 0 loaded" \
        in out
    assert err.strip().splitlines()[-1].startswith("check undelivered")
    return json.loads(out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    res = run_tiny(capsys)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_control_in_the_programs_place_is_not_correct(capsys):
    res = run_tiny(capsys, control=True)
    assert res["correct"] is False
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    assert res["checks"]["undelivered"]["value"] == 0


def test_altered_answer_is_not_correct(capsys, monkeypatch):
    from repro.launch.serve import CNNBackend

    collect = CNNBackend.collect

    def altered(self, state, handle, slots):
        state, emis = collect(self, state, handle, slots)
        emis[0] = emis[0] * 1.001  # one answer changed where produced
        return state, emis

    monkeypatch.setattr(CNNBackend, "collect", altered)
    res = run_tiny(capsys)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_dropped_request_is_not_correct(capsys, monkeypatch):
    from repro.launch.serve import CNNServer

    serve = CNNServer.serve
    monkeypatch.setattr(CNNServer, "serve",
                        lambda self, reqs: serve(self, reqs[:-1]))
    res = run_tiny(capsys)
    assert res["correct"] is False and res["failed"] > 0


def test_no_tpu_means_no_result(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path)}
    p = subprocess.run(
        [sys.executable, str(BENCH_DIR / "run.py"), "--workload",
         "resnet50-offline", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""

"""The generator repeats exactly for a seed, differs across seeds, and
gives every seed the same amount of work."""
import json
import pathlib

import numpy as np
import pytest

from harness.traffic import Traffic

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
MIX = {"wave": 32, "per_call": 128, "image_side": 32, "pool": 8}


def test_same_seed_same_traffic():
    a, b = Traffic(MIX, 7), Traffic(MIX, 7)
    np.testing.assert_array_equal(a.pool, b.pool)
    for i in range(300):
        np.testing.assert_array_equal(a.pixels(i), b.pixels(i))


def test_seeds_differ_in_images_not_amount():
    a, b = Traffic(MIX, 7), Traffic(MIX, 2**31 + 8)
    assert a.pool.shape == b.pool.shape == (8, 32, 32, 3)
    assert not np.array_equal(a.pool, b.pool)
    assert [a.image(i) for i in range(64)] == [b.image(i) for i in range(64)]
    assert a.warm_widths() == b.warm_widths()


def test_pool_is_standard_normal():
    pool = Traffic(dict(MIX, pool=64), 3).pool
    assert pool.dtype == np.float32
    assert abs(float(pool.mean())) < 0.02
    assert float(pool.std()) == pytest.approx(1.0, abs=0.02)


@pytest.mark.parametrize("per_call,wave,widths", [
    (128, 32, [32]), (40, 32, [8, 32]), (1, 1, [1]), (3, 32, [4])])
def test_warm_widths(per_call, wave, widths):
    spec = dict(MIX, per_call=per_call, wave=wave)
    assert Traffic(spec, 0).warm_widths() == widths


def test_a_call_needs_a_request():
    with pytest.raises(ValueError):
        Traffic(dict(MIX, per_call=0), 0)


@pytest.mark.parametrize("path", sorted(TRAFFIC.glob("*.json")),
                         ids=lambda p: p.stem)
def test_committed_mixes_load(path):
    spec = json.loads(path.read_text())
    t = Traffic(dict(spec, pool=2), 1)
    assert t.warm_widths()
    assert t.pixels(0).shape == (spec["image_side"], spec["image_side"], 3)

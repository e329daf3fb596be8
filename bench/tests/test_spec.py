"""Every name BENCHMARK.json gives resolves to a file under bench/."""
import json

import pytest

from harness import reference
from harness.spec import BENCH_DIR, ROOT, load_cell, metric_reader

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_pieces_load(cell):
    c = load_cell(cell)
    assert c.end_to_end and c.per_layer
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert reference.network(c.config)
    for m in c.end_to_end + c.per_layer:
        assert callable(metric_reader(m["name"]))


def test_configs_match_their_files():
    for conf in BENCH["configs"]:
        data = json.loads((ROOT / conf["file"]).read_text())
        assert data["name"] == conf["name"]
        assert data["source"] == conf["source"]
        assert conf["file"].startswith("bench/")
    assert (BENCH_DIR / "configs" / "resnet50-dense-f32.json").is_file()

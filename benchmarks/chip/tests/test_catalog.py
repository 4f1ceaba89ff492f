"""Every configuration, traffic mix, entry and per-layer metric loads by name."""
import json

import pytest
from yardstick import harness

BENCH = json.loads((harness.CHECKOUT / "BENCHMARK.json").read_text())
D = harness.BENCH_DIR


def test_every_file_in_the_listing_loads_by_name():
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert sorted(p.stem for p in (D / "configs").glob("*.json")) == sorted(configs)
    traffic = {w["traffic"] for w in BENCH["workloads"]}
    assert sorted(p.stem for p in (D / "traffic").glob("*.json")) == sorted(traffic)
    for p in (D / "configs").glob("*.json"):
        cfg = json.loads(p.read_text())
        assert cfg["name"] == p.stem
        assert configs[p.stem]["file"] == str(p.relative_to(harness.CHECKOUT))
        assert configs[p.stem]["reduced"] == cfg["reduced"]
    entries = {json.loads(p.read_text())["entry"] for p in (D / "traffic").glob("*.json")}
    assert sorted(p.stem for p in (D / "entries").glob("*.py")) == sorted(entries)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_by_name(name):
    cell = harness.load_cell(name)
    entry = cell["entry"]
    assert {m["name"] for m in cell["end_to_end"]} == {"setup_s", entry.METRIC}
    assert cell["per_layer"], "every cell reports a per-layer metric"
    for m in cell["per_layer"]:
        assert m["moves"] == entry.METRIC
        assert callable(harness.layer_reader(m["name"]).read)
    assert set(cell["traffic"]["limits"]) and cell["traffic"]["check_sample"] >= 1
    assert cell["config"]["dtype"] in ("float32", "float64")


@pytest.mark.parametrize("path", sorted((D / "layers").glob("*.py")), ids=lambda p: p.stem)
def test_layer_reader_returns_nothing_without_a_reading(path):
    from yardstick.trace import Reduced

    empty = Reduced(window_s=1.0, busy_s=0.0, module_s={}, idle_by_host={}, n_spans=1)
    assert harness.load_module(path).read(empty, 10.0) is None


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.load_cell("no.such_cell")

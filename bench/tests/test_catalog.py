"""Cells, configurations, mixes and metrics are found by name in files."""
import pytest

from bench import catalog


def test_every_cell_resolves_to_its_files():
    bench = catalog.benchmark()
    for w in bench["workloads"]:
        cell = catalog.cell(bench, w["name"])
        model = catalog.config(cell["config"])
        mix = catalog.traffic(cell["traffic"])
        assert model["name"] == cell["config"]
        assert mix["name"] == cell["traffic"]
        catalog.reference(model["reference"])
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"


def test_every_config_file_matches_benchmark_json():
    bench = catalog.benchmark()
    for c in bench["configs"]:
        model = catalog.config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert model["source"] == c["source"]
        assert sorted(model["reduced"]) == sorted(c["reduced"])


def test_every_per_layer_metric_has_a_reader():
    bench = catalog.benchmark()
    for m in bench["per_layer"]:
        assert callable(catalog.reader(m["name"]))
        moved = [e for e in bench["end_to_end"] if e["name"] == m["moves"]]
        assert moved, m["name"]
        for cell in m["workloads"]:
            assert cell in moved[0].get("workloads", [cell])


def test_metrics_of_a_cell():
    bench = catalog.benchmark()
    names = {m["name"] for m in catalog.metrics_of(
        bench, "end_to_end", "qwen3-8b.chat-backlog")}
    assert names == {"out_tokens_per_s", "setup_s"}


def test_unknown_names_are_refused():
    bench = catalog.benchmark()
    with pytest.raises(KeyError):
        catalog.cell(bench, "no-such.cell")
    with pytest.raises(ValueError):
        catalog.config("../BENCHMARK")
    with pytest.raises(FileNotFoundError):
        catalog.reader("no_such_metric")

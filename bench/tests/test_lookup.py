"""The harness finds configurations, traffic mixes and metrics by name, so a
later change adds files and BENCHMARK.json entries and edits none."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import spec  # noqa: E402
from harness.generator import generate  # noqa: E402

REPO = Path(__file__).resolve().parents[2]


def test_a_cell_added_as_new_files_resolves(tmp_path):
    """A configuration, a mix and a per-layer metric that only exist as new
    files plus new entries are found and read."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "layer_metrics", "e2e_metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "new-model.json").write_text(
        json.dumps({"hidden_size": 64, "reference": "none"}))
    (bench / "traffic" / "new-mix.json").write_text(
        json.dumps({"loop": "closed", "contexts": [32],
                    "engine": {"max_seq": 64}}))
    (bench / "layer_metrics" / "new.metric.py").write_text(
        "def read(run):\n    return run['x'] * 2\n")
    (bench / "e2e_metrics" / "setup_s.py").write_text(
        "def read(run):\n    return None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "new-model", "file": "bench/configs/new-model.json"}],
        "workloads": [{"name": "new-model.new-mix", "config": "new-model",
                       "traffic": "new-mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}],
        "per_layer": [{"name": "new.metric", "workloads": ["new-model.new-mix"]},
                      {"name": "other.metric", "workloads": ["elsewhere"]}]}))
    cell = spec.find_cell(spec.load_spec(tmp_path), "new-model.new-mix", tmp_path)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["contexts"] == [32]
    assert [m["name"] for m in cell.per_layer] == ["new.metric"]
    assert spec.metric_reader(cell, cell.per_layer[0], "layer")({"x": 21}) == 42
    items = generate(cell.traffic, seed=5, vocab=10)
    assert len(items) == 1 and items[0].max_new == 32


def test_an_unknown_cell_or_a_missing_file_is_refused(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "c", "file": "bench/configs/c.json"}],
        "workloads": [{"name": "c.t", "config": "c", "traffic": "t", "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    s = spec.load_spec(tmp_path)
    with pytest.raises(spec.SpecError):
        spec.find_cell(s, "nope", tmp_path)
    with pytest.raises(spec.SpecError):
        spec.find_cell(s, "c.t", tmp_path)


def test_every_cell_of_the_benchmark_resolves():
    """Each cell's files exist, each metric it reports has a reader, and each
    per-layer metric's cells report the metric it moves."""
    s = spec.load_spec(REPO)
    for w in s["workloads"]:
        cell = spec.find_cell(s, w["name"], REPO)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.end_to_end:
            assert callable(spec.metric_reader(cell, m, "e2e"))
        for m in cell.per_layer:
            assert callable(spec.metric_reader(cell, m, "layer"))
            assert m["moves"] in names, (w["name"], m["name"])
        assert callable(spec.reference_module(cell).logits)

"""Mixes, metrics and cells are found by name, from files alone."""
import json
import shutil

import pytest

from chipbench import bench, traffic
from chipbench.tests.cells import ROOT


def test_a_mix_dropped_in_a_directory_is_found(tmp_path):
    mix = dict(traffic.load_mix("chat"), rate_per_s=7.5)
    (tmp_path / "bursty.json").write_text(json.dumps(mix))
    assert traffic.load_mix("bursty", tmp_path)["rate_per_s"] == 7.5
    with pytest.raises(FileNotFoundError):
        traffic.load_mix("absent", tmp_path)


def test_a_metric_dropped_in_a_directory_is_found(tmp_path):
    (tmp_path / "steps_in_window.chat.py").write_text(
        "def read(win, cell, peaks):\n    return float(len(win.steps))\n")
    reader = bench.load_metric("steps_in_window.chat", tmp_path)

    class Win:
        steps = [1, 2, 3]
    assert reader.read(Win(), None, None) == 3.0
    # a metric split by its use is read by its quantity's reader
    (tmp_path / "steps_seen.py").write_text(
        "def read(win, cell, peaks):\n    return 2.0 * len(win.steps)\n")
    assert bench.load_metric("steps_seen.bursty", tmp_path).read(
        Win(), None, None) == 6.0
    with pytest.raises(FileNotFoundError):
        bench.load_metric("absent", tmp_path)


def test_every_named_piece_of_the_benchmark_has_its_file():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(bench.load_metric(m["name"]).read)
    for w in spec["workloads"]:
        cell = bench.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.model.__name__.startswith("chipbench_model_")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        assert names - {"setup_s"} <= set(bench.END_TO_END)


def test_a_cell_added_as_files_and_entries_loads(tmp_path):
    """A later cell needs new files and new entries, no edit."""
    shutil.copytree(ROOT / "chipbench" / "configs", tmp_path / "configs")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "qwen3-0.6b.offline2",
                              "config": "qwen3-0.6b", "traffic": "offline",
                              "chips": 1, "why": "a test"})
    for c in spec["configs"]:
        c["file"] = str(tmp_path / "configs" / (c["name"] + ".json"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = bench.load_cell("qwen3-0.6b.offline2", tmp_path)
    # metrics that list their cells leave a cell they do not name alone
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert cell.per_layer == []
    with pytest.raises(KeyError):
        bench.load_cell("nope", tmp_path)

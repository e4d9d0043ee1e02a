"""A cell, a configuration, a traffic mix and a per-layer metric added as
new files and entries only are found by the harness and run."""

import json
import time
from pathlib import Path

import pytest

from bench.lib import harness

NEW_METRIC = '''"""Queries the window completed (a test metric)."""


def read(run):
    return run.answers or None
'''


def add_cell(root: Path) -> None:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/g500-s17-1chip.json").read_text())
    cfg.update(name="g500-s7-new", scale=7)
    (root / "bench/configs/g500-s7-new.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/closed-k5.json").write_text(
        json.dumps({"loop": "closed", "seeds_per_query": 5}))
    (root / "bench/metrics/answers_new.py").write_text(NEW_METRIC)
    bench["configs"].append({"name": "g500-s7-new", "source": "a test", "reduced": ["scale"],
                             "file": "bench/configs/g500-s7-new.json", "why": "a test"})
    bench["workloads"].append({"name": "new-s7-k5", "config": "g500-s7-new",
                               "traffic": "closed-k5", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "tree_s":
            m["workloads"].append("new-s7-k5")
    bench["per_layer"].append({"name": "answers_new", "unit": "queries", "better": "higher",
                               "source": "program_counter", "layer": "device",
                               "moves": "tree_s", "workloads": ["new-s7-k5"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_files_are_found(tiny_root, fresh_jit):
    before = {p.relative_to(tiny_root) for p in tiny_root.rglob("*") if p.is_file()}
    add_cell(tiny_root)
    after = {p.relative_to(tiny_root) for p in tiny_root.rglob("*") if p.is_file()}
    assert after - before == {Path("bench/configs/g500-s7-new.json"),
                              Path("bench/traffic/closed-k5.json"),
                              Path("bench/metrics/answers_new.py")}
    cell = harness.load_cell(tiny_root, "new-s7-k5")
    assert cell.config["scale"] == 7 and cell.traffic["seeds_per_query"] == 5
    assert [m["name"] for m in cell.end_to_end] == ["tree_s", "setup_s"]
    assert set(cell.readers) == {"answers_new"}
    res = harness.run(tiny_root, "new-s7-k5", 11, 0.5, False, time.perf_counter(),
                      require_chip=False)
    assert res["correct"] and set(res["metrics"]) == {"tree_s", "setup_s"}
    assert cell.readers["answers_new"](harness.RunData(
        "new-s7-k5", {}, {}, 1, "TPU v5 lite", 128, 1000, 7, [], {}, [], None)) == 7


def test_every_cell_resolves():
    root = Path(__file__).resolve().parents[2]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(root, w["name"])
        assert cell.chips == w["chips"]
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        moved = {m["name"] for m in cell.end_to_end}
        assert all(m["moves"] in moved for m in cell.per_layer)


def test_unknown_workload():
    root = Path(__file__).resolve().parents[2]
    with pytest.raises(KeyError):
        harness.load_cell(root, "no-such-cell")

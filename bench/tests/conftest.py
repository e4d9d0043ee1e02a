"""Shared set-up of the benchmark's own tests (``python -m pytest bench/tests``).

They run on the CPU at small sizes.  ``tiny_root`` is a copy of the
benchmark's files with every graph cut to a size a test run can hold.
"""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def with_waiting_cells(bench: dict) -> dict:
    """``bench`` with the cells built but not yet in ``BENCHMARK.json``
    (``data/waiting_cells.json``: 10,240 seeds), so that the tests drive
    every loop and system.  Only what ``bench`` lacks is merged, so a
    benchmark change that adds a waiting cell leaves no double; the one
    that adds the last deletes the file and this merge."""
    waiting = json.loads((Path(__file__).parent / "data" / "waiting_cells.json").read_text())

    def lacking(key):
        have = {e["name"] for e in bench[key]}
        return [e for e in waiting[key] if e["name"] not in have]

    new = {w["name"] for w in lacking("workloads")}
    if not new:
        return bench
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] += lacking(key)
    for m in bench["end_to_end"]:
        if m["name"] == "tree_s":
            m["workloads"] += [w for w in waiting["tree_s_workloads"] if w in new]
    for m in bench["per_layer"]:
        m["workloads"] += [w for w in waiting["per_layer_workloads"].get(m["name"], [])
                           if w in new]
    return bench


def make_tiny_root(dest: Path, scale: int = 8, traffic: dict | None = None) -> Path:
    """``BENCHMARK.json`` (with the waiting cells) and ``bench/`` copied to
    ``dest``, every graph cut to ``scale``; ``traffic`` overrides mixes by
    name."""
    bench = with_waiting_cells(json.loads((ROOT / "BENCHMARK.json").read_text()))
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for f in (dest / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg["scale"] = scale
        f.write_text(json.dumps(cfg))
    for name, mix in (traffic or {}).items():
        (dest / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    return dest


SMALL_TRAFFIC = {
    "closed-k10k": {"loop": "closed", "seeds_per_query": 40, "check_sample": 2},
    "open-fresh": {"loop": "open", "rate_qps": 400.0,
                   "sizes": {"law": "log_uniform", "min": 3, "max": 64}, "check_sample": 96},
}


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path, traffic=SMALL_TRAFFIC)


@pytest.fixture
def fresh_jit():
    """Drops JAX's compiled programs before and after, so a fault planted
    with ``monkeypatch`` is traced in, and traced out again."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()

"""BENCHMARK.json and the files it names: every cell finds its
configuration, entry, work count and metric readers by name, and every
name and unit keeps to the allowed characters."""
import ast
import json
import re
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(ROOT))

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w[k] for w in BENCH["workloads"]
              for k in ("name", "config", "traffic")]
    names += [m["name"] for m in METRICS]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for kind in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[kind]}) == len(BENCH[kind])
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for text in ([w["why"] for w in BENCH["workloads"]]
                 + [c["why"] for c in BENCH["configs"]]
                 + [m["layer"] for m in BENCH["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_cell_finds_its_files(cell):
    traffic = json.loads(
        (CHIP / "workloads" / f"{cell['traffic']}.json").read_text())
    assert traffic["config"] == cell["config"]
    assert traffic["why"] == cell["why"]
    cfg = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg["file"]).read_text())
    assert config["name"] == cfg["name"]
    assert (CHIP / "data" / f"{config['generator']}.py").exists()
    for kind in ("entries", "reference", "work"):
        assert (CHIP / kind / f"{traffic['entry']}.py").exists(), kind
    served = [m for m in METRICS
              if cell["name"] in m.get("workloads", [cell["name"]])]
    assert any(m["name"] == "setup_s" for m in served)
    assert any(m["name"] != "setup_s" for m in served
               if m in BENCH["end_to_end"])
    assert any(m in BENCH["per_layer"] for m in served)
    for m in served:
        files = [CHIP / "metrics" / f"{m['name']}.py",
                 CHIP / "metrics" / f"{m['name'].split('.')[0]}.py"]
        assert any(f.exists() for f in files), m["name"]


def test_per_layer_metrics_move_a_metric_of_their_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w]), (m["name"], w)


def _imports(path):
    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""


@pytest.mark.parametrize("kind", ["reference", "work", "data"])
def test_yardstick_imports_nothing_of_the_system(kind):
    for path in (CHIP / kind).glob("*.py"):
        assert not any(m.startswith("repro") for m in _imports(path)), path

"""A whole run of the harness, on the CPU at a small size, with the look
for a chip skipped: the program's answers pass the check, an answer
altered where it is produced fails it, the control fails it, a demoted
call counts as failed, and ``run.py`` itself refuses to measure without a
TPU or without the system under test."""
import gzip
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import control, harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def small(name):
    """The cell at a size a CPU test holds: its shapes cut, nothing else."""
    cell = harness.Cell.load(BENCH, name)
    if "rows" in cell.config:
        cell.config = dict(cell.config, rows=20_000, orders_drawn=6_000)
    else:
        cell.traffic = dict(cell.traffic, tokens=1024)
        cell.config = dict(cell.config, num_hidden_layers=3)
    return cell


def run(cell, seed=2 ** 31 + 7, traced=False, keep_trace=None):
    from repro.engine.planner import default_planner
    default_planner.clear()
    try:
        return harness.run_cell(cell, seed, 0.05, traced, jax.devices()[:1],
                                time.monotonic(), log=lambda m: None,
                                keep_trace=keep_trace)
    finally:
        default_planner.clear()


def altered(fn, alter):
    return lambda *a, **k: alter(fn(*a, **k))


#: one answer altered where the engine produces it
FAULTS = {
    "argsort": lambda p: p.at[0].set(p[1]),
    "moe_route": lambda r: r._replace(experts=r.experts.at[0].add(1)),
}


@pytest.mark.parametrize("name", CELLS)
def test_program_answers_pass_the_check(name):
    res = run(small(name))
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    want = {m["name"] for m in BENCH["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(res["metrics"]) == want


@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_is_not_correct(name, monkeypatch):
    from repro import engine
    cell = small(name)
    op = cell.entry.OP
    monkeypatch.setattr(engine, op, altered(getattr(engine, op), FAULTS[op]))
    res = run(cell)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limits(name):
    cell = small(name)
    got = control.readings(cell, 11, 1, control=True)
    assert any(got[n] > lim for n, lim in cell.entry.LIMITS.items()), got
    got = control.readings(cell, 11, 1, control=False)
    assert all(got[n] <= lim for n, lim in cell.entry.LIMITS.items()), got


def test_demoted_calls_count_as_failed():
    from repro.engine.planner import Plan, default_planner
    from repro.guard import inject
    cell = small("lineitem-shipdate-argsort")
    sets = harness.make_inputs(cell, 1)
    with inject.failing_variant("argsort") as name:
        default_planner.clear()
        default_planner.put(harness.plan_key(cell, sets[0]),
                            Plan(variant=name))
        lines = []
        res = harness.run_cell(cell, 1, 0.05, False, jax.devices()[:1],
                               time.monotonic(), log=lines.append)
        default_planner.clear()
    assert res["correct"] is True          # the reference rung answered
    assert res["failed"] == res["attempted"] >= 1
    assert any(line.startswith("resolved: argsort") and name in line
               for line in lines)


def test_same_seed_same_inputs():
    cell = small("mixtral-route-16k")
    a, b = harness.make_inputs(cell, 5), harness.make_inputs(cell, 5)
    c = harness.make_inputs(cell, 6)
    assert all(bool(jnp.all(x[0] == y[0])) for x, y in zip(a, b))
    assert not bool(jnp.all(a[0][0] == c[0][0]))
    assert not bool(jnp.all(a[0][0] == a[1][0]))   # the sets differ


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mixtral-route-16k", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_the_cpu():
    p = _run_py(ROOT, {})
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout


def test_run_py_refuses_a_tree_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout


def test_traced_run_reports_the_per_layer_metrics_it_can_read(tmp_path):
    kept = tmp_path / "window.xplane.pb.gz"
    res = run(small("lineitem-shipdate-argsort"), traced=True,
              keep_trace=str(kept))
    assert res["correct"] is True
    assert set(res["device"]) >= {"busy_s", "window_s"}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device plane: only the host-clock metric is read
    assert set(res["metrics"]) == {"dispatch_ms.sort"}
    from benchmarks.chip import trace as tr
    from jax.profiler import ProfileData
    window = tr.reduce(ProfileData.from_serialized_xspace(
        gzip.decompress(kept.read_bytes())), [0])
    assert len(window.calls) == res["attempted"]


def test_window_stops_at_its_call_limit():
    x = jnp.ones(3)
    w = harness.run_window(lambda a: a + 1, [(x,), (x,)], 60.0, 3,
                           traced=False, max_calls=5)
    assert len(w.calls) == 5 and w.error is None
    assert [s for _, s, _ in w.kept] == [0, 1, 0, 1, 0]
    assert all(t0 <= t1 <= t2 for t0, t1, t2 in w.calls)

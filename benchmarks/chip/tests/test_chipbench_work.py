"""The roofline's work counts: a function of the cell's shapes only, equal
to the hand formula, the same whatever variant the planner resolves."""
import json
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP.parents[1]), str(CHIP.parents[1] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import harness, peaks  # noqa: E402

BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
N = 6_001_215
L, T, E, K = 56, 16384, 8, 2
HAND = {
    "lineitem-shipdate-argsort": 4 * N + 4 * N,      # keys in, row ids out
    # each layer: logits in, pairs out
    "mixtral-route-16k": L * (T * E * 4 + T * K * 21),
}


def shaped_args(cell):
    """The cell's arguments as shapes, with no data behind them."""
    gen = harness.load_module("data", cell.config["generator"])
    data = jax.eval_shape(lambda k: gen.make(k, cell.config, cell.traffic),
                          jax.random.key(0))
    return cell.entry.args(data, cell.config, cell.traffic)


@pytest.mark.parametrize("name", sorted(HAND))
def test_count_is_the_hand_formula_at_the_cells_shapes(name):
    cell = harness.Cell.load(BENCH, name)
    work = cell.work(shaped_args(cell))
    assert work["bytes"] == HAND[name]
    assert work["flops"] == 0


def test_count_does_not_depend_on_the_variant():
    from repro.engine.planner import Plan, default_planner
    cell = harness.Cell.load(BENCH, "lineitem-shipdate-argsort")
    args = shaped_args(cell)
    key = harness.plan_key(cell, args)
    before = cell.work(args)
    try:
        for variant in ("pallas", "xla", "flims"):
            default_planner.put(key, Plan(variant=variant))
            assert cell.work(args) == before
    finally:
        default_planner.clear()


def test_peaks_known_device_and_unknown_raises():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e.hbm_bw == 819e9 and v5e.flops == 197e12 and v5e.source
    with pytest.raises(ValueError):
        peaks.peaks("cpu")


def test_inputs_are_as_the_configuration_states():
    cell = harness.Cell.load(BENCH, "mixtral-route-16k")
    (logits,) = shaped_args(cell)
    assert logits.shape == (L, T, E) and logits.dtype == jnp.float32
    # dropless: one expert's capacity holds every pair of the chunk
    assert cell.entry.capacity(cell.config, cell.traffic) == T * K
    cell = harness.Cell.load(BENCH, "lineitem-shipdate-argsort")
    (x,) = shaped_args(cell)
    assert x.shape == (N,) and x.dtype == jnp.int32

"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 ... \
        [--control-seeds 1 2 3] [--calls-per-seed N]

In one process, for each seed: the cell's inputs at the cell's own size,
the timed entry called on them (``--calls-per-seed`` input sets, all by
default) and each output compared with the plain reference, as a run's
check does. For each control seed, the control (the reference in a lower
precision, or with one guarantee broken) is compared with the reference
in the same way. Prints one JSON line per seed and, last, the lower
reading (the largest the program gives) and the upper reading (the
smallest the control gives) of each compared number. The benchmark's own
runs do not run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def readings(cell, seed: int, calls: int, control: bool) -> dict:
    """Worst compared numbers over ``calls`` input sets of one seed, for
    the program (``control=False``) or for the control."""
    import jax

    from benchmarks.chip import harness
    sets = harness.make_inputs(cell, seed)[:calls]
    if control:
        call = jax.jit(lambda *a: cell.entry.reference(
            a, cell.config, cell.traffic, control=True))
    else:
        call = cell.entry.timed(cell.config, cell.traffic)
    outs = [jax.block_until_ready(call(*s)) for s in sets]
    window = harness.Window(calls=[], kept=[(i, i, o) for i, o in
                                            enumerate(outs)],
                            demoted=0, error=None)
    return harness.check(cell, sets, window)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--calls-per-seed", type=int, default=0)
    a = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness
    with open(ROOT / "BENCHMARK.json") as f:
        cell = harness.Cell.load(json.load(f), a.workload)
    harness.enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: {jax.devices()}", file=sys.stderr)
        return 3
    calls = a.calls_per_seed or int(cell.traffic["input_sets"])
    lower, upper = {}, {}
    for kind, seeds, acc, pick in (("program", a.seeds, lower, max),
                                   ("control", a.control_seeds, upper, min)):
        for seed in seeds:
            t = time.perf_counter()
            vals = readings(cell, seed, calls, control=kind == "control")
            for n, v in vals.items():
                acc[n] = pick(acc.get(n, v), v)
            print(json.dumps({"kind": kind, "seed": seed, "readings": vals,
                              "seconds": time.perf_counter() - t}),
                  flush=True)
    print(json.dumps({"workload": a.workload, "lower": lower,
                      "upper": upper, "limits": cell.entry.LIMITS,
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one benchmark cell once on the accelerator this machine holds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Prints progress and the compared numbers on standard error and, as the
last line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or its per-layer
metrics with ``--trace 1``), ``device``, ``breakdown`` (traced runs) and
``checks``. ``--keep-trace PATH`` keeps the traced window's trace. Exits
non-zero, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", metavar="PATH",
                   help="with --trace 1, write the traced window's "
                        ".xplane.pb, gzipped, to PATH")
    a = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        import repro  # noqa: F401  the system under test
        from benchmarks.chip import harness
    except ImportError as e:
        log(f"cannot import the system under test: {e}")
        return 2
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = harness.Cell.load(bench, a.workload)
    log(f"compile cache: {harness.enable_compile_cache()}")
    import jax
    devices = [d for d in jax.devices() if d.platform == "tpu"]
    if len(devices) < cell.chips:
        log(f"{a.workload} needs {cell.chips} TPU chip(s); JAX found "
            f"{jax.devices()}")
        return 3
    devices = devices[:cell.chips]
    log(f"devices: {devices} ({devices[0].device_kind})")
    with jax.default_device(devices[0]):
        result = harness.run_cell(cell, a.seed, a.seconds, bool(a.trace),
                                  devices, T_START, log=log,
                                  keep_trace=a.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

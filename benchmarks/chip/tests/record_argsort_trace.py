"""Record the small argsort trace that ``test_chipbench_scopes.py`` reads.

    python3 benchmarks/chip/tests/record_argsort_trace.py OUT.xplane.pb.gz

On a TPU: two input sets of 2^15 int32 keys drawn from 64 values (heavy
ties; below 2^13 keys the planner's tile width falls under 128 lanes, which
Mosaic refuses, and the call is demoted), one warm call each, then two
calls of ``engine.argsort`` in the benchmark's closed loop
(``harness.run_window``, with its ``chipbench.*`` spans) under the
profiler with ``repro.obs`` on, kept gzipped at OUT.
"""
import sys
import tempfile
from pathlib import Path

N, VALUES, CALLS, SEED = 1 << 15, 64, 2, 15


def main(out: str) -> int:
    sys.path[:0] = [str(Path(__file__).resolve().parents[3] / "src"),
                    str(Path(__file__).resolve().parents[3])]
    import jax
    from repro import engine, obs

    from benchmarks.chip import harness
    from benchmarks.chip import trace as tr
    if jax.default_backend() != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    keys = [jax.random.randint(k, (N,), 0, VALUES, jax.numpy.int32)
            for k in jax.random.split(jax.random.key(SEED), CALLS)]
    call = lambda k: engine.argsort(k, descending=True)
    for k in keys:
        jax.block_until_ready(call(k))
    trace_dir = tempfile.mkdtemp(prefix="chipbench-fixture-")
    obs.enable()
    jax.profiler.start_trace(trace_dir,
                             profiler_options=harness.trace_options())
    window = harness.run_window(call, [(k,) for k in keys], float("inf"),
                                SEED, traced=True, max_calls=CALLS)
    jax.profiler.stop_trace()
    obs.disable()
    tr.save_gz(trace_dir, out)
    tr.remove_dir(trace_dir)
    print(f"{len(window.calls)} calls kept in {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

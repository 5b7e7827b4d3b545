"""One run of one benchmark cell: set-up, a closed-loop window, the check.

Everything that belongs to one configuration, traffic mix, entry or metric
lives in a file of its own and is found here by the name that
``BENCHMARK.json`` or the workload file gives it:

- ``workloads/<cell>.json``: the configuration, the entry and the traffic
  parameters of a cell;
- ``configs/<config>.json``: the sizes, with ``generator`` naming the
  ``data/<generator>.py`` that makes the inputs on the device;
- ``entries/<entry>.py``: the timed call into the engine, the comparison
  with the plain reference (``reference/<entry>.py``) and its limits;
- ``work/<entry>.py``: the bytes the entry has to move, from shapes only;
- ``metrics/<metric>.py`` (or ``metrics/<prefix>.py`` for a metric named
  ``<prefix>.<suffix>``): a reader that returns the metric, or None where
  the run has nothing for it to read.

The window runs with ``repro.obs`` off, as users run the engine.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import random
import tempfile
import time
from contextlib import nullcontext
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: outputs of at most this many window calls, drawn from the seed, are kept
#: and compared with the reference once the window has closed
KEEP = 32
#: device op events a traced window may hold: the profiler keeps about six
#: million and then drops the rest (seen on a v5e), and reading a trace
#: costs about a second per hundred thousand events
TRACE_EVENTS = 1_000_000
#: host spans of the loop, named in the trace (trace.py reads them back)
CALL, DISPATCH, WAIT, ROTATE = ("chipbench.call", "chipbench.dispatch",
                                "chipbench.wait", "chipbench.rotate")


def load_json(kind: str, name: str) -> dict:
    with open(HERE / kind / f"{name}.json") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``metrics/<name>.py``, else ``metrics/<prefix>.py`` for a metric
    split by the cells it serves (``dispatch_ms.sort``)."""
    if (HERE / "metrics" / f"{name}.py").exists():
        return load_module("metrics", name)
    return load_module("metrics", name.split(".")[0])


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    entry: object
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, bench: dict, name: str) -> "Cell":
        spec = next((w for w in bench["workloads"] if w["name"] == name),
                    None)
        if spec is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        traffic = load_json("workloads", spec["traffic"])
        if traffic["config"] != spec["config"]:
            raise ValueError(f"{name}: workload file names config "
                             f"{traffic['config']!r}, BENCHMARK.json "
                             f"{spec['config']!r}")
        applies = lambda m: name in m.get("workloads", [name])
        return cls(name=name, chips=int(spec["chips"]),
                   config=load_json("configs", spec["config"]),
                   traffic=traffic,
                   entry=load_module("entries", traffic["entry"]),
                   end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                   per_layer=[m for m in bench["per_layer"] if applies(m)])

    def shapes(self, args) -> dict:
        return self.entry.shapes(args, self.config, self.traffic)

    def work(self, args) -> dict:
        return load_module("work", self.traffic["entry"]).count(
            **self.shapes(args))


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache where the system under test keeps
    it (``JAX_COMPILATION_CACHE_DIR``, else a fixed directory inside the
    checkout), holding every program, however quick to compile, so that a
    second run compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (JAX keeps 32 bits of an int)."""
    import jax
    s = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


def make_inputs(cell: Cell, seed: int):
    """The cell's input sets, made on the device in one jitted call."""
    import jax
    gen = load_module("data", cell.config["generator"])
    n_sets = int(cell.traffic["input_sets"])

    @jax.jit
    def make(key):
        return [gen.make(k, cell.config, cell.traffic)
                for k in jax.random.split(key, n_sets)]

    sets = jax.block_until_ready(make(seed_key(seed)))
    return [cell.entry.args(d, cell.config, cell.traffic) for d in sets]


def plan_key(cell: Cell, args):
    """The planner's cache key for the entry's call (its quarantine is how
    a demotion by the guard ladder shows without ``repro.obs``)."""
    from repro.engine.api import infer_key
    key_args = getattr(cell.entry, "key_args", lambda a, c, t: a)(
        args, cell.config, cell.traffic)
    return infer_key(cell.entry.OP, *key_args)


class CompileCounter:
    """Seconds JAX spends per monitored event (tracing, lowering, compiling,
    cache reads) inside a ``with`` block, and how many traces or compiles
    it saw there."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        self.count = 0
        self.seconds = {}

    def _on(self, event, duration, **kw):
        self.seconds[event] = self.seconds.get(event, 0.0) + duration
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def summary(self) -> str:
        return ", ".join(f"{k.rsplit('/', 1)[-1]} {v:.3f} s"
                         for k, v in sorted(self.seconds.items()))


@dataclasses.dataclass
class Window:
    calls: list          # (start, dispatched, end) host seconds per call
    kept: list           # (call index, input set, output)
    demoted: int
    error: Optional[str]

    @property
    def seconds(self) -> float:
        return self.calls[-1][2] - self.calls[0][0] if self.calls else 0.0


def run_window(call, sets, seconds: float, seed: int, *, traced: bool,
               is_demoted=lambda: False, max_calls: Optional[int] = None
               ) -> Window:
    """Back-to-back calls from one caller, each ending in
    ``block_until_ready`` and taking the next input set, until a call would
    start after ``seconds`` (or ``max_calls`` calls have run). Outputs of up
    to ``KEEP`` calls, a uniform sample drawn from the seed, are kept for
    the check."""
    import jax
    span = jax.profiler.TraceAnnotation if traced else (
        lambda name: nullcontext())
    pick = random.Random(seed)
    calls, kept, demoted, error = [], [], 0, None
    i, first = 0, None
    limit = max_calls or float("inf")
    while first is None or (time.perf_counter() - first < seconds
                            and i < limit):
        with span(ROTATE):
            args = sets[i % len(sets)]
        with span(CALL):
            t0 = time.perf_counter()
            first = t0 if first is None else first
            try:
                with span(DISPATCH):
                    out = call(*args)
                t1 = time.perf_counter()
                with span(WAIT):
                    out = jax.block_until_ready(out)
            except Exception as e:  # the answer never came: not correct
                error = f"{type(e).__name__}: {e}"[:2000]
                break
            t2 = time.perf_counter()
        with span(ROTATE):
            calls.append((t0, t1, t2))
            demoted += bool(is_demoted())
            slot = i if i < KEEP else pick.randrange(i + 1)
            if slot < KEEP:
                entry = (i, i % len(sets), out)
                if slot < len(kept):
                    kept[slot] = entry
                else:
                    kept.append(entry)
        i += 1
    return Window(calls, kept, demoted, error)


def check(cell: Cell, sets, window: Window, control: bool = False) -> dict:
    """Compare every kept output with the plain reference of its input set;
    returns ``{name: value}``, the worst over the kept calls."""
    import jax
    ref = jax.jit(lambda *a: cell.entry.reference(
        a, cell.config, cell.traffic, control=control))
    refs, worst = {}, {}
    for _, s, out in sorted(window.kept, key=lambda e: e[0]):
        if s not in refs:
            refs[s] = jax.block_until_ready(ref(*sets[s]))
        for name, value in cell.entry.compare(out, refs[s]).items():
            worst[name] = max(worst.get(name, value), value)
    return worst


def ops_per_call(call, args, device_ids) -> int:
    """Device op events of one traced call (how many calls a traced window
    can hold)."""
    import jax

    from benchmarks.chip import trace as tr
    probe = tempfile.mkdtemp(prefix="chipbench-probe-")
    try:
        jax.profiler.start_trace(probe, profiler_options=trace_options())
        jax.block_until_ready(call(*args))
        jax.profiler.stop_trace()
        reduced = tr.reduce_dir(probe, device_ids)
    finally:
        tr.remove_dir(probe)
    return sum(len(d.ops) for d in reduced.devices) if reduced else 0


def peak_memory(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Run:
    """What a metric reader may read."""
    cell: Cell
    setup_s: float
    calls: list
    window_s: float
    units_per_call: int
    work: dict
    device_kind: str
    trace: object = None      # trace.Reduced of the traced window, or None


def trace_options():
    """Device and runtime events and the benchmark's own spans; no Python
    function tracer (it would slow the dispatch it is meant to show) and no
    HLO protos (they only make the file larger)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, devices,
             t_start: float, log=print, keep_trace: Optional[str] = None
             ) -> dict:
    """Set up, measure and check one cell; returns the result object.
    ``keep_trace`` names a file that receives the traced window's
    ``.xplane.pb``, gzipped."""
    import jax
    from repro.engine.planner import default_planner

    from benchmarks.chip import trace as tr

    with CompileCounter() as setup:
        t0 = time.monotonic()
        sets = make_inputs(cell, seed)
        t1 = time.monotonic()
        call = cell.entry.timed(cell.config, cell.traffic)
        key = plan_key(cell, sets[0])
        jax.block_until_ready(call(*sets[0]))      # the one warm call
        t2 = time.monotonic()
    log(f"setup: to inputs {t0 - t_start:.3f} s, inputs {t1 - t0:.3f} s, "
        f"warm call {t2 - t1:.3f} s; jax: {setup.summary()}")
    plan = default_planner.lookup(key)
    log(f"resolved: {cell.entry.OP} -> "
        f"{plan.variant if plan else 'unresolved'} "
        f"(quarantined: {sorted(p.variant for p in default_planner.infeasible_for(key))})")
    is_demoted = lambda: bool(default_planner.infeasible_for(key))
    max_calls, trace_dir = None, None
    if traced:
        n_ops = ops_per_call(call, sets[0], [d.id for d in devices])
        max_calls = max(1, TRACE_EVENTS // max(n_ops, 1))
        log(f"trace: {n_ops} device ops a call; the traced window holds at "
            f"most {max_calls} calls")
        trace_dir = tempfile.mkdtemp(prefix="chipbench-")
        jax.profiler.start_trace(trace_dir, profiler_options=trace_options())
    setup_s = time.monotonic() - t_start
    with CompileCounter() as counter:
        window = run_window(call, sets, seconds, seed, traced=traced,
                            is_demoted=is_demoted, max_calls=max_calls)
    if traced:
        jax.profiler.stop_trace()
    memory = peak_memory(devices)
    log(f"window: {len(window.calls)} calls in {window.seconds:.6f} s, "
        f"{counter.count} traces or compiles inside it, "
        f"{window.demoted} demoted")
    values = check(cell, sets, window) if window.kept else {}
    reduced = None
    if traced:
        try:
            reduced = tr.reduce_dir(trace_dir, [d.id for d in devices])
            if keep_trace:
                tr.save_gz(trace_dir, keep_trace)
        finally:
            tr.remove_dir(trace_dir)
    run = Run(cell=cell, setup_s=setup_s, calls=window.calls,
              window_s=window.seconds,
              units_per_call=cell.entry.units(sets[0], cell.config,
                                              cell.traffic),
              work=cell.work(sets[0]), device_kind=devices[0].device_kind,
              trace=reduced)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = metric_reader(m["name"]).read(run) if window.calls else None
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    limits = cell.entry.LIMITS
    checks = {n: {"value": values.get(n), "limit": limits[n]}
              for n in limits}
    correct = (window.error is None and bool(window.kept)
               and all(values.get(n) is not None and values[n] <= limits[n]
                       for n in limits))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    result = {"correct": correct,
              "attempted": len(window.calls) + (window.error is not None),
              "failed": window.demoted + (window.error is not None),
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    if window.error:
        log(f"call failed: {window.error}")
    for n, c in checks.items():
        log(f"check {n}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result

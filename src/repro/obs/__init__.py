"""``repro.obs`` — the engine's flight recorder (DESIGN.md §7).

A lightweight tracing/metrics layer threaded through every engine layer:
plan resolution, autotuning, the Pallas kernel variants, MergeSchedule
passes, and the sharded exchange. **Disabled by default and free when
disabled**: every instrumentation site checks one module-level flag before
touching anything, and the decision-heavy sites live in host dispatch code
that jitted steady-state calls never re-run.

    from repro import obs

    obs.enable()
    engine.sort(x)                     # decisions now recorded
    snap = obs.snapshot()              # JSON-clean dict
    print(obs.report())                # human-readable rendering
    obs.disable()

What gets recorded (the event taxonomy — DESIGN.md §7.1):

- ``plan.resolve``        cache hit / heuristic fallback / explicit plan
- ``autotune.candidate``  one per measured candidate, incl. infeasible ones
- ``autotune.winner``     the installed plan and its median time
- ``schedule.pass``       each fused merge-tree pass (executor, levels, runs;
  ``level_kind='hbm_run'`` on the streaming executors whose runs live in
  HBM rather than a scratch bank; on ``tree_pallas`` ``bank='uniform'``
  where the pass's banks are a dense pad of uniform runs, else
  ``'gather'``, tallied by the ``schedule.bank.uniform`` /
  ``schedule.bank.gather`` counters)
- ``schedule.reduce``     one per reduction: passes vs tree levels (the HBM
  round trips a fused schedule saved)
- ``external.run_form``   out-of-core phase 1: tiles sorted into runs, with
  the bytes streamed (DESIGN.md §8)
- ``external.pass``       one per out-of-core phase-2 pass: fan-in, run
  count/length, and ``bytes_streamed`` — their count is the measured
  ``ceil(log_fan_in(runs))`` HBM round-trip claim
- ``external.delegate``   single-tile inputs handed to ``engine.sort``
- ``sharded.plan``        the cap ladder, splitter policy, and executor
- ``sharded.exec``        the cap-ladder rung the ``lax.switch`` actually
  took, the pmax'd needed cap, and the overflow flag (via
  ``jax.debug.callback`` — one event per participating device)
- ``moe.route``           one per ``engine.moe_route`` call: group/token/
  expert geometry, k, capacity, and the serving variant — its count is the
  one-pallas_call-per-chunk claim (DESIGN.md §9); the caller counts dropped
  pairs from ``RouteResult.keep``
- ``moe.route_ep.plan``   the expert-parallel geometry: device count, local
  tokens, candidate cap, and the local route variant
- ``moe.route_ep.exec``   owner-side merge outcome per device per run:
  arrived candidates and globally-dropped pairs (debug callback; the
  companion ``moe.dropped_tokens`` counter tallies the drops)
- ``serve.admit`` / ``serve.retire``  one per scheduler admission /
  retirement: request uid, slot, prompt/output length, finish reason
  (DESIGN.md §10); the companion counters ``serve.submitted`` /
  ``serve.admitted`` / ``serve.retired`` / ``serve.tokens`` tally request
  flow and emitted tokens, and ``serve.trace`` counts decode/prefill
  compilations (trace-time increment — the no-retrace acceptance contract
  reads it). Gauges ``serve.live_slots`` / ``serve.waiting`` /
  ``serve.kv_free`` / ``serve.traces`` track occupancy; spans
  ``serve.step`` / ``serve.prefill`` feed the p50/p99 the serving stats
  line reports.
- ``serve.reject``          one per refused submission (structured
  ``RequestRejected``/``QueueFull`` — DESIGN.md §11) with the rejection
  details; counted by ``serve.rejected``. The companion counters
  ``serve.timeout`` (deadline retirements, ``status=TIMEOUT``) and
  ``serve.poisoned`` (non-finite-logit slots isolated with
  ``status=ERROR``) tally the hardened retirement paths.
- ``guard.fallback``        one per variant demotion on the guard layer's
  fallback ladder (DESIGN.md §11): op, failing variant, error type, and
  the rung tried next; counted by ``guard.fallback``
- ``guard.quarantine``      one per variant quarantined for the session
  (with ``guard.quarantine.skip`` counting rungs skipped as already
  quarantined on later calls)
- ``guard.verify``          one per armed postcondition check
  (``REPRO_VERIFY=1``): op, check kind, pass/fail — via debug callback,
  so it fires per executed call; counters ``guard.verify.checked`` /
  ``guard.verify.fail`` feed the CI chaos job's zero-failure assertion

Spans (``obs.span``) mark the layer boundaries of an eager engine call:
``engine.<op>`` (the public call) and, nested in it, ``engine.validate``
(lane width, NaN policy), ``engine.plan`` (plan resolution),
``engine.guard`` (the fallback ladder's bookkeeping) and
``engine.<op>.<variant>`` (the registry dispatch: the jitted program's
dispatch and enqueue). Each span is recorded as ``{name, id, parent, call,
start_ns, end_ns}`` into a bounded ring (``snapshot()["spans"]``) and into
the ``name`` timer histogram; every span of one public call shares its
``call`` id. While enabled a span also opens a
``jax.profiler.TraceAnnotation`` carrying the same ``call`` id, so under a
profiler it lands on the trace's host plane, on the clock the device trace
is aligned to. ``jax.named_scope`` labels (``repro.*``, DESIGN.md §7.2) name
the stages of the compiled programs whether or not obs is enabled; they
reach each device op's event metadata (``tf_op``) in a profiler trace.

Turning obs on compiles no different program: no instrumentation site
stages anything into traced code on the flag, except the sharded entry
points' documented static ``record`` argument (their debug callbacks).

Trace-time semantics: events fired from inside traced code (plan lookup
under ``jit``, schedule passes) are emitted when the decision is MADE —
i.e. at trace time, once per compilation, not once per executed call.
The sharded rung event is the exception: it reports the executed branch via
a debug callback, so it fires per run (and per device under ``shard_map``).
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Callable, Optional

from repro.obs.metrics import Registry, percentile, plain, self_times

__all__ = [
    "enable", "disable", "enabled",
    "inc", "gauge", "observe", "event", "on", "span", "kernel_scope",
    "scoped", "snapshot", "report", "reset", "registry", "percentile",
    "plain", "self_times",
]

#: the process-wide registry every instrumentation site writes to
registry = Registry()

_enabled = False


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


# --------------------------------------------------------------------------
# fast-path write API: one flag check, then the registry
# --------------------------------------------------------------------------

def inc(name: str, n: int = 1) -> None:
    if _enabled:
        registry.inc(name, n)


def gauge(name: str, value) -> None:
    if _enabled:
        registry.set_gauge(name, value)


def observe(name: str, seconds: float) -> None:
    if _enabled:
        registry.observe(name, seconds)


def event(kind: str, **data) -> None:
    if _enabled:
        registry.event(kind, **data)


def on(kind: str, fn: Callable) -> Callable:
    """Subscribe ``fn(event_dict)`` to events of ``kind`` ('*' for all).
    Subscriptions are independent of the enabled flag (events only fire
    while enabled)."""
    return registry.on(kind, fn)


#: span ids and call ids: one counter each, shared by every thread
_span_ids = itertools.count(1)
_call_ids = itertools.count(1)
#: the spans open on each thread, innermost last: (span id, call id)
_open = threading.local()
_OFF = contextlib.nullcontext()


class _Span:
    __slots__ = ("name", "id", "parent", "call", "start", "note")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = _open.__dict__.setdefault("stack", [])
        self.parent, self.call = stack[-1] if stack else (
            None, next(_call_ids))
        self.id = next(_span_ids)
        stack.append((self.id, self.call))
        from jax.profiler import TraceAnnotation
        self.note = TraceAnnotation(self.name, call=self.call)
        self.note.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.note.__exit__(*exc)
        _open.stack.pop()
        registry.span(self.name, self.id, self.parent, self.call,
                      self.start, end)
        return False


def span(name: str):
    """Context manager marking one layer boundary: records ``{name, id,
    parent, call, start_ns, end_ns}`` into the span ring and the ``name``
    timer, and opens a ``jax.profiler.TraceAnnotation(name, call=...)``.
    The outermost open span of the thread starts a new call id; nested
    spans take their parent's. While disabled it is a shared no-op context
    (one flag check, no timestamps)."""
    return _Span(name) if _enabled else _OFF


def kernel_scope(name: str):
    """``jax.named_scope`` labelling a stage of a compiled program as
    ``repro.<name>``: its ops carry the label in their HLO metadata, which
    a profiler trace keeps per device op (``tf_op``). Always on — the label
    only exists at trace time and costs nothing at run time."""
    import jax
    return jax.named_scope(f"repro.{name}")


def scoped(name: str):
    """Decorator form of ``kernel_scope``: every call to the wrapped
    function traces under ``repro.<name>``."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kw):
            with kernel_scope(name):
                return fn(*args, **kw)
        return wrapper
    return deco


# --------------------------------------------------------------------------
# reporting
# --------------------------------------------------------------------------

def snapshot(kinds: Optional[tuple] = None) -> dict:
    """One JSON-clean dict of everything recorded so far: counters, gauges,
    timer summaries (count/total/p50/p99/max in µs), the event ring
    (optionally filtered to ``kinds``) and the span ring (``spans``, oldest
    first). Round-trips through ``json``."""
    snap = registry.snapshot(kinds)
    snap["enabled"] = _enabled
    return snap


def report(snap: Optional[dict] = None) -> str:
    """Human-readable rendering of a snapshot (current one by default)."""
    from repro.obs.reporting import render_report
    return render_report(snap if snap is not None else snapshot())


def reset() -> None:
    """Clear every counter, gauge, timer, event and span (the enabled flag
    and subscriptions survive)."""
    registry.reset()

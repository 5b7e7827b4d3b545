"""Device ops named by the engine stage behind them, idle device time by the
host span or runtime event behind it.

A TPU trace's op events carry only the HLO instruction; the instruction's
*event metadata* in the ``.xplane.pb`` also holds ``tf_op``, the full name
stack of the op (``jit(route_layers)/while/body/closed_call/
repro.engine.moe_route.fused/jit(moe_route_pallas)/repro.kernels.route_fuse/
flims_route_fuse/pallas_call:``), its ``source`` line and its
``program_id``. ``jax.profiler.ProfileData`` shows per-event stats only, so
this module reads the metadata from the file's protobuf wire format (an
``XSpace``; no schema or package is needed for the few fields it reads).

A device op's *stage* is the innermost ``repro.*`` component of its name
stack: the engine's ``jax.named_scope`` labels (``obs.kernel_scope``), e.g.
``repro.kernels.merge_tree.corank``. An op with no ``repro.*`` label is
``<program>/unlabelled``. Fused merge passes run under
``repro.schedule.pass<i>_L<levels>``, so the passes a call made can be
counted on the device.

Everything here is computed on top of ``trace.reduce``, which it leaves as
it is: the same clock shift, window, calls and busy intervals.

    python3 benchmarks/chip/scopes.py TRACE.xplane.pb[.gz]

prints, as one JSON object, device 0's breakdown by stage and by host
span and the per-call readings of an argsort (``corank_share``,
``merge_passes``, ``plan_ms``).
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import heapq
import json
import re
import statistics
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import trace as tr  # noqa: E402

REPRO = "repro."
UNLABELLED = "unlabelled"
PASS = re.compile(r"^repro\.schedule\.pass\d+_L\d+$")
#: host events that are the benchmark's own spans, and the engine's
BENCH, ENGINE = "chipbench.", "engine."


# --------------------------------------------------------------------------
# the XSpace wire format: only the fields read here
# --------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryview
    slices for length-delimited fields, raw bytes for fixed widths."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind == 1:
            v, i = bytes(buf[i:i + 8]), i + 8
        elif kind == 5:
            v, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


@dataclasses.dataclass(frozen=True)
class OpMeta:
    tf_op: str
    source: str


def op_metadata(xspace) -> Dict[str, Dict[Tuple[Optional[int], str],
                                          OpMeta]]:
    """Per device plane (``/device:...``) that has ops, the event metadata
    of its ops:
    ``(program_id, HLO text) -> OpMeta``. XSpace.planes = 1;
    XPlane: name = 2, event_metadata = 4 (map: id -> XEventMetadata),
    stat_metadata = 5 (map: id -> XStatMetadata, name = 2);
    XEventMetadata: name = 2, stats = 5; XStat: metadata_id = 1,
    uint64 = 3, int64 = 4, str = 5."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = _text(pv)
                if not name.startswith("/device:"):
                    break
            elif pf == 4:
                events.append(dict(_fields(pv)).get(2, b""))
            elif pf == 5:
                kv = dict(_fields(pv))
                stat_names[kv.get(1, 0)] = _text(
                    dict(_fields(kv.get(2, b""))).get(2, b""))
        if not name.startswith("/device:"):
            continue
        ops = {}
        for ev in events:
            hlo, stats = "", {}
            for ef, ev_v in _fields(ev):
                if ef == 2:
                    hlo = _text(ev_v)
                elif ef == 5:
                    st = dict(_fields(ev_v))
                    key = stat_names.get(st.get(1))
                    if key in ("tf_op", "source"):
                        stats[key] = _text(st.get(5, b""))
                    elif key == "program_id":
                        stats[key] = st.get(3, st.get(4))
            if "tf_op" in stats or "source" in stats:
                ops[(stats.get("program_id"), hlo)] = OpMeta(
                    stats.get("tf_op", ""), stats.get("source", ""))
        if ops:
            out[name] = ops
    return out


# --------------------------------------------------------------------------
# stages
# --------------------------------------------------------------------------

def _components(tf_op: str) -> List[str]:
    return [c.rstrip(":") for c in tf_op.split("/")]


def stage_of(tf_op: str) -> Optional[str]:
    """The innermost ``repro.*`` component of a name stack, or None."""
    inner = [c for c in _components(tf_op) if c.startswith(REPRO)]
    return inner[-1] if inner else None


def pass_of(tf_op: str) -> Optional[str]:
    """The ``repro.schedule.pass<i>_L<levels>`` scope an op ran under."""
    found = [c for c in _components(tf_op) if PASS.match(c)]
    return found[-1] if found else None


def _program_id(module: str) -> Optional[int]:
    m = re.search(r"\((\d+)\)$", module)
    return int(m.group(1)) if m else None


@dataclasses.dataclass
class Staged:
    """A reduced trace whose device ops carry their stage: ``stages[d][i]``
    and ``passes[d][i]`` belong to ``reduced.devices[d].ops[i]``."""
    reduced: tr.Reduced
    stages: List[List[str]]
    passes: List[List[Optional[str]]]
    host: List[Tuple[float, float, str, Optional[int]]]  # engine + runtime

    def _in_window(self, d: int, a: float, b: float):
        ops = self.reduced.devices[d].ops
        return [(max(s, a), min(e, b), i) for i, (s, e, _) in enumerate(ops)
                if e > a and s < b]

    def stage_seconds(self, a: Optional[float] = None,
                      b: Optional[float] = None) -> Dict[str, float]:
        """Own device seconds per stage in [a, b] (default: the window),
        summed over the chips: each op's duration less the ops nested in
        it, credited to the op's stage."""
        if a is None:
            a, b = self.reduced.window
        total: Dict[str, float] = {}
        for d in range(len(self.reduced.devices)):
            inside = [(s, e, self.stages[d][i])
                      for s, e, i in self._in_window(d, a, b)]
            for n, t in tr.self_times(inside).items():
                total[n] = total.get(n, 0.0) + t / 1e9
        return total

    def passes_per_call(self) -> List[int]:
        """Distinct ``repro.schedule.pass*`` scopes whose merge kernel (the
        ``….kernel`` stage) ran inside each call's host interval."""
        out = []
        for a, b in self.reduced.calls:
            seen = set()
            for d in range(len(self.reduced.devices)):
                for _, _, i in self._in_window(d, a, b):
                    if self.passes[d][i] and \
                            self.stages[d][i].endswith(".kernel"):
                        seen.add(self.passes[d][i])
            out.append(len(seen))
        return out

    def stage_share_per_call(self, suffix: str) -> List[float]:
        """Own device time of the stages ending in ``suffix`` over the
        device-busy time inside each call's host interval, in %."""
        out = []
        for (a, b), busy in zip(self.reduced.calls,
                                self.reduced.busy_per_call()):
            if busy <= 0:
                continue
            own = sum(t for n, t in self.stage_seconds(a, b).items()
                      if n.endswith(suffix))
            own /= max(len(self.reduced.devices), 1)
            out.append(100.0 * own / busy)
        return out

    def idle_by_host_span(self) -> Dict[str, float]:
        """Idle device seconds in the window (averaged over the chips),
        named by what the host was doing: inside a running program, the
        gaps between its ops (``trace.IN_PROGRAM``); elsewhere the
        innermost engine span (``engine.*``) open at the time, else the
        innermost runtime event of a thread that works on the calls (the
        calling thread, and the threads the runtime hands a call's enqueue
        and completion to: ``_call_threads``), else the innermost
        ``chipbench.*`` span, else ``trace.BETWEEN``."""
        r = self.reduced
        a, b = r.window
        out: Dict[str, float] = {}
        if not r.devices:
            return out
        engine = [(s, e, n) for s, e, n, _ in self.host
                  if n.startswith(ENGINE)]
        runtime = [(s, e, n) for s, e, n, _ in self.host
                   if not n.startswith(ENGINE)]
        bench = [(s, e, n) for n, spans in r.spans.items() for s, e in spans]
        for d in r.devices:
            gaps = tr.complement(d.busy, a, b)
            inside = sum(tr.covered(d.programs, s, e) for s, e in gaps)
            out[tr.IN_PROGRAM] = out.get(tr.IN_PROGRAM, 0.0) + inside
            pieces = [g for s, e in gaps
                      for g in tr.complement(d.programs, s, e)]
            for n, t in _name_pieces(pieces,
                                     [engine, runtime, bench]).items():
                out[n] = out.get(n, 0.0) + t
        return {k: v / len(r.devices) / 1e9 for k, v in out.items()}

    def breakdown(self, top: int = 10) -> dict:
        rank = lambda d: sorted(([k, v] for k, v in d.items() if v > 0),
                                key=lambda kv: -kv[1])[:top]
        return {"device_stages": rank(self.stage_seconds()),
                "idle_by_host_span": rank(self.idle_by_host_span())}


def _name_pieces(pieces: Sequence[tr.Interval], classes) -> Dict[str, float]:
    """Split sorted disjoint ``pieces`` among events: each moment goes to
    the first class (in priority order) with an event open then, and within
    it to the open event that started last; moments no event covers go to
    ``trace.BETWEEN``."""
    order = [sorted(c) for c in classes]
    nxt = [0] * len(order)
    open_ = [[] for _ in order]          # heaps of (-start, end, name)
    out: Dict[str, float] = {}
    for a, b in pieces:
        cuts = {a, b}
        for k, evs in enumerate(order):
            while nxt[k] < len(evs) and evs[nxt[k]][0] < b:
                s, e, n = evs[nxt[k]]
                nxt[k] += 1
                if e > a:
                    heapq.heappush(open_[k], (-s, e, n))
            open_[k] = [x for x in open_[k] if x[1] > a]
            heapq.heapify(open_[k])
            cuts.update(p for x in open_[k] for p in (-x[0], x[1])
                        if a < p < b)
        cuts = sorted(cuts)
        for t0, t1 in zip(cuts, cuts[1:]):
            name = tr.BETWEEN
            for live in open_:
                started = [(-ns, n) for ns, e, n in live
                           if -ns <= t0 and e >= t1]
                if started:
                    name = max(started)[1]
                    break
            out[name] = out.get(name, 0.0) + (t1 - t0)
    return out


def _check_same_ops(dev_id: int, seen, ops) -> None:
    """Stages are attached to ``trace.reduce``'s ops by position: the two
    walks of a device's ops must meet the same op, by start and name, at
    every index."""
    if len(seen) != len(ops):
        raise ValueError(f"TPU:{dev_id}: {len(seen)} ops walked here, "
                         f"{len(ops)} in trace.reduce")
    for i, ((s, name), (s2, _, name2)) in enumerate(zip(seen, ops)):
        if s != s2 or name != name2:
            raise ValueError(f"TPU:{dev_id} op {i}: {name} at {s} here, "
                             f"{name2} at {s2} in trace.reduce")


#: runtime events that mark the threads working on a call: the calling
#: thread's native line executes it, two runtime threads enqueue and
#: complete its program
CALL_WORK = ("PJRT_LoadedExecutable_Execute", "DoEnqueueProgram",
             "tpu::System::Execute=>Done")


def _call_threads(profile) -> List[List[tuple]]:
    """The host events, per thread line, of the threads that work on the
    calls: the line that holds the ``chipbench.*`` and ``engine.*`` spans
    (the calling thread's Python events), and each line that runs an event
    named in ``CALL_WORK``. Events are ``(start, end, name, call id)``."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                    dict(ev.stats).get("call")
                    if ev.name.startswith(ENGINE) else None)
                   for ev in line.events]
            if any(n.startswith((BENCH, ENGINE)) or n in CALL_WORK
                   for _, _, n, _ in evs):
                out.append(evs)
    return out


def reduce(profile, xspace, device_ids: Sequence[int]) -> Staged:
    """``trace.reduce`` of a ``ProfileData``, with each device op's stage
    read from the same trace's serialized ``xspace``."""
    reduced = tr.reduce(profile, device_ids)
    meta = op_metadata(xspace)
    stages, passes = [], []
    for dev_id in device_ids:
        plane = profile.find_plane_with_name(f"/device:TPU:{dev_id}")
        if plane is None:
            continue
        d = len(stages)
        ops_meta = meta.get(f"/device:TPU:{dev_id}", {})
        by_name = {}
        for (_, hlo), m in ops_meta.items():
            by_name.setdefault(hlo, []).append(m)
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in lines.get("XLA Modules", []))
        starts = [m[0] for m in modules]
        ops = reduced.devices[d].ops
        shift = reduced.shift_ns[dev_id]
        st, ps, seen = [], [], []
        for ev in lines.get("XLA Ops", []):
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            prog = (modules[i][2] if i >= 0 and modules[i][1] >= ev.start_ns
                    else "?")
            m = ops_meta.get((_program_id(prog), ev.name))
            if m is None and len(by_name.get(ev.name, ())) == 1:
                m = by_name[ev.name][0]
            stage = stage_of(m.tf_op) if m else None
            st.append(stage or f"{prog.split('(', 1)[0]}/{UNLABELLED}")
            ps.append(pass_of(m.tf_op) if m else None)
            seen.append((ev.start_ns + shift, tr.op_name(prog, ev.name)))
        if not st:       # no per-op line: whole programs are the ops
            st = [f"{n.split('(', 1)[0]}/{UNLABELLED}" for _, _, n in modules]
            ps = [None] * len(st)
            seen = [(s + shift, tr.op_name(n, n)) for s, _, n in modules]
        _check_same_ops(dev_id, seen, ops)
        stages.append(st)
        passes.append(ps)
    host = [ev for line in _call_threads(profile) for ev in line
            if not ev[2].startswith(BENCH)]
    return Staged(reduced=reduced, stages=stages, passes=passes, host=host)


def load(path: str, device_ids: Sequence[int] = (0,)) -> Staged:
    """``reduce`` of a trace file, ``.xplane.pb`` or gzipped."""
    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    if path.endswith(".gz"):
        data = gzip.decompress(data)
    return reduce(ProfileData.from_serialized_xspace(data), data,
                  device_ids)


# --------------------------------------------------------------------------
# engine spans: records of ``obs.snapshot()["spans"]`` or the trace's
# ``engine.*`` annotations
# --------------------------------------------------------------------------

def plan_ms(spans, op: str) -> Optional[float]:
    """Median over the calls of ``engine.<op>``'s span less its
    ``engine.<op>.<variant>`` children (validation, planning, the guard's
    bookkeeping and the glue between them), in ms. ``spans`` are dicts
    with ``name``, ``call``, ``start_ns`` and ``end_ns``."""
    root, child = f"{ENGINE}{op}", f"{ENGINE}{op}."
    calls: Dict[int, List[float]] = {}
    for s in spans:
        if s["name"] == root and s["call"] not in calls:
            calls[s["call"]] = [s["start_ns"], s["end_ns"], 0.0]
    for s in spans:
        c = calls.get(s["call"])
        if c and s["name"].startswith(child) and \
                c[0] <= s["start_ns"] and s["end_ns"] <= c[1]:
            c[2] += s["end_ns"] - s["start_ns"]
    if not calls:
        return None
    return statistics.median((e - s - v) / 1e6
                             for s, e, v in calls.values())


def trace_spans(staged: Staged) -> List[dict]:
    """The trace's ``engine.*`` annotations as span records."""
    return [{"name": n, "call": c, "start_ns": s, "end_ns": e}
            for s, e, n, c in staged.host if n.startswith(ENGINE)]


def summary(staged: Staged) -> dict:
    """The breakdowns and per-call readings of a trace of argsort calls."""
    r = staged.reduced
    ops = [sum(1 for d in r.devices for s, e, _ in d.ops if e > a and s < b)
           for a, b in r.calls]
    busy = r.busy_s
    labelled = sum(t for n, t in staged.stage_seconds().items()
                   if n.startswith(REPRO))
    return {"calls": len(r.calls), "window_s": r.window_s, "busy_s": busy,
            "busy_per_call_s": r.busy_per_call(), "ops_per_call": ops,
            "labelled_share": 100.0 * labelled / len(r.devices) / busy
            if busy else None,
            "corank_share_per_call": staged.stage_share_per_call(".corank"),
            "merge_passes_per_call": staged.passes_per_call(),
            "plan_ms": plan_ms(trace_spans(staged), "argsort"),
            **r.breakdown(), **staged.breakdown(top=20)}


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("trace")
    a = p.parse_args(argv)
    print(json.dumps(summary(load(a.trace))))
    return 0


if __name__ == "__main__":
    sys.exit(main())

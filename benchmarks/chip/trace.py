"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics read.

What a TPU trace holds, as read from one by hand (TPU v5e, jax 0.9):

- one plane per chip, ``/device:TPU:<id>``, whose ``XLA Ops`` line has an
  event per executed HLO instruction, named by its HLO text
  (``%flims_merge_tree.3 = ... custom-call(...)``: a Pallas kernel is a
  custom call named after the kernel), and whose ``XLA Modules`` line has
  an event per program execution, named ``<jit name>(<fingerprint>)``
  with a ``run_id`` stat;
- a ``/host:CPU`` plane whose lines are host threads: the benchmark's own
  spans (``chipbench.*``, ``jax.profiler.TraceAnnotation``) and the
  runtime's ``DoEnqueueProgram`` events, which carry the ``run_id`` of
  the program they enqueue.

The device's clock is not the host's: within one trace they differ by a
constant, which was 1.4 ms in one recorded trace and under 0.1 ms in
another. So device times are shifted onto the host's clock first, by the
one rule the hardware guarantees: a program cannot start before the host
has enqueued it. The shift makes the tightest enqueue-to-start gap zero.

Device-busy time is the union of the ``XLA Ops`` intervals (a ``while``
and the ops inside it overlap; the union counts them once), and a device
operation's own time is its duration less that of the operations nested
in it. The ``named_scope`` labels of the engine do not reach these events,
so an operation is named ``<program>/<instruction>``: the jitted program's
name and the HLO instruction's, without numeric suffixes; a Pallas kernel
is its own instruction.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: host spans of the benchmark loop (harness.py) and the names their idle
#: time is reported under
SPAN_NAMES = {"chipbench.dispatch": "engine dispatch",
              "chipbench.wait": "block_until_ready",
              "chipbench.rotate": "input rotation"}
CALL = "chipbench.call"
BETWEEN = "between calls"
#: idle time while a program is running: the device between two of its ops
IN_PROGRAM = "between ops of a running program"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Interval], a: float, b: float) -> float:
    """Length of [a, b] covered by sorted disjoint ``merged`` intervals."""
    i = max(bisect.bisect_right(merged, (a, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < b:
        s, e = merged[i]
        total += max(0.0, min(e, b) - max(s, a))
        i += 1
    return total


def complement(merged: Sequence[Interval], a: float, b: float
               ) -> List[Interval]:
    """The parts of [a, b] that ``merged`` leaves uncovered."""
    gaps, t = [], a
    first = max(bisect.bisect_right(merged, (a, float("inf"))) - 1, 0)
    for s, e in merged[first:]:
        if e <= t:
            continue
        if s >= b:
            break
        if s > t:
            gaps.append((t, min(s, b)))
        t = max(t, e)
    if t < b:
        gaps.append((t, b))
    return gaps


def self_times(events: Sequence[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Own time per name: each event's duration less that of the events
    nested inside it (events on one line nest or follow one another)."""
    out: Dict[str, float] = {}
    stack: List[List] = []          # [end, name, child time]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm, child = stack.pop()
            out[nm] = out.get(nm, 0.0) - child
        if stack:
            stack[-1][2] += e - s
        out[name] = out.get(name, 0.0) + (e - s)
        stack.append([e, name, 0.0])
    for end, nm, child in stack:
        out[nm] = out.get(nm, 0.0) - child
    return out


_SUFFIX = re.compile(r"(\.\d+)+$")


def op_name(program: str, hlo_text: str) -> str:
    instr = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    prog = program.split("(", 1)[0]
    return f"{prog}/{_SUFFIX.sub('', instr)}"


@dataclasses.dataclass
class Device:
    """One chip's events, on the host's clock (ns)."""
    ops: List[Tuple[float, float, str]]     # (start, end, name)
    busy: List[Interval]                    # union of the ops
    programs: List[Interval]                # union of program executions


@dataclasses.dataclass
class Reduced:
    devices: List[Device]
    spans: Dict[str, List[Interval]]        # host span name -> intervals
    shift_ns: Dict[int, float]              # device id -> shift applied

    @property
    def calls(self) -> List[Interval]:
        return self.spans.get(CALL, [])

    @property
    def window(self) -> Interval:
        c = self.calls
        return (c[0][0], c[-1][1]) if c else (0.0, 0.0)

    @property
    def window_s(self) -> float:
        a, b = self.window
        return (b - a) / 1e9

    def _mean_busy(self, a: float, b: float) -> float:
        if not self.devices:
            return 0.0
        return sum(covered(d.busy, a, b) for d in self.devices) / len(
            self.devices) / 1e9

    @property
    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the chips."""
        return self._mean_busy(*self.window)

    def busy_per_call(self) -> List[float]:
        """Device-busy seconds inside each call's host interval."""
        return [self._mean_busy(a, b) for a, b in self.calls]

    def idle_by_activity(self) -> Dict[str, float]:
        """Idle device seconds in the window (averaged over the chips):
        the gaps between ops of a running program, and the rest split by
        the benchmark's host span active at the time."""
        a, b = self.window
        out: Dict[str, float] = {}
        if not self.devices:
            return out
        spans = {span: union(self.spans.get(span, []))
                 for span in SPAN_NAMES}
        for d in self.devices:
            gaps = complement(d.busy, a, b)
            inside = sum(covered(d.programs, s, e) for s, e in gaps)
            out[IN_PROGRAM] = out.get(IN_PROGRAM, 0.0) + inside / len(
                self.devices)
            gaps = [g for s, e in gaps for g in complement(d.programs, s, e)]
            named = 0.0
            for span, label in SPAN_NAMES.items():
                t = sum(covered(spans[span], s, e) for s, e in gaps)
                out[label] = out.get(label, 0.0) + t / len(self.devices)
                named += t
            rest = sum(e - s for s, e in gaps) - named
            out[BETWEEN] = out.get(BETWEEN, 0.0) + rest / len(self.devices)
        return {k: v / 1e9 for k, v in out.items()}

    def op_seconds(self) -> Dict[str, float]:
        """Own device seconds per operation name inside the window, summed
        over the chips."""
        a, b = self.window
        total: Dict[str, float] = {}
        for d in self.devices:
            inside = [(max(s, a), min(e, b), n) for s, e, n in d.ops
                      if e > a and s < b]
            for n, t in self_times(inside).items():
                total[n] = total.get(n, 0.0) + t / 1e9
        return total

    def breakdown(self, top: int = 10) -> dict:
        rank = lambda d: sorted(([k, v] for k, v in d.items() if v > 0),
                                key=lambda kv: -kv[1])[:top]
        return {"device_ops": rank(self.op_seconds()),
                "idle_gaps": rank(self.idle_by_activity())}


def _stats(event) -> dict:
    return dict(event.stats)


def reduce(profile, device_ids: Sequence[int]) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData`` to device busy intervals and
    the benchmark's host spans, with device times on the host's clock."""
    spans: Dict[str, List[Interval]] = {}
    enqueued: Dict[Tuple[int, int], float] = {}   # (device, run_id) -> end
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("chipbench."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
                elif ev.name == "DoEnqueueProgram":
                    st = _stats(ev)
                    if "run_id" in st:
                        k = (int(st.get("device_ordinal", 0)),
                             int(st["run_id"]))
                        enqueued[k] = ev.start_ns + ev.duration_ns
    for v in spans.values():
        v.sort()
    devices, shifts = [], {}
    for dev_id in device_ids:
        plane = profile.find_plane_with_name(f"/device:TPU:{dev_id}")
        if plane is None:
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                    _stats(ev)) for ev in lines.get("XLA Modules", [])]
        leads = [s - enqueued[(dev_id, int(st["run_id"]))]
                 for s, _, _, st in modules
                 if "run_id" in st and (dev_id, int(st["run_id"]))
                 in enqueued]
        shift = -min(leads) if leads else 0.0
        shifts[dev_id] = shift
        modules.sort()
        starts = [m[0] for m in modules]
        ops = []
        for ev in lines.get("XLA Ops", []):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            i = bisect.bisect_right(starts, s) - 1
            prog = modules[i][2] if i >= 0 and modules[i][1] >= s else "?"
            ops.append((s + shift, e + shift, op_name(prog, ev.name)))
        if not ops:      # no per-op line: whole programs count as busy
            ops = [(s + shift, e + shift, op_name(n, n))
                   for s, e, n, _ in modules]
        devices.append(Device(
            ops=ops, busy=union((s, e) for s, e, _ in ops),
            programs=union((s + shift, e + shift) for s, e, _, _ in modules)))
    return Reduced(devices=devices, spans=spans, shift_ns=shifts)


def reduce_file(path: str, device_ids: Sequence[int]) -> Reduced:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), device_ids)


def find_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def reduce_dir(trace_dir: str, device_ids: Sequence[int]
               ) -> Optional[Reduced]:
    path = find_xplane(trace_dir)
    return reduce_file(path, device_ids) if path else None


def save_gz(trace_dir: str, dest: str) -> None:
    """Copy the trace's ``.xplane.pb``, gzipped, to ``dest``."""
    path = find_xplane(trace_dir)
    if path:
        with open(path, "rb") as f, gzip.open(dest, "wb") as g:
            shutil.copyfileobj(f, g)


def remove_dir(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)

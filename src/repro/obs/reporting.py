"""Rendering for ``obs.snapshot()``: the human-readable ``obs.report()``
text and the one-line serving stats summary."""
from __future__ import annotations

from typing import Optional


def _fmt_us(us: float) -> str:
    if us >= 1e6:
        return f"{us / 1e6:.2f}s"
    if us >= 1e3:
        return f"{us / 1e3:.2f}ms"
    return f"{us:.1f}us"


def render_report(snap: dict) -> str:
    lines = [f"repro.obs report (enabled={snap.get('enabled')})"]
    counters = snap.get("counters", {})
    if counters:
        lines.append("  counters:")
        for k in sorted(counters):
            lines.append(f"    {k:<40} {counters[k]}")
    gauges = snap.get("gauges", {})
    if gauges:
        lines.append("  gauges:")
        for k in sorted(gauges):
            lines.append(f"    {k:<40} {gauges[k]}")
    timers = snap.get("timers", {})
    if timers:
        lines.append("  timers:                                    "
                     "count    p50      p99      max      total")
        for k in sorted(timers):
            t = timers[k]
            lines.append(
                f"    {k:<40} {t['count']:<8} {_fmt_us(t['p50_us']):<8} "
                f"{_fmt_us(t['p99_us']):<8} {_fmt_us(t['max_us']):<8} "
                f"{_fmt_us(t['total_us'])}")
    events = snap.get("events", [])
    if events:
        by_kind = {}
        for e in events:
            by_kind[e["kind"]] = by_kind.get(e["kind"], 0) + 1
        lines.append("  events: " + ", ".join(
            f"{k} x{n}" for k, n in sorted(by_kind.items())))
        for e in events[-12:]:
            data = ";".join(f"{k}={v}" for k, v in e["data"].items())
            lines.append(f"    [{e['kind']}] {data}")
        if len(events) > 12:
            lines.insert(len(lines) - 12, f"    ... showing last 12 of "
                                          f"{len(events)}")
    spans = snap.get("spans", [])
    if spans:
        lines.extend(_render_spans(spans))
    if len(lines) == 1:
        lines.append("  (empty)")
    return "\n".join(lines)


def _render_spans(spans, calls: int = 4) -> list:
    """The span trees of the last ``calls`` call ids: each span's duration
    and own time (duration less its children), children indented."""
    from repro.obs.metrics import self_times
    own = self_times(spans)
    ids = sorted({s["call"] for s in spans})
    keep = set(ids[-calls:])
    depth = {}
    out = [f"  spans: {len(spans)} in {len(ids)} calls; last "
           f"{len(keep)} calls (duration / own time):"]
    for s in sorted((s for s in spans if s["call"] in keep),
                    key=lambda s: (s["call"], s["start_ns"], s["id"])):
        depth[s["id"]] = depth.get(s["parent"], -1) + 1
        name = "  " * depth[s["id"]] + s["name"]
        dur = (s["end_ns"] - s["start_ns"]) / 1e3
        out.append(f"    [call {s['call']}] {name:<40} {_fmt_us(dur):<8} "
                   f"{_fmt_us(own[s['id']] / 1e3)}")
    return out


def serve_stats_line(snap: dict, step: Optional[int] = None) -> str:
    """One periodic serving-stats line sourced entirely from the obs
    registry (requires ``obs.enable()``): step-latency percentiles from the
    ``serve.step`` span-timer histogram — not an ad-hoc wall-time list —
    throughput from the ``serve.tokens`` counter over the timer total, and
    the scheduler occupancy gauges."""
    t = snap.get("timers", {}).get("serve.step") or {}
    c = snap.get("counters", {})
    g = snap.get("gauges", {})
    total_s = t.get("total_us", 0.0) / 1e6
    tok_s = c.get("serve.tokens", 0) / total_s if total_s > 0 else 0.0
    return (f"[serve] step={step if step is not None else t.get('count', 0)} "
            f"p50={_fmt_us(t.get('p50_us', 0.0))} "
            f"p99={_fmt_us(t.get('p99_us', 0.0))} tok_s={tok_s:.1f} "
            f"live={g.get('serve.live_slots', 0)} "
            f"waiting={g.get('serve.waiting', 0)} "
            f"traces={g.get('serve.traces', 0)}")

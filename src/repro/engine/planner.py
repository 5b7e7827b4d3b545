"""Plan selection for the sorting engine: cache → table → heuristic.

A ``Plan`` fixes every degree of freedom of one engine op: the variant
(ref / banked / Pallas kernel / XLA) and its tile parameters (``w``,
``block_out``, ``chunk``, segment capacity ``cap``). Resolution order for a
call (DESIGN.md §3):

1. explicit ``plan=`` / ``variant=`` from the caller,
2. the in-process plan cache (autotuned or previously resolved),
3. the persisted plan table (JSON, ``load_plans``/``save_plans``),
4. the backend heuristic.

Shapes are bucketed to powers of two, so one autotuned entry serves the whole
neighbourhood of sizes — the plan cache stays tiny and every ``jax.jit``
retrace reuses the same static parameters.

``autotune(op, *example_args)`` measures every registered variant (times a
small parameter grid) on the example workload, installs the winner in the
cache, and returns it. ``save_plans``/``load_plans`` round-trip the table
through JSON so a fleet can ship pre-tuned tables per backend.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.core.flims import next_pow2 as _next_pow2
from repro.engine import registry


@dataclasses.dataclass(frozen=True)
class Plan:
    variant: str
    w: int = 32
    block_out: int = 1024
    chunk: int = 256
    cap: int = 0           # per-segment capacity; 0 = derive from shape
    levels: int = 1        # tree levels fused per pass (MergeSchedule)
    tie: str = "b"         # selector tie policy: 'b' (alg.1) | 'skew' (alg.2)
    # external (out-of-core) sort only — engine/external.py, DESIGN.md §8
    tile_elems: int = 0    # phase-1 run length; 0 = backend default
    fan_in: int = 0        # runs merged per phase-2 pass; 0 = default (8)
    # sharded (cross-device) ops only — engine/sharded.py, DESIGN.md §6
    cap_factor: int = 4    # base bucket cap = cap_factor * n_local / n_dev
    splitter: str = "hist"  # splitter policy: 'regular' | 'hist'
    retries: int = 2       # cap-doubling rungs in the overflow-recovery ladder

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Plan":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    def replace(self, **kw) -> "Plan":
        return dataclasses.replace(self, **kw)


Key = Tuple[str, str, str, int, int, str]


def backend_name() -> str:
    return jax.default_backend()


def plan_key(op: str, *, n: int, dtype, backend: Optional[str] = None,
             segments: int = 0, axis: str = "") -> Key:
    """Bucketed cache key: op, backend, dtype, pow2(n), pow2(segments), and —
    for the sharded ops — the mesh axis name (``segments`` then carries the
    device count P along that axis)."""
    return (op, backend or backend_name(), str(jax.numpy.dtype(dtype)),
            _next_pow2(n), _next_pow2(segments) if segments else 0, axis)


def _key_str(key: Key) -> str:
    op, backend, dtype, n, s, axis = key
    base = f"{op}|{backend}|{dtype}|n{n}|s{s}"
    return base + (f"|a{axis}" if axis else "")


def _key_parse(s: str) -> Key:
    parts = s.split("|")
    op, backend, dtype, n, seg = parts[:5]
    axis = parts[5][1:] if len(parts) > 5 else ""   # pre-PR4 tables: 5 fields
    return (op, backend, dtype, int(n[1:]), int(seg[1:]), axis)


# --------------------------------------------------------------------------
# heuristics: sensible defaults per backend with no measurements at all
# --------------------------------------------------------------------------

def heuristic_plan(op: str, key: Key) -> Plan:
    _, backend, _, n, _, _ = key
    w = max(8, min(128, _next_pow2(max(n, 1) // 64)))
    block_out = max(w, min(4096, _next_pow2(max(n, 1)) // 8 or w))
    if backend == "tpu":
        table = {"sort": "pallas", "merge": "pallas", "argsort": "pallas",
                 "topk": "flims", "segment_merge": "pallas",
                 "segment_sort": "pallas_two_phase",
                 "segment_argsort": "pallas_two_phase",
                 "merge_runs": "tree_pallas",
                 "external_sort": "stream_pallas",
                 "sharded_sort": "tree_pallas", "sharded_topk": "flims",
                 "moe_route": "fused", "moe_route_ep": "fused",
                 "sample_topp": "flims", "sample_minp": "flims"}
        # fuse two tree levels per pass by default on the real hardware
        levels = 2 if op in ("merge_runs", "sharded_sort",
                             "external_sort") else 1
    else:
        # CPU/GPU interpret-mode kernels are for correctness, not speed:
        # serve the hot path from XLA, keep merge on the banked dataflow.
        table = {"sort": "xla", "merge": "banked", "argsort": "xla",
                 "topk": "xla", "segment_merge": "xla",
                 "segment_sort": "xla", "segment_argsort": "xla",
                 "merge_runs": "xla", "external_sort": "xla",
                 "sharded_sort": "xla", "sharded_topk": "xla",
                 "moe_route": "xla", "moe_route_ep": "xla",
                 "sample_topp": "xla", "sample_minp": "xla"}
        levels = 1
    return Plan(variant=table[op], w=w, block_out=block_out, chunk=256,
                levels=levels)


# --------------------------------------------------------------------------
# planner: cache + persistence + autotune
# --------------------------------------------------------------------------

class Planner:
    def __init__(self):
        self._plans: Dict[Key, Plan] = {}
        self._infeasible: Dict[Key, set] = {}

    # -- cache ------------------------------------------------------------
    def lookup(self, key: Key) -> Optional[Plan]:
        return self._plans.get(key)

    def put(self, key: Key, plan: Plan) -> None:
        self._plans[key] = plan

    def clear(self) -> None:
        self._plans.clear()
        self._infeasible.clear()

    def infeasible_for(self, key: Key) -> frozenset:
        """Candidate plans recorded as unable to serve this shape bucket."""
        return frozenset(self._infeasible.get(key, ()))

    # -- quarantine (guard.fallback): failed variants sit out the session --
    def quarantine(self, key: Key, plan: "Plan") -> None:
        """Record ``plan`` as unable to serve ``key`` for the session: the
        autotuner skips it as known-infeasible and the fallback ladder
        skips its rung without paying for another failure."""
        self._infeasible.setdefault(key, set()).add(plan)

    def is_quarantined(self, key: Key, variant: str) -> bool:
        return any(p.variant == variant for p in self._infeasible.get(key, ()))

    def clear_quarantine(self, variant: Optional[str] = None) -> None:
        """Drop quarantine/infeasibility records — all of them, or only the
        plans naming ``variant`` (used when an injected variant stub is
        deregistered)."""
        if variant is None:
            self._infeasible.clear()
            return
        for key in list(self._infeasible):
            kept = {p for p in self._infeasible[key] if p.variant != variant}
            if kept:
                self._infeasible[key] = kept
            else:
                del self._infeasible[key]

    def plan_for(self, op: str, *, n: int, dtype, segments: int = 0,
                 backend: Optional[str] = None) -> Plan:
        key = plan_key(op, n=n, dtype=dtype, backend=backend,
                       segments=segments)
        hit = self._plans.get(key)
        if hit is not None:
            return hit
        plan = heuristic_plan(op, key)
        self._plans[key] = plan          # resolve once per bucket
        return plan

    # -- persistence ------------------------------------------------------
    def to_table(self) -> dict:
        return {_key_str(k): p.to_dict() for k, p in self._plans.items()}

    def from_table(self, table: dict) -> None:
        for ks, pd in table.items():
            self._plans[_key_parse(ks)] = Plan.from_dict(pd)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"version": 1, "plans": self.to_table()}, f, indent=2,
                      sort_keys=True)

    def load(self, path: str) -> None:
        with open(path) as f:
            doc = json.load(f)
        self.from_table(doc.get("plans", {}))

    # -- autotune ---------------------------------------------------------
    def autotune(self, op: str, *example_args, key: Optional[Key] = None,
                 run: Optional[Callable] = None, repeats: int = 3,
                 candidates=None) -> Plan:
        """Measure candidate plans on an example workload; cache the winner.

        ``run(plan, *example_args)`` executes the op under a plan — the
        engine api passes its own dispatcher. Candidates default to every
        registered variant crossed with a small parameter grid.
        """
        if run is None:
            from repro.engine import api
            run = lambda plan, *a: api.run_op(op, plan, *a)
        if key is None:
            from repro.engine import api
            key = api.infer_key(op, *example_args)
        if candidates is None:
            candidates = candidate_plans(op, key)
        from repro import obs
        bad = self._infeasible.setdefault(key, set())
        best, best_t = None, float("inf")
        with obs.span(f"autotune.{op}"):
            for plan in candidates:
                if plan in bad:          # known-infeasible: skip, don't retry
                    obs.event("autotune.candidate", op=op, key=_key_str(key),
                              variant=plan.variant, status="known_infeasible")
                    continue
                try:
                    t = _time(lambda: run(plan, *example_args),
                              repeats=repeats)
                except Exception as e:
                    # a raising candidate (e.g. a Pallas lowering failure at
                    # this shape) is recorded as infeasible; the tune carries
                    # on with the remaining candidates instead of aborting.
                    bad.add(plan)
                    obs.inc("autotune.infeasible")
                    obs.event("autotune.candidate", op=op, key=_key_str(key),
                              variant=plan.variant, status="infeasible",
                              plan=plan.to_dict(),
                              error=f"{type(e).__name__}: {e}"[:200])
                    continue
                obs.inc("autotune.measured")
                obs.event("autotune.candidate", op=op, key=_key_str(key),
                          variant=plan.variant, status="ok", us=t * 1e6,
                          plan=plan.to_dict())
                if t < best_t:
                    best, best_t = plan, t
        if best is None:
            best = heuristic_plan(op, key)
            obs.event("autotune.winner", op=op, key=_key_str(key),
                      variant=best.variant, source="heuristic_fallback")
        else:
            obs.event("autotune.winner", op=op, key=_key_str(key),
                      variant=best.variant, us=best_t * 1e6,
                      plan=best.to_dict())
        obs.inc("autotune.runs")
        self._plans[key] = best
        return best


def candidate_plans(op: str, key: Key):
    """Small per-op search grid over the registered variants."""
    _, _, _, n, _, _ = key
    out = []
    for variant in registry.variants(op):
        if op == "merge_runs":
            # the MergeSchedule grid: fused-pass depth is the key dof
            if variant == "tree_pallas":
                out.extend(Plan(variant, w=32, levels=lv)
                           for lv in (1, 2, 3))
            else:
                out.append(Plan(variant, w=32))
        elif op == "sharded_sort":
            # dofs: local-reduction executor (x fused depth) and splitter
            # policy — cap_factor/retries stay at their contract defaults
            for splitter in ("regular", "hist"):
                if variant == "tree_pallas":
                    out.extend(Plan(variant, w=32, levels=lv,
                                    splitter=splitter) for lv in (1, 2))
                else:
                    out.append(Plan(variant, w=32, splitter=splitter))
        elif op == "external_sort":
            # the two out-of-core dofs: phase-1 tile size x phase-2 fan-in
            n2 = _next_pow2(max(n, 4))
            for tile in sorted({max(1024, n2 // 16), max(1024, n2 // 4)}):
                for fan in (4, 16):
                    out.append(Plan(variant, w=32, tile_elems=tile,
                                    fan_in=fan))
        elif op in ("merge", "segment_merge"):
            for w in (32, 128):
                for block_out in (1024, 4096):
                    out.append(Plan(variant, w=min(w, max(8, n)),
                                    block_out=block_out))
        elif op in ("sort", "argsort", "segment_sort", "segment_argsort"):
            for chunk in (256, 512):
                out.append(Plan(variant, w=32, chunk=chunk))
            if variant.endswith("two_phase"):
                # phase 2 is a MergeSchedule: also sweep the fused depth
                out.append(Plan(variant, w=32, chunk=256, levels=2))
        else:
            out.append(Plan(variant))
    return out


def _time(thunk: Callable[[], object], repeats: int = 3,
          warmup: int = 1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(thunk())
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


# module-level default planner (the in-process plan cache)
default_planner = Planner()

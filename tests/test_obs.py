"""repro.obs — the flight recorder (DESIGN.md §7).

Covers the PR's acceptance criterion end to end: enabling obs, running one
engine.sort autotune and one sharded_sort on skewed input, and reading a
single snapshot that shows plan-cache hit/miss counts, per-candidate
autotune timings (including infeasible candidates), the selected cap-ladder
rung, and per-variant span timings — plus the zero-overhead-when-disabled
contract.
"""
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import engine, obs


@pytest.fixture(autouse=True)
def clean_obs():
    """Every test starts disabled and empty, and leaves no global residue."""
    obs.disable()
    obs.reset()
    engine.default_planner.clear()
    yield
    obs.disable()
    obs.reset()
    engine.default_planner.clear()


def _mesh1():
    return jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:1])


class TestDisabledIsNoop:
    def test_nothing_recorded_while_disabled(self):
        obs.inc("x")
        obs.gauge("g", 3)
        obs.observe("t", 0.5)
        obs.event("k", a=1)
        with obs.span("s"):
            pass
        snap = obs.snapshot()
        assert snap["counters"] == {}
        assert snap["gauges"] == {}
        assert snap["timers"] == {}
        assert snap["events"] == []
        assert snap["spans"] == []
        assert snap["enabled"] is False

    def test_engine_ops_record_nothing_while_disabled(self):
        x = jnp.array(np.random.default_rng(0).integers(0, 99, 256), jnp.int32)
        engine.sort(x)
        engine.argsort(x)
        snap = obs.snapshot()
        assert snap["counters"] == {}
        assert snap["events"] == []

    def test_disable_stops_recording(self):
        obs.enable()
        obs.inc("a")
        obs.disable()
        obs.inc("a")
        assert obs.snapshot()["counters"] == {"a": 1}


class TestPlanCacheEvents:
    def test_miss_then_hit(self):
        obs.enable()
        x = jnp.array(np.random.default_rng(1).integers(0, 99, 512), jnp.int32)
        engine.sort(x)                       # cold: heuristic fallback
        engine.sort(x)                       # warm: cache hit
        snap = obs.snapshot()
        assert snap["counters"]["plan_cache.miss"] == 1
        assert snap["counters"]["plan_cache.fallback"] == 1
        assert snap["counters"]["plan_cache.hit"] >= 1
        sources = [e["data"]["source"] for e in snap["events"]
                   if e["kind"] == "plan.resolve"]
        assert "heuristic" in sources and "cache" in sources

    def test_explicit_plan_counts_pinned(self):
        obs.enable()
        x = jnp.arange(128, dtype=jnp.int32)
        engine.sort(x, plan=engine.Plan("xla"))
        snap = obs.snapshot()
        assert snap["counters"]["plan_cache.pinned"] == 1
        assert "plan_cache.miss" not in snap["counters"]

    def test_resolve_event_names_op_and_variant(self):
        obs.enable()
        x = jnp.arange(256, dtype=jnp.int32)
        engine.argsort(x)
        ev = [e for e in obs.snapshot()["events"]
              if e["kind"] == "plan.resolve"]
        assert ev and ev[0]["data"]["op"] == "argsort"
        assert ev[0]["data"]["variant"]


class TestAutotuneEvents:
    def test_per_candidate_events_including_infeasible(self):
        obs.enable()
        x = jnp.array(np.random.default_rng(2).integers(0, 99, 512), jnp.int32)
        plan = engine.autotune("sort", x, repeats=1,
                               candidates=[engine.Plan("xla"),
                                           engine.Plan("nope")])
        assert plan.variant == "xla"         # the only feasible candidate
        snap = obs.snapshot()
        cands = [e["data"] for e in snap["events"]
                 if e["kind"] == "autotune.candidate"]
        by_status = {c["status"]: c for c in cands}
        assert by_status["ok"]["variant"] == "xla"
        assert by_status["ok"]["us"] > 0
        assert by_status["infeasible"]["variant"] == "nope"
        assert "error" in by_status["infeasible"]
        winners = [e["data"] for e in snap["events"]
                   if e["kind"] == "autotune.winner"]
        assert winners and winners[0]["variant"] == "xla"
        assert snap["counters"]["autotune.measured"] >= 1
        assert snap["counters"]["autotune.infeasible"] == 1

    def test_known_infeasible_skip_is_an_event(self):
        obs.enable()
        x = jnp.arange(512, dtype=jnp.int32)
        cands = [engine.Plan("xla"), engine.Plan("nope")]
        engine.autotune("sort", x, repeats=1, candidates=cands)
        engine.autotune("sort", x, repeats=1, candidates=cands)
        statuses = [e["data"]["status"] for e in obs.snapshot()["events"]
                    if e["kind"] == "autotune.candidate"]
        assert "known_infeasible" in statuses

    def test_autotune_span_timer(self):
        obs.enable()
        x = jnp.arange(256, dtype=jnp.int32)
        engine.autotune("sort", x, repeats=1,
                        candidates=[engine.Plan("xla")])
        timers = obs.snapshot()["timers"]
        assert "autotune.sort" in timers
        assert timers["autotune.sort"]["count"] == 1
        assert timers["autotune.sort"]["p50_us"] > 0


class TestVariantSpans:
    def test_engine_dispatch_records_per_variant_timers(self):
        obs.enable()
        x = jnp.array(np.random.default_rng(3).integers(0, 99, 512), jnp.int32)
        engine.sort(x, plan=engine.Plan("xla"))
        engine.sort(x, plan=engine.Plan("ref", chunk=128, w=16))
        timers = obs.snapshot()["timers"]
        assert "engine.sort.xla" in timers
        assert "engine.sort.ref" in timers
        assert timers["engine.sort.xla"]["count"] == 1

    def test_no_spans_while_disabled(self):
        x = jnp.arange(128, dtype=jnp.int32)
        engine.sort(x, plan=engine.Plan("xla"))
        assert obs.snapshot()["timers"] == {}


class TestShardedEvents:
    def test_rung_and_overflow_recorded(self):
        obs.enable()
        from repro.parallel.sharding import data_shard_1d
        mesh = _mesh1()
        x = np.random.default_rng(4).integers(-10**6, 10**6, 2048)
        res = engine.sharded_sort(data_shard_1d(
            jnp.array(x.astype(np.int32)), mesh), mesh)
        jax.block_until_ready(res.values)
        snap = obs.snapshot()
        plans = [e["data"] for e in snap["events"]
                 if e["kind"] == "sharded.plan"]
        assert plans and plans[0]["caps"]          # the cap ladder
        assert plans[0]["splitter"]
        execs = [e["data"] for e in snap["events"]
                 if e["kind"] == "sharded.exec"]
        assert execs, "sharded.exec debug-callback event missing"
        e0 = execs[0]
        assert e0["rung"] >= 0 and e0["cap"] >= e0["need"]
        assert e0["overflow"] is False
        assert snap["counters"]["sharded.ok"] >= 1

    def test_toggling_obs_retraces_the_callback(self):
        """The record flag is a static jit arg: runs traced while disabled
        must not leak events, and enabling afterwards must still record."""
        from repro.parallel.sharding import data_shard_1d
        mesh = _mesh1()
        x = jnp.array(np.arange(1024, dtype=np.int32)[::-1].copy())
        xs = data_shard_1d(x, mesh)
        jax.block_until_ready(engine.sharded_sort(xs, mesh).values)
        assert obs.snapshot()["events"] == []      # disabled: nothing
        obs.enable()
        jax.block_until_ready(engine.sharded_sort(xs, mesh).values)
        kinds = {e["kind"] for e in obs.snapshot()["events"]}
        assert "sharded.exec" in kinds


class TestScheduleEvents:
    def test_reduce_event_counts_passes(self):
        obs.enable()
        rng = np.random.default_rng(5)
        K, n = 8, 256
        runs = np.sort(rng.integers(-10**6, 10**6, (K, n)).astype(np.int32),
                       axis=1)[:, ::-1].reshape(-1)
        offs = np.arange(K + 1, dtype=np.int32) * n
        engine.merge_runs(jnp.array(runs), jnp.array(offs),
                          plan=engine.Plan("tree_vmapped", w=16))
        evs = [e["data"] for e in obs.snapshot()["events"]
               if e["kind"] == "schedule.reduce"]
        assert evs
        assert evs[0]["executor"] == "tree_vmapped"
        assert evs[0]["levels_total"] == 3         # log2(8) tree levels
        assert evs[0]["passes"] == 3               # one HBM trip per level
        assert evs[0]["hbm_trips_saved"] == 0


class TestSnapshotAndReport:
    def test_flagship_snapshot(self):
        """The acceptance criterion: one autotuned sort + one sharded sort
        on skewed input -> a single JSON-round-trippable snapshot with
        cache counts, per-candidate timings (incl. infeasible), the cap
        rung, and per-variant span timings."""
        obs.enable()
        rng = np.random.default_rng(6)
        x = jnp.array(rng.integers(0, 99, 1024), jnp.int32)
        engine.autotune("sort", x, repeats=1,
                        candidates=[engine.Plan("xla"),
                                    engine.Plan("nope")])
        engine.sort(x)                              # hits the tuned plan

        from repro.parallel.sharding import data_shard_1d
        mesh = _mesh1()
        skew = np.sort(rng.choice([1, 2, 3], 2048).astype(np.int32))
        res = engine.sharded_sort(data_shard_1d(jnp.array(skew), mesh), mesh)
        jax.block_until_ready(res.values)

        snap = json.loads(json.dumps(obs.snapshot()))   # JSON round-trip
        assert snap["counters"]["plan_cache.hit"] >= 1
        statuses = {e["data"]["status"] for e in snap["events"]
                    if e["kind"] == "autotune.candidate"}
        assert {"ok", "infeasible"} <= statuses
        execs = [e["data"] for e in snap["events"]
                 if e["kind"] == "sharded.exec"]
        assert execs and "rung" in execs[0]
        assert any(k.startswith("engine.") for k in snap["timers"])
        assert any(k.startswith("autotune.") for k in snap["timers"])

    def test_report_renders(self):
        obs.enable()
        obs.inc("plan_cache.hit", 3)
        with obs.span("engine.sort.xla"):
            pass
        obs.event("plan.resolve", op="sort", source="cache", variant="xla")
        text = obs.report()
        assert "plan_cache.hit" in text
        assert "engine.sort.xla" in text
        assert "plan.resolve" in text

    def test_event_hooks(self):
        obs.enable()
        seen = []
        obs.on("plan.resolve", seen.append)
        x = jnp.arange(64, dtype=jnp.int32)
        engine.sort(x)
        assert seen and seen[0]["kind"] == "plan.resolve"

    def test_snapshot_kind_filter(self):
        obs.enable()
        obs.event("a.b", x=1)
        obs.event("c.d", y=2)
        evs = obs.snapshot(kinds=("a.b",))["events"]
        assert [e["kind"] for e in evs] == ["a.b"]

    def test_reset_clears_but_keeps_enabled(self):
        obs.enable()
        obs.inc("x")
        obs.reset()
        snap = obs.snapshot()
        assert snap["counters"] == {} and snap["enabled"] is True


class TestSpans:
    def test_span_records_parent_call_and_self_time(self):
        obs.enable()
        with obs.span("outer"):
            with obs.span("inner"):
                with obs.span("leaf"):
                    pass
            with obs.span("inner"):
                pass
        with obs.span("next"):
            pass
        spans = {s["id"]: s for s in obs.snapshot()["spans"]}
        by = lambda name: [s for s in spans.values() if s["name"] == name]
        outer, nxt = by("outer")[0], by("next")[0]
        assert outer["parent"] is None and nxt["parent"] is None
        assert nxt["call"] != outer["call"]
        for s in by("inner") + by("leaf"):
            assert s["call"] == outer["call"]
            parent = spans[s["parent"]]
            assert parent["start_ns"] <= s["start_ns"] <= s["end_ns"] \
                <= parent["end_ns"]
        assert by("leaf")[0]["parent"] == by("inner")[0]["id"]
        own = obs.self_times(list(spans.values()))
        dur = lambda s: s["end_ns"] - s["start_ns"]
        assert own[outer["id"]] == dur(outer) - sum(
            dur(s) for s in by("inner"))
        inner = by("inner")[0]
        assert own[inner["id"]] == dur(inner) - dur(by("leaf")[0])
        assert obs.snapshot()["timers"]["inner"]["count"] == 2

    def test_engine_call_spans_each_layer_boundary(self):
        obs.enable()
        x = jnp.array(np.random.default_rng(7).integers(0, 9, 512), jnp.int32)
        engine.argsort(x, variant="xla")
        engine.argsort(x, variant="xla")
        spans = obs.snapshot()["spans"]
        roots = [s for s in spans if s["parent"] is None]
        assert [s["name"] for s in roots] == ["engine.argsort"] * 2
        assert roots[0]["call"] != roots[1]["call"]
        for root in roots:
            kids = [s["name"] for s in spans if s["parent"] == root["id"]]
            assert kids == ["engine.validate", "engine.plan", "engine.guard",
                            "engine.argsort.xla"]
            assert all(s["call"] == root["call"] for s in spans
                       if s["parent"] == root["id"])

    def test_nested_public_call_shares_the_call_id(self):
        obs.enable()
        x = jnp.array(np.random.default_rng(8).integers(0, 9, 256), jnp.int32)
        engine.sort(x, stable=True, variant="xla")
        spans = obs.snapshot()["spans"]
        assert len({s["call"] for s in spans}) == 1
        inner = next(s for s in spans if s["name"] == "engine.argsort")
        outer = next(s for s in spans if s["name"] == "engine.sort")
        assert inner["parent"] == outer["id"]

    def test_disabled_span_is_one_shared_noop(self):
        assert obs.span("a") is obs.span("b")
        with obs.span("a"):
            pass
        assert obs.snapshot()["spans"] == []

    def test_report_renders_the_span_tree(self):
        obs.enable()
        with obs.span("engine.sort"):
            with obs.span("engine.plan"):
                pass
        text = obs.report()
        assert "spans: 2 in 1 calls" in text
        assert re.search(r"\[call \d+\] engine\.sort ", text)
        assert re.search(r"\[call \d+\]   engine\.plan ", text)


class TestObsCompilesNoOtherProgram:
    """Turning obs on stages nothing into traced code."""

    @pytest.mark.parametrize("variant", ["xla", "fused"])
    def test_moe_route_jaxpr(self, variant):
        lg = jnp.asarray(np.random.default_rng(9).normal(size=(2, 64, 8)),
                         jnp.float32)
        f = lambda x: engine.moe_route(x, 2, 20, variant=variant)
        off = str(jax.make_jaxpr(f)(lg))
        obs.enable()
        on = str(jax.make_jaxpr(f)(lg))
        assert on == off

    def test_kernel_argsort_jaxpr(self):
        from repro.kernels.ops import kernel_argsort
        x = jnp.array(np.random.default_rng(10).integers(0, 9, 2048),
                      jnp.int32)
        f = lambda k: kernel_argsort(k, chunk=128, w=32, interpret=True)
        off = str(jax.make_jaxpr(f)(x))
        obs.enable()
        on = str(jax.make_jaxpr(f)(x))
        assert on == off

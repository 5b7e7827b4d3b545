"""Device ops named by engine stage, idle time by host span
(``benchmarks/chip/scopes.py``), on synthetic events and on the recorded
router trace (``data/route.xplane.pb.gz``, see ``test_chipbench_trace.py``).
"""
import gzip
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP.parents[1]))

from benchmarks.chip import scopes  # noqa: E402
from benchmarks.chip import trace as tr  # noqa: E402

ROUTE = CHIP / "tests" / "data" / "route.xplane.pb.gz"


@pytest.mark.parametrize("tf_op, stage, pass_", [
    ("jit(route_layers)/while/body/closed_call/repro.engine.moe_route.fused/"
     "jit(moe_route_pallas)/repro.kernels.route_fuse/flims_route_fuse/"
     "pallas_call:", "repro.kernels.route_fuse", None),
    ("jit(kernel_argsort)/repro.kernels.kernel_argsort/"
     "repro.schedule.pass3_L2/jit(merge_tree_runs_kv)/"
     "repro.kernels.merge_tree_kv/repro.kernels.merge_tree.corank/"
     "vmap()/while/body/add:", "repro.kernels.merge_tree.corank",
     "repro.schedule.pass3_L2"),
    ("jit(route_layers)/while/body/dynamic_update_slice:", None, None),
])
def test_stage_is_the_innermost_repro_scope(tf_op, stage, pass_):
    assert scopes.stage_of(tf_op) == stage
    assert scopes.pass_of(tf_op) == pass_


def test_wire_reader_finds_each_ops_metadata():
    meta = scopes.op_metadata(gzip.decompress(ROUTE.read_bytes()))
    assert list(meta) == ["/device:TPU:0"]
    fuse = [(k, m) for k, m in meta["/device:TPU:0"].items()
            if k[1].startswith("%flims_route_fuse.")]
    assert fuse
    for (program, _), m in fuse:
        assert program == 7394113867909586594
        assert m.source.endswith("src/repro/kernels/route_fuse.py:174")
        assert "/repro.kernels.route_fuse/" in m.tf_op


@pytest.fixture(scope="module")
def route():
    return scopes.load(str(ROUTE))


def test_route_ops_map_to_their_stage(route):
    ops = route.reduced.devices[0].ops
    assert len(route.stages[0]) == len(ops)
    names = {}
    for (_, _, op), stage in zip(ops, route.stages[0]):
        names.setdefault(op, set()).add(stage)
    assert names["jit_route_layers/flims_route_fuse"] == {
        "repro.kernels.route_fuse"}
    # the harness's stacking of the layers' results is not engine code
    assert names["jit_route_layers/dynamic_update_slice"] == {
        "jit_route_layers/unlabelled"}


def test_route_stage_seconds_are_the_busy_time(route):
    stages = route.stage_seconds()
    assert sum(stages.values()) == pytest.approx(route.reduced.busy_s,
                                                 rel=1e-6)
    assert max(stages, key=stages.get) == "repro.kernels.route_fuse"
    assert route.passes_per_call() == [0, 0, 0]        # no merge pass


def test_route_idle_is_named_by_runtime_events(route):
    idle = route.idle_by_host_span()
    total = route.reduced.window_s - route.reduced.busy_s
    assert sum(idle.values()) == pytest.approx(total, rel=1e-6)
    assert idle[tr.IN_PROGRAM] == pytest.approx(
        route.reduced.idle_by_activity()[tr.IN_PROGRAM])
    # the dispatch's own runtime work and the completion path on the
    # runtime's threads, each narrower than the chipbench span around it
    for name in ("PjitFunction(route_layers)", "DoEnqueueProgram",
                 "ReadSyncFlag", "tpu::System::Execute=>Done"):
        assert idle.get(name, 0) > 0, name
    named = route.reduced.idle_by_activity()
    assert idle.get("chipbench.dispatch", 0) < 0.1 * named["engine dispatch"]


def test_route_existing_readings_are_unchanged(route):
    """``scopes`` builds on ``trace.reduce`` and leaves its readings as
    they were (the values PR 12's reduction gives on this fixture)."""
    r = route.reduced
    assert r.busy_s == pytest.approx(0.049796112, abs=1e-9)
    assert r.window_s == pytest.approx(0.053698168, abs=1e-9)
    b = r.breakdown()
    assert dict(b["device_ops"]) == pytest.approx({
        "jit_route_layers/flims_route_fuse": 0.045872851,
        "jit_route_layers/dynamic_update_slice": 0.003658947,
        "jit_route_layers/dynamic-slice_bitcast_fusion": 0.000189302,
        "jit_route_layers/copy_bitcast_fusion": 6.8118e-05,
        "jit_route_layers/compare_bitcast_fusion": 4.804e-06,
        "jit_route_layers/while": 2.052e-06,
        "jit_route_layers/custom-call": 3.8e-08}, abs=1e-12)
    assert dict(b["idle_gaps"]) == pytest.approx({
        "block_until_ready": 0.00232422, "engine dispatch": 0.00150335,
        "between calls": 4.15e-05, "input rotation": 3.214e-05,
        tr.IN_PROGRAM: 8.46e-07}, abs=1e-12)


def test_pieces_go_to_the_first_class_then_the_newest_event():
    engine = [(10, 20, "engine.argsort"), (12, 14, "engine.plan")]
    runtime = [(0, 30, "PjitFunction(f)"), (15, 25, "DoEnqueueProgram")]
    bench = [(0, 40, "chipbench.call"), (0, 5, "chipbench.dispatch")]
    got = scopes._name_pieces([(2, 18), (26, 35), (38, 45)],
                              [engine, runtime, bench])
    assert got == {"PjitFunction(f)": 8 + 4, "engine.argsort": 2 + 1 + 3,
                   "engine.plan": 2, "chipbench.call": 5 + 2,
                   tr.BETWEEN: 5}


def test_op_walks_that_disagree_raise():
    ops = [(0.0, 5.0, "f/add"), (5.0, 9.0, "f/mul")]
    scopes._check_same_ops(0, [(0.0, "f/add"), (5.0, "f/mul")], ops)
    for seen in ([(0.0, "f/add")],                       # one op short
                 [(0.0, "f/mul"), (5.0, "f/add")],       # reordered
                 [(0.0, "f/add"), (6.0, "f/mul")]):      # another start
        with pytest.raises(ValueError):
            scopes._check_same_ops(0, seen, ops)


def _line(name, *events):
    evs = [SimpleNamespace(name=n, start_ns=s, duration_ns=d, stats=[])
           for n, s, d in events]
    return SimpleNamespace(name=name, events=evs)


def test_idle_is_named_only_by_threads_that_work_on_the_calls():
    host = SimpleNamespace(name="/host:CPU", lines=[
        _line("python3", ("chipbench.wait", 0, 100)),
        _line("main/1", ("PJRT_LoadedExecutable_Execute", 0, 10),
              ("DeferredTpuAllocator::Allocate", 2, 5)),
        _line("queue/2", ("DoEnqueueProgram", 10, 5)),
        _line("futex/3", ("tpu::System::Execute=>Done", 90, 5),
              ("ReadSyncFlag", 50, 40)),
        _line("poller/4", ("SomeBackgroundPoll", 0, 100))])
    device = SimpleNamespace(name="/device:TPU:0", lines=[])
    lines = scopes._call_threads(SimpleNamespace(planes=[host, device]))
    names = {n for ln in lines for _, _, n, _ in ln}
    assert "SomeBackgroundPoll" not in names
    assert {"chipbench.wait", "DeferredTpuAllocator::Allocate",
            "DoEnqueueProgram", "ReadSyncFlag"} <= names


def test_plan_ms_is_the_call_less_its_dispatch():
    spans = [{"name": "engine.argsort", "call": 1, "start_ns": 0,
              "end_ns": 1_000_000},
             {"name": "engine.plan", "call": 1, "start_ns": 100_000,
              "end_ns": 200_000},
             {"name": "engine.argsort.pallas", "call": 1,
              "start_ns": 300_000, "end_ns": 900_000},
             {"name": "engine.argsort", "call": 2, "start_ns": 2_000_000,
              "end_ns": 2_500_000},
             {"name": "engine.argsort.pallas", "call": 2,
              "start_ns": 2_100_000, "end_ns": 2_400_000}]
    assert scopes.plan_ms(spans, "argsort") == pytest.approx(0.3)
    assert scopes.plan_ms(spans, "sort") is None
    assert scopes.plan_ms([], "argsort") is None


# --------------------------------------------------------------------------
# a small argsort recorded on a TPU v5e by ``record_argsort_trace.py``:
# two eager ``engine.argsort`` calls of 2^15 int32 keys with heavy ties in
# the benchmark's closed loop, ``repro.obs`` on
# --------------------------------------------------------------------------

ARGSORT = CHIP / "tests" / "data" / "argsort.xplane.pb.gz"
TOP = "repro.kernels.kernel_argsort"


@pytest.fixture(scope="module")
def argsort():
    return scopes.load(str(ARGSORT))


def test_argsort_every_labelled_op_has_a_stage_below_the_top(argsort):
    meta = scopes.op_metadata(gzip.decompress(ARGSORT.read_bytes()))
    labelled = [m.tf_op for m in meta["/device:TPU:0"].values() if m.tf_op]
    assert labelled
    for tf_op in labelled:
        stage = scopes.stage_of(tf_op)
        assert stage and stage.startswith("repro.") and stage != TOP, tf_op
    assert TOP not in argsort.stages[0]
    stages = argsort.stage_seconds()
    busy = argsort.reduced.busy_s
    assert sum(stages.values()) == pytest.approx(busy, rel=1e-6)
    # the ops XLA leaves without a name stack (while, copies) are little
    unlabelled = sum(t for n, t in stages.items() if n.endswith(
        "/" + scopes.UNLABELLED))
    assert unlabelled < 0.01 * busy
    for stage in ("merge_tree.bank", "merge_tree.corank", "merge_tree.kernel",
                  "merge_tree.unpad", "segmented_merge.kernel",
                  "kernel_argsort.chunk_sort"):
        assert stages.get("repro.kernels." + stage, 0) > 0, stage


def test_argsort_counts_the_schedules_passes(argsort):
    """The pass scopes that launch a merge kernel, per call, are the
    passes ``schedule.reduce`` reports for the plan the chip resolved."""
    import jax
    import jax.numpy as jnp
    from repro import obs
    from repro.engine.planner import heuristic_plan
    from repro.kernels.ops import kernel_argsort

    n = 1 << 15
    plan = heuristic_plan("argsort", ("argsort", "tpu", "int32", n, 0, ""))
    assert plan.variant == "pallas"
    obs.reset()
    obs.enable()
    try:
        jax.make_jaxpr(lambda k: kernel_argsort(
            k, chunk=plan.chunk, w=plan.w, interpret=True))(
                jax.ShapeDtypeStruct((n,), jnp.int32))
        reduce = [e["data"] for e in obs.snapshot()["events"]
                  if e["kind"] == "schedule.reduce"]
    finally:
        obs.disable()
        obs.reset()
    assert [r["passes"] for r in reduce] == [4]
    assert argsort.passes_per_call() == [4, 4]


def test_argsort_per_call_readings(argsort):
    r = argsort.reduced
    assert len(r.calls) == 2
    corank = argsort.stage_share_per_call(".corank")
    assert len(corank) == 2 and all(0 < c < 100 for c in corank)
    plan = scopes.plan_ms(scopes.trace_spans(argsort), "argsort")
    dispatch = [1e-6 * (e - s) for s, e in r.spans["chipbench.dispatch"]]
    assert 0 < plan < min(dispatch)
    # every span of one engine call shares its call id
    spans = scopes.trace_spans(argsort)
    roots = [s for s in spans if s["name"] == "engine.argsort"]
    assert len(roots) == 2 and roots[0]["call"] != roots[1]["call"]
    for root in roots:
        kids = {s["name"] for s in spans if s["call"] == root["call"]}
        assert kids == {"engine.argsort", "engine.validate", "engine.plan",
                        "engine.guard", "engine.argsort.pallas"}


def test_argsort_idle_names_the_engine_spans(argsort):
    idle = argsort.idle_by_host_span()
    total = argsort.reduced.window_s - argsort.reduced.busy_s
    assert sum(idle.values()) == pytest.approx(total, rel=1e-6)
    for name in ("engine.argsort.pallas", "engine.plan", "engine.guard"):
        assert idle.get(name, 0) > 0, name


def test_argsort_existing_readings_are_unchanged(argsort):
    """The values PR 12's ``trace.reduce`` gives on this fixture."""
    r = argsort.reduced
    assert r.busy_s == pytest.approx(0.043955401, abs=1e-9)
    assert r.window_s == pytest.approx(0.04639414, abs=1e-9)
    assert r.busy_per_call() == pytest.approx([0.021977921, 0.02197748],
                                              abs=1e-9)
    b = r.breakdown()
    assert [n for n, _ in b["device_ops"][:4]] == [
        "jit_kernel_argsort/fusion", "jit_kernel_argsort/flims_merge_tree",
        "jit_kernel_argsort/select_select_fusion",
        "jit_kernel_argsort/flims_segmented_merge_kv"]
    assert [t for _, t in b["device_ops"][:4]] == pytest.approx(
        [0.032138976, 0.009912838, 0.000695955, 0.000680957], abs=1e-12)
    assert dict(b["idle_gaps"]) == pytest.approx({
        "block_until_ready": 0.001291471, "engine dispatch": 0.001101242,
        "between calls": 2.763e-05, "input rotation": 1.206e-05,
        tr.IN_PROGRAM: 6.336e-06}, abs=1e-12)

"""The trace reduction, on synthetic intervals and on a small trace that
``run.py --trace 1 --keep-trace`` recorded on a TPU v5e
(``data/route.xplane.pb.gz``: three calls of the ``mixtral-route-16k``
cell in a 0.04 s window, each in ``chipbench.*`` host spans)."""
import gzip
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP.parents[1]))

from benchmarks.chip import trace as tr  # noqa: E402

FIXTURE = CHIP / "tests" / "data" / "route.xplane.pb.gz"


def test_union_merges_overlapping_and_touching_intervals():
    got = tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 12), (11, 11.5)])
    assert got == [(0, 4), (5, 7), (10, 12)]


def test_covered_and_complement():
    merged = [(0, 4), (5, 7), (10, 12)]
    assert tr.covered(merged, 3, 11) == 1 + 2 + 1
    assert tr.complement(merged, 3, 11) == [(4, 5), (7, 10)]
    assert tr.complement([], 1, 2) == [(1, 2)]


def test_self_time_subtracts_nested_operations():
    ev = [(0, 10, "while"), (1, 4, "fusion"), (5, 9, "fusion"),
          (11, 12, "copy")]
    assert tr.self_times(ev) == {"while": 3, "fusion": 7, "copy": 1}


def test_op_names_are_program_then_instruction():
    text = ("%flims_route_fuse.1 = (s32[1,256,128]) custom-call(f32[1]), "
            'custom_call_target="tpu_custom_call"')
    assert tr.op_name("jit__lambda(1365)", text) == \
        "jit__lambda/flims_route_fuse"
    assert tr.op_name("jit_kernel_argsort(69)", "%fusion.12.3 = s32[8]") == \
        "jit_kernel_argsort/fusion"


def _synthetic(shift):
    """A closed loop of two calls whose device clock runs ``shift`` ns
    behind the host's."""
    class Ev:
        def __init__(self, name, start, dur, **stats):
            self.name, self.start_ns, self.duration_ns = name, start, dur
            self.stats = list(stats.items())

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Profile:
        def __init__(self, planes):
            self.planes = planes

        def find_plane_with_name(self, name):
            return next((p for p in self.planes if p.name == name), None)

    host = Plane("/host:CPU", [
        Line("python", [Ev("chipbench.call", 0, 100),
                        Ev("chipbench.dispatch", 0, 20),
                        Ev("chipbench.wait", 20, 80),
                        Ev("chipbench.rotate", 100, 10),
                        Ev("chipbench.call", 110, 100),
                        Ev("chipbench.dispatch", 110, 30),
                        Ev("chipbench.wait", 140, 70)]),
        Line("queue", [Ev("DoEnqueueProgram", 15, 5, run_id=1,
                          device_ordinal=0),
                       Ev("DoEnqueueProgram", 130, 5, run_id=2,
                          device_ordinal=0)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_f(1)", 20 - shift, 60, run_id=1),
                             Ev("jit_f(1)", 150 - shift, 50, run_id=2)]),
        Line("XLA Ops", [Ev("%while.1 = ()", 20 - shift, 60),
                         Ev("%fusion.2 = ()", 30 - shift, 20),
                         Ev("%k.3 = () custom-call()", 150 - shift, 50)])])
    return Profile([host, dev])


@pytest.mark.parametrize("shift", [0, 1_400])
def test_device_clock_is_put_on_the_hosts(shift):
    r = tr.reduce(_synthetic(shift), [0])
    assert r.shift_ns[0] == shift
    assert r.window_s == pytest.approx(210e-9)
    assert r.busy_per_call() == pytest.approx([60e-9, 50e-9])
    assert r.busy_s == pytest.approx(110e-9)
    idle = r.idle_by_activity()
    # dispatch 0-20 and 110-140, wait 80-100, 140-150 and 200-210, rotate
    # 100-110 idle; nothing else
    assert idle == pytest.approx({"engine dispatch": 50e-9,
                                  "block_until_ready": 40e-9,
                                  "input rotation": 10e-9,
                                  "between calls": 0.0,
                                  tr.IN_PROGRAM: 0.0})
    ops = r.op_seconds()
    assert ops == pytest.approx({"jit_f/while": 40e-9, "jit_f/fusion": 20e-9,
                                 "jit_f/k": 50e-9})


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    xspace = gzip.decompress(FIXTURE.read_bytes())
    return tr.reduce(ProfileData.from_serialized_xspace(xspace), [0])


def test_recorded_trace_has_the_tpus_planes(recorded):
    assert len(recorded.devices) == 1
    assert len(recorded.calls) == 3
    assert all(n.startswith("jit_route_layers/") for n, _ in
               recorded.breakdown()["device_ops"])
    ops = recorded.op_seconds()
    # the Pallas kernel, named after itself, takes most of the device time
    assert max(ops, key=ops.get) == "jit_route_layers/flims_route_fuse"


def test_recorded_busy_is_the_union(recorded):
    ops = recorded.devices[0].ops
    a, b = recorded.window
    summed = sum(min(e, b) - max(s, a) for s, e, _ in ops if e > a and s < b)
    assert recorded.busy_s * 1e9 < summed          # nested ops overlap
    assert recorded.busy_s * 1e9 == pytest.approx(
        tr.covered(tr.union((s, e) for s, e, _ in ops), a, b))
    assert 0 < recorded.busy_s <= recorded.window_s
    # closed loop: every call's device work lies inside its host interval
    assert sum(recorded.busy_per_call()) == pytest.approx(recorded.busy_s,
                                                          rel=1e-6)


def test_recorded_gaps_are_named_by_the_host_span(recorded):
    idle = recorded.idle_by_activity()
    assert set(idle) == {"engine dispatch", "block_until_ready",
                         "input rotation", "between calls", tr.IN_PROGRAM}
    assert idle["engine dispatch"] > 0
    total = recorded.window_s - recorded.busy_s
    assert sum(idle.values()) == pytest.approx(total, rel=1e-6)
    assert sum(recorded.op_seconds().values()) == pytest.approx(
        recorded.busy_s, rel=1e-6)


def test_save_gz_keeps_the_newest_trace(tmp_path):
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(b"\x00trace bytes")
    dest = tmp_path / "kept.xplane.pb.gz"
    tr.save_gz(str(tmp_path), str(dest))
    assert gzip.decompress(dest.read_bytes()) == b"\x00trace bytes"
    tr.save_gz(str(tmp_path / "plugins" / "none"), str(tmp_path / "no.gz"))
    assert not (tmp_path / "no.gz").exists()

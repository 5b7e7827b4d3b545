"""Ahead-of-time compiles of the TPU-default Pallas variants for a TPU v5e.

The TPU compiler is installed with jax, so every variant the planner picks
on a TPU (``engine.planner.heuristic_plan``) is compiled here for a
described, unattached v5e at its real widths (w = 128 lanes, 4096-key
output blocks, the fused tree depths). Interpret mode runs the same
kernels but cannot see Mosaic's refusals — block tiling, unsupported
vector ops, fast-memory (VMEM/SMEM) limits — and this can, without a chip.
Each compile must contain a Mosaic kernel (``tpu_custom_call``): a variant
that quietly took an interpreted or XLA path would not.

Nothing runs, so nothing here says anything about results or time.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.engine import registry
from repro.engine.planner import heuristic_plan, plan_key
from repro.kernels.stream_merge import _CALL_STEPS


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def tpu_plan(op, n, dtype=jnp.int32, segments=0, **kw):
    plan = heuristic_plan(op, plan_key(op, n=n, dtype=dtype, backend="tpu",
                                       segments=segments))
    return plan.replace(**kw)


# (op, plan kwargs, build) — build(plan) -> (fn, [(shape, dtype), ...]);
# sizes are the smallest that give each variant its TPU-default geometry,
# plus those of chip_smoke.py where the limits that grow with the input
# bind: per-step tables past SMEM's 1 MiB (sort at 2^24 keys) and the
# stream merge's grid cut into calls of at most 4096 steps (2^27 keys),
# and the TPC-H SF1 LINEITEM row count the argsort benchmark cell sorts
N = 1 << 15


def _sort(dtype, n=N):
    def build(plan):
        fn = registry.get("sort", plan.variant)
        return (lambda x: fn(x, plan=plan, interpret=False)), [((n,), dtype)]
    return "sort", dict(n=n, dtype=dtype), build


def _argsort(n=N):
    def build(plan):
        fn = registry.get("argsort", plan.variant)
        return (lambda x: fn(x, plan=plan, descending=True, interpret=False),
                [((n,), jnp.int32)])
    return "argsort", dict(n=n), build


def _merge(n=N):
    def build(plan):
        fn = registry.get("merge", plan.variant)
        return (lambda a, b: fn(a, b, plan=plan, interpret=False),
                [((n // 2,), jnp.int32)] * 2)
    return "merge", dict(n=n), build


def _segment(op):
    def build(plan):
        fn = registry.get(op, plan.variant)
        kw = {} if op == "segment_sort" else {"descending": True}
        return (lambda v, o: fn(v, o, plan=plan, interpret=False, **kw),
                [((N,), jnp.int32), ((65,), jnp.int32)])
    return op, dict(n=N, segments=64, cap=1024), build


def _merge_runs(plan):
    fn = registry.get("merge_runs", plan.variant)
    return (lambda v, o: fn(v, o, plan=plan, descending=True,
                            interpret=False),
            [((N,), jnp.int32), ((65,), jnp.int32)])


def _external(kv, n=1 << 18, tile=1 << 15):
    def build(plan):
        fn = registry.get("external_sort", plan.variant)
        if kv:
            return (lambda x, r: fn(x, plan=plan, descending=True,
                                    interpret=False, ranks=r),
                    [((n,), jnp.float32), ((n,), jnp.int32)])
        return (lambda x: fn(x, plan=plan, descending=True, interpret=False),
                [((n,), jnp.float32)])
    return ("external_sort", dict(n=n, dtype=jnp.float32, tile_elems=tile,
                                  fan_in=8), build)


def _moe_route(T, E, k):
    from repro.models.moe import expert_capacity
    cap = expert_capacity(1.25, T, k, E)

    def build(plan):
        fn = registry.get("moe_route", plan.variant)
        return (lambda l: fn(l, k, cap, plan=plan, interpret=False),
                [((1, T, E), jnp.float32)])
    return "moe_route", dict(n=T * k, dtype=jnp.float32, segments=1), build


CASES = {
    "sort-int32": _sort(jnp.int32),
    "sort-float32": _sort(jnp.float32),
    "sort-int32-2^24": _sort(jnp.int32, 1 << 24),
    "argsort": _argsort(),
    "argsort-6001215": _argsort(6_001_215),
    "merge": _merge(),
    "merge-2x2^23": _merge(1 << 24),
    "segment_sort": _segment("segment_sort"),
    "segment_argsort": _segment("segment_argsort"),
    "merge_runs": ("merge_runs", dict(n=N, segments=64), _merge_runs),
    "external_sort-keys": _external(False),
    "external_sort-kv": _external(True),
    "external_sort-keys-2^27": _external(False, 1 << 27, 1 << 18),
    "moe_route-mixtral": _moe_route(16384, 8, 2),
    "moe_route-moonshot": _moe_route(16384, 64, 6),
}

EXPECTED = {"sort": "pallas", "argsort": "pallas", "merge": "pallas",
            "segment_sort": "pallas_two_phase",
            "segment_argsort": "pallas_two_phase",
            "merge_runs": "tree_pallas", "external_sort": "stream_pallas",
            "moe_route": "fused"}


@pytest.mark.parametrize("case", sorted(CASES))
def test_tpu_default_variant_compiles_for_v5e(case, one_chip):
    op, key_kw, build = CASES[case]
    key_kw = dict(key_kw)
    plan_kw = {k: key_kw.pop(k) for k in ("cap", "tile_elems", "fan_in")
               if k in key_kw}
    plan = tpu_plan(op, **key_kw, **plan_kw)
    assert plan.variant == EXPECTED[op]
    assert plan.w == 128
    if op in ("merge_runs", "external_sort"):
        assert plan.levels == 2                     # the fused tree depth
    fn, shapes = build(plan)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt
    if op == "external_sort":       # every pass in calls of <= 4096 steps
        n = shapes[0][0][0]
        passes = round(math.log(n // plan.tile_elems, plan.fan_in))
        steps = n // plan.block_out
        assert len(re.findall(
            r"%flims_stream_merge[.\d]* = [^\n]*custom-call\(", txt)) == \
            passes * -(-steps // _CALL_STEPS)


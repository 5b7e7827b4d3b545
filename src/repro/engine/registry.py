"""Variant registry: which implementations can serve each engine op.

Every op (``sort``, ``argsort``, ``merge``, ``topk``, ``moe_route``,
``segment_sort``, ``segment_merge``, ``segment_argsort``) has a family of
registered variants — the readable
reference formulations, the banked/windowed FLiMS dataflow, the Pallas
kernels, and plain XLA — all behind one calling convention:

    fn(*op_args, plan=Plan, interpret=bool) -> result

The planner picks among ``variants(op)`` by heuristic or autotuned plan
(DESIGN.md §3); callers can pin one explicitly via ``variant=``.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

_REGISTRY: Dict[str, Dict[str, Callable]] = {}


def register(op: str, name: str):
    def deco(fn):
        _REGISTRY.setdefault(op, {})[name] = fn
        return fn
    return deco


def unregister(op: str, name: str) -> None:
    """Remove a registered variant (chaos-suite stubs clean up with this;
    unknown names are a no-op)."""
    _REGISTRY.get(op, {}).pop(name, None)


def get(op: str, name: str) -> Callable:
    try:
        return _REGISTRY[op][name]
    except KeyError:
        raise KeyError(
            f"no variant {name!r} for op {op!r}; known: "
            f"{sorted(_REGISTRY.get(op, {}))}") from None


def call(op: str, name: str, *args, **kw):
    """Dispatch ``op`` to variant ``name`` under the engine's observability
    wrappers: a ``jax.named_scope`` labelling the variant in XLA profiler
    traces (always on — trace-time only), and, when ``repro.obs`` is
    enabled, an ``engine.<op>.<variant>`` span: for an eager call, the
    jitted program's dispatch and enqueue."""
    from repro import obs
    fn = get(op, name)
    label = f"repro.engine.{op}.{name}"
    if not obs.enabled():
        with jax.named_scope(label):
            return fn(*args, **kw)
    with obs.span(f"engine.{op}.{name}"), jax.named_scope(label):
        return fn(*args, **kw)


def variants(op: str):
    return tuple(sorted(_REGISTRY.get(op, {})))


def ops():
    return tuple(sorted(_REGISTRY))


# --------------------------------------------------------------------------
# merge: two sorted 1-D arrays -> one sorted array (descending)
# --------------------------------------------------------------------------

@register("merge", "ref")
def _merge_ref(a, b, *, plan, interpret):
    from repro.core.flims import flims_merge_ref
    return flims_merge_ref(a, b, plan.w, tie=plan.tie)


@register("merge", "banked")
def _merge_banked(a, b, *, plan, interpret):
    from repro.core.flims import flims_merge_banked
    return flims_merge_banked(a, b, plan.w, tie=plan.tie)


@register("merge", "pallas")
def _merge_pallas(a, b, *, plan, interpret):
    from repro.kernels.flims_merge import flims_merge_pallas
    return flims_merge_pallas(a, b, w=plan.w, block_out=plan.block_out,
                              interpret=interpret)


# --------------------------------------------------------------------------
# sort: full descending sort of a 1-D array
# --------------------------------------------------------------------------

@register("sort", "ref")
def _sort_ref(x, *, plan, interpret):
    from repro.core.mergesort import flims_sort
    return flims_sort(x, chunk=plan.chunk, w=plan.w)


@register("sort", "pallas")
def _sort_pallas(x, *, plan, interpret):
    from repro.kernels.ops import kernel_sort
    return kernel_sort(x, chunk=plan.chunk, w=plan.w, interpret=interpret)


@register("sort", "xla")
def _sort_xla(x, *, plan, interpret):
    return jnp.sort(x, descending=True)


# --------------------------------------------------------------------------
# argsort: stable permutation ordering keys (1-D, or 2-D row-wise)
# --------------------------------------------------------------------------

@register("argsort", "flims")
def _argsort_flims(keys, *, plan, descending, interpret):
    from repro.core.mergesort import flims_argsort
    fn = lambda k: flims_argsort(k, chunk=plan.chunk, w=plan.w,
                                 descending=descending)
    if keys.ndim == 2:
        return jax.vmap(fn)(keys)
    return fn(keys)


@register("argsort", "pallas")
def _argsort_pallas(keys, *, plan, descending, interpret):
    from repro.kernels.ops import kernel_argsort
    fn = lambda k: kernel_argsort(k, chunk=plan.chunk, w=plan.w,
                                  descending=descending, interpret=interpret)
    if keys.ndim == 2:
        return jax.vmap(fn)(keys)
    return fn(keys)


@register("argsort", "xla")
def _argsort_xla(keys, *, plan, descending, interpret):
    return jnp.argsort(keys, axis=-1, stable=True,
                       descending=descending).astype(jnp.int32)


# --------------------------------------------------------------------------
# topk: (values, indices) of the k largest along the trailing axis
# --------------------------------------------------------------------------

@register("topk", "flims")
def _topk_flims(x, k, *, plan, interpret, values=None):
    from repro.core.topk import flims_topk
    return flims_topk(x, k, values=values)


@register("topk", "xla")
def _topk_xla(x, k, *, plan, interpret, values=None):
    vals, idx = lax.top_k(x, k)
    if values is None:
        return vals, idx
    pay = jax.tree.map(lambda v: jnp.take_along_axis(v, idx, axis=-1), values)
    return vals, idx, pay


# --------------------------------------------------------------------------
# sample_topp / sample_minp: token sampling as a thin mask over the
# sorted-prefix-sum of the stable KV argsort (DESIGN.md §10) — the variant
# names the sort that produces the descending prefix; the nucleus/min-p
# cut and the Gumbel-max draw are shared elementwise math, so variants
# agree bit-for-bit (stable sorts yield identical permutations)
# --------------------------------------------------------------------------

def _sample_sorted_prefix(key, logits, perm, *, temperature, top_p, min_p):
    from repro.serve.sampler import SamplingState, sorted_prefix_sample
    state = SamplingState.full(logits.shape[0], temperature=temperature,
                               top_p=top_p, min_p=min_p)
    svals = jnp.take_along_axis(logits, perm, axis=-1)
    return sorted_prefix_sample(key, svals, perm, state)


def _full_sort_perm(variant, logits, plan, interpret):
    if variant == "flims":
        from repro.core.mergesort import flims_argsort
        fn = lambda row: flims_argsort(row, chunk=plan.chunk, w=plan.w,
                                       descending=True)
        return jax.vmap(fn)(logits)
    return jnp.argsort(logits, axis=-1, stable=True,
                       descending=True).astype(jnp.int32)


def _sample_topp_with(variant):
    def fn(key, logits, p, *, plan, temperature=1.0, interpret):
        perm = _full_sort_perm(variant, logits, plan, interpret)
        return _sample_sorted_prefix(key, logits, perm,
                                     temperature=temperature, top_p=p,
                                     min_p=0.0)
    return fn


def _sample_minp_with(variant):
    def fn(key, logits, mp, *, plan, temperature=1.0, interpret):
        perm = _full_sort_perm(variant, logits, plan, interpret)
        return _sample_sorted_prefix(key, logits, perm,
                                     temperature=temperature, top_p=1.0,
                                     min_p=mp)
    return fn


for _v in ("flims", "xla"):
    register("sample_topp", _v)(_sample_topp_with(_v))
    register("sample_minp", _v)(_sample_minp_with(_v))


# --------------------------------------------------------------------------
# moe_route: fused MoE routing — logits to permuted capacity slabs
# --------------------------------------------------------------------------

@register("moe_route", "fused")
def _moe_route_fused(logits, k, capacity, *, plan, interpret):
    from repro.kernels.route_fuse import moe_route_pallas
    return moe_route_pallas(logits, k, capacity, interpret=interpret)


@register("moe_route", "xla")
def _moe_route_xla(logits, k, capacity, *, plan, interpret):
    from repro.kernels.route_fuse import moe_route_xla
    return moe_route_xla(logits, k, capacity)


# --------------------------------------------------------------------------
# segment_merge: ragged batch of 2-way merges
# --------------------------------------------------------------------------

@register("segment_merge", "pallas")
def _segment_merge_pallas(a, ao, b, bo, *, plan, interpret):
    from repro.kernels.segmented_merge import segmented_merge_pallas
    return segmented_merge_pallas(a, ao, b, bo, w=plan.w,
                                  block_out=plan.block_out,
                                  interpret=interpret)


@register("segment_merge", "xla")
def _segment_merge_xla(a, ao, b, bo, *, plan, interpret):
    from repro.engine.segments import segment_merge_ref
    return segment_merge_ref(a, ao, b, bo)


# --------------------------------------------------------------------------
# segment_sort: ragged batch of full sorts
# --------------------------------------------------------------------------

@register("segment_sort", "pallas_fused")
def _segment_sort_fused(values, offsets, *, plan, interpret):
    from repro.kernels.segmented_merge import segment_sort_pallas
    return segment_sort_pallas(values, offsets, cap=plan.cap,
                               interpret=interpret)


@register("segment_sort", "pallas_two_phase")
def _segment_sort_two_phase(values, offsets, *, plan, interpret):
    from repro.kernels.segmented_merge import segment_sort_two_phase
    return segment_sort_two_phase(values, offsets, cap=plan.cap,
                                  chunk=min(plan.chunk, plan.cap), w=plan.w,
                                  levels=plan.levels, interpret=interpret)


@register("segment_sort", "xla")
def _segment_sort_xla(values, offsets, *, plan, interpret):
    from repro.engine.segments import segment_sort_ref
    return segment_sort_ref(values, offsets, cap=plan.cap)


# --------------------------------------------------------------------------
# segment_argsort: ragged batch of stable local argsorts (rank-lane kernels)
# --------------------------------------------------------------------------

@register("segment_argsort", "pallas_fused")
def _segment_argsort_fused(keys, offsets, *, plan, descending, interpret):
    from repro.kernels.segmented_merge import segment_argsort_pallas
    return segment_argsort_pallas(keys, offsets, cap=plan.cap,
                                  descending=descending, interpret=interpret)


@register("segment_argsort", "pallas_two_phase")
def _segment_argsort_two_phase(keys, offsets, *, plan, descending, interpret):
    from repro.kernels.segmented_merge import segment_argsort_two_phase
    return segment_argsort_two_phase(keys, offsets, cap=plan.cap,
                                     chunk=min(plan.chunk, plan.cap),
                                     w=plan.w, descending=descending,
                                     levels=plan.levels, interpret=interpret)


@register("segment_argsort", "xla")
def _segment_argsort_xla(keys, offsets, *, plan, descending, interpret):
    from repro.engine.segments import segment_argsort_ref
    return segment_argsort_ref(keys, offsets, cap=plan.cap,
                               descending=descending)


# --------------------------------------------------------------------------
# merge_runs: K sorted runs (ragged, contiguous) reduce to one — the
# MergeSchedule executors behind one op (DESIGN.md §5)
# --------------------------------------------------------------------------

def _merge_runs_with(variant):
    def fn(keys, offsets, *, plan, descending, interpret):
        from repro.engine.schedule import MergeSchedule, merge_runs
        sched = MergeSchedule.from_plan(plan, variant=variant)
        return merge_runs(keys, offsets, schedule=sched,
                          descending=descending, interpret=interpret)
    return fn


for _v in ("xla", "tree_vmapped", "tree_pallas", "stream_pallas",
           "stream_xla"):
    register("merge_runs", _v)(_merge_runs_with(_v))


# --------------------------------------------------------------------------
# external_sort: the TopSort two-phase out-of-core sort — the variant names
# both phase-1 run formation (Pallas chunk+tree vs XLA row sort) and the
# phase-2 streaming executor (DESIGN.md §8)
# --------------------------------------------------------------------------

def _external_sort_with(variant):
    def fn(keys, *, plan, descending, interpret, ranks=None):
        from repro.engine.external import run_external_sort
        return run_external_sort(keys, plan=plan.replace(variant=variant),
                                 descending=descending, ranks=ranks,
                                 interpret=interpret)
    return fn


for _v in ("xla", "stream_pallas"):
    register("external_sort", _v)(_external_sort_with(_v))


# --------------------------------------------------------------------------
# sharded_sort / sharded_topk: cross-device sample sort and top-k — the
# variant names the local K-way reduction executor (sharded_sort) or the
# local top-k formulation (sharded_topk); splitter policy, cap_factor and
# the overflow-recovery retries ride the plan (DESIGN.md §6)
# --------------------------------------------------------------------------

def _sharded_sort_with(executor):
    def fn(x, mesh, axis, *, plan, interpret, payload=None):
        from repro.engine.sharded import run_sharded_sort
        return run_sharded_sort(x, mesh, axis, payload=payload,
                                plan=plan.replace(variant=executor))
    return fn


for _v in ("xla", "tree_vmapped", "tree_pallas"):
    register("sharded_sort", _v)(_sharded_sort_with(_v))


def _sharded_topk_with(variant):
    def fn(x, k, mesh, axis, *, plan, interpret, payload=None):
        from repro.engine.sharded import run_sharded_topk
        return run_sharded_topk(x, k, mesh, axis, payload=payload,
                                plan=plan.replace(variant=variant))
    return fn


for _v in ("flims", "xla"):
    register("sharded_topk", _v)(_sharded_topk_with(_v))


# --------------------------------------------------------------------------
# moe_route_ep: expert-parallel routing — the variant names the LOCAL
# per-shard route executor (fused megakernel vs unfused XLA); the exchange
# and owner-side merge are variant-independent (DESIGN.md §9)
# --------------------------------------------------------------------------

def _moe_route_ep_with(local):
    def fn(logits, k, capacity, mesh, axis, *, plan, interpret):
        from repro.engine.sharded import run_moe_route_ep
        return run_moe_route_ep(logits, k, capacity, mesh, axis,
                                plan=plan.replace(variant=local))
    return fn


for _v in ("fused", "xla"):
    register("moe_route_ep", _v)(_moe_route_ep_with(_v))

"""`repro.engine` — the production entry point for every sorting workload.

One facade over the whole FLiMS stack: full sorts, stable argsorts, 2-way
merges, top-k, and — the ragged-batch capability — ``segment_sort`` /
``segment_merge`` / ``segment_argsort`` over flat arrays described by segment
offsets (the MoE-dispatch / ragged-sampler shape). Each call resolves a
``Plan`` (variant + tile parameters) through the planner's cache → table →
heuristic chain; ``autotune`` measures the registered variants on an example
workload and installs the winner. See DESIGN.md §3-§4.

Payload lanes are first-class: ``sort`` / ``merge`` / ``segment_sort`` take
``values=`` (a pytree of payload arrays carried with the keys) and
``stable=`` (paper algorithm 3 tie semantics), ``topk`` takes ``values=``,
and ``argsort`` / ``segment_argsort`` return the stable permutation itself.

    from repro import engine
    y     = engine.sort(x)                       # descending
    k, v  = engine.sort(x, values=v)             # stable key/value sort
    perm  = engine.argsort(keys, descending=False)
    m     = engine.merge(a, b)
    v, i  = engine.topk(logits, 16)
    s     = engine.segment_sort(values, offsets) # ragged batch, one kernel
    perm  = engine.segment_argsort(keys, offsets)  # local stable perms
    m     = engine.merge_runs(keys, run_offsets)   # K sorted runs -> one
    tok   = engine.sample_topp(key, logits, 0.9) # nucleus over the KV sort
    tok   = engine.sample_minp(key, logits, 0.1) # min-p over the same prefix
    res   = engine.sharded_sort(xs, mesh)        # mesh-sharded sample sort
    v, i  = engine.sharded_topk(xs, 16, mesh)    # global top-k on the mesh
    r     = engine.moe_route(logits, k=2, capacity=64)  # fused MoE routing:
    #       softmax+top-k+stable expert sort+capacity cut, one megakernel
    rs    = engine.moe_route_ep(logits, 2, 64, mesh)    # expert-parallel
    plan  = engine.autotune("segment_sort", values, offsets)
    engine.save_plans("plans.json")
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.engine import registry, segments
from repro.engine.planner import (Plan, _key_str, default_planner, plan_key,
                                  heuristic_plan)
from repro.engine.schedule import MergeSchedule, default_interpret as _interpret
from repro.guard import validate as _validate
from repro.guard import verify as _verify

__all__ = [
    "sort", "argsort", "merge", "topk", "segment_sort", "segment_merge",
    "segment_argsort", "merge_runs", "external_sort", "sample_topp",
    "sample_minp", "sharded_sort",
    "sharded_topk", "moe_route", "moe_route_ep", "RouteResult",
    "autotune", "save_plans", "load_plans", "clear_plans",
    "Plan", "MergeSchedule",
]

#: rank/offset lanes are int32 throughout the engine (PR 6's reduce_rows
#: overflow was this class of bug) — reject sizes the lanes cannot index.
_LANE_LIMIT = _validate.LANE_LIMIT

# boundary guards live in repro.guard.validate; this alias keeps the
# engine-internal call sites (and their history) readable
_check_lane_width = _validate.check_lane_width


def _gcall(op: str, plan: Plan, *args, **kw):
    """Registry dispatch under the guard layer's variant fallback ladder
    (guard.fallback, DESIGN.md §11): infrastructure failures demote down
    the candidate order with quarantine; input errors propagate."""
    from repro.guard.fallback import guarded_call
    return guarded_call(op, plan, *args, **kw)


def _public(fn):
    """A public op: its whole call runs under the ``engine.<op>`` span, the
    root of the call's span tree (DESIGN.md §7)."""
    name = f"engine.{fn.__name__}"

    @functools.wraps(fn)
    def call(*args, **kw):
        with obs.span(name):
            return fn(*args, **kw)
    return call


def _validated(op: str, keys, nan: Optional[str], width=None):
    """The boundary checks of one op under the ``engine.validate`` span:
    the int32 lane width (when ``width`` is given), then the NaN policy;
    returns what :func:`_nan_keys` returns."""
    with obs.span("engine.validate"):
        if width is not None:
            _check_lane_width(width, op)
        return _nan_keys(op, keys, nan)


def _nan_keys(op: str, keys, nan: Optional[str]):
    """Resolve the NaN policy for one op's float keys (guard.validate).

    Returns the monotone total-order int32 keys when the resolved policy is
    ``"sort_last"`` and ``keys`` is float (the caller reroutes through the
    int sort and gathers the floats back); ``None`` when no transform is
    needed (int keys, ``"unsafe"``, or ``"raise"`` — which has already
    checked and possibly raised).
    """
    policy = _validate.resolve_nan_policy(nan, op)
    if policy == "unsafe" or not _validate.check_float_dtype(op, keys):
        return None
    if policy == "raise":
        _validate.check_finite_keys(op, keys)
        return None
    return _validate.total_order_key(keys)


def infer_key(op: str, *args):
    """Plan-cache key for an op's example arguments."""
    if op == "merge":
        a, b = args[:2]
        return plan_key(op, n=a.shape[0] + b.shape[0], dtype=a.dtype)
    if op in ("sort", "argsort", "topk", "external_sort"):
        x = args[0]
        return plan_key(op, n=x.shape[-1], dtype=x.dtype)
    if op in ("sample_topp", "sample_minp"):
        logits = args[1]                      # args are (key, logits, p)
        return plan_key(op, n=logits.shape[-1], dtype=logits.dtype)
    if op in ("segment_sort", "segment_argsort", "merge_runs"):
        values, offsets = args[:2]
        return plan_key(op, n=values.shape[0], dtype=values.dtype,
                        segments=offsets.shape[0] - 1)
    if op == "segment_merge":
        a, ao, b, _bo = args[:4]
        return plan_key(op, n=a.shape[0] + b.shape[0], dtype=a.dtype,
                        segments=ao.shape[0] - 1)
    if op in ("sharded_sort", "sharded_topk"):
        # keyed by mesh axis + device count P on top of the usual bucket
        x = args[0]
        mesh, axis = (args[1], args[2]) if op == "sharded_sort" \
            else (args[2], args[3])
        return plan_key(op, n=x.shape[0], dtype=x.dtype,
                        segments=mesh.shape[axis], axis=str(axis))
    if op == "moe_route":
        logits, k = args[:2]
        groups = logits.shape[0] if logits.ndim == 3 else 1
        return plan_key(op, n=logits.shape[-2] * k, dtype=logits.dtype,
                        segments=groups)
    if op == "moe_route_ep":
        logits, k, _cap, mesh, axis = args[:5]
        return plan_key(op, n=logits.shape[-2] * k, dtype=logits.dtype,
                        segments=mesh.shape[axis], axis=str(axis))
    raise ValueError(f"unknown op {op!r}")


def _resolve(op: str, plan: Optional[Plan], variant: Optional[str], *args,
             **key_extra) -> Plan:
    with obs.span("engine.plan"):
        if plan is None:
            key = infer_key(op, *args)
            plan = default_planner.lookup(key)
            if plan is None:
                plan = heuristic_plan(op, key)
                obs.inc("plan_cache.miss")
                obs.inc("plan_cache.fallback")
                obs.event("plan.resolve", op=op, key=_key_str(key),
                          source="heuristic", variant=plan.variant)
            else:
                obs.inc("plan_cache.hit")
                obs.event("plan.resolve", op=op, key=_key_str(key),
                          source="cache", variant=plan.variant)
            default_planner.put(key, plan)
        else:
            obs.inc("plan_cache.pinned")
        if variant is not None:
            plan = plan.replace(variant=variant)
        return plan


def run_op(op: str, plan: Plan, *args):
    """Execute ``op`` under an explicit plan (the autotuner's entry point)."""
    if op in ("segment_sort", "segment_merge", "segment_argsort") \
            and plan.cap == 0:
        total = (args[0].shape[0] + args[2].shape[0]
                 if op == "segment_merge" else args[0].shape[0])
        plan = plan.replace(cap=segments.static_cap(args[1], total))
    if op == "external_sort":
        from repro.engine.external import resolve_dofs
        plan = resolve_dofs(plan, args[0].shape[0])
    kw = {"plan": plan, "interpret": _interpret()}
    if op in ("argsort", "segment_argsort", "merge_runs", "external_sort"):
        kw["descending"] = True
    return registry.call(op, plan.variant, *args, **kw)


# --------------------------------------------------------------------------
# public ops
# --------------------------------------------------------------------------

@_public
def sort(x, *, descending: bool = True, values=None, stable: bool = False,
         nan: Optional[str] = None, plan: Optional[Plan] = None,
         variant: Optional[str] = None):
    """Full sort of a 1-D array.

    ``values=`` carries a payload pytree of ``x``-shaped leaves through the
    sort and returns ``(sorted_keys, sorted_values)``; ``stable=True``
    requests paper-algorithm-3 tie semantics (ties keep input order — only
    observable through payloads or the permutation). Either flag routes
    through the stable ``argsort`` op, so ``plan=``/``variant=`` then name
    an *argsort* variant.

    ``nan=`` sets the float-key NaN policy (``"raise"`` | ``"sort_last"`` |
    ``"unsafe"``, default the process policy — guard.validate, DESIGN.md
    §11). ``"sort_last"`` matches ``jnp.sort`` NaN semantics bit-for-bit:
    NaN orders above everything (last ascending / first descending), both
    NaN signs one tie class, ``±0.0`` one tie class, ties in input order.
    """
    ik = _validated("sort", x, nan, x.shape[-1])
    if ik is not None:
        perm = argsort(ik, descending=descending, plan=plan, variant=variant)
        keys = x[perm]
        if _verify.verify_enabled():
            _verify.check_sorted(ik[perm], descending=descending, op="sort")
            _verify.check_permutation(x, keys, op="sort")
        if values is None:
            return keys
        return keys, jax.tree.map(lambda v: v[perm], values)
    if values is not None or stable:
        perm = argsort(x, descending=descending, plan=plan, variant=variant)
        keys = x[perm]
        if values is None:
            return keys
        return keys, jax.tree.map(lambda v: v[perm], values)
    plan = _resolve("sort", plan, variant, x)
    out = _gcall("sort", plan, x, interpret=_interpret())
    out = out if descending else out[::-1]
    if _verify.verify_enabled():
        _verify.check_sorted(out, descending=descending, op="sort")
        _verify.check_permutation(x, out, op="sort")
    return out


@_public
def argsort(keys, *, descending: bool = True, nan: Optional[str] = None,
            plan: Optional[Plan] = None, variant: Optional[str] = None):
    """Stable argsort of 1-D keys, or row-wise over a 2-D batch.

    Ties keep their original order (paper algorithm 3 semantics) in every
    variant — the pure-JAX FLiMS lanes ('flims'), the KV Pallas kernels
    ('pallas'), and XLA — callers may rely on it for MoE dispatch.

    ``nan="sort_last"`` runs the argsort on the monotone total-order int32
    transform of the float keys — bit-for-bit ``jnp.argsort(stable=True)``
    NaN semantics (guard.validate; see :func:`sort`).
    """
    ik = _validated("argsort", keys, nan, keys.shape[-1])
    if ik is not None:
        keys = ik
    plan = _resolve("argsort", plan, variant, keys)
    perm = _gcall("argsort", plan, keys, descending=descending,
                  interpret=_interpret())
    if _verify.verify_enabled():
        _verify.check_permutation(
            jnp.broadcast_to(jnp.arange(keys.shape[-1], dtype=jnp.int32),
                             keys.shape), perm, op="argsort")
    return perm


@_public
def merge(a, b, *, descending: bool = True, values=None,
          stable: bool = False, tie: Optional[str] = None,
          nan: Optional[str] = None,
          plan: Optional[Plan] = None, variant: Optional[str] = None):
    """Merge two sorted 1-D arrays into one sorted array.

    ``values=(vals_a, vals_b)`` carries payload pytrees through the merge
    and returns ``(merged_keys, merged_values)``; with ``stable=True`` (or
    any payload) ties order A-first then by input position (algorithm 3) —
    via rank lanes in the Pallas kernel, natively in the lane formulations.

    ``tie='skew'`` applies the paper's §4.1 skewness optimisation (the
    oscillating dir bit, algorithm 2) on the key-only path: same merged
    keys, balanced dequeue rates. Honoured by the 'ref'/'banked' dataflow
    variants; the partitioned Pallas kernel's key output is tie-invariant,
    so it ignores the policy. ``tie=None`` (default) inherits the plan's
    policy. Incompatible with ``stable``/``values``.

    ``nan="sort_last"`` merges the monotone total-order int32 transforms of
    the float keys with the floats riding the payload lanes — each input
    must itself be ordered under the same policy (NaN above every real,
    ``jnp.sort``'s order, in the call's direction). Incompatible with
    ``tie='skew'``.
    """
    ik_a = _validated("merge", a, nan)
    if ik_a is not None:
        if tie == "skew":
            raise _validate.EngineInputError(
                "merge", 'tie="skew" is key-only and cannot combine with '
                'nan="sort_last" (the rescue rides the payload lanes)',
                tie="skew", nan="sort_last")
        pay_a = {"k": a} if values is None else {"k": a, "v": values[0]}
        pay_b = {"k": b} if values is None else {"k": b, "v": values[1]}
        _, mv = merge(ik_a, _validate.total_order_key(b),
                      values=(pay_a, pay_b), descending=descending,
                      plan=plan, variant=variant)
        return mv["k"] if values is None else (mv["k"], mv["v"])
    if values is not None or stable:
        assert tie != "skew", \
            "tie='skew' is key-only (stable order has no ties)"
        return _merge_kv(a, b, values, descending, plan, variant)
    if not descending:
        return merge(a[::-1], b[::-1], tie=tie, plan=plan,
                     variant=variant)[::-1]
    plan = _resolve("merge", plan, variant, a, b)
    if tie is not None and tie != plan.tie:
        plan = plan.replace(tie=tie)
    out = _gcall("merge", plan, a, b, interpret=_interpret())
    if _verify.verify_enabled():
        _verify.check_sorted(out, descending=True, op="merge")
        _verify.check_permutation(jnp.concatenate([a, b]), out, op="merge")
    return out


def _merge_kv(a, b, values, descending, plan, variant):
    rev = lambda t: jax.tree.map(lambda x: x[::-1], t)
    if not descending:
        # mirror with the OPERANDS SWAPPED: the descending merge puts its
        # first operand's ties first, so reversing (B', A') restores the
        # A-first tie order the stable contract promises.
        out = _merge_kv(b[::-1], a[::-1],
                        (rev(values[1]), rev(values[0]))
                        if values is not None else None,
                        True, plan, variant)
        if values is None:
            return out[::-1]
        return out[0][::-1], rev(out[1])
    plan = _resolve("merge", plan, variant, a, b)
    va, vb = values if values is not None else ({}, {})
    if plan.variant == "pallas":
        from repro.kernels.flims_merge import flims_merge_kv_pallas
        nA = a.shape[0]
        ra = jnp.arange(nA, dtype=jnp.int32)
        rb = nA + jnp.arange(b.shape[0], dtype=jnp.int32)
        keys, ranks = flims_merge_kv_pallas(
            a, ra, b, rb, w=plan.w, block_out=plan.block_out,
            interpret=_interpret())
        if values is None:
            return keys
        vals = jax.tree.map(lambda x, y: jnp.concatenate([x, y])[ranks],
                            va, vb)
        return keys, vals
    # scan formulations carry the payload natively through the lane network
    from repro.core.flims import flims_merge_kv_stable
    keys, vals = flims_merge_kv_stable(a, va, b, vb, w=plan.w)
    if values is None:
        return keys
    return keys, vals


@_public
def topk(x, k: int, *, values=None, nan: Optional[str] = None,
         plan: Optional[Plan] = None, variant: Optional[str] = None):
    """(values, indices) of the k largest along the trailing axis,
    values descending, ties broken by lower index (lax.top_k order).

    With ``values=`` (a payload pytree of ``x``-shaped leaves) returns
    ``(vals, indices, payload_topk)``: the payload rides extra lanes through
    the FLiMS selector tree (or is gathered by the XLA variant).

    ``nan="sort_last"`` selects by the monotone total-order transform (NaN
    above every real — NaN keys fill the leading slots when present,
    matching the sort-family policy; clean rows are untouched).
    """
    ik = _validated("topk", x, nan, x.shape[-1])
    if ik is not None:
        pay = {"k": x} if values is None else {"k": x, "v": values}
        _, idx, pv = topk(ik, k, values=pay, plan=plan, variant=variant)
        return (pv["k"], idx) if values is None else (pv["k"], idx, pv["v"])
    plan = _resolve("topk", plan, variant, x)
    return _gcall("topk", plan, x, k, values=values, interpret=_interpret())


def _sample_sorted(op: str, key, logits, knob: float, temperature, plan,
                   variant):
    if not 0.0 < knob <= 1.0:
        name = "p" if op == "sample_topp" else "min_p"
        raise ValueError(f"{op}: {name}={knob} outside (0, 1]")
    squeeze = logits.ndim == 1
    if squeeze:
        logits = logits[None]
    if logits.ndim != 2:
        raise ValueError(f"{op} expects (V,) or (B, V) logits, got shape "
                         f"{logits.shape}")
    plan = _resolve(op, plan, variant, key, logits, knob)
    out = _gcall(op, plan, key, logits, float(knob),
                 temperature=float(temperature), interpret=_interpret())
    return out[0] if squeeze else out


@_public
def sample_topp(key, logits, p: float, *, temperature: float = 1.0,
                plan: Optional[Plan] = None, variant: Optional[str] = None):
    """Nucleus (top-p) sampling: one token id per row of ``logits``.

    A thin op over the sorted-prefix-sum of the engine KV sort: the row is
    stable-argsorted descending (``'flims'`` lanes or ``'xla'``,
    planner's choice — identical permutations, so the variants agree
    bit-for-bit), the softmax prefix-sum cuts the smallest candidate set
    whose mass reaches ``p`` (the argmax always survives), and a Gumbel-max
    draw picks within it. ``temperature <= 0`` degenerates to greedy.
    Returns int32 token ids shaped ``logits.shape[:-1]``.
    """
    return _sample_sorted("sample_topp", key, logits, p, temperature, plan,
                          variant)


@_public
def sample_minp(key, logits, min_p: float, *, temperature: float = 1.0,
                plan: Optional[Plan] = None, variant: Optional[str] = None):
    """Min-p sampling: one token id per row of ``logits``.

    Same sorted-prefix formulation as :func:`sample_topp`, with the cut
    keeping candidates whose probability is at least ``min_p`` times the
    row maximum's. Returns int32 token ids shaped ``logits.shape[:-1]``.
    """
    return _sample_sorted("sample_minp", key, logits, min_p, temperature,
                          plan, variant)


@_public
def segment_sort(keys, offsets, *, descending: bool = True, values=None,
                 stable: bool = False, cap: int = 0,
                 nan: Optional[str] = None,
                 plan: Optional[Plan] = None,
                 variant: Optional[str] = None):
    """Sort every segment of a ragged batch independently.

    ``keys`` is the flat (N,) concatenation of S segments with boundaries
    ``offsets`` ((S+1,), ``offsets[0]==0``, ``offsets[-1]==N``; empty
    segments allowed). ``cap`` bounds the longest segment (power of two); it
    is derived from ``offsets`` when concrete, else defaults to
    ``next_pow2(N)`` — pass it explicitly under ``jit`` to keep blocks tight.

    ``values=`` carries a payload pytree of (N,)-leaves and returns
    ``(sorted_keys, sorted_values)``; with ``stable=True`` (or any payload)
    ties keep input order. Both route through ``segment_argsort`` — the
    permutation comes from the rank-lane kernels and the payload is applied
    inside the engine, so consumers need no external gather round trip.

    ``nan="sort_last"`` sorts each segment by the monotone total-order
    transform (NaN last per segment ascending, ``jnp`` semantics).
    """
    ik = _validated("segment_sort", keys, nan, keys.shape[0])
    if ik is not None:
        pay = {"k": keys} if values is None else {"k": keys, "v": values}
        _, pv = segment_sort(ik, offsets, descending=descending, values=pay,
                             cap=cap, plan=plan, variant=variant)
        return pv["k"] if values is None else (pv["k"], pv["v"])
    if values is not None or stable:
        offsets = jnp.asarray(offsets, jnp.int32)
        perm = segment_argsort(keys, offsets, descending=descending, cap=cap,
                               plan=plan, variant=variant)
        src = offsets[segments.segment_ids(offsets, keys.shape[0])] + perm
        out = keys[src]
        if values is None:
            return out
        return out, jax.tree.map(lambda v: v[src], values)
    segments.validate_offsets(offsets, keys.shape[0])
    offsets = jnp.asarray(offsets, jnp.int32)
    plan = _resolve("segment_sort", plan, variant, keys, offsets)
    if cap or not plan.cap:
        cap = (segments._next_pow2(cap) if cap
               else segments.static_cap(offsets, keys.shape[0]))
        plan = plan.replace(cap=cap)
    segments.validate_cap(offsets, plan.cap)
    out = _gcall("segment_sort", plan, keys, offsets, interpret=_interpret())
    if not descending:
        out = segments.reverse_segments(out, offsets, keys.shape[0])
    if _verify.verify_enabled():
        _verify.check_segments(out, offsets, descending=descending,
                               op="segment_sort")
        _verify.check_permutation(keys, out, op="segment_sort")
    return out


@_public
def segment_argsort(keys, offsets, *, descending: bool = True, cap: int = 0,
                    nan: Optional[str] = None,
                    plan: Optional[Plan] = None,
                    variant: Optional[str] = None):
    """Stable argsort of every segment of a ragged batch.

    Returns a flat int32 array of *segment-local* source positions: for
    segment ``s``, ``keys[offsets[s] + perm[offsets[s]:offsets[s+1]]]`` is
    that segment's sort, and equal keys keep their input order (paper
    algorithm 3) in every variant and either direction. This is the
    MoE-dispatch primitive: the whole ragged batch is one kernel launch, no
    flatten→argsort→gather round trip per segment.

    ``nan="sort_last"`` orders each segment by the monotone total-order
    transform — bit-for-bit per-segment ``jnp.argsort(stable=True)``.
    """
    ik = _validated("segment_argsort", keys, nan, keys.shape[0])
    if ik is not None:
        keys = ik
    segments.validate_offsets(offsets, keys.shape[0])
    offsets = jnp.asarray(offsets, jnp.int32)
    plan = _resolve("segment_argsort", plan, variant, keys, offsets)
    if cap or not plan.cap:
        cap = (segments._next_pow2(cap) if cap
               else segments.static_cap(offsets, keys.shape[0]))
        plan = plan.replace(cap=cap)
    segments.validate_cap(offsets, plan.cap)
    return _gcall("segment_argsort", plan, keys, offsets,
                  descending=descending, interpret=_interpret())


@_public
def merge_runs(keys, run_offsets, *, descending: bool = True, values=None,
               stable: bool = False, tie: Optional[str] = None, cap: int = 0,
               nan: Optional[str] = None,
               plan: Optional[Plan] = None, variant: Optional[str] = None):
    """Merge K sorted runs into one sorted array (the paper's §2.1 merge
    tree as an engine op).

    ``keys`` is the flat concatenation of K runs — each sorted in the call's
    direction, ragged lengths and empty runs fine — with boundaries
    ``run_offsets`` ((K+1,), ``run_offsets[0] == 0``,
    ``run_offsets[-1] == len(keys)``). The resolved plan names a
    MergeSchedule executor (``xla`` | ``tree_vmapped`` | ``tree_pallas``)
    and, for the Pallas tree, how many levels each fused pass executes
    (``plan.levels``; DESIGN.md §5).

    ``values=`` carries a payload pytree of ``keys``-shaped leaves and
    returns ``(merged_keys, merged_values)``; with ``stable=True`` (or any
    payload) equal keys keep run-then-position order (algorithm 3) via rank
    lanes. ``tie='skew'`` applies algorithm 2's selector on the key-only
    vmapped tree (``None`` inherits the plan's policy). ``cap`` is unused
    today and reserved for parity with the segmented ops.

    ``nan="sort_last"`` merges the monotone total-order transforms with the
    float keys riding the payload lanes (each run already ordered under the
    same policy); incompatible with ``tie='skew'``.
    """
    del cap
    ik = _validated("merge_runs", keys, nan, keys.shape[0])
    if ik is not None:
        if tie == "skew":
            raise _validate.EngineInputError(
                "merge_runs", 'tie="skew" is key-only and cannot combine '
                'with nan="sort_last" (the rescue rides the payload lanes)',
                tie="skew", nan="sort_last")
        pay = {"k": keys} if values is None else {"k": keys, "v": values}
        _, pv = merge_runs(ik, run_offsets, descending=descending,
                           values=pay, plan=plan, variant=variant)
        return pv["k"] if values is None else (pv["k"], pv["v"])
    segments.validate_offsets(run_offsets, keys.shape[0])
    run_offsets = jnp.asarray(run_offsets, jnp.int32)
    plan = _resolve("merge_runs", plan, variant, keys, run_offsets)
    if tie is not None and tie != plan.tie:
        plan = plan.replace(tie=tie)
    if values is None and not stable:
        out = _gcall("merge_runs", plan, keys, run_offsets,
                     descending=descending, interpret=_interpret())
        if _verify.verify_enabled():
            _verify.check_sorted(out, descending=descending, op="merge_runs")
            _verify.check_permutation(keys, out, op="merge_runs")
        return out
    assert tie != "skew", "tie='skew' is key-only (stable order has no ties)"
    from repro.engine.schedule import merge_runs as _sched_merge_runs
    # rank lanes leave no ties for skew to balance: pin the stable policy
    sched = MergeSchedule.from_plan(plan).replace(tie="b")
    ranks = jnp.arange(keys.shape[0], dtype=jnp.int32)
    mk, mr = _sched_merge_runs(keys, run_offsets, ranks=ranks, schedule=sched,
                               descending=descending, interpret=_interpret())
    if values is None:
        return mk
    return mk, jax.tree.map(lambda v: v[mr], values)


@_public
def external_sort(keys, *, descending: bool = True, values=None,
                  stable: bool = False, tile_elems: int = 0, fan_in: int = 0,
                  nan: Optional[str] = None,
                  plan: Optional[Plan] = None, variant: Optional[str] = None):
    """Sort a 1-D array larger than fast memory: the TopSort two-phase
    out-of-core sort (DESIGN.md §8).

    Phase 1 forms ``ceil(n / tile_elems)`` sorted runs by streaming
    scratch-resident tiles through the full-width sorters; phase 2 reduces
    them with ``ceil(log_fan_in(runs))`` streamed merge passes whose runs
    stay HBM-resident (``stream_pallas``: the double-buffered DMA kernel in
    ``kernels/stream_merge.py``; ``xla``: vectorised searchsorted pairwise
    merges). Inputs no larger than one tile delegate to ``engine.sort``
    untouched.

    ``tile_elems``/``fan_in`` override the resolved plan's out-of-core
    degrees of freedom (both clamp to powers of two; autotune sweeps them).
    ``values=`` carries a payload pytree through the sort and returns
    ``(sorted_keys, sorted_values)``; ``stable=True`` (or any payload)
    orders ties by input position, bit-for-bit
    ``jnp.argsort(stable=True)``. Sizes past the int32 lanes (``n >= 2**31``)
    raise ``ValueError`` — shard instead (``engine.sharded_sort``).
    """
    if keys.ndim != 1:
        raise _validate.EngineInputError(
            "external_sort", f"expects a 1-D key array, got shape "
            f"{keys.shape}", shape=tuple(keys.shape))
    n = keys.shape[0]
    ik = _validated("external_sort", keys, nan, n)
    if ik is not None:
        pay = {"k": keys} if values is None else {"k": keys, "v": values}
        _, pv = external_sort(ik, descending=descending, values=pay,
                              tile_elems=tile_elems, fan_in=fan_in,
                              plan=plan, variant=variant)
        return pv["k"] if values is None else (pv["k"], pv["v"])
    from repro.engine.external import resolve_dofs
    plan = _resolve("external_sort", plan, variant, keys)
    plan = resolve_dofs(plan, n, tile_elems=tile_elems, fan_in=fan_in)
    if n <= plan.tile_elems:
        # the whole input is one scratch-resident tile: no out-of-core
        # machinery, no copy — hand the array itself to the direct path
        obs.event("external.delegate", n=int(n), tile=int(plan.tile_elems))
        return sort(keys, descending=descending, values=values,
                    stable=stable)
    kv = values is not None or stable
    if not kv:
        out = _gcall("external_sort", plan, keys, descending=descending,
                     interpret=_interpret())
        if _verify.verify_enabled():
            _verify.check_sorted(out, descending=descending,
                                 op="external_sort")
            _verify.check_permutation(keys, out, op="external_sort")
        return out
    ranks = jnp.arange(n, dtype=jnp.int32)
    mk, mr = _gcall("external_sort", plan, keys, descending=descending,
                    ranks=ranks, interpret=_interpret())
    if values is None:
        return mk
    return mk, jax.tree.map(lambda v: v[mr], values)


@_public
def segment_merge(a, a_offsets, b, b_offsets, *, descending: bool = True,
                  plan: Optional[Plan] = None,
                  variant: Optional[str] = None):
    """Merge S segment pairs of two ragged batches (segment s of the result
    is the sorted union of a-segment s and b-segment s; its offsets are
    ``a_offsets + b_offsets``)."""
    segments.validate_offsets(a_offsets, a.shape[0])
    segments.validate_offsets(b_offsets, b.shape[0])
    a_offsets = jnp.asarray(a_offsets, jnp.int32)
    b_offsets = jnp.asarray(b_offsets, jnp.int32)
    if not descending:
        ar = segments.reverse_segments(a, a_offsets, a.shape[0])
        br = segments.reverse_segments(b, b_offsets, b.shape[0])
        out = segment_merge(ar, a_offsets, br, b_offsets,
                            plan=plan, variant=variant)
        return segments.reverse_segments(
            out, a_offsets + b_offsets, a.shape[0] + b.shape[0])
    plan = _resolve("segment_merge", plan, variant, a, a_offsets, b,
                    b_offsets)
    return _gcall("segment_merge", plan, a, a_offsets, b, b_offsets,
                  interpret=_interpret())


# --------------------------------------------------------------------------
# moe_route: fused MoE routing — logits → permuted capacity slabs
# (DESIGN.md §9)
# --------------------------------------------------------------------------

class RouteResult(NamedTuple):
    """One routed token chunk, every lane in stable sorted pair order
    (expert ascending, then original pair position — paper algorithm 3)."""
    experts: jax.Array   # (..., T*k) int32 expert id of each routed pair
    tokens: jax.Array    # (..., T*k) int32 source token within the chunk
    perm: jax.Array      # (..., T*k) int32 stable pair permutation (t*k + j)
    weights: jax.Array   # (..., T*k) f32 combine weight (softmax over top-k)
    slabs: jax.Array     # (..., T*k) int32 e*cap + rank, or E*cap if dropped
    keep: jax.Array      # (..., T*k) bool — False = over capacity (dropped)


@_public
def moe_route(logits, k: int, capacity: int, *, values=None,
              plan: Optional[Plan] = None, variant: Optional[str] = None):
    """Route a chunk of tokens to expert capacity slabs in one planned op.

    ``logits`` are (T, E) — or (G, T, E) for G independent groups — f32
    router logits; ``k`` experts activate per token and each expert keeps
    its first ``capacity`` assigned pairs in stable order (GShard drop
    semantics, bit-for-bit the historical ``segment_sort``-based dispatch).
    Returns a :class:`RouteResult` of (G, T*k) lanes in sorted pair order;
    scattering ``x[tokens]`` to ``slabs`` builds the (E, capacity, d) expert
    slabs directly and ``weights * keep`` are the combine coefficients;
    ``~keep`` marks the pairs dropped past capacity (the caller counts
    them: nothing is staged into the program to count them).

    The ``fused`` variant executes top-k, the stable expert sort and the
    capacity drop in ONE ``pallas_call`` per chunk, and the softmax
    combine weights with the same XLA ops as ``xla``, the unfused
    reference pipeline. ``values=`` (leaves shaped like one logit
    column, i.e. (G, T)) gathers a payload by ``tokens`` and returns
    ``(RouteResult, routed_values)``.
    """
    if logits.ndim == 2:
        vv = None if values is None else jax.tree.map(
            lambda v: v[None], values)
        out = moe_route(logits[None], k, capacity, values=vv, plan=plan,
                        variant=variant)
        squeeze = lambda r: RouteResult(*(x[0] for x in r))
        if values is None:
            return squeeze(out)
        return squeeze(out[0]), jax.tree.map(lambda v: v[0], out[1])
    if logits.ndim != 3:
        raise ValueError(f"moe_route expects (T, E) or (G, T, E) logits, "
                         f"got shape {logits.shape}")
    G, T, E = logits.shape
    if not 1 <= k <= E:
        raise ValueError(f"moe_route: k={k} outside [1, E={E}]")
    if capacity < 1:
        raise ValueError(f"moe_route: capacity={capacity} must be >= 1")
    with obs.span("engine.validate"):
        _check_lane_width(T * k, "moe_route")
    logits = logits.astype(jnp.float32)
    plan = _resolve("moe_route", plan, variant, logits, k, capacity)
    plan = plan.replace(cap=int(capacity))
    obs.event("moe.route", groups=G, tokens=T, experts=E, k=k,
              capacity=int(capacity), n_pairs=G * T * k,
              variant=plan.variant)
    out = _gcall("moe_route", plan, logits, k, int(capacity),
                 interpret=_interpret())
    e_s, t_s, perm, w_s, slab, keep = out
    keep = keep.astype(bool)
    res = RouteResult(e_s, t_s, perm, w_s, slab, keep)
    if values is None:
        return res
    pay = jax.tree.map(lambda v: jnp.take_along_axis(v, t_s, axis=-1),
                       values)
    return res, pay


@_public
def moe_route_ep(logits, k: int, capacity: int, mesh, axis: str = "data", *,
                 plan: Optional[Plan] = None, variant: Optional[str] = None):
    """Expert-parallel routing across a mesh axis: tokens are sharded over
    ``axis`` (logits (T, E) with rows split across the P devices) and the E
    experts are owned round-robin by the same devices (E/P each).

    Each shard routes its local tokens with :func:`moe_route` — the local
    per-expert capacity cut doubling as ``sharded_topk``'s union-of-local-
    top-k prefilter, which provably contains every globally kept pair —
    exchanges candidates to their expert's owner with one ``all_to_all``,
    and the owner merges the P arrived runs and re-cuts at ``capacity`` by
    global stable rank. Returns a :class:`~repro.engine.sharded.RouteShard`
    of per-device slab assignments (see ``run_moe_route_ep``); semantics
    are bit-for-bit :func:`moe_route` on the gathered logits, restricted to
    each owner's experts.
    """
    plan = _resolve("moe_route_ep", plan, variant, logits, k, capacity,
                    mesh, axis)
    plan = plan.replace(cap=int(capacity))
    return _gcall("moe_route_ep", plan, logits, k, int(capacity), mesh,
                  axis, interpret=_interpret())


# --------------------------------------------------------------------------
# sharded ops: sort / top-k across a device mesh (DESIGN.md §6)
# --------------------------------------------------------------------------

@_public
def sharded_sort(x, mesh, axis: str = "data", *, payload=None,
                 plan: Optional[Plan] = None, variant: Optional[str] = None):
    """Sort a 1-D array sharded over ``axis`` of ``mesh``. Descending.

    The planned face of the distributed sample sort: local FLiMS sorts,
    splitter selection, one all_to_all bucket exchange, and a per-device
    K-way reduction through the plan's MergeSchedule executor (the
    ``variant``: ``xla`` | ``tree_vmapped`` | ``tree_pallas``). The plan
    also carries the sharded degrees of freedom — ``splitter`` (``'hist'``
    oversampled + exact-rank refined splitters by default; ``'regular'`` for
    the paper's plain sampling), ``cap_factor``, and ``retries`` rungs of
    in-graph cap escalation, which make the documented overflow contract
    hold: a result is only flagged ``overflow=True`` when even the last
    rung's cap cannot fit the largest bucket. A ladder whose last rung
    reaches ``n_local`` cannot overflow at all — true whenever
    ``cap_factor * 2**retries >= n_dev`` (any mesh up to 16 devices on the
    default plan); on wider meshes raise ``retries`` to keep the guarantee,
    or read the flag.

    Returns a ``ShardedSort`` of per-device padded runs — ``values`` with
    spec P(axis) concatenates to the global descending order, ``count`` the
    valid prefix per device (``parallel.sharding.collect_sorted`` gathers on
    host). With ``payload=`` (a pytree of 1-D arrays of ``x``'s length,
    sharded the same way) returns ``(ShardedSort, payload)`` permuted
    identically and stably (paper algorithm 3).
    """
    plan = _resolve("sharded_sort", plan, variant, x, mesh, axis)
    return _gcall("sharded_sort", plan, x, mesh, axis,
                  interpret=_interpret(), payload=payload)


@_public
def sharded_topk(x, k: int, mesh, axis: str = "data", *, payload=None,
                 plan: Optional[Plan] = None, variant: Optional[str] = None):
    """(values, global indices) of the k largest elements of a 1-D array
    sharded over ``axis`` — bit-for-bit ``lax.top_k`` of the gathered array
    (ties to the lower global index), replicated on every device.

    Local top-k candidates (``variant``: ``'flims'`` selector tree or
    ``'xla'``) are all_gathered and stable-merged through the plan's
    schedule. With ``payload=`` returns ``(values, indices, payload_topk)``
    with the payload riding the lanes end-to-end.
    """
    plan = _resolve("sharded_topk", plan, variant, x, k, mesh, axis)
    return _gcall("sharded_topk", plan, x, k, mesh, axis,
                  interpret=_interpret(), payload=payload)


# --------------------------------------------------------------------------
# plan management
# --------------------------------------------------------------------------

def autotune(op: str, *example_args, repeats: int = 3, candidates=None):
    """Measure every registered variant of ``op`` on the example workload and
    cache the fastest plan for that shape bucket. Returns the winning Plan.
    Candidates that raise (e.g. a Pallas lowering failure at this shape) are
    recorded as infeasible and skipped, not fatal."""
    return default_planner.autotune(op, *example_args, repeats=repeats,
                                    candidates=candidates)


def save_plans(path: str) -> None:
    default_planner.save(path)


def load_plans(path: str) -> None:
    default_planner.load(path)


def clear_plans() -> None:
    default_planner.clear()

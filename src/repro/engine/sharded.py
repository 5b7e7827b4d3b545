"""Sharded sort / top-k across a device mesh as planned engine ops.

The distributed subsystem (DESIGN.md §6): `core/distributed.sample_sort`
promoted to first-class engine ops, the same consolidation move PR 1-3 made
for local sorting. The TopSort-style two-phase pipeline —

  1. every device FLiMS-sorts its local shard              (compute-bound)
  2. splitter selection (regular sampling, or oversampled + exact-rank
     histogram refinement for skewed keys) -> (P-1,) global splitters
  3. bucket partition via searchsorted + one all_to_all    (collective-bound)
  4. every device reduces the P sorted runs it received through the
     plan's MergeSchedule executor (paper fig. 1)

— is driven by an engine ``Plan``: the variant names the step-4 merge
executor (``xla`` | ``tree_vmapped`` | ``tree_pallas`` @ ``levels``), and
the sharded degrees of freedom (``cap_factor``, ``splitter``, ``retries``)
ride the same plan cache, keyed by (op, backend, dtype, n, P, mesh axis).

Overflow contract — honoured IN-GRAPH. Buckets are sentinel-padded to a
static cap (collectives need static shapes); on skewed or duplicate-heavy
keys one bucket can exceed it. Instead of silently truncating, the pass
computes the globally needed cap *before* any exchange (``pmax`` of the
bucket sizes) and a ``lax.switch`` selects the smallest rung of a bounded
cap-doubling ladder ``cap, 2*cap, ..., n_local`` that fits — one compiled
graph, no host round trip, no wasted exchange. Since a bucket can never
exceed ``n_local``, a ladder whose last rung reaches ``n_local`` makes
``overflow=False`` a guarantee, not a hope; with fewer retries the flag
stays meaningful.

Payload lanes ride the whole pipeline natively, exactly as in
``core/distributed`` (stable KV local sort, payload rows beside the keys in
every all_to_all, validity-aware KV merge tree).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.engine.planner import Plan
from repro.engine.schedule import MergeSchedule

#: per-device sample count for the 'hist' policy = OVERSAMPLE * n_dev
OVERSAMPLE = 8

SPLITTER_POLICIES = ("regular", "hist")


class ShardedSort(NamedTuple):
    values: jnp.ndarray   # (P * cap,) per device, sentinel-padded, descending
    count: jnp.ndarray    # () valid prefix length per device
    overflow: jnp.ndarray # () bool: some bucket exceeded the final-rung cap


def cap_ladder(n_local: int, n_dev: int, cap_factor: int,
               retries: int) -> tuple:
    """Static cap-escalation rungs: the documented base cap, then doubling
    (bounded by ``retries``) toward ``n_local`` — the cap no bucket can
    exceed, so a ladder that reaches it cannot overflow."""
    base = min(n_local, cap_factor * max(n_local // n_dev, 1))
    caps = [base]
    for _ in range(max(retries, 0)):
        if caps[-1] >= n_local:
            break
        caps.append(min(2 * caps[-1], n_local))
    return tuple(caps)


# --------------------------------------------------------------------------
# per-device pipeline pieces (run inside shard_map)
# --------------------------------------------------------------------------

def _local_sort(xl, payload, w: int):
    """Descending local sort through the engine; with payload lanes the
    stable KV path permutes keys and payload together."""
    from repro.engine import api
    if payload is None:
        return api.sort(xl, plan=Plan("ref", w=w, chunk=512)), None
    # pin the pure-JAX lane argsort: honours w and stays shard_map-safe
    return api.sort(xl, values=payload, stable=True,
                    plan=Plan("flims", w=w, chunk=512))


def _sample_ids(n_local: int, m: int):
    """``m`` regular sample positions into a sorted local shard, padded to a
    STATIC ``m`` by clamping — ``loc[::step][:m]`` produces fewer than ``m``
    samples when ``n_local < m``, which silently skewed the downstream
    ``allsmp[::n_dev]`` stride math (tiny-shard bugfix)."""
    step = max(n_local // m, 1)
    return jnp.minimum(jnp.arange(m, dtype=jnp.int32) * step, n_local - 1)


def _splitters_regular(loc, axis_name: str, n_dev: int, w: int):
    """Paper-style regular sampling: n_dev local quantile draws per device,
    all_gather, sort, stride — cheap, adequate on near-uniform keys."""
    from repro.core.mergesort import _next_pow2
    from repro.engine import api
    samples = loc[_sample_ids(loc.shape[0], n_dev)]
    allsmp = lax.all_gather(samples, axis_name).reshape(-1)      # (P*P,)
    allsmp = api.sort(allsmp, plan=Plan(
        "ref", w=min(w, _next_pow2(allsmp.shape[0])), chunk=512))
    return allsmp[::n_dev][1:n_dev]                               # (P-1,) desc


def _splitters_hist(loc, axis_name: str, n_dev: int):
    """Skew-robust splitters: oversample local quantiles, then refine by the
    EXACT global rank of every candidate (a searchsorted histogram psum'd
    across the mesh) and pick, per target rank p*n/P, the closest candidate.
    Heavy-duplicate keys can still force one big bucket (equal keys are
    indivisible) — that is what the cap ladder recovers — but skewed yet
    distinct distributions (zipf tails) land near-balanced buckets."""
    n_local = loc.shape[0]
    m = max(min(n_local, OVERSAMPLE * n_dev), 1)
    pool = lax.all_gather(loc[_sample_ids(n_local, m)],
                          axis_name).reshape(-1)                  # (P*m,)
    asc = loc[::-1]
    ge = (n_local - jnp.searchsorted(asc, pool, side="left")).astype(
        jnp.int32)                       # local count of elements >= cand
    g = lax.psum(ge, axis_name)                                   # exact rank
    n_glob = n_local * n_dev
    targets = jnp.arange(1, n_dev, dtype=jnp.int32) * (n_glob // n_dev)
    pick = jnp.argmin(jnp.abs(g[None, :] - targets[:, None]), axis=1)
    # enforce descending splitters so bucket sizes stay non-negative
    return jnp.sort(pool[pick], descending=True)


def _bucket_bounds(loc, splitters):
    """Bucket boundaries b_p = #elements strictly >= s_p (ties stay with the
    higher-value bucket, matching the strict-> selector everywhere else)."""
    n_local = loc.shape[0]
    asc = loc[::-1]
    b = n_local - jnp.searchsorted(asc, splitters, side="left")
    bounds = jnp.concatenate([jnp.zeros((1,), b.dtype), b,
                              jnp.full((1,), n_local, b.dtype)])  # (P+1,)
    return bounds, bounds[1:] - bounds[:-1]


def _exchange_merge(loc, ploc, bounds, sizes, *, cap: int, out_cap: int,
                    axis_name: str, n_dev: int, sched: MergeSchedule):
    """One ladder rung: gather each bucket into a fixed-``cap`` row, exchange
    with one all_to_all, reduce the received runs through the schedule, and
    pad the result to the ladder's uniform ``out_cap`` shape."""
    from repro.core.flims import sentinel_for
    from repro.core.merge_tree import pmt_merge, pmt_merge_kv_padded
    from repro.core.mergesort import _next_pow2
    n_local = loc.shape[0]
    sent = sentinel_for(loc.dtype)
    pos = bounds[:-1][:, None] + jnp.arange(cap)[None, :]         # (P, cap)
    valid = jnp.arange(cap)[None, :] < jnp.minimum(sizes, cap)[:, None]
    src = jnp.clip(pos, 0, n_local - 1)
    send = jnp.where(valid, loc[src], sent)
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                             # (P, cap)
    cnt = lax.all_to_all(jnp.minimum(sizes, cap), axis_name,
                         split_axis=0, concat_axis=0, tiled=True)
    if ploc is not None:
        # payload rows exchange natively beside the keys; validity is
        # governed by counts, so out-of-range rows need no masking.
        precv = jax.tree.map(
            lambda pv: lax.all_to_all(pv[src], axis_name, split_axis=0,
                                      concat_axis=0, tiled=True), ploc)
    # --- K-way reduction of the received runs (schedule executor) ----------
    k_pad = _next_pow2(recv.shape[0])
    if k_pad != recv.shape[0]:
        grow = k_pad - recv.shape[0]
        recv = jnp.concatenate(
            [recv, jnp.full((grow, cap), sent, loc.dtype)])
        if ploc is not None:
            precv = jax.tree.map(
                lambda pv: jnp.concatenate(
                    [pv, jnp.zeros((grow, cap), pv.dtype)]), precv)
    total = jnp.sum(cnt).reshape(1)
    # a lane width wider than the rung's rows is wasted selector work
    sched = sched.replace(w=min(sched.w, _next_pow2(cap)))

    def grow_tail(v, fill):
        return jnp.concatenate(
            [v, jnp.full((k_pad * out_cap - v.shape[0],), fill, v.dtype)])

    if ploc is None:
        merged = pmt_merge(recv, w=sched.w, schedule=sched)
        return grow_tail(merged, sent), None, total
    # validity-aware KV merge: padding must sort behind *real* sentinel-
    # valued keys or its garbage payload would land inside the count prefix
    cnt_pad = jnp.concatenate(
        [cnt, jnp.zeros((k_pad - cnt.shape[0],), cnt.dtype)])
    merged, pmerged = pmt_merge_kv_padded(recv, cnt_pad, precv, w=sched.w,
                                          schedule=sched)
    pmerged = jax.tree.map(
        lambda v: grow_tail(v, jnp.zeros((), v.dtype)), pmerged)
    return grow_tail(merged, sent), pmerged, total


def _emit_exec(rung, need, overflow, *, caps: tuple):
    """Host-side sink for the in-graph rung decision (``jax.debug.callback``
    target): the ladder rung the ``lax.switch`` took, the pmax'd needed cap,
    and the overflow flag — one event per participating device."""
    r = int(rung)
    ovf = bool(overflow)
    obs.event("sharded.exec", rung=r, cap=int(caps[min(r, len(caps) - 1)]),
              need=int(need), overflow=ovf, rungs=len(caps))
    obs.inc("sharded.overflow" if ovf else "sharded.ok")


def _sharded_pass(xl, payload, *, axis_name: str, n_dev: int, caps: tuple,
                  w: int, sched: MergeSchedule, splitter: str,
                  record: bool = False):
    """The whole per-device pipeline: local sort, splitters, bucket sizes,
    then the in-graph overflow-recovery switch over the cap ladder."""
    loc, ploc = _local_sort(xl, payload, w)
    if splitter == "hist":
        spl = _splitters_hist(loc, axis_name, n_dev)
    else:
        spl = _splitters_regular(loc, axis_name, n_dev, w)
    bounds, sizes = _bucket_bounds(loc, spl)
    # the needed cap is known BEFORE any exchange — pick the smallest rung
    # that fits (uniform across devices: `need` is pmax'd, so every device
    # takes the same branch and its collectives)
    need = lax.pmax(jnp.max(sizes), axis_name)
    overflow = (need > caps[-1]).reshape(1)
    branches = [partial(_exchange_merge, cap=c, out_cap=caps[-1],
                        axis_name=axis_name, n_dev=n_dev, sched=sched)
                for c in caps]
    if len(branches) == 1:
        rung = jnp.zeros((), jnp.int32)
        merged, pmerged, total = branches[0](loc, ploc, bounds, sizes)
    else:
        rung = jnp.minimum(jnp.sum(need > jnp.asarray(caps, sizes.dtype)),
                           len(caps) - 1).astype(jnp.int32)
        merged, pmerged, total = lax.switch(rung, branches, loc, ploc,
                                            bounds, sizes)
    if record:
        # report the branch that actually EXECUTED, not a trace-time guess —
        # the one decision span timers and trace-time events cannot see
        jax.debug.callback(partial(_emit_exec, caps=caps), rung, need,
                           overflow[0])
    res = ShardedSort(merged, total, overflow)
    return res if payload is None else (res, pmerged)


# --------------------------------------------------------------------------
# mesh-level runners (the registry's entry points)
# --------------------------------------------------------------------------

def _pass_kwargs(x, mesh, axis: str, plan: Plan, kv: bool,
                 schedule: Optional[MergeSchedule] = None,
                 record: bool = False) -> dict:
    n_dev = mesh.shape[axis]
    n_local = x.shape[0] // n_dev
    sched = schedule or MergeSchedule.from_plan(plan)
    if kv:
        sched = sched.replace(tie="b")   # rank lanes leave no ties for skew
    assert plan.splitter in SPLITTER_POLICIES, plan.splitter
    caps = cap_ladder(n_local, n_dev, plan.cap_factor, plan.retries)
    # trace-time record of the static degrees of freedom: one event per
    # compilation (re-traced when obs is toggled, via the `record` static)
    obs.event("sharded.plan", n_local=n_local, n_dev=n_dev, axis=axis,
              caps=list(caps), splitter=plan.splitter,
              executor=sched.variant, levels=sched.levels_per_pass,
              kv=kv, w=plan.w)
    return dict(axis_name=axis, n_dev=n_dev, caps=caps,
                w=plan.w, sched=sched, splitter=plan.splitter, record=record)


@partial(jax.jit,
         static_argnames=("mesh", "axis", "plan", "schedule", "record"))
def _sorted_keys(x, mesh, axis, plan, schedule=None, record=False):
    fn = partial(_sharded_pass, payload=None,
                 **_pass_kwargs(x, mesh, axis, plan, kv=False,
                                schedule=schedule, record=record))
    return jax.shard_map(fn, mesh=mesh, in_specs=P(axis),
                         out_specs=ShardedSort(P(axis), P(axis), P(axis)),
                         check_vma=False)(x)


@partial(jax.jit,
         static_argnames=("mesh", "axis", "plan", "schedule", "record"))
def _sorted_kv(x, payload, mesh, axis, plan, schedule=None, record=False):
    fn = partial(_sharded_pass,
                 **_pass_kwargs(x, mesh, axis, plan, kv=True,
                                schedule=schedule, record=record))
    pspec = jax.tree.map(lambda _: P(axis), payload)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P(axis), pspec),
        out_specs=(ShardedSort(P(axis), P(axis), P(axis)), pspec),
        check_vma=False)(x, payload)


def run_sharded_sort(x, mesh, axis: str = "data", *, payload=None,
                     plan: Optional[Plan] = None,
                     schedule: Optional[MergeSchedule] = None):
    """Execute the sharded sort under an explicit plan (no planner lookup).

    Returns per-device padded runs: ``values`` with spec P(axis) concatenates
    to the global descending order (``parallel.sharding.collect_sorted``
    does the host-side gather). With ``payload=`` returns
    ``(ShardedSort, payload)`` permuted identically to ``values``.

    ``schedule`` overrides the step-4 reduction executor derived from the
    plan — the legacy ``sample_sort(merge_schedule=)`` path, where the
    caller's ``w`` must keep driving the local sort while the explicit
    schedule keeps its own tiles.
    """
    plan = plan or Plan("tree_vmapped")
    record = obs.enabled()       # static: toggling obs re-traces with the
    if payload is None:          # rung callback staged in (or out) cleanly
        return _sorted_keys(x, mesh, axis, plan, schedule, record)
    return _sorted_kv(x, payload, mesh, axis, plan, schedule, record)


# --------------------------------------------------------------------------
# sharded top-k
# --------------------------------------------------------------------------

def _topk_pass(xl, payload, *, axis_name: str, k: int, kk: int,
               variant: Optional[str], sched: MergeSchedule):
    """Per-device: local top-kk with global indices (and payload) on the
    lanes, all_gather the P candidate runs, stable-merge, take k. The union
    of local top-kk runs provably contains the global top-k including
    lax.top_k tie order: an element beaten locally by kk others is beaten
    globally by the same kk."""
    from repro.core.merge_tree import pmt_merge_kv
    from repro.engine import api
    n_local = xl.shape[0]
    base = lax.axis_index(axis_name).astype(jnp.int32) * n_local
    lanes = {"idx": base + jnp.arange(n_local, dtype=jnp.int32)}
    if payload is not None:
        lanes["pay"] = payload
    vals, _, sel = api.topk(xl, kk, variant=variant, values=lanes)
    av = lax.all_gather(vals, axis_name)                      # (P, kk)
    asel = jax.tree.map(lambda v: lax.all_gather(v, axis_name), sel)
    # row-major ranks == (device, local-rank) == global-index tie order
    mk, mp = pmt_merge_kv(av, asel, schedule=sched)
    out = (mk[:k], mp["idx"][:k])
    if payload is None:
        return out
    return out + (jax.tree.map(lambda v: v[:k], mp["pay"]),)


@partial(jax.jit, static_argnames=("k", "mesh", "axis", "plan"))
def _topk_impl(x, payload, k, mesh, axis, plan):
    n_dev = mesh.shape[axis]
    n_local = x.shape[0] // n_dev
    assert k <= n_local * n_dev, f"k={k} exceeds the {n_local * n_dev} keys"
    sched = MergeSchedule.from_plan(plan).replace(tie="b")
    variant = plan.variant if plan.variant in ("flims", "xla") else None
    fn = partial(_topk_pass, axis_name=axis, k=k, kk=min(k, n_local),
                 variant=variant, sched=sched)
    rep = P()                              # replicated: same on every device
    if payload is None:
        return jax.shard_map(lambda xl: fn(xl, None), mesh=mesh,
                             in_specs=P(axis), out_specs=(rep, rep),
                             check_vma=False)(x)
    pspec = jax.tree.map(lambda _: P(axis), payload)
    prep = jax.tree.map(lambda _: rep, payload)
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(axis), pspec),
                         out_specs=(rep, rep, prep), check_vma=False)(
                             x, payload)


def run_sharded_topk(x, k: int, mesh, axis: str = "data", *, payload=None,
                     plan: Optional[Plan] = None):
    """(values, global indices) of the k globally largest elements of a
    sharded 1-D array — bit-for-bit ``lax.top_k`` of the gathered array,
    replicated on every device. With ``payload=`` returns
    ``(values, indices, payload_topk)``."""
    plan = plan or Plan("xla")
    return _topk_impl(x, payload, k, mesh, axis, plan)


# --------------------------------------------------------------------------
# expert-parallel MoE routing (composes the fused route with the
# sharded-topk candidate lemma — DESIGN.md §9)
# --------------------------------------------------------------------------

class RouteShard(NamedTuple):
    """Per-device routing result: the (token, expert) pairs that landed on
    this device's experts, in global stable (expert, pair-rank) order —
    lanes are (P * A,) per device (A = the static per-source candidate cap),
    sentinel-tailed past ``count``."""
    experts: jnp.ndarray   # global expert id, E where invalid
    tokens: jnp.ndarray    # global source token id
    perm: jnp.ndarray      # global stable pair position t*k + j
    weights: jnp.ndarray   # combine weight (f32)
    slabs: jnp.ndarray     # LOCAL slab (e - e0)*cap + pos, E_loc*cap if drop
    keep: jnp.ndarray      # bool: survives the GLOBAL capacity cut
    count: jnp.ndarray     # (1,) arrived candidate count on this device


def _emit_route_ep(arrived, dropped) -> None:
    """Host sink for the owner-side merge outcome (``jax.debug.callback``
    target) — one event per device per execution."""
    obs.event("moe.route_ep.exec", arrived=int(arrived), dropped=int(dropped))
    obs.inc("moe.dropped_tokens", int(dropped))


def _route_ep_pass(lg, *, axis_name: str, n_dev: int, k: int, cap: int,
                   local_variant: str, w: int, interpret: bool,
                   record: bool):
    """Per-device EP pipeline: fused-route the local token rows (the local
    capacity cut doubling as the sharded-topk union-of-local-top-k
    prefilter), exchange candidates to each expert's owner with one
    all_to_all, and re-rank at the owner by global stable pair position.

    Why the prefilter is lossless: a pair's owner-side rank within its
    expert counts only *arrived* earlier pairs, so it can undercount the
    global rank — but any missing earlier pair was locally dropped (local
    rank >= cap), and the cap locally-kept pairs preceding *it* all arrive,
    so an undercounted pair already has >= cap arrivals ahead of it. Hence
    owner rank < cap iff global rank < cap, and they are equal on every
    kept pair — the global GShard cut, computed from P local cuts.
    """
    from repro.engine import api
    from repro.kernels.route_fuse import moe_route_pallas, moe_route_xla
    T_loc, E = lg.shape
    d = lax.axis_index(axis_name).astype(jnp.int32)
    Npl = T_loc * k
    E_loc = E // n_dev
    A = min(Npl, E_loc * cap)      # kept-per-owner bound: both are hard caps
    span = n_dev * Npl             # one expert's band of global pair ranks
    if local_variant == "fused":
        route = moe_route_pallas(lg[None], k, cap, interpret=interpret)
    else:
        route = moe_route_xla(lg[None], k, cap)
    e_s, _t_s, perm, w_s, _slab, keep = (x[0] for x in route)
    keep = keep.astype(bool)

    # ---- pack the locally-kept candidates into (n_dev, A) owner rows -----
    grank = d * Npl + perm                     # global stable pair position
    ckey = e_s * span + grank                  # global compound sort key
    owner = jnp.clip(e_s // E_loc, 0, n_dev - 1)
    onehot_o = owner[:, None] == lax.broadcasted_iota(jnp.int32,
                                                      (Npl, n_dev), 1)
    sel = onehot_o & keep[:, None]
    col = jnp.sum(jnp.where(sel, jnp.cumsum(
        sel.astype(jnp.int32), axis=0) - 1, 0), axis=1)
    row = jnp.where(keep, owner, n_dev)        # dropped lanes -> dump row
    send_k = jnp.full((n_dev + 1, A), _NEG_PAD, jnp.int32)
    send_w = jnp.zeros((n_dev + 1, A), jnp.int32)
    wbits = lax.bitcast_convert_type(w_s, jnp.int32)
    # negate so the engine's DESCENDING sort yields ascending compound order
    send_k = send_k.at[row, col].set(jnp.where(keep, -ckey, _NEG_PAD))
    send_w = send_w.at[row, col].set(wbits)
    cnt_send = jnp.sum(sel.astype(jnp.int32), axis=0)             # (n_dev,)

    # ---- one all_to_all: candidates travel to their expert's owner -------
    recv_k = lax.all_to_all(send_k[:n_dev], axis_name, split_axis=0,
                            concat_axis=0, tiled=True)            # (P, A)
    recv_w = lax.all_to_all(send_w[:n_dev], axis_name, split_axis=0,
                            concat_axis=0, tiled=True)
    cnt = lax.all_to_all(cnt_send, axis_name, split_axis=0,
                         concat_axis=0, tiled=True)
    total = jnp.sum(cnt)

    # ---- owner merge: P sorted runs -> global stable order, re-cut -------
    keys, pay = api.sort(recv_k.reshape(-1), values={"w": recv_w.reshape(-1)},
                         stable=True, plan=Plan("flims", w=w, chunk=512))
    M = n_dev * A
    iota_m = lax.broadcasted_iota(jnp.int32, (M,), 0)
    valid = iota_m < total                     # pads sort to the tail
    ckey2 = -keys
    e_g = jnp.where(valid, ckey2 // span, E)
    gr = jnp.where(valid, ckey2 % span, 0)
    el = jnp.where(valid, e_g - (d * E_loc), E_loc)
    onehot_e = el[:, None] == lax.broadcasted_iota(jnp.int32, (M, E_loc), 1)
    counts = jnp.sum(onehot_e.astype(jnp.int32), axis=0)
    first = jnp.cumsum(counts) - counts
    pos = iota_m - jnp.sum(jnp.where(onehot_e, first[None, :], 0), axis=1)
    keep2 = valid & (pos < cap)
    if record:
        jax.debug.callback(_emit_route_ep, total,
                           total - jnp.sum(keep2.astype(jnp.int32)))
    return RouteShard(
        experts=e_g,
        tokens=jnp.where(valid, gr // k, 0),
        perm=gr,
        weights=jnp.where(valid, lax.bitcast_convert_type(pay["w"],
                                                          jnp.float32), 0.0),
        slabs=jnp.where(keep2, el * cap + pos, E_loc * cap),
        keep=keep2,
        count=total.reshape(1),
    )


_NEG_PAD = jnp.iinfo(jnp.int32).min + 1   # -ckey of any real pair is larger


@partial(jax.jit, static_argnames=("k", "capacity", "mesh", "axis", "plan",
                                   "record"))
def _route_ep_impl(logits, k, capacity, mesh, axis, plan, record):
    n_dev = mesh.shape[axis]
    T, E = logits.shape
    assert T % n_dev == 0, f"moe_route_ep: T={T} not divisible by P={n_dev}"
    assert E % n_dev == 0, f"moe_route_ep: E={E} not divisible by P={n_dev}"
    T_loc = T // n_dev
    span = n_dev * T_loc * k
    assert E * span < 2 ** 31, (
        f"moe_route_ep: compound key e*{span}+grank overflows int32 at "
        f"E={E}; shrink the token chunk")
    local_variant = plan.variant if plan.variant in ("fused", "xla") \
        else "xla"
    from repro.engine.schedule import default_interpret
    obs.event("moe.route_ep.plan", n_dev=n_dev, axis=axis, t_local=T_loc,
              experts=E, k=k, capacity=int(capacity),
              cand_cap=min(T_loc * k, (E // n_dev) * int(capacity)),
              local_variant=local_variant)
    fn = partial(_route_ep_pass, axis_name=axis, n_dev=n_dev, k=k,
                 cap=int(capacity), local_variant=local_variant,
                 w=plan.w, interpret=default_interpret(),
                 record=record)
    spec = RouteShard(*([P(axis)] * 7))
    return jax.shard_map(fn, mesh=mesh, in_specs=P(axis), out_specs=spec,
                         check_vma=False)(logits)


def run_moe_route_ep(logits, k: int, capacity: int, mesh, axis: str = "data",
                     *, plan: Optional[Plan] = None):
    """Expert-parallel MoE routing: (T, E) logits token-sharded over ``axis``
    (P devices), experts owned contiguously (device d owns
    ``[d*E/P, (d+1)*E/P)``). Returns a :class:`RouteShard` whose lanes have
    spec P(axis): each device's slice holds the pairs routed to ITS experts
    in global stable order, with local slab indices ready to scatter into a
    per-device (E/P * cap + 1, d) slab buffer. The keep mask equals the
    unsharded :func:`~repro.engine.api.moe_route` capacity cut on the
    gathered logits, pair for pair."""
    plan = plan or Plan("xla")
    return _route_ep_impl(logits, k, int(capacity), mesh, axis, plan,
                          obs.enabled())

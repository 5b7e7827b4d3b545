"""Merge schedules: ONE description of how K sorted runs reduce to one.

Every multi-pass merge in the repo — ``flims_sort``'s chunk tree,
``pmt_merge``'s PMT reduction, the two-phase segmented sort's merge passes,
``sample_sort``'s local K-way reduction, and the public
``engine.merge_runs`` — used to carry its own private level loop. A
``MergeSchedule`` replaces them all: a plan-cached, autotunable value object
naming the executor (``xla`` | ``tree_vmapped`` | ``tree_pallas``), how many
tree levels each fused pass executes (``levels_per_pass``), the FLiMS tile
parameters (``w``, ``block_out``) and the tie policy (``'b'`` | ``'skew'``,
paper §4.1 — key-only formulations; the stable compound order has no ties).

Executors (DESIGN.md §5):

- ``xla``           one shot: per-group lexicographic sort (rank-then-key
                    double stable argsort) — the planner's CPU/GPU default.
- ``tree_vmapped``  the classic per-level scheme: one vmapped FLiMS lane
                    merge per tree level (each level a full HBM round trip).
- ``tree_pallas``   batched Pallas passes: ``levels_per_pass == 1`` runs the
                    segmented pair-merge kernel per level;
                    ``levels_per_pass >= 2`` runs ``kernels/merge_tree`` —
                    multiple tree levels fused into one ``pallas_call`` with
                    the intermediate runs resident in kernel scratch.
- ``stream_pallas`` the out-of-core level kind: runs LIVE IN HBM and each
                    pass is one ``kernels/stream_merge`` call that merges
                    ``fan_in = 2^levels_per_pass`` runs per group through
                    double-buffered DMA windows — the working set never has
                    to fit a pallas_call's scratch (DESIGN.md §8).
- ``stream_xla``    the same HBM-resident pass structure on XLA: each pass
                    is ``log2(fan_in)`` rounds of vectorised searchsorted
                    pairwise merges (no per-pass re-sort) — the CPU/GPU
                    executor of ``engine.external_sort`` phase 2.

The flat calling convention is *grouped contiguous runs*: a flat buffer of
``R = n_groups * runs_per_group`` descending (or ascending, see below) runs
described by an ``(R+1,)`` offsets vector, consecutive ``runs_per_group``
runs forming one independent reduction. ``engine.merge_runs`` is the
single-group case; the two-phase segment sort is the many-group case.

Stability and direction: with ``ranks=`` every executor orders ties by the
compound ``(key, rank asc)`` order (paper algorithm 3) bit-for-bit. The
Pallas executors sort ascending natively (static direction flag); the
vmapped lane executor mirrors — runs are reversed per segment and ranks
negated around ``INVALID_RANK - 1`` so the descending compound merge of the
mirror IS the ascending compound merge reversed.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.core.flims import next_pow2 as _next_pow2
from repro.core.lanes import (INVALID_RANK, KEY, RANK, merge_lanes,
                              stable_compare)
from repro.engine import segments
from repro.kernels.flims_merge import bound_keys, lane_first

#: mirror pivot for the ascending rank trick (INVALID_RANK stays padding)
_RANK_MIRROR = INVALID_RANK - 1

_VARIANTS = ("xla", "tree_vmapped", "tree_pallas", "stream_pallas",
             "stream_xla")

#: executors whose per-pass inputs are HBM-resident runs, not scratch banks
STREAM_VARIANTS = ("stream_pallas", "stream_xla")


@dataclasses.dataclass(frozen=True)
class MergeSchedule:
    """How K sorted runs become one: executor + fused-pass shape + tiles."""
    variant: str = "tree_vmapped"
    levels_per_pass: int = 1
    w: int = 32
    block_out: int = 1024
    tie: str = "b"

    def __post_init__(self):
        assert self.variant in _VARIANTS, self.variant
        assert self.levels_per_pass >= 1
        assert self.tie in ("b", "skew")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "MergeSchedule":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})

    @classmethod
    def from_plan(cls, plan, variant: Optional[str] = None) -> "MergeSchedule":
        """Lift an engine ``Plan`` (which carries ``levels``/``tie`` since
        PR 3) into a MergeSchedule; ``variant`` overrides the plan's."""
        v = variant or plan.variant
        if v not in _VARIANTS:
            v = "tree_vmapped"
        return cls(variant=v, levels_per_pass=getattr(plan, "levels", 1),
                   w=plan.w, block_out=plan.block_out,
                   tie=getattr(plan, "tie", "b"))

    def to_plan(self, **extra):
        """Lower the schedule into an engine ``Plan`` (the inverse of
        ``from_plan``) — how a raw ``merge_schedule=`` kwarg enters the
        planned sharded ops. ``extra`` sets further Plan fields
        (``cap_factor``, ``splitter``, ``retries``, ...)."""
        from repro.engine.planner import Plan
        return Plan(variant=self.variant, w=self.w, block_out=self.block_out,
                    levels=self.levels_per_pass, tie=self.tie, **extra)

    def replace(self, **kw) -> "MergeSchedule":
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _uniform_len(offsets) -> Optional[int]:
    """Static per-run length when offsets are concrete and uniform."""
    import numpy as np
    if not segments.is_concrete(offsets):
        return None
    lens = np.diff(np.asarray(offsets))
    if lens.size and (lens == lens[0]).all() and int(lens[0]) > 0:
        return int(lens[0])
    return None


def default_interpret() -> bool:
    """Pallas kernels interpret everywhere but on a real TPU backend — the
    one backend predicate the schedule consumers share."""
    return jax.default_backend() != "tpu"


def schedule_or(schedule: Optional[MergeSchedule], w: int,
                tie: str = "b") -> MergeSchedule:
    """The consumers' default: the classic per-level vmapped tree at ``w``."""
    if schedule is not None:
        return schedule
    return MergeSchedule("tree_vmapped", w=w, tie=tie)


def _mirror(keys, offsets, ranks):
    """Reverse every run and flip rank priorities: the descending compound
    merge of the mirror, un-mirrored per group, is the ascending compound
    merge."""
    n = keys.shape[0]
    rev_k = segments.reverse_segments(keys, offsets, n)
    if ranks is None:
        return rev_k, None
    rev_r = segments.reverse_segments(_RANK_MIRROR - ranks, offsets, n)
    return rev_k, rev_r


def _unmirror(keys, ranks, group_offsets):
    """Undo ``_mirror`` on the merged output: each GROUP's descending
    sequence reverses in place (group order itself must not flip)."""
    n = keys.shape[0]
    k = segments.reverse_segments(keys, group_offsets, n)
    if ranks is None:
        return k
    return k, segments.reverse_segments(_RANK_MIRROR - ranks, group_offsets,
                                        n)


def _pad_group_runs(offsets, m: int, m2: int):
    """Extend each group's ``m`` contiguous runs with ``m2 - m`` empty runs
    (start = group end, len = 0). Returns flat (R2,) starts and lens."""
    starts = offsets[:-1].reshape(-1, m)
    lens = jnp.diff(offsets).reshape(-1, m)
    gend = offsets[m::m].reshape(-1, 1)            # end offset of each group
    pad_s = jnp.broadcast_to(gend, (starts.shape[0], m2 - m))
    starts = jnp.concatenate([starts, pad_s], axis=1).reshape(-1)
    lens = jnp.concatenate(
        [lens, jnp.zeros((lens.shape[0], m2 - m), lens.dtype)],
        axis=1).reshape(-1)
    return starts.astype(jnp.int32), lens.astype(jnp.int32)


# --------------------------------------------------------------------------
# executors (all descending; direction is normalised by merge_runs)
# --------------------------------------------------------------------------

def _xla_reduce(keys, offsets, ranks, m: int, descending: bool):
    """One-shot per-group sort. Key-only: a directional segment sort. KV:
    the lexicographic double-stable-argsort — order rows by rank, then
    stably by key — so ties land in rank order for ANY rank assignment."""
    from repro.kernels.segmented_merge import padded_bank, unpad_bank
    n = keys.shape[0]
    goff = offsets[::m]
    cap = segments.static_cap(goff, n)
    _, last_k = bound_keys(keys.dtype, descending)
    kb = padded_bank(keys, goff, cap, fill=last_k)
    if ranks is None:
        out = jnp.sort(kb, axis=-1, descending=descending)
        return unpad_bank(out, goff, n)
    rb = padded_bank(ranks, goff, cap, fill=INVALID_RANK)
    p1 = jnp.argsort(rb, axis=-1, stable=True)
    kb1 = jnp.take_along_axis(kb, p1, axis=-1)
    p2 = jnp.argsort(kb1, axis=-1, stable=True, descending=descending)
    perm = jnp.take_along_axis(p1, p2, axis=-1)
    return (unpad_bank(jnp.take_along_axis(kb, perm, axis=-1), goff, n),
            unpad_bank(jnp.take_along_axis(rb, perm, axis=-1), goff, n))


def _vmapped_reduce(keys, offsets, ranks, m: int, sched: MergeSchedule,
                    uniform_len: Optional[int] = None):
    """The per-level tree: one vmapped FLiMS lane merge per level (descending
    only — ``merge_runs`` mirrors ascending calls into this form)."""
    from repro.core.flims import flims_merge_ref, sentinel_for
    n = keys.shape[0]
    K = offsets.shape[0] - 1
    n_groups = K // m
    # offsets created inside a jit trace are tracers even when their values
    # are static (ambient tracing), so concreteness sniffing alone would
    # silently fall through to the padded-bank path and pad EVERY run to
    # next_pow2(total) — quadratic memory, and an int32-overflow crash at
    # n = 2^20 with 2048 chunks. Callers that know the uniform run length
    # statically (reduce_rows) pass it explicitly.
    ulen = uniform_len if uniform_len is not None else _uniform_len(offsets)
    if ulen is not None:
        krows = keys.reshape(K, ulen)
        rrows = None if ranks is None else ranks.reshape(K, ulen)
    else:
        from repro.kernels.segmented_merge import padded_bank
        cap = segments.static_cap(offsets, n)
        krows = padded_bank(keys, offsets, cap)
        rrows = None if ranks is None else padded_bank(ranks, offsets, cap,
                                                       fill=INVALID_RANK)
    m2 = _next_pow2(m)
    if m2 != m:                      # sentinel runs complete each group
        cap = krows.shape[1]
        pad = jnp.full((n_groups, m2 - m, cap), sentinel_for(keys.dtype),
                       keys.dtype)
        krows = jnp.concatenate([krows.reshape(n_groups, m, cap), pad],
                                axis=1).reshape(n_groups * m2, cap)
        if rrows is not None:
            rpad = jnp.full((n_groups, m2 - m, cap), INVALID_RANK, jnp.int32)
            rrows = jnp.concatenate([rrows.reshape(n_groups, m, cap), rpad],
                                    axis=1).reshape(n_groups * m2, cap)
    if rrows is None:
        merge = jax.vmap(
            lambda a, b: flims_merge_ref(a, b, sched.w, tie=sched.tie))
        while krows.shape[0] > n_groups:
            krows = merge(krows[0::2], krows[1::2])
    else:
        def merge_kv(ka, ra, kb, rb):
            out = merge_lanes({KEY: ka, RANK: ra}, {KEY: kb, RANK: rb},
                              w=sched.w, compare=stable_compare)
            return out[KEY], out[RANK]
        merge = jax.vmap(merge_kv)
        while krows.shape[0] > n_groups:
            krows, rrows = merge(krows[0::2], rrows[0::2],
                                 krows[1::2], rrows[1::2])
    # gather each group's valid prefix back to the flat layout
    from repro.kernels.segmented_merge import unpad_bank
    glen = jnp.diff(offsets).reshape(n_groups, m).sum(axis=1)
    goff = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(glen)]).astype(jnp.int32)
    krows = krows.reshape(n_groups, -1)
    if rrows is None:
        return unpad_bank(krows, goff, n)
    return (unpad_bank(krows, goff, n),
            unpad_bank(rrows.reshape(n_groups, -1), goff, n))


def _pallas_reduce(keys, offsets, ranks, m: int, sched: MergeSchedule,
                   descending: bool, interpret: bool,
                   uniform_len: Optional[int] = None):
    """Fused-pass tree: each pass collapses ``2^levels_per_pass`` runs per
    group in one ``pallas_call`` (the segmented pair kernel at one level,
    the merge-tree kernel at two or more).

    Runs of one static length (``uniform_len``, else read from concrete
    offsets) that are whole ``w``-wide rows, with no empty runs to pad a
    group, keep that length through the passes; a pass whose groups are
    also whole output blocks hands it to the kernel, which then builds its
    banks by a pad and unpads by a reshape instead of gathering
    (``bank="uniform"`` on the pass's event, else ``"gather"``).

    Stage scopes: pass ``i`` (from 0) runs under
    ``repro.schedule.pass<i>_L<levels>``, so a device trace counts the
    passes a call made; the run bounds each pass reads (``starts``,
    ``lens``) are computed under ``repro.schedule.run_bounds``."""
    from repro.kernels.merge_tree import merge_tree_runs, merge_tree_runs_kv
    from repro.kernels.segmented_merge import (segmented_merge_runs,
                                               segmented_merge_runs_kv)
    n = keys.shape[0]
    m2 = _next_pow2(m)
    levels_total = m2.bit_length() - 1
    passes = 0
    ulen = uniform_len if uniform_len is not None else _uniform_len(offsets)
    if (ulen is None or ulen % sched.w or m2 != m
            or ulen * (offsets.shape[0] - 1) != n):
        ulen = None
    with obs.kernel_scope("schedule.run_bounds"):
        starts, lens = _pad_group_runs(offsets, m, m2)
    buf, rbuf = keys, ranks
    while m2 > 1:
        Lp = min(sched.levels_per_pass, m2.bit_length() - 1)
        # clamp the block to this pass's per-group output so the padded
        # (G, C) block buffer stays O(n) even with many runs per pass
        groups = max(starts.shape[0] >> Lp, 1)
        bo = max(sched.w, min(sched.block_out, _next_pow2(-(-n // groups))))
        # the kernels' block is ``bo`` itself when a multiple of ``w``
        dense = (ulen is not None and bo % sched.w == 0
                 and (ulen << Lp) % bo == 0)
        bank = "uniform" if dense else "gather"
        obs.event("schedule.pass", executor="tree_pallas", levels=int(Lp),
                  runs=int(starts.shape[0]), n=int(n), block_out=int(bo),
                  kv=rbuf is not None, bank=bank)
        obs.inc(f"schedule.bank.{bank}")
        ul = ulen if dense else None
        with obs.kernel_scope(f"schedule.pass{passes}_L{Lp}"):
            if Lp == 1:
                if rbuf is None:
                    buf = segmented_merge_runs(
                        buf, buf, starts[0::2], lens[0::2], starts[1::2],
                        lens[1::2], n_out=n, w=sched.w, block_out=bo,
                        uniform_len=ul, interpret=interpret)
                else:
                    buf, rbuf = segmented_merge_runs_kv(
                        buf, rbuf, buf, rbuf, starts[0::2], lens[0::2],
                        starts[1::2], lens[1::2], n_out=n, w=sched.w,
                        block_out=bo, descending=descending, uniform_len=ul,
                        interpret=interpret)
            else:
                if rbuf is None:
                    buf = merge_tree_runs(
                        buf, starts, lens, group=1 << Lp, n_out=n, w=sched.w,
                        block_out=bo, uniform_len=ul, interpret=interpret)
                else:
                    buf, rbuf = merge_tree_runs_kv(
                        buf, rbuf, starts, lens, group=1 << Lp, n_out=n,
                        w=sched.w, block_out=bo, descending=descending,
                        uniform_len=ul, interpret=interpret)
        passes += 1
        if ulen is not None:
            ulen <<= Lp
        with obs.kernel_scope("schedule.run_bounds"):
            lens = lens.reshape(-1, 1 << Lp).sum(axis=1).astype(jnp.int32)
            starts = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32),
                 jnp.cumsum(lens)[:-1]]).astype(jnp.int32)
        m2 >>= Lp
    # the per-level tree would have taken `levels_total` HBM round trips;
    # the fused passes took `passes` — the difference is the saving this
    # schedule bought (PR 3's whole point, now observable).
    obs.event("schedule.reduce", executor="tree_pallas", passes=passes,
              levels_total=levels_total,
              hbm_trips_saved=levels_total - passes, n=int(n),
              kv=ranks is not None)
    return buf if rbuf is None else (buf, rbuf)


def _bcount(xk, xr, vk, vr, pred, length: int):
    """Per-element monotone-prefix count: for each query ``v[i, j]`` the
    number of elements in sorted row ``x[i]`` satisfying ``pred`` (true on
    a prefix of the row). Vectorised binary search — no per-pass re-sort."""
    lo = jnp.zeros(vk.shape, jnp.int32)
    hi = jnp.full(vk.shape, length, jnp.int32)

    def step(_, lh):
        lo_, hi_ = lh
        mid = (lo_ + hi_) // 2
        take = lambda a: jnp.take_along_axis(
            a, jnp.minimum(mid, length - 1), axis=-1)
        ok = pred(take(xk), None if xr is None else take(xr), vk, vr)
        ok = ok & (mid < hi_)
        return jnp.where(ok, mid + 1, lo_), jnp.where(ok, hi_, mid)

    return lax.fori_loop(0, max(length, 2).bit_length() + 1, step,
                         (lo, hi))[0]


def _pair_merge_rows(k, r, descending: bool):
    """Merge adjacent row pairs of a ``(R, L)`` bank of sorted rows into
    ``(R/2, 2L)`` by computing every element's merged position directly
    (scatter by rank count). Key-only ties take the even (A) row first;
    with ranks the compound ``(key, rank)`` order decides — equal compound
    lanes (sentinel padding) still land A-first, keeping pads contiguous."""
    R2, L = k.shape[0] // 2, k.shape[1]
    a, b = k[0::2], k[1::2]
    if r is not None:
        ra, rb = r[0::2], r[1::2]
        first = lane_first(descending)
        prec = lambda xk, xr, vk, vr: first(xk, xr, vk, vr)
        prec_or_tie = lambda xk, xr, vk, vr: ~first(vk, vr, xk, xr)
        ca = _bcount(b, rb, a, ra, prec, L)           # b strictly before a_i
        cb = _bcount(a, ra, b, rb, prec_or_tie, L)    # a before-or-tying b_j
    else:
        ra = rb = None
        if descending:
            prec = lambda xk, _, vk, __: xk > vk
            prec_or_tie = lambda xk, _, vk, __: xk >= vk
        else:
            prec = lambda xk, _, vk, __: xk < vk
            prec_or_tie = lambda xk, _, vk, __: xk <= vk
        ca = _bcount(b, None, a, None, prec, L)
        cb = _bcount(a, None, b, None, prec_or_tie, L)
    idx = jnp.arange(L, dtype=jnp.int32)[None, :]
    rows = jnp.arange(R2, dtype=jnp.int32)[:, None]
    ko = jnp.zeros((R2, 2 * L), k.dtype)
    ko = ko.at[rows, idx + ca].set(a).at[rows, idx + cb].set(b)
    if r is None:
        return ko, None
    ro = jnp.zeros((R2, 2 * L), jnp.int32)
    ro = ro.at[rows, idx + ca].set(ra).at[rows, idx + cb].set(rb)
    return ko, ro


def stream_pass(buf, rbuf, *, runs: int, run_len: int, fan_in: int,
                executor: str, w: int, block_out: int, descending: bool,
                interpret: bool, out_slack: int = 0):
    """ONE out-of-core pass: consecutive groups of ``fan_in`` HBM-resident
    uniform sorted runs (``runs`` total, each ``run_len`` elements, a power
    of two ``>= w``) each merge into one run of ``fan_in * run_len``.

    ``buf``/``rbuf`` are flat; with ``executor='stream_pallas'`` they may
    carry trailing slack past ``runs * run_len`` (``stream_merge.stream_slack``)
    and the returned buffers carry ``>= out_slack``, so a pass chain touches
    HBM exactly once per pass. This is the primitive ``engine.external_sort``
    phase 2 drives directly."""
    n_val = runs * run_len
    if executor == "stream_pallas":
        from repro.kernels.stream_merge import (stream_merge_runs,
                                                stream_merge_runs_kv)
        if rbuf is None:
            return stream_merge_runs(
                buf, runs=runs, run_len=run_len, fan_in=fan_in, w=w,
                block_out=block_out, out_slack=out_slack,
                interpret=interpret), None
        return stream_merge_runs_kv(
            buf, rbuf, runs=runs, run_len=run_len, fan_in=fan_in, w=w,
            block_out=block_out, out_slack=out_slack, descending=descending,
            interpret=interpret)
    k = buf[:n_val].reshape(runs, run_len)
    r = None if rbuf is None else rbuf[:n_val].reshape(runs, run_len)
    f = fan_in
    while f > 1:
        k, r = _pair_merge_rows(k, r, descending)
        f >>= 1
    return k.reshape(-1), None if r is None else r.reshape(-1)


def _stream_reduce(keys, offsets, ranks, m: int, sched: MergeSchedule,
                   descending: bool, interpret: bool,
                   uniform_len: Optional[int] = None):
    """HBM-resident level kind: uniformise the ragged runs once (a no-op
    when rows are already uniform power-of-two), then reduce each group with
    ``ceil(log_fan_in(m))`` streamed passes instead of ``log2(m)`` levels."""
    from repro.kernels.segmented_merge import padded_bank, unpad_bank
    n = keys.shape[0]
    K = offsets.shape[0] - 1
    n_groups = K // m
    fan = 1 << max(sched.levels_per_pass, 1)
    _, last_k = bound_keys(keys.dtype, descending)

    ulen = uniform_len if uniform_len is not None else _uniform_len(offsets)
    if (ulen is not None and ulen >= sched.w
            and ulen & (ulen - 1) == 0 and ulen * K == n):
        run_len = ulen
        krows = keys.reshape(K, run_len)
        rrows = None if ranks is None else ranks.reshape(K, run_len)
    else:
        run_len = max(_next_pow2(segments.static_cap(offsets, n)), sched.w)
        krows = padded_bank(keys, offsets, run_len, fill=last_k)
        rrows = (None if ranks is None else
                 padded_bank(ranks, offsets, run_len, fill=INVALID_RANK))
    m2 = _next_pow2(m)
    if m2 != m:                          # sentinel runs complete each group
        pad = jnp.full((n_groups, m2 - m, run_len), last_k, keys.dtype)
        krows = jnp.concatenate([krows.reshape(n_groups, m, run_len), pad],
                                axis=1).reshape(n_groups * m2, run_len)
        if rrows is not None:
            rpad = jnp.full((n_groups, m2 - m, run_len), INVALID_RANK,
                            jnp.int32)
            rrows = jnp.concatenate(
                [rrows.reshape(n_groups, m, run_len), rpad],
                axis=1).reshape(n_groups * m2, run_len)

    levels_total = m2.bit_length() - 1
    buf = krows.reshape(-1)
    rbuf = None if rrows is None else rrows.reshape(-1)
    n_runs, mleft, passes = n_groups * m2, m2, 0
    slack = 0
    if sched.variant == "stream_pallas":
        from repro.kernels.stream_merge import stream_slack
        slack = stream_slack(fan, sched.w, sched.block_out)
    while mleft > 1:
        f = min(fan, mleft)
        passes += 1
        obs.event("schedule.pass", executor=sched.variant,
                  levels=f.bit_length() - 1, runs=int(n_runs),
                  n=int(n_runs * run_len), kv=rbuf is not None,
                  level_kind="hbm_run")
        with jax.named_scope(f"repro.schedule.stream_pass_f{f}"):
            buf, rbuf = stream_pass(
                buf, rbuf, runs=n_runs, run_len=run_len, fan_in=f,
                executor=sched.variant, w=sched.w,
                block_out=sched.block_out, descending=descending,
                interpret=interpret, out_slack=slack)
        n_runs //= f
        run_len *= f
        mleft //= f
    obs.event("schedule.reduce", executor=sched.variant, passes=passes,
              levels_total=levels_total,
              hbm_trips_saved=levels_total - passes, n=int(n),
              kv=ranks is not None)

    # gather each group's valid prefix back to the flat ragged layout
    glen = jnp.diff(offsets).reshape(n_groups, m).sum(axis=1)
    goff = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(glen)]).astype(jnp.int32)
    kb = buf[:n_groups * run_len].reshape(n_groups, run_len)
    if rbuf is None:
        return unpad_bank(kb, goff, n)
    return (unpad_bank(kb, goff, n),
            unpad_bank(rbuf[:n_groups * run_len].reshape(n_groups, run_len),
                       goff, n))


# --------------------------------------------------------------------------
# the one entry point every former tree loop compiles to
# --------------------------------------------------------------------------

def merge_runs(keys, offsets, *, ranks=None, schedule: MergeSchedule,
               runs_per_group: Optional[int] = None, descending: bool = True,
               interpret: bool = True, uniform_len: Optional[int] = None):
    """Reduce grouped contiguous sorted runs to one sorted run per group.

    ``keys`` is the flat concatenation of ``R`` runs with boundaries
    ``offsets`` ((R+1,)); each run is sorted in the call's direction, empty
    runs are fine, and consecutive ``runs_per_group`` runs (default: all R)
    reduce independently. Returns the flat merged groups in group order.
    With ``ranks=`` (int32, any priority assignment) the reduction is the
    stable compound-order merge and returns ``(keys, ranks)``.
    """
    offsets = jnp.asarray(offsets, jnp.int32)
    K = offsets.shape[0] - 1
    m = runs_per_group or max(K, 1)
    assert K % max(m, 1) == 0, "run count must divide into equal groups"
    n = keys.shape[0]
    if ranks is not None:
        ranks = jnp.asarray(ranks, jnp.int32)
    if K <= 1 or m == 1 or n == 0:
        return keys if ranks is None else (keys, ranks)

    sched = schedule
    if not descending:
        if sched.variant == "xla":
            pass                              # sorts ascending natively
        elif (sched.variant in ("tree_pallas",) + STREAM_VARIANTS
                and ranks is not None):
            pass                              # static direction flag
        else:
            keys, ranks = _mirror(keys, offsets, ranks)
            out = merge_runs(keys, offsets, ranks=ranks, schedule=sched,
                             runs_per_group=m, descending=True,
                             interpret=interpret, uniform_len=uniform_len)
            goff = offsets[::m]               # group boundaries survive
            return (_unmirror(out, None, goff) if ranks is None
                    else _unmirror(out[0], out[1], goff))

    levels_total = _next_pow2(m).bit_length() - 1
    if sched.variant == "xla":
        obs.event("schedule.reduce", executor="xla", passes=1,
                  levels_total=levels_total, hbm_trips_saved=levels_total - 1,
                  n=int(n), kv=ranks is not None)
        with jax.named_scope("repro.schedule.xla_reduce"):
            return _xla_reduce(keys, offsets, ranks, m, descending)
    if sched.variant == "tree_vmapped":
        obs.event("schedule.reduce", executor="tree_vmapped",
                  passes=levels_total, levels_total=levels_total,
                  hbm_trips_saved=0, n=int(n), kv=ranks is not None)
        with jax.named_scope("repro.schedule.vmapped_reduce"):
            return _vmapped_reduce(keys, offsets, ranks, m, sched,
                                   uniform_len=uniform_len)
    if sched.variant in STREAM_VARIANTS:
        with jax.named_scope("repro.schedule.stream_reduce"):
            return _stream_reduce(keys, offsets, ranks, m, sched, descending,
                                  interpret, uniform_len=uniform_len)
    return _pallas_reduce(keys, offsets, ranks, m, sched, descending,
                          interpret, uniform_len=uniform_len)


def reduce_rows(rows, *, schedule: MergeSchedule, ranks=None,
                runs_per_group: Optional[int] = None, descending: bool = True,
                interpret: bool = True):
    """Uniform-rows convenience form: merge the K rows of a ``(K, n)`` bank
    (each a sorted run) per group of ``runs_per_group`` consecutive rows.
    The PMT / flims_sort / sample-sort shape — rows are already banked, so
    no repacking gather is needed on the vmapped path. Returns the flat
    merged groups (and ranks, when given)."""
    K, n = rows.shape
    with obs.kernel_scope("schedule.run_bounds"):
        offsets = jnp.arange(K + 1, dtype=jnp.int32) * n
    return merge_runs(rows.reshape(-1), offsets,
                      ranks=None if ranks is None else ranks.reshape(-1),
                      schedule=schedule, runs_per_group=runs_per_group,
                      descending=descending, interpret=interpret,
                      uniform_len=n)

"""Pallas TPU kernel: segmented (batched ragged) FLiMS merge and sort.

Extends the merge-path partitioning of ``kernels/flims_merge.py`` (DESIGN.md
§2) from one merge to a whole *ragged batch* of merges in a single
``pallas_call``: the grid is flattened over (segment, output-block) pairs and
one SMEM row per grid step carries the co-rank row/rotation of each input
run. Because every output block is ``C`` elements with ``C`` a
multiple of ``w``, the FLiMS rotation invariant ``(lA + lB) ≡ 0 (mod w)``
holds at every (segment, block) boundary, so each grid step starts the banked
dataflow mid-rotation with zero realignment — the same property the
single-merge kernel exploits, now across an arbitrary ragged batch.

Layout: each run is repacked (host-side gather) into its own row-aligned
sentinel-padded bank of width ``w``; run ``s`` owns rows
``[row0[s], row0[s+1])``. Per-segment co-ranks are found by the same
vectorised merge-path binary search, but bounded by *dynamic* run lengths.
Empty segments and one-sided runs need no special casing: their banks are all
sentinel rows and the selector drains the other side.

This is the compute core of ``repro.engine.segment_merge`` /
``segment_sort`` (DESIGN.md §3), i.e. the MoE-dispatch / ragged-batch shape.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.flims import sentinel_for, next_pow2 as _next_pow2
from repro.core.lanes import INVALID_RANK
from repro.kernels.bitonic_sort import (_bitonic_rows_desc, _sort_kv_kernel,
                                        sort_chunks_kv_pallas)
from repro.kernels.flims_merge import (_merge_kernel, _merge_kv_kernel,
                                       bound_keys, lane_first, merge_call,
                                       pad_rows, plus_inf_for)
from repro import obs


def padded_bank(values, offsets, cap: int, fill=None):
    """Gather a ragged batch into a dense padded (S, cap) bank.

    Shared by both segment-sort strategies and re-exported as
    ``engine.pad_segments``. ``cap`` must cover the longest segment; shorter
    tails are filled with ``fill`` (default: the dtype sentinel, which sorts
    last descending — ascending callers pass ``plus_inf_for``).
    """
    S = offsets.shape[0] - 1
    N = values.shape[0]
    fill = sentinel_for(values.dtype) if fill is None else fill
    if N == 0:
        return jnp.full((S, cap), fill, values.dtype)
    offsets = offsets.astype(jnp.int32)
    lens = jnp.diff(offsets)
    idx = jnp.arange(cap, dtype=jnp.int32)
    src = jnp.clip(offsets[:-1, None] + idx[None, :], 0, N - 1)
    return jnp.where(idx[None, :] < lens[:, None], values[src], fill)


def unpad_bank(bank, offsets, total: int):
    """Inverse of ``padded_bank``: gather the valid prefixes back flat.

    The single unpad gather shared by the segment-sort/argsort strategies
    and re-exported as ``engine.unpad_segments``.
    """
    offsets = offsets.astype(jnp.int32)
    S = bank.shape[0]
    i = jnp.arange(total, dtype=jnp.int32)
    s = jnp.clip(jnp.searchsorted(offsets, i, side="right") - 1, 0, S - 1)
    return bank[s, i - offsets[s]]


_plus_inf_for = plus_inf_for       # back-compat alias (moved to flims_merge)


def _build_bank(buf, starts, lens, row0, cap_rows: int, w: int, fill=None):
    """Gather flat runs into a (cap_rows, w) row-aligned padded bank.

    Run ``s`` (``buf[starts[s] : starts[s]+lens[s]]``) fills rows
    ``[row0[s], row0[s+1])`` row-major; everything else is ``fill``
    (default: the dtype sentinel — rank banks pass ``INVALID_RANK``,
    ascending key banks ``plus_inf_for``).
    """
    fill = sentinel_for(buf.dtype) if fill is None else fill
    if buf.shape[0] == 0:
        return jnp.full((cap_rows, w), fill, buf.dtype)
    rows = jnp.arange(cap_rows, dtype=jnp.int32)
    n_runs = starts.shape[0]
    s = jnp.clip(jnp.searchsorted(row0, rows, side="right") - 1, 0, n_runs - 1)
    base = (rows - row0[s]) * w                       # in-run offset of row
    idx = base[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    valid = (idx >= 0) & (idx < lens[s][:, None])
    src = jnp.clip(starts[s][:, None] + idx, 0, buf.shape[0] - 1)
    return jnp.where(valid, buf[src], fill)


def _uniform_bank(runs, pad: int, cap_rows: int, fill=None):
    """Dense twin of ``_build_bank`` for runs of one static length ``ℓ``
    (a multiple of ``w``): ``runs`` is (R, ℓ/w, w), each run is followed
    by ``pad`` fill rows and the bank by fill rows up to ``cap_rows``.
    Bit-identical to ``_build_bank`` with ``row0[s] = s·(ℓ/w + pad)``."""
    fill = sentinel_for(runs.dtype) if fill is None else fill
    bank = jnp.pad(runs, ((0, 0), (0, pad), (0, 0)), constant_values=fill)
    bank = bank.reshape(-1, runs.shape[2])
    return jnp.pad(bank, ((0, cap_rows - bank.shape[0]), (0, 0)),
                   constant_values=fill)


def _uniform_grid(G: int, group_len: int, C: int):
    """(group, output offset) of each of the ``G`` steps of an exact grid
    over groups of ``group_len`` elements, a multiple of ``C``."""
    per = group_len // C                              # blocks per group
    g = jnp.arange(G, dtype=jnp.int32)
    return g // per, (g % per) * C


def _corank_runs(o, la, lb, astart, bstart, a, b, steps: int):
    """Merge-path co-rank inside one (A-run, B-run) pair: #A-elements among
    the top-``o`` of the descending union, ties preferring B. ``la``/``lb``
    are *dynamic* run lengths; reads index the flat buffers with clipping."""
    bigA = _plus_inf_for(a.dtype)
    bigB = _plus_inf_for(b.dtype)
    sentA = sentinel_for(a.dtype)
    sentB = sentinel_for(b.dtype)
    nA = max(a.shape[0], 1)
    nB = max(b.shape[0], 1)
    ap = a if a.shape[0] else jnp.full((1,), sentA, a.dtype)
    bp = b if b.shape[0] else jnp.full((1,), sentB, b.dtype)

    def getA(i):
        v = ap[jnp.clip(astart + i, 0, nA - 1)]
        v = jnp.where(i < 0, bigA, v)
        return jnp.where(i >= la, sentA, v)

    def getB(i):
        v = bp[jnp.clip(bstart + i, 0, nB - 1)]
        v = jnp.where(i < 0, bigB, v)
        return jnp.where(i >= lb, sentB, v)

    lo = jnp.maximum(0, o - lb)
    hi = jnp.minimum(o, la)

    def step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi + 1) // 2
        ok = getA(mid - 1) > getB(o - mid)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, hi = lax.fori_loop(0, steps, step, (lo, hi))
    return lo


def _stage(name: str):
    """Stage scope of the segmented run merges: ``bank`` (the row-aligned
    banks), ``grid`` (the flat (segment, block) grid), ``corank`` (the
    per-step co-rank search), ``kernel`` (the ``pallas_call``) and
    ``unpad`` (the gather back to the flat layout)."""
    return obs.kernel_scope(f"kernels.segmented_merge.{name}")


def _unpad_blocks(outs, lo_len, blk0, n_out: int, G: int, C: int):
    """Gather each padded (G, C) block output back to the flat ragged
    layout of the R merged runs (lengths ``lo_len``)."""
    R = lo_len.shape[0]
    oo = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(lo_len)])
    i = jnp.arange(n_out, dtype=jnp.int32)
    s = jnp.clip(jnp.searchsorted(oo, i, side="right") - 1, 0, R - 1)
    pos = i - oo[s]
    gg = jnp.clip(blk0[s] + pos // C, 0, G - 1)
    return [out[gg, pos % C] for out in outs]


@functools.partial(jax.jit,
                   static_argnames=("n_out", "w", "block_out", "uniform_len",
                                    "interpret"))
@obs.scoped("kernels.segmented_merge_runs")
def segmented_merge_runs(a, b, a_starts, a_lens, b_starts, b_lens, *,
                         n_out: int, w: int = 32, block_out: int = 1024,
                         uniform_len: Optional[int] = None,
                         interpret: bool = True):
    """Merge R run pairs — ``a[a_starts[s]:+a_lens[s]]`` with
    ``b[b_starts[s]:+b_lens[s]]``, each descending — in ONE ``pallas_call``.

    Returns the (n_out,) concatenation of the merged runs in run order;
    ``n_out`` must equal ``sum(a_lens) + sum(b_lens)`` (static contract —
    callers derive it from shapes or static paddings).

    ``uniform_len`` (static) says the pairs are the consecutive runs of one
    buffer, each of that length ``ℓ``: ``a`` and ``b`` are the same (n_out,)
    array of 2R runs, A-run s starts at ``2sℓ`` and B-run s at ``(2s+1)ℓ``.
    ``ℓ`` must be a multiple of ``w`` and ``2ℓ`` of the output block. The
    banks are then built by a pad, the grid has no spare steps and the
    output needs no unpad gather (``_uniform_bank``); ``None`` gathers them
    for ragged runs.
    """
    R = a_starts.shape[0]
    assert a.dtype == b.dtype and w & (w - 1) == 0
    if R == 0 or n_out == 0:
        return jnp.zeros((n_out,), a.dtype)
    C = max(w, min(block_out, _next_pow2(n_out)))
    C = (C // w) * w
    cycles = C // w
    Ha = cycles + 2
    Hp = pad_rows(Ha)                     # rows each step's window DMAs
    G = n_out // C + R                    # >= sum ceil(out_len_s / C)
    RA = n_out // w + R * (Hp + 3)                    # static row capacity
    RB = RA
    ell = uniform_len
    if ell is not None:
        assert ell % w == 0 and (2 * ell) % C == 0
        assert 2 * ell * R == n_out == a.shape[0] == b.shape[0]
        G = n_out // C

    # --- flat grid over (segment, block) pairs -----------------------------
    with _stage("grid"):
        a_starts = a_starts.astype(jnp.int32)
        b_starts = b_starts.astype(jnp.int32)
        la = a_lens.astype(jnp.int32)
        lb = b_lens.astype(jnp.int32)
        if ell is not None:
            seg, o = _uniform_grid(G, 2 * ell, C)
        else:
            lo_len = la + lb
            nb = -(-lo_len // C)                      # blocks per segment
            blk0 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                    jnp.cumsum(nb)])
            g = jnp.arange(G, dtype=jnp.int32)
            seg = jnp.clip(jnp.searchsorted(blk0, g, side="right") - 1,
                           0, R - 1)
            # tail steps past the last real block recompute segment-final
            # co-ranks; their outputs are never gathered.
            o = jnp.minimum((g - blk0[seg]) * C, (lo_len[seg] // C) * C)

    # --- per-run row-aligned banks -----------------------------------------
    with _stage("bank"):
        if ell is not None:
            rows = ell // w + Hp + 2                  # rows of each run
            ra0 = rb0 = jnp.arange(R + 1, dtype=jnp.int32) * rows
            abank = _uniform_bank(a.reshape(R, 2, ell // w, w)[:, 0],
                                  Hp + 2, RA)
            bbank = _uniform_bank(b.reshape(R, 2, ell // w, w)[:, 1],
                                  Hp + 2, RB)
        else:
            ra = -(-la // w) + Hp + 2
            rb = -(-lb // w) + Hp + 2
            ra0 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(ra)])
            rb0 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(rb)])
            abank = _build_bank(a, a_starts, la, ra0, RA, w)
            bbank = _build_bank(b, b_starts, lb, rb0, RB, w)

    # --- per-(segment, block) co-ranks (vectorised binary search) ----------
    steps = max(1, math.ceil(math.log2(max(n_out, 2))) + 1)
    with _stage("corank"):
        acut = jax.vmap(lambda oo, s: _corank_runs(
            oo, la[s], lb[s], a_starts[s], b_starts[s], a, b, steps))(o, seg)
        acut = acut.astype(jnp.int32)
        bcut = o - acut
        arow0 = jnp.minimum(ra0[seg] + acut // w, RA - Hp)
        brow0 = jnp.minimum(rb0[seg] + bcut // w, RB - Hp)
        la0 = acut % w
        lb0 = bcut % w

    with _stage("kernel"):
        (out,) = merge_call(_merge_kernel, [abank, bbank],
                            jnp.stack([arow0, brow0, la0, lb0], axis=1),
                            G=G, C=C, w=w, Ha=Ha,
                            out_dtypes=[a.dtype],
                            name="flims_segmented_merge",
                            interpret=interpret)

    # --- gather padded blocks back to the flat ragged layout ---------------
    with _stage("unpad"):
        if ell is not None:
            return out.reshape(-1)
        return _unpad_blocks([out], lo_len, blk0, n_out, G, C)[0]


@functools.partial(jax.jit, static_argnames=("w", "block_out", "interpret"))
@obs.scoped("kernels.segmented_merge")
def segmented_merge_pallas(a, a_offsets, b, b_offsets, *, w: int = 32,
                           block_out: int = 1024, interpret: bool = True):
    """Merge S segment pairs described by offset vectors, one ``pallas_call``.

    ``a``/``b`` are flat concatenations of S descending runs with boundaries
    ``a_offsets``/``b_offsets`` (each ``(S+1,)``, ``offsets[0] == 0``,
    ``offsets[-1] == len``). Segment s of the result is the descending merge
    of a-run s and b-run s; the output offsets are
    ``a_offsets + b_offsets``. Empty segments are fine.
    """
    assert a.ndim == b.ndim == 1 and a.dtype == b.dtype
    assert a_offsets.shape == b_offsets.shape and a_offsets.ndim == 1
    S = a_offsets.shape[0] - 1
    n_out = a.shape[0] + b.shape[0]
    if S <= 0 or n_out == 0:
        return jnp.zeros((n_out,), a.dtype)
    a_offsets = a_offsets.astype(jnp.int32)
    b_offsets = b_offsets.astype(jnp.int32)
    return segmented_merge_runs(
        a, b, a_offsets[:-1], jnp.diff(a_offsets),
        b_offsets[:-1], jnp.diff(b_offsets),
        n_out=n_out, w=w, block_out=block_out, interpret=interpret)


# --------------------------------------------------------------------------
# KV (rank-lane) segmented merge: identical grid, one extra int32 ref per side
# --------------------------------------------------------------------------

def _corank_runs_kv(o, la, lb, astart, bstart, a, ra, b, rb, steps: int,
                    descending: bool = True):
    """Merge-path co-rank inside one (A-run, B-run) pair under the compound
    (key, rank) order — the stable split. Payload-oblivious: only the
    comparator lanes enter the search."""
    first = lane_first(descending)
    firstA, lastA = bound_keys(a.dtype, descending)
    firstB, lastB = bound_keys(b.dtype, descending)
    rank_lo = jnp.int32(jnp.iinfo(jnp.int32).min)
    nA = max(a.shape[0], 1)
    nB = max(b.shape[0], 1)
    ap = a if a.shape[0] else jnp.full((1,), lastA, a.dtype)
    bp = b if b.shape[0] else jnp.full((1,), lastB, b.dtype)
    rap = ra if ra.shape[0] else jnp.full((1,), INVALID_RANK, jnp.int32)
    rbp = rb if rb.shape[0] else jnp.full((1,), INVALID_RANK, jnp.int32)

    def get(x, rx, n, start, l, i, first_k, last_k):
        v = x[jnp.clip(start + i, 0, n - 1)]
        r = rx[jnp.clip(start + i, 0, n - 1)]
        v = jnp.where(i < 0, first_k, v)
        r = jnp.where(i < 0, rank_lo, r)
        v = jnp.where(i >= l, last_k, v)
        r = jnp.where(i >= l, INVALID_RANK, r)
        return v, r

    lo = jnp.maximum(0, o - lb)
    hi = jnp.minimum(o, la)

    def step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi + 1) // 2
        ka, rka = get(ap, rap, nA, astart, la, mid - 1, firstA, lastA)
        kb, rkb = get(bp, rbp, nB, bstart, lb, o - mid, firstB, lastB)
        ok = first(ka, rka, kb, rkb)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, hi = lax.fori_loop(0, steps, step, (lo, hi))
    return lo


@functools.partial(jax.jit,
                   static_argnames=("n_out", "w", "block_out", "descending",
                                    "uniform_len", "interpret"))
@obs.scoped("kernels.segmented_merge_runs_kv")
def segmented_merge_runs_kv(a, ra, b, rb, a_starts, a_lens, b_starts, b_lens,
                            *, n_out: int, w: int = 32, block_out: int = 1024,
                            descending: bool = True,
                            uniform_len: Optional[int] = None,
                            interpret: bool = True):
    """Stable KV variant of ``segmented_merge_runs``: merge R run pairs of
    (key, rank) lanes in ONE ``pallas_call``. Returns (keys, ranks).

    Same flat (segment, block) grid, per-step co-ranks, bank layout and
    ``uniform_len`` contract as the keys-only kernel — the co-rank
    partition is payload-oblivious, so the only change is one extra int32
    bank per side and the compound comparator end-to-end.
    """
    R = a_starts.shape[0]
    assert a.dtype == b.dtype and w & (w - 1) == 0
    if R == 0 or n_out == 0:
        return jnp.zeros((n_out,), a.dtype), jnp.zeros((n_out,), jnp.int32)
    ra = ra.astype(jnp.int32)
    rb = rb.astype(jnp.int32)
    C = max(w, min(block_out, _next_pow2(n_out)))
    C = (C // w) * w
    cycles = C // w
    Ha = cycles + 2
    Hp = pad_rows(Ha)
    G = n_out // C + R
    RA = n_out // w + R * (Hp + 3)
    RB = RA
    ell = uniform_len
    if ell is not None:
        assert ell % w == 0 and (2 * ell) % C == 0
        assert 2 * ell * R == n_out == a.shape[0] == b.shape[0]
        G = n_out // C

    with _stage("grid"):
        a_starts = a_starts.astype(jnp.int32)
        b_starts = b_starts.astype(jnp.int32)
        la = a_lens.astype(jnp.int32)
        lb = b_lens.astype(jnp.int32)
        if ell is not None:
            seg, o = _uniform_grid(G, 2 * ell, C)
        else:
            lo_len = la + lb
            nb = -(-lo_len // C)
            blk0 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                    jnp.cumsum(nb)])
            g = jnp.arange(G, dtype=jnp.int32)
            seg = jnp.clip(jnp.searchsorted(blk0, g, side="right") - 1,
                           0, R - 1)
            o = jnp.minimum((g - blk0[seg]) * C, (lo_len[seg] // C) * C)

    with _stage("bank"):
        _, lastK = bound_keys(a.dtype, descending)
        if ell is not None:
            rows = ell // w + Hp + 2                  # rows of each run
            ra0 = rb0 = jnp.arange(R + 1, dtype=jnp.int32) * rows
            pairs = lambda x, side: x.reshape(R, 2, ell // w, w)[:, side]
            abank = _uniform_bank(pairs(a, 0), Hp + 2, RA, fill=lastK)
            bbank = _uniform_bank(pairs(b, 1), Hp + 2, RB, fill=lastK)
            arbank = _uniform_bank(pairs(ra, 0), Hp + 2, RA,
                                   fill=INVALID_RANK)
            brbank = _uniform_bank(pairs(rb, 1), Hp + 2, RB,
                                   fill=INVALID_RANK)
        else:
            rra = -(-la // w) + Hp + 2
            rrb = -(-lb // w) + Hp + 2
            ra0 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(rra)])
            rb0 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(rrb)])
            abank = _build_bank(a, a_starts, la, ra0, RA, w, fill=lastK)
            bbank = _build_bank(b, b_starts, lb, rb0, RB, w, fill=lastK)
            arbank = _build_bank(ra, a_starts, la, ra0, RA, w,
                                 fill=INVALID_RANK)
            brbank = _build_bank(rb, b_starts, lb, rb0, RB, w,
                                 fill=INVALID_RANK)

    steps = max(1, math.ceil(math.log2(max(n_out, 2))) + 1)
    with _stage("corank"):
        acut = jax.vmap(lambda oo, s: _corank_runs_kv(
            oo, la[s], lb[s], a_starts[s], b_starts[s], a, ra, b, rb, steps,
            descending))(o, seg)
        acut = acut.astype(jnp.int32)
        bcut = o - acut
        arow0 = jnp.minimum(ra0[seg] + acut // w, RA - Hp)
        brow0 = jnp.minimum(rb0[seg] + bcut // w, RB - Hp)
        la0 = acut % w
        lb0 = bcut % w

    with _stage("kernel"):
        ok, orr = merge_call(_merge_kv_kernel,
                             [abank, arbank, bbank, brbank],
                             jnp.stack([arow0, brow0, la0, lb0], axis=1),
                             G=G, C=C, w=w, Ha=Ha,
                             out_dtypes=[a.dtype, jnp.int32],
                             name="flims_segmented_merge_kv",
                             interpret=interpret, descending=descending)

    with _stage("unpad"):
        if ell is not None:
            return ok.reshape(-1), orr.reshape(-1)
        return tuple(_unpad_blocks([ok, orr], lo_len, blk0, n_out, G, C))


# --------------------------------------------------------------------------
# segmented sort
# --------------------------------------------------------------------------

def _sort_row_kernel(x_ref, o_ref):
    o_ref[...] = _bitonic_rows_desc(x_ref[...])


@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
@obs.scoped("kernels.segment_sort")
def segment_sort_pallas(values, offsets, *, cap: int = 0,
                        interpret: bool = True):
    """Sort every segment of a ragged batch descending in ONE ``pallas_call``.

    The fused strategy: each grid step owns one segment, padded to the static
    capacity ``cap`` (a power of two ≥ the longest segment; defaults to
    ``next_pow2(len(values))``), and runs the full bitonic network over it.
    Good up to moderate ``cap``; the engine's two-phase strategy
    (chunk sort + segmented FLiMS merge passes) covers the long-segment end.
    """
    assert values.ndim == 1 and offsets.ndim == 1
    S = offsets.shape[0] - 1
    N = values.shape[0]
    if S <= 0 or N == 0:
        return jnp.zeros((N,), values.dtype)
    cap = cap or _next_pow2(max(N, 1))
    assert cap & (cap - 1) == 0 and cap >= 1
    offsets = offsets.astype(jnp.int32)
    bank = padded_bank(values, offsets, cap)

    out = pl.pallas_call(
        _sort_row_kernel,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, cap), lambda s: (s, 0))],
        out_specs=pl.BlockSpec((1, cap), lambda s: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((S, cap), values.dtype),
        interpret=interpret,
        name="flims_segment_sort",
    )(bank)

    i = jnp.arange(N, dtype=jnp.int32)
    s = jnp.clip(jnp.searchsorted(offsets, i, side="right") - 1, 0, S - 1)
    return out[s, i - offsets[s]]


@functools.partial(jax.jit,
                   static_argnames=("cap", "chunk", "w", "levels",
                                    "interpret"))
@obs.scoped("kernels.segment_sort_two_phase")
def segment_sort_two_phase(values, offsets, *, cap: int, chunk: int = 256,
                           w: int = 32, levels: int = 1,
                           interpret: bool = True):
    """Two-phase segmented sort: one chunk-sort ``pallas_call`` over ALL
    segments' rows, then a ``tree_pallas`` MergeSchedule over the uniform
    chunk runs (TopSort-style phase plan). With ``levels == 1`` each tree
    level is one segmented pair-merge ``pallas_call`` across the whole
    batch; ``levels >= 2`` fuses that many levels per pass through the
    merge-tree kernel (DESIGN.md §5).

    Every segment is padded to the static ``cap`` (power of two ≥ longest
    segment); sentinels ride through the merges and sort last, so the valid
    prefix of each segment is its true descending sort.
    """
    from repro.engine.schedule import MergeSchedule, merge_runs
    from repro.kernels.bitonic_sort import sort_chunks_pallas
    assert values.ndim == 1 and offsets.ndim == 1
    S = offsets.shape[0] - 1
    N = values.shape[0]
    if S <= 0 or N == 0:
        return jnp.zeros((N,), values.dtype)
    assert cap & (cap - 1) == 0 and chunk & (chunk - 1) == 0
    chunk = min(chunk, cap)
    offsets = offsets.astype(jnp.int32)
    bank = padded_bank(values, offsets, cap)

    # phase 1: sort width-``chunk`` rows of every segment at once
    rows = sort_chunks_pallas(bank.reshape(S * (cap // chunk), chunk),
                              interpret=interpret)
    flat = rows.reshape(S * cap)

    # phase 2: reduce each segment's cap/chunk uniform runs per schedule
    if cap > chunk:
        run_offs = jnp.arange(S * (cap // chunk) + 1, dtype=jnp.int32) * chunk
        sched = MergeSchedule("tree_pallas", levels_per_pass=levels,
                              w=min(w, chunk), block_out=max(2 * chunk, w))
        flat = merge_runs(flat, run_offs, schedule=sched,
                          runs_per_group=cap // chunk, interpret=interpret)

    i = jnp.arange(N, dtype=jnp.int32)
    s = jnp.clip(jnp.searchsorted(offsets, i, side="right") - 1, 0, S - 1)
    return flat.reshape(S, cap)[s, i - offsets[s]]


# --------------------------------------------------------------------------
# segmented argsort: the same strategies over (key, rank) lanes
# --------------------------------------------------------------------------

def _rank_bank(offsets, cap: int):
    """(S, cap) int32 bank of local positions; padding is INVALID_RANK."""
    lens = jnp.diff(offsets.astype(jnp.int32))
    idx = jnp.arange(cap, dtype=jnp.int32)
    return jnp.where(idx[None, :] < lens[:, None], idx[None, :],
                     INVALID_RANK)


@functools.partial(jax.jit, static_argnames=("cap", "descending", "interpret"))
@obs.scoped("kernels.segment_sort_kv")
def segment_sort_kv_pallas(keys, offsets, *, cap: int = 0,
                           descending: bool = True, interpret: bool = True):
    """Fused stable KV segment sort: ONE ``pallas_call`` carrying key and
    rank banks through per-segment compound bitonic networks.

    Returns ``(sorted_keys, perm)`` flat over the ragged batch, where
    ``perm`` holds *segment-local* source positions: for segment ``s``,
    ``keys[offsets[s] + perm[offsets[s]:offsets[s+1]]]`` is its stable sort.
    """
    assert keys.ndim == 1 and offsets.ndim == 1
    S = offsets.shape[0] - 1
    N = keys.shape[0]
    if S <= 0 or N == 0:
        return jnp.zeros((N,), keys.dtype), jnp.zeros((N,), jnp.int32)
    cap = cap or _next_pow2(max(N, 1))
    assert cap & (cap - 1) == 0 and cap >= 1
    offsets = offsets.astype(jnp.int32)
    _, lastK = bound_keys(keys.dtype, descending)
    kbank = padded_bank(keys, offsets, cap, fill=lastK)
    rbank = _rank_bank(offsets, cap)

    spec = pl.BlockSpec((1, cap), lambda s: (s, 0))
    ok, orr = pl.pallas_call(
        functools.partial(_sort_kv_kernel, descending=descending),
        grid=(S,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((S, cap), keys.dtype),
                   jax.ShapeDtypeStruct((S, cap), jnp.int32)],
        interpret=interpret,
        name="flims_segment_sort_kv",
    )(kbank, rbank)
    return unpad_bank(ok, offsets, N), unpad_bank(orr, offsets, N)


@functools.partial(jax.jit, static_argnames=("cap", "descending", "interpret"))
@obs.scoped("kernels.segment_argsort")
def segment_argsort_pallas(keys, offsets, *, cap: int = 0,
                           descending: bool = True, interpret: bool = True):
    """Stable per-segment argsort (fused strategy): local permutation only."""
    _, perm = segment_sort_kv_pallas(keys, offsets, cap=cap,
                                     descending=descending,
                                     interpret=interpret)
    return perm


@functools.partial(jax.jit,
                   static_argnames=("cap", "chunk", "w", "descending",
                                    "levels", "interpret"))
@obs.scoped("kernels.segment_argsort_two_phase")
def segment_argsort_two_phase(keys, offsets, *, cap: int, chunk: int = 256,
                              w: int = 32, descending: bool = True,
                              levels: int = 1, interpret: bool = True):
    """Two-phase stable per-segment argsort: one KV chunk-sort
    ``pallas_call`` over ALL segments' rows, then the KV ``tree_pallas``
    MergeSchedule over the uniform chunk runs (``levels`` tree levels fused
    per pass). Mirrors ``segment_sort_two_phase`` with rank lanes; the rank
    lane of the fully merged bank is the permutation.
    """
    from repro.engine.schedule import MergeSchedule, merge_runs
    assert keys.ndim == 1 and offsets.ndim == 1
    S = offsets.shape[0] - 1
    N = keys.shape[0]
    if S <= 0 or N == 0:
        return jnp.zeros((N,), jnp.int32)
    assert cap & (cap - 1) == 0 and chunk & (chunk - 1) == 0
    chunk = min(chunk, cap)
    offsets = offsets.astype(jnp.int32)
    _, lastK = bound_keys(keys.dtype, descending)
    kbank = padded_bank(keys, offsets, cap, fill=lastK)
    rbank = _rank_bank(offsets, cap)

    # phase 1: stable KV sort of width-``chunk`` rows of every segment
    kr, rr = sort_chunks_kv_pallas(
        kbank.reshape(S * (cap // chunk), chunk),
        rbank.reshape(S * (cap // chunk), chunk),
        descending=descending, interpret=interpret)
    kflat = kr.reshape(S * cap)
    rflat = rr.reshape(S * cap)

    # phase 2: KV schedule over uniform chunk runs (earlier chunks hold
    # smaller local ranks, so the compound comparator's rank tiebreak keeps
    # every fused pass stable)
    if cap > chunk:
        run_offs = jnp.arange(S * (cap // chunk) + 1, dtype=jnp.int32) * chunk
        sched = MergeSchedule("tree_pallas", levels_per_pass=levels,
                              w=min(w, chunk), block_out=max(2 * chunk, w))
        kflat, rflat = merge_runs(kflat, run_offs, ranks=rflat,
                                  schedule=sched,
                                  runs_per_group=cap // chunk,
                                  descending=descending, interpret=interpret)

    return unpad_bank(rflat.reshape(S, cap), offsets, N)

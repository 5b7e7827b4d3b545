"""Pallas TPU kernel: merge-path partitioned FLiMS 2-way merge.

Beyond-paper composition (DESIGN.md §2): the FPGA FLiMS is one physical
pipeline; on TPU we shard the merge across a grid. A host-side vectorised
co-rank binary search (merge path) finds, for every output chunk of size C,
how many elements come from A vs B. Because C is a multiple of w, the FLiMS
rotation invariant (lA + lB) ≡ 0 (mod w) holds at every partition boundary
(aStart + bStart = g·C), so each grid step starts the banked FLiMS dataflow
mid-rotation with *zero* realignment work.

Memory behaviour per grid step (the TPU adaptation of the paper's banked
BRAM): A and B stay in HBM as row-major (rows, w) banks; each step DMAs only
the C/w + 2 rows each side can consume (the window starts at the step's
co-rank row, read from SMEM, which need not be tile-aligned), and the inner
loop issues only (1, w) row loads — the lane-rotation that a naive vectorised
merge would need is algebraically eliminated, exactly the paper's core trick.
Lane permutations inside a step (the B-side reversal, the butterfly's
partner exchange) are built from the TPU's native lane rotate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.flims import sentinel_for
from repro.core.lanes import INVALID_RANK

from repro import obs


def plus_inf_for(dtype):
    """Key that sorts first in descending order (never strictly loses)."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.array(jnp.iinfo(dtype).max, dtype)


def bound_keys(dtype, descending: bool = True):
    """(first, last): key values sorting before/after everything real."""
    lo, hi = sentinel_for(dtype), plus_inf_for(dtype)
    return (hi, lo) if descending else (lo, hi)


def lane_first(descending: bool = True):
    """The compound (key, rank) comparator the KV kernels share: key
    descending-or-ascending, rank ascending (`lanes.stable_compare` with a
    static direction — kernels sort ascending natively instead of mirroring).
    """
    if descending:
        return lambda ka, ra, kb, rb: (ka > kb) | ((ka == kb) & (ra < rb))
    return lambda ka, ra, kb, rb: (ka < kb) | ((ka == kb) & (ra < rb))


def pad_rows(n: int) -> int:
    """Rows of a VMEM window holding ``n`` bank rows: the sublane tile (8)."""
    return -(-n // 8) * 8


def lane_partner(v: jnp.ndarray, d: int, hi: jnp.ndarray,
                 axis: int = -1) -> jnp.ndarray:
    """Each element's XOR-``d`` partner along ``axis`` (the lanes by
    default): element ``i`` reads ``v[i ^ d]``. ``hi`` marks the elements
    whose bit ``d`` is set (they read ``d`` places down). Two native TPU
    rotates (``pltpu.roll`` follows ``jnp.roll``) and a select — Mosaic has
    no gather and no shape cast that would do this otherwise."""
    axis = axis % v.ndim
    n = v.shape[axis]
    return jnp.where(hi, pltpu.roll(v, d, axis), pltpu.roll(v, n - d, axis))


def lane_iota(v: jnp.ndarray) -> jnp.ndarray:
    return lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)


def lane_rev(v: jnp.ndarray) -> jnp.ndarray:
    """Reverse the lanes of ``v`` (w a power of two): lane ``i`` takes
    ``i ^ (w-1)``, composed from one XOR-partner exchange per index bit —
    Mosaic has no lane-reverse primitive, but rotates are native."""
    iota = lane_iota(v)
    d = 1
    while d < v.shape[-1]:
        v = lane_partner(v, d, (iota & d) != 0)
        d *= 2
    return v


def _butterfly_desc(v: jnp.ndarray) -> jnp.ndarray:
    """Sort (rotated-)bitonic rows of ``v`` descending along the lanes:
    log2(w) compare-exchange stages between XOR-partner lanes."""
    iota = lane_iota(v)
    d = v.shape[-1] // 2
    while d >= 1:
        hi = (iota & d) != 0
        p = lane_partner(v, d, hi)
        v = jnp.where(hi, jnp.minimum(v, p), jnp.maximum(v, p))
        d //= 2
    return v


def fetch_windows(windows, sem) -> None:
    """Copy each ``(src, row, dst)`` window — ``dst.shape[0]`` rows of the
    HBM bank ``src`` starting at (dynamic, unaligned) ``row`` — into its
    VMEM scratch ``dst``; all copies are in flight together."""
    copies = [pltpu.make_async_copy(src.at[pl.ds(row, dst.shape[0])], dst,
                                    sem.at[i])
              for i, (src, row, dst) in enumerate(windows)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def row_reader(ref, n_rows: int):
    """``read(r)`` → the (1, w) row ``r`` of a VMEM window, clamped to its
    last real row (reads past a window's end only feed discarded state)."""
    return lambda r: ref[pl.ds(jnp.minimum(r, n_rows - 1), 1), :]


def row_blocks(G: int, C: int, w: int, dtype):
    """Output geometry shared by the merge kernels: ``G`` blocks of ``C``
    keys as (G, C/w, w) rows, one block per grid step — a block that spans
    the array's trailing dims, which the TPU tiling accepts for any w."""
    spec = pl.BlockSpec((1, C // w, w), lambda g, *_: (g, 0, 0))
    return spec, jax.ShapeDtypeStruct((G, C // w, w), dtype)


def meta_block(meta):
    """Per-step scalars (G, M) → a (G, 1, M) SMEM operand whose (1, 1, M)
    block is step ``g``'s row: the pipeline streams one row per step, so
    SMEM holds two rows however long the grid (a scalar-prefetched table
    of every step's scalars outgrows SMEM's 1 MiB at large ``G``)."""
    G, M = meta.shape
    spec = pl.BlockSpec((1, 1, M), lambda g, *_: (g, 0, 0),
                        memory_space=pltpu.SMEM)
    return spec, meta.astype(jnp.int32).reshape(G, 1, M)


def _merge_kernel(m_ref, a_hbm, b_hbm, out_ref, a_win, b_win, sem, *,
                  w: int, cycles: int, Ha: int):
    fetch_windows([(a_hbm, m_ref[0, 0, 0], a_win),
                   (b_hbm, m_ref[0, 0, 1], b_win)], sem)
    lA0 = m_ref[0, 0, 2]
    lB0 = m_ref[0, 0, 3]
    iota = lax.broadcasted_iota(jnp.int32, (1, w), 1)
    read_a, read_b = row_reader(a_win, Ha), row_reader(b_win, Ha)

    def heads(W0, W1, l):
        return jnp.where(iota < l, W1, W0)

    def body(t, carry):
        WA0, WA1, WB0, WB1, lA, lB, rA, rB = carry
        cA = heads(WA0, WA1, lA)
        cBr = lane_rev(heads(WB0, WB1, lB))  # MAX_i pairs a_i with b_{w-1-i}
        mask = cA > cBr                     # algorithm 1: ties dequeue from B
        chunk = _butterfly_desc(jnp.maximum(cA, cBr))
        out_ref[0, pl.ds(t, 1), :] = chunk
        k = jnp.sum(mask.astype(jnp.int32))

        def advance(W0, W1, l, r, read, consumed):
            l2 = l + consumed
            shift = l2 >= w
            nxt = read(r)
            W0n = jnp.where(shift, W1, W0)
            W1n = jnp.where(shift, nxt, W1)
            return W0n, W1n, jnp.where(shift, l2 - w, l2), r + shift.astype(jnp.int32)

        WA0, WA1, lA, rA = advance(WA0, WA1, lA, rA, read_a, k)
        WB0, WB1, lB, rB = advance(WB0, WB1, lB, rB, read_b, w - k)
        return WA0, WA1, WB0, WB1, lA, lB, rA, rB

    init = (read_a(0), read_a(1), read_b(0), read_b(1),
            lA0, lB0, jnp.int32(2), jnp.int32(2))
    lax.fori_loop(0, cycles, body, init)


def merge_call(kernel, banks, meta, *, G: int, C: int, w: int, Ha: int,
               out_dtypes, name: str, interpret: bool, **kw):
    """One merge ``pallas_call`` over ``G`` output blocks: the ``banks`` stay
    in HBM and every grid step DMAs its ``Ha``-row window of each (rows
    and initial rotations from step ``g``'s row of the (G, 4) ``meta``:
    A row, B row, A rotation, B rotation); returns (G, C) per output
    lane."""
    Hp = pad_rows(Ha)
    spec, _ = row_blocks(G, C, w, jnp.int32)
    mspec, meta = meta_block(meta)
    outs = pl.pallas_call(
        functools.partial(kernel, w=w, cycles=C // w, Ha=Ha, **kw),
        grid=(G,),
        in_specs=[mspec] + [pl.BlockSpec(memory_space=pl.ANY)] * len(banks),
        out_specs=[spec] * len(out_dtypes),
        out_shape=[row_blocks(G, C, w, d)[1] for d in out_dtypes],
        scratch_shapes=[pltpu.VMEM((Hp, w), b.dtype) for b in banks]
        + [pltpu.SemaphoreType.DMA((len(banks),))],
        interpret=interpret,
        name=name,
    )(meta, *banks)
    return [o.reshape(G, C) for o in outs]


def _corank(o, a, b):
    """Vectorised merge-path co-rank: #A-elements among the top-``o`` of the
    descending union, ties preferring B (FLiMS algorithm-1 order)."""
    nA, nB = a.shape[0], b.shape[0]

    def getA(i):  # a[i] with +inf below 0 and -inf beyond nA
        v = a[jnp.clip(i, 0, nA - 1)]
        big = jnp.asarray(jnp.inf if jnp.issubdtype(a.dtype, jnp.floating)
                          else jnp.iinfo(a.dtype).max, a.dtype)
        v = jnp.where(i < 0, big, v)
        return jnp.where(i >= nA, sentinel_for(a.dtype), v)

    def getB(i):
        v = b[jnp.clip(i, 0, nB - 1)]
        big = jnp.asarray(jnp.inf if jnp.issubdtype(b.dtype, jnp.floating)
                          else jnp.iinfo(b.dtype).max, b.dtype)
        v = jnp.where(i < 0, big, v)
        return jnp.where(i >= nB, sentinel_for(b.dtype), v)

    lo = jnp.maximum(0, o - nB)
    hi = jnp.minimum(o, nA)

    def step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi + 1) // 2
        # predicate: taking mid from A is consistent: a[mid-1] > b[o-mid]
        ok = getA(mid - 1) > getB(o - mid)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    import math
    steps = max(1, math.ceil(math.log2(max(nA + nB, 2))) + 1)
    lo, hi = lax.fori_loop(0, steps, step, (lo, hi))
    return lo


@functools.partial(jax.jit,
                   static_argnames=("w", "block_out", "interpret"))
@obs.scoped("kernels.flims_merge")
def flims_merge_pallas(a: jnp.ndarray, b: jnp.ndarray, *, w: int = 128,
                       block_out: int = 4096, interpret: bool = True):
    """Merge two descending 1-D arrays with the partitioned FLiMS kernel."""
    assert a.ndim == b.ndim == 1 and a.dtype == b.dtype
    assert w & (w - 1) == 0
    n_out = a.shape[0] + b.shape[0]
    if n_out == 0:
        return jnp.zeros((0,), a.dtype)
    if a.shape[0] == 0:
        return b
    if b.shape[0] == 0:
        return a
    C = max(w, min(block_out, 1 << (n_out - 1).bit_length()))
    C = (C // w) * w
    G = -(-n_out // C)
    Ha = C // w + 2                      # rows of each input a block may touch
    sent = sentinel_for(a.dtype)

    def rows_of(x):
        r = -(-x.shape[0] // w) + pad_rows(Ha) + 2
        xp = jnp.pad(x, (0, r * w - x.shape[0]), constant_values=sent)
        return xp.reshape(r, w)

    ar, br = rows_of(a), rows_of(b)
    # --- host-side merge-path co-ranks (vectorised binary search) ----------
    os_ = jnp.arange(G, dtype=jnp.int32) * C
    acut = jax.vmap(lambda o: _corank(o, a, b))(os_).astype(jnp.int32)
    bcut = os_ - acut
    arow0, la0 = acut // w, acut % w
    brow0, lb0 = bcut // w, bcut % w

    (out,) = merge_call(_merge_kernel, [ar, br],
                        jnp.stack([arow0, brow0, la0, lb0], axis=1),
                        G=G, C=C, w=w, Ha=Ha, out_dtypes=[a.dtype],
                        name="flims_merge", interpret=interpret)
    return out.reshape(-1)[:n_out]


# --------------------------------------------------------------------------
# KV (rank-lane) variant: the same dataflow with one extra int32 ref per side
# --------------------------------------------------------------------------
#
# Merge Path co-ranks are payload-oblivious: the split point of every output
# block depends only on the comparator over (key, rank), never on any payload
# — so the KV kernel reuses the identical grid, BlockSpecs, and scalar
# prefetch, and simply carries a rank bank beside each key bank. Stability
# (paper algorithm 3) falls out of ranks assigned in input order; arbitrary
# payload pytrees are gathered once by the merged rank permutation at the
# engine layer.

def _butterfly_kv(v: jnp.ndarray, r: jnp.ndarray, descending: bool = True):
    """Butterfly CAS over (key, rank) lanes: log2(w) compound-compare stages
    between XOR-partner lanes (the lower lane of each pair keeps the one
    that orders first)."""
    first = lane_first(descending)
    iota = lane_iota(v)
    d = v.shape[-1] // 2
    while d >= 1:
        hi = (iota & d) != 0
        pv, pr = lane_partner(v, d, hi), lane_partner(r, d, hi)
        keep = (hi & first(pv, pr, v, r)) | (~hi & first(v, r, pv, pr))
        v, r = jnp.where(keep, v, pv), jnp.where(keep, r, pr)
        d //= 2
    return v, r


def _merge_kv_kernel(m_ref, a_hbm, ar_hbm, b_hbm, br_hbm, ok_ref, or_ref,
                     a_win, ar_win, b_win, br_win, sem, *,
                     w: int, cycles: int, Ha: int, descending: bool = True):
    arow, brow = m_ref[0, 0, 0], m_ref[0, 0, 1]
    fetch_windows([(a_hbm, arow, a_win), (ar_hbm, arow, ar_win),
                   (b_hbm, brow, b_win), (br_hbm, brow, br_win)], sem)
    lA0 = m_ref[0, 0, 2]
    lB0 = m_ref[0, 0, 3]
    iota = lax.broadcasted_iota(jnp.int32, (1, w), 1)
    first = lane_first(descending)
    reads_a = (row_reader(a_win, Ha), row_reader(ar_win, Ha))
    reads_b = (row_reader(b_win, Ha), row_reader(br_win, Ha))

    def heads(W0, W1, l):
        return jnp.where(iota < l, W1, W0)

    def body(t, carry):
        (WA0, WA1, RA0, RA1, WB0, WB1, RB0, RB1, lA, lB, rA, rB) = carry
        cA = heads(WA0, WA1, lA)
        cAr = heads(RA0, RA1, lA)
        cB = lane_rev(heads(WB0, WB1, lB))  # MAX_i pairs a_i with b_{w-1-i}
        cBr = lane_rev(heads(RB0, RB1, lB))
        take = first(cA, cAr, cB, cBr)      # stable selector (algorithm 3)
        ck, cr = _butterfly_kv(jnp.where(take, cA, cB),
                               jnp.where(take, cAr, cBr), descending)
        ok_ref[0, pl.ds(t, 1), :] = ck
        or_ref[0, pl.ds(t, 1), :] = cr
        k = jnp.sum(take.astype(jnp.int32))

        def advance(W0, W1, R0, R1, l, r, reads, consumed):
            l2 = l + consumed
            shift = l2 >= w
            W0n = jnp.where(shift, W1, W0)
            W1n = jnp.where(shift, reads[0](r), W1)
            R0n = jnp.where(shift, R1, R0)
            R1n = jnp.where(shift, reads[1](r), R1)
            return (W0n, W1n, R0n, R1n, jnp.where(shift, l2 - w, l2),
                    r + shift.astype(jnp.int32))

        WA0, WA1, RA0, RA1, lA, rA = advance(WA0, WA1, RA0, RA1, lA, rA,
                                             reads_a, k)
        WB0, WB1, RB0, RB1, lB, rB = advance(WB0, WB1, RB0, RB1, lB, rB,
                                             reads_b, w - k)
        return (WA0, WA1, RA0, RA1, WB0, WB1, RB0, RB1, lA, lB, rA, rB)

    init = (reads_a[0](0), reads_a[0](1), reads_a[1](0), reads_a[1](1),
            reads_b[0](0), reads_b[0](1), reads_b[1](0), reads_b[1](1),
            lA0, lB0, jnp.int32(2), jnp.int32(2))
    lax.fori_loop(0, cycles, body, init)


def _corank_kv(o, a, ra, b, rb, descending: bool = True):
    """Merge-path co-rank under the compound (key, rank) order: #A-elements
    among the top-``o`` of the merged union (stable split)."""
    nA, nB = a.shape[0], b.shape[0]
    first = lane_first(descending)
    firstA, lastA = bound_keys(a.dtype, descending)
    firstB, lastB = bound_keys(b.dtype, descending)
    rank_lo = jnp.int32(jnp.iinfo(jnp.int32).min)

    def get(x, rx, n, i, first_k, last_k):
        v = x[jnp.clip(i, 0, n - 1)]
        r = rx[jnp.clip(i, 0, n - 1)]
        v = jnp.where(i < 0, first_k, v)
        r = jnp.where(i < 0, rank_lo, r)
        v = jnp.where(i >= n, last_k, v)
        r = jnp.where(i >= n, INVALID_RANK, r)
        return v, r

    lo = jnp.maximum(0, o - nB)
    hi = jnp.minimum(o, nA)

    def step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi + 1) // 2
        ka, rka = get(a, ra, nA, mid - 1, firstA, lastA)
        kb, rkb = get(b, rb, nB, o - mid, firstB, lastB)
        ok = first(ka, rka, kb, rkb)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    import math
    steps = max(1, math.ceil(math.log2(max(nA + nB, 2))) + 1)
    lo, hi = lax.fori_loop(0, steps, step, (lo, hi))
    return lo


@functools.partial(jax.jit, static_argnames=("w", "block_out", "descending",
                                             "interpret"))
@obs.scoped("kernels.flims_merge_kv")
def flims_merge_kv_pallas(a, ra, b, rb, *, w: int = 128,
                          block_out: int = 4096, descending: bool = True,
                          interpret: bool = True):
    """Stable partitioned FLiMS merge of (key, rank) lanes.

    Same grid/BlockSpec geometry as ``flims_merge_pallas`` with one extra
    int32 bank per side riding the identical co-rank partition. Returns
    ``(merged_keys, merged_ranks)``; ties order by rank ascending, so with
    ranks assigned in input order the merge is stable end-to-end.
    """
    assert a.ndim == b.ndim == 1 and a.dtype == b.dtype
    assert ra.shape == a.shape and rb.shape == b.shape
    assert w & (w - 1) == 0
    n_out = a.shape[0] + b.shape[0]
    if n_out == 0:
        return jnp.zeros((0,), a.dtype), jnp.zeros((0,), jnp.int32)
    if a.shape[0] == 0:
        return b, rb
    if b.shape[0] == 0:
        return a, ra
    ra = ra.astype(jnp.int32)
    rb = rb.astype(jnp.int32)
    C = max(w, min(block_out, 1 << (n_out - 1).bit_length()))
    C = (C // w) * w
    G = -(-n_out // C)
    Ha = C // w + 2                      # rows of each input a block may touch
    _, last = bound_keys(a.dtype, descending)

    def rows_of(x, fill):
        r = -(-x.shape[0] // w) + pad_rows(Ha) + 2
        xp = jnp.pad(x, (0, r * w - x.shape[0]), constant_values=fill)
        return xp.reshape(r, w)

    ak, rak = rows_of(a, last), rows_of(ra, INVALID_RANK)
    bk, rbk = rows_of(b, last), rows_of(rb, INVALID_RANK)
    # --- host-side compound-order co-ranks (vectorised binary search) ------
    os_ = jnp.arange(G, dtype=jnp.int32) * C
    acut = jax.vmap(lambda o: _corank_kv(o, a, ra, b, rb, descending))(os_)
    acut = acut.astype(jnp.int32)
    bcut = os_ - acut
    arow0, la0 = acut // w, acut % w
    brow0, lb0 = bcut // w, bcut % w

    ok, orr = merge_call(_merge_kv_kernel, [ak, rak, bk, rbk],
                         jnp.stack([arow0, brow0, la0, lb0], axis=1),
                         G=G, C=C, w=w, Ha=Ha,
                         out_dtypes=[a.dtype, jnp.int32],
                         name="flims_merge_kv", interpret=interpret,
                         descending=descending)
    return ok.reshape(-1)[:n_out], orr.reshape(-1)[:n_out]

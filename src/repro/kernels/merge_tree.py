"""Pallas TPU kernel: fused multi-level FLiMS merge tree.

The paper's HPMT (§2.1, fig. 1) feeds a binary tree of FLiMS mergers so K
sorted lists reduce in a single hardware pass. The per-level TPU scheme
(one vmapped/segmented merge per tree level) pays a full HBM round trip per
level; this kernel instead executes ``L = log2(group)`` tree levels inside
ONE ``pallas_call``: each grid step owns one ``C``-wide output block of a
group's K-way union, co-rank partitions *every* level of its subtree on the
host, and merges pairs-of-pairs through in-kernel scratch streams so the
intermediate runs never touch HBM.

Geometry (extends ``kernels/flims_merge.py`` §2 / DESIGN.md §5):

- Runs live in one row-aligned sentinel-padded ``(ROWS, w)`` bank (layout of
  ``segmented_merge._build_bank``); consecutive ``group = 2^L`` runs form one
  group, the grid is flattened over (group, output-block) pairs.
- For output offset ``o`` of a group, a *nested* merge-path search assigns
  every tree node a start offset into its (conceptual) merged sequence:
  the root splits ``o`` between its children, each child start is rounded
  DOWN to a multiple of ``w`` and the residual becomes the parent dataflow's
  initial rotation. Because aligned starts are multiples of ``w`` and sibling
  rotations sum to the parent's aligned start, the FLiMS invariant
  ``(lA + lB) ≡ 0 (mod w)`` holds at every node of every block — each of the
  ``2^L - 1`` in-kernel dataflows starts mid-rotation with zero realignment.
- Inner nodes stream into sentinel-initialised scratch (a node at depth
  ``d`` produces ``C/w + d`` chunks — exactly what its parent can consume
  plus one rotation's slack); only the root writes the output block.
- Tie consistency: every host search and every in-kernel selector uses the
  same order — strict ``>`` (ties dequeue from B, algorithm 1) for key-only,
  the compound ``(key, rank)`` order (algorithm 3) for the KV variant — so
  duplicates crossing any (group, block, level) boundary split identically
  to the sequential dataflow.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.flims import next_pow2 as _next_pow2
from repro.core.lanes import INVALID_RANK
from repro.kernels.flims_merge import (_butterfly_desc, _butterfly_kv,
                                       bound_keys, fetch_windows, lane_first,
                                       lane_rev, meta_block, pad_rows,
                                       row_blocks, row_reader)
from repro import obs

_RANK_LO = jnp.iinfo(jnp.int32).min


def _tree_nodes(group: int):
    """Static preorder list of internal nodes: (lo, mid, hi, idx) over leaf
    slots [lo, hi). Shared by the host partitioner and the kernel so meta
    rows line up."""
    nodes = []

    def rec(lo, hi):
        if hi - lo <= 1:
            return
        mid = (lo + hi) // 2
        nodes.append((lo, mid, hi, len(nodes)))
        rec(lo, mid)
        rec(mid, hi)

    rec(0, group)
    return nodes


def _node_index(group: int):
    return {(lo, hi): idx for lo, mid, hi, idx in _tree_nodes(group)}


# --------------------------------------------------------------------------
# host side: nested co-rank partition of the whole subtree
# --------------------------------------------------------------------------

def _tree_fns(buf, rbuf, starts_g, lens_g, *, steps: int, descending: bool):
    """(elem, corank, node_len) closures over one group's leaf runs.

    ``elem(lo, hi, i)`` is the i-th element of the node's merged descending
    sequence under the SAME order the kernel selector uses (i < 0 → a value
    that precedes everything, i >= len → one that follows everything);
    ``corank(lo, mid, hi, o)`` is the left-child count among the node's
    top-``o``. Internal elements are recovered by nesting: position ``i``
    takes from the right child unless the left child's candidate strictly
    precedes it — the exact dequeue rule of the dataflow.
    """
    kv = rbuf is not None
    N = max(buf.shape[0], 1)
    bufp = buf if buf.shape[0] else jnp.zeros((1,), buf.dtype)
    first_k, last_k = bound_keys(buf.dtype, descending)
    if kv:
        rbufp = rbuf if rbuf.shape[0] else jnp.zeros((1,), jnp.int32)
        first = lane_first(descending)
        wins = lambda a, b: first(a[0], a[1], b[0], b[1])
    else:
        wins = lambda a, b: a[0] > b[0]

    def guard(lanes, i, ln):
        k = jnp.where(i < 0, first_k, lanes[0])
        k = jnp.where(i >= ln, last_k, k)
        if not kv:
            return (k,)
        r = jnp.where(i < 0, _RANK_LO, lanes[1])
        r = jnp.where(i >= ln, INVALID_RANK, r)
        return (k, r)

    def node_len(lo, hi):
        return sum(lens_g[j] for j in range(lo, hi))

    def elem(lo, hi, i):
        if hi - lo == 1:
            src = jnp.clip(starts_g[lo] + i, 0, N - 1)
            lanes = (bufp[src], rbufp[src]) if kv else (bufp[src],)
            return guard(lanes, i, lens_g[lo])
        mid = (lo + hi) // 2
        c = corank(lo, mid, hi, jnp.clip(i, 0, node_len(lo, hi)))
        ea = elem(lo, mid, c)
        eb = elem(mid, hi, i - c)
        take = wins(ea, eb)
        out = tuple(jnp.where(take, xa, xb) for xa, xb in zip(ea, eb))
        return guard(out, i, node_len(lo, hi))

    def corank(lo, mid, hi, o):
        la, lb = node_len(lo, mid), node_len(mid, hi)
        lo_b = jnp.maximum(0, o - lb)
        hi_b = jnp.minimum(o, la)

        def step(_, lh):
            lo_, hi_ = lh
            m = (lo_ + hi_ + 1) // 2
            ok = wins(elem(lo, mid, m - 1), elem(mid, hi, o - m))
            return jnp.where(ok, m, lo_), jnp.where(ok, hi_, m - 1)

        return lax.fori_loop(0, steps, step, (lo_b, hi_b))[0]

    return elem, corank, node_len


def _tree_meta_one(grp, o, buf, rbuf, starts, lens, row0, *, group: int,
                   w: int, max_row, steps: int, descending: bool):
    """Meta vector for one grid step: per-leaf bank row starts, then per
    internal node (preorder) the (left, right) initial rotations."""
    base = grp * group
    take = lambda v: lax.dynamic_slice(v, (base,), (group,))
    starts_g, lens_g, row0_g = take(starts), take(lens), take(row0)
    _, corank, _ = _tree_fns(buf, rbuf, starts_g, lens_g, steps=steps,
                             descending=descending)

    leaf_rows = [None] * group
    rots = []

    def assign(lo, hi, a):
        # ``a`` is this node's aligned production start (multiple of w)
        mid = (lo + hi) // 2
        sx = corank(lo, mid, hi, a)
        sy = a - sx
        rots.append(sx % w)
        rots.append(sy % w)
        for clo, chi, s in ((lo, mid, sx), (mid, hi, sy)):
            if chi - clo == 1:
                leaf_rows[clo] = jnp.minimum(row0_g[clo] + s // w, max_row)
            else:
                assign(clo, chi, s - s % w)

    assign(0, group, o)
    return jnp.stack([x.astype(jnp.int32) for x in leaf_rows + rots])


# --------------------------------------------------------------------------
# kernel: 2^L - 1 windowed dataflows, inner nodes through scratch streams
# --------------------------------------------------------------------------

def tree_scratch(*, w: int, L: int, C: int, key_dtype, kv: bool):
    """VMEM scratch for ``tree_dataflow``'s inner-node streams: one
    ``(2^L - 2, rows, w)`` buffer per lane, slot ``idx - 1`` for preorder
    node ``idx`` (the root writes the output instead). A node at depth
    ``d`` produces ``C/w + d`` rows and is read two fill rows past them."""
    n_inner = max((1 << L) - 2, 1)
    rows = pad_rows(C // w + L + 2)
    dtypes = (key_dtype, jnp.int32) if kv else (key_dtype,)
    return [pltpu.VMEM((n_inner, rows, w), d) for d in dtypes]


def tree_dataflow(get_rot, leaf_reader, write_chunk, acc, *, w: int, L: int,
                  C: int, kv: bool, descending: bool, key_dtype):
    """The in-kernel nested-dataflow tree, abstracted over storage.

    ``2^L - 1`` windowed FLiMS dataflows reduce ``2^L`` leaves to one
    ``C``-wide output block; inner nodes stream through the VMEM scratch
    ``acc`` (``tree_scratch``: one ref per lane), so only the leaves and
    the root touch the kernel's inputs and outputs. Callers supply the
    storage plumbing:

    - ``get_rot(idx)`` → the (left, right) initial rotations of preorder
      internal node ``idx`` (from the host nested co-rank partition);
    - ``leaf_reader(j)`` → a ``read(r) -> lanes`` row reader for leaf ``j``
      (``r`` is a *relative* row; the reader owns clamping/masking; every
      lane is a (1, w) row);
    - ``write_chunk(t, chunk)`` stores the root's ``t``-th w-wide chunk.

    Shared by the fused merge-tree kernel (leaves = DMA'd bank windows) and
    ``kernels/stream_merge.py`` (leaves = double-buffered DMA windows over
    HBM-resident runs).
    """
    group = 1 << L
    iota = lax.broadcasted_iota(jnp.int32, (1, w), 1)
    node_idx = _node_index(group)
    _, last_k = bound_keys(key_dtype, descending)
    if kv:
        first = lane_first(descending)
        wins = lambda a, b: first(a[0], a[1], b[0], b[1])
        butterfly = lambda s: _butterfly_kv(s[0], s[1], descending)
        fills = (last_k, jnp.int32(INVALID_RANK))
        dtypes = (key_dtype, jnp.int32)
    else:
        wins = lambda a, b: a[0] > b[0]
        butterfly = lambda s: (_butterfly_desc(s[0]),)
        fills = (last_k,)
        dtypes = (key_dtype,)

    def acc_reader(slot, nrows):
        return lambda r: tuple(
            a[slot, pl.ds(jnp.minimum(r, nrows - 1), 1), :] for a in acc)

    def heads(W0, W1, l):
        return tuple(jnp.where(iota < l, w1, w0) for w0, w1 in zip(W0, W1))

    def merge_stream(read_a, read_b, lA0, lB0, cycles, slot):
        """One windowed FLiMS dataflow: ``cycles`` w-wide chunks, either into
        the out refs (root, ``slot is None``) or into scratch stream
        ``slot``, whose two rows past the production hold fill."""
        if slot is not None:
            for a, f, d in zip(acc, fills, dtypes):
                a[slot, pl.ds(cycles, 2), :] = jnp.full((2, w), f, d)

        def body(t, carry):
            WA0, WA1, WB0, WB1, lA, lB, rA, rB = carry
            cA = heads(WA0, WA1, lA)
            cB = tuple(lane_rev(x) for x in heads(WB0, WB1, lB))
            take = wins(cA, cB)
            chunk = butterfly(tuple(jnp.where(take, xa, xb)
                                    for xa, xb in zip(cA, cB)))
            if slot is None:
                write_chunk(t, chunk)
            else:
                for a, c in zip(acc, chunk):
                    a[slot, pl.ds(t, 1), :] = c
            k = jnp.sum(take.astype(jnp.int32))

            def advance(W0, W1, l, r, read, consumed):
                l2 = l + consumed
                shift = l2 >= w
                nxt = read(r)
                W0n = tuple(jnp.where(shift, b, a) for a, b in zip(W0, W1))
                W1n = tuple(jnp.where(shift, b, a) for a, b in zip(W1, nxt))
                return (W0n, W1n, jnp.where(shift, l2 - w, l2),
                        r + shift.astype(jnp.int32))

            WA0, WA1, lA, rA = advance(WA0, WA1, lA, rA, read_a, k)
            WB0, WB1, lB, rB = advance(WB0, WB1, lB, rB, read_b, w - k)
            return WA0, WA1, WB0, WB1, lA, lB, rA, rB

        init = (read_a(jnp.int32(0)), read_a(jnp.int32(1)),
                read_b(jnp.int32(0)), read_b(jnp.int32(1)),
                lA0, lB0, jnp.int32(2), jnp.int32(2))
        lax.fori_loop(0, cycles, body, init)

    def produce(lo, hi, depth):
        """Post-order: children first (leaf windows or scratch streams),
        then this node's dataflow. Root (depth 0) writes the out refs."""
        mid = (lo + hi) // 2
        idx = node_idx[(lo, hi)]
        rotL, rotR = get_rot(idx)
        cycles = C // w + depth

        def child(clo, chi):
            if chi - clo == 1:
                return leaf_reader(clo)
            slot, ccycles = produce(clo, chi, depth + 1)
            return acc_reader(slot, ccycles + 2)

        slot = None if depth == 0 else idx - 1
        merge_stream(child(lo, mid), child(mid, hi), rotL, rotR, cycles, slot)
        return slot, cycles

    produce(0, group, 0)


def _tree_kernel(m_ref, *refs, w: int, L: int, C: int, Ha: int,
                 kv: bool, descending: bool):
    group = 1 << L
    nl = 2 if kv else 1
    banks, outs = refs[:nl], refs[nl:2 * nl]
    wins, acc, sem = refs[2 * nl:3 * nl], refs[3 * nl:4 * nl], refs[-1]
    fetch_windows([(banks[li], m_ref[0, 0, j], wins[li].at[j])
                   for j in range(group) for li in range(nl)], sem)

    def leaf_reader(j):
        reads = [row_reader(wins[li].at[j], Ha) for li in range(nl)]
        return lambda r: tuple(read(r) for read in reads)

    def get_rot(idx):
        return m_ref[0, 0, group + 2 * idx], m_ref[0, 0, group + 2 * idx + 1]

    def write_chunk(t, chunk):
        for ref, c in zip(outs, chunk):
            ref[0, pl.ds(t, 1), :] = c

    tree_dataflow(get_rot, leaf_reader, write_chunk, acc, w=w, L=L, C=C,
                  kv=kv, descending=descending, key_dtype=banks[0].dtype)


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------

def _merge_tree_call(buf, ranks, starts, lens, *, group: int, n_out: int,
                     w: int, block_out: int, descending: bool,
                     interpret: bool):
    from repro.kernels.segmented_merge import _build_bank

    kv = ranks is not None
    R = starts.shape[0]
    assert group >= 2 and group & (group - 1) == 0, "group must be 2^L >= 2"
    assert R % group == 0, "run count must be a multiple of the group size"
    assert w & (w - 1) == 0
    L = group.bit_length() - 1
    n_groups = R // group
    if R == 0 or n_out == 0:
        empty = jnp.zeros((n_out,), buf.dtype)
        return (empty, jnp.zeros((n_out,), jnp.int32)) if kv else empty

    starts = starts.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    C = max(w, min(block_out, _next_pow2(n_out)))
    C = (C // w) * w
    Ha = C // w + L + 2
    Hp = pad_rows(Ha)                     # rows each leaf window DMAs

    # --- row-aligned banks (one shared bank, one DMA window per leaf) ------
    rows_per_run = -(-lens // w) + Hp + 2
    row0 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(rows_per_run)]).astype(jnp.int32)
    ROWS = n_out // w + R * (Hp + 3)
    _, last_k = bound_keys(buf.dtype, descending)
    kbank = _build_bank(buf, starts, lens, row0, ROWS, w, fill=last_k)
    rbank = (_build_bank(ranks.astype(jnp.int32), starts, lens, row0, ROWS,
                         w, fill=INVALID_RANK) if kv else None)

    # --- flat grid over (group, block) pairs -------------------------------
    glen = lens.reshape(n_groups, group).sum(axis=1)
    nb = -(-glen // C)
    blk0 = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(nb)])
    G = n_out // C + n_groups
    gsteps = jnp.arange(G, dtype=jnp.int32)
    grp = jnp.clip(jnp.searchsorted(blk0, gsteps, side="right") - 1,
                   0, n_groups - 1)
    o = jnp.minimum((gsteps - blk0[grp]) * C, (glen[grp] // C) * C)

    # --- nested co-rank partition per grid step ----------------------------
    steps = max(1, math.ceil(math.log2(max(n_out, 2))) + 1)
    meta = jax.vmap(lambda gr, oo: _tree_meta_one(
        gr, oo, buf, ranks if kv else None, starts, lens, row0, group=group,
        w=w, max_row=ROWS - Hp, steps=steps, descending=descending))(grp, o)
    mspec, meta = meta_block(meta)                        # (G, 1, n_meta)

    banks = [kbank, rbank] if kv else [kbank]
    spec, _ = row_blocks(G, C, w, buf.dtype)
    kern = functools.partial(_tree_kernel, w=w, L=L, C=C, Ha=Ha, kv=kv,
                             descending=descending)
    out = pl.pallas_call(
        kern,
        grid=(G,),
        in_specs=[mspec] + [pl.BlockSpec(memory_space=pl.ANY)] * len(banks),
        out_specs=[spec] * len(banks),
        scratch_shapes=[pltpu.VMEM((group, Hp, w), b.dtype) for b in banks]
        + tree_scratch(w=w, L=L, C=C, key_dtype=buf.dtype, kv=kv)
        + [pltpu.SemaphoreType.DMA((group * len(banks),))],
        out_shape=[row_blocks(G, C, w, b.dtype)[1] for b in banks],
        interpret=interpret,
        name="flims_merge_tree",
    )(meta, *banks)
    out = [o.reshape(G, C) for o in out]

    # --- gather padded blocks back to the flat group-order layout ----------
    goff = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(glen)])
    i = jnp.arange(n_out, dtype=jnp.int32)
    s = jnp.clip(jnp.searchsorted(goff, i, side="right") - 1,
                 0, n_groups - 1)
    pos = i - goff[s]
    gg = jnp.clip(blk0[s] + pos // C, 0, G - 1)
    if kv:
        return out[0][gg, pos % C], out[1][gg, pos % C]
    return out[0][gg, pos % C]


@functools.partial(jax.jit, static_argnames=("group", "n_out", "w",
                                             "block_out", "interpret"))
@obs.scoped("kernels.merge_tree")
def merge_tree_runs(buf, starts, lens, *, group: int, n_out: int, w: int = 32,
                    block_out: int = 1024, interpret: bool = True):
    """Merge consecutive groups of ``group = 2^L`` descending runs — run ``r``
    is ``buf[starts[r] : starts[r] + lens[r]]`` — through ``L`` fused tree
    levels in ONE ``pallas_call``. Returns the (n_out,) concatenation of the
    merged groups in group order; ``n_out`` must equal ``sum(lens)`` (static
    contract). Ragged and empty runs are fine (their bank rows are sentinel).
    """
    return _merge_tree_call(buf, None, starts, lens, group=group,
                            n_out=n_out, w=w, block_out=block_out,
                            descending=True, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("group", "n_out", "w",
                                             "block_out", "descending",
                                             "interpret"))
@obs.scoped("kernels.merge_tree_kv")
def merge_tree_runs_kv(buf, ranks, starts, lens, *, group: int, n_out: int,
                       w: int = 32, block_out: int = 1024,
                       descending: bool = True, interpret: bool = True):
    """Stable KV variant of ``merge_tree_runs``: (key, rank) lanes ride every
    level of the fused tree under the compound order (paper algorithm 3), so
    with ranks assigned in priority order the whole K-way reduction is
    stable; ascending is sorted natively via the static direction flag.
    Returns ``(merged_keys, merged_ranks)``.
    """
    return _merge_tree_call(buf, ranks, starts, lens, group=group,
                            n_out=n_out, w=w, block_out=block_out,
                            descending=descending, interpret=interpret)

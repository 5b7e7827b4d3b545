"""Pallas TPU kernel: streaming k-way merge of HBM-resident sorted runs.

Phase 2 of the out-of-core two-phase sort (TopSort, arXiv:2205.07991;
DESIGN.md §8). The fused merge-tree kernel (``kernels/merge_tree.py``)
assumes every run has been gathered into a scratch-resident bank, which
caps the mergeable size at VMEM; here the runs stay in HBM and each grid
step pulls only the windows its output block can touch:

- Input is one flat buffer of ``runs`` uniform sorted runs of ``run_len``
  elements (``run_len`` a power of two multiple of ``w``), viewed as a
  ``(ROWS, w)`` array in ``pl.ANY`` memory — never mapped to
  scratch by a BlockSpec.
- Consecutive ``fan_in = 2^L`` runs form one group; the grid flattens
  (group, output-block) pairs exactly like the fused tree kernel, and the
  same host-side nested co-rank partition (``merge_tree._tree_fns``)
  computes, per step, each leaf's *within-run* aligned start row plus the
  per-node initial rotations.
- Each step DMAs, per leaf, an ``Ha``-row window starting at that row into
  a double-buffered VMEM scratch slot (``pltpu.make_async_copy``); step
  ``g`` kicks off step ``g+1``'s copies into the other slot before waiting
  on its own, so the FLiMS dataflow of block ``g`` overlaps the HBM
  fetches of block ``g+1``. The dataflow itself is the shared
  ``merge_tree.tree_dataflow`` — only the leaf plumbing differs.
- A run's tail needs no sentinel rows in HBM: windows may extend past the
  run's end (the buffer carries ``stream_slack`` trailing elements so the
  DMA stays in bounds) and the leaf reader masks rows past ``run_rows``
  to sentinel lanes in-register.

The output is written in ``C``-key blocks and returned flat with the same
trailing-slack contract as the input, so phase-2 passes chain without a
re-pack: each pass costs exactly one read + one write of the data —
``ceil(log_fan_in(runs))`` HBM round trips total.
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
from repro.kernels.flims_merge import bound_keys, pad_rows, row_blocks
from repro.kernels.merge_tree import _tree_fns, tree_dataflow, tree_scratch
from repro import obs


#: grid steps per ``pallas_call``: bounds the scalar-prefetched meta slice
_CALL_STEPS = 4096


def _block(block_out: int, run_len: int, fan_in: int, w: int) -> int:
    """The kernel's output block: a power of two, ``>= w``, capped at the
    group length so the grid stays uniform (``glen % C == 0``)."""
    glen = fan_in * run_len
    C = max(w, min(block_out, glen))
    return 1 << (C.bit_length() - 1)


def stream_slack(fan_in: int, w: int, block_out: int) -> int:
    """Trailing elements a buffer must carry beyond the live data so every
    leaf DMA window stays in bounds: the deepest window is ``Ha`` rows
    (``C//w + L + 2``, DMA'd as a whole sublane tile) and the worst-case
    start is the run's end."""
    L = max(fan_in.bit_length() - 1, 1)
    C = 1 << (max(w, block_out).bit_length() - 1)
    return pad_rows(C // w + L + 2) * w


def _stream_meta_one(grp, o, buf, rbuf, *, runs: int, run_len: int,
                     fan_in: int, w: int, steps: int, descending: bool):
    """Meta column for one grid step: per-leaf *within-run* aligned start
    rows, then per internal node (preorder) the (left, right) rotations.
    Same recursion as ``merge_tree._tree_meta_one`` but over uniform
    HBM-resident runs, so leaf rows are run-relative (the kernel adds the
    run's base row) and clamp to ``run_len // w`` = "run exhausted"."""
    base = grp * fan_in
    starts_g = (base + jnp.arange(fan_in, dtype=jnp.int32)) * run_len
    lens_g = [run_len] * fan_in
    _, corank, _ = _tree_fns(buf, rbuf, starts_g, lens_g, steps=steps,
                             descending=descending)

    leaf_rows = [None] * fan_in
    rots = []

    def assign(lo, hi, a):
        mid = (lo + hi) // 2
        sx = corank(lo, mid, hi, a)
        sy = a - sx
        rots.append(sx % w)
        rots.append(sy % w)
        for clo, chi, s in ((lo, mid, sx), (mid, hi, sy)):
            if chi - clo == 1:
                leaf_rows[clo] = s // w
            else:
                assign(clo, chi, s - s % w)

    assign(0, fan_in, o)
    return jnp.stack([jnp.asarray(x, jnp.int32) for x in leaf_rows + rots])


def _stream_kernel(meta_ref, *refs, w: int, L: int, C: int, Ha: int,
                   bpg: int, run_rows: int, G: int, g0: int, kv: bool,
                   descending: bool):
    group = 1 << L
    nl = 2 if kv else 1
    hbm = refs[:nl]                           # ANY-space (ROWS, w) views
    # refs[nl:2 * nl] are the output buffers so far (aliased to ``outs``)
    outs = refs[2 * nl:3 * nl]                # (1, C/w, w) output blocks
    bufs = refs[3 * nl:4 * nl]                # VMEM (2, group, Hp, w)
    acc = refs[4 * nl:5 * nl]                 # inner-node streams
    sems = refs[-1]                           # DMA sems (2, group, nl)
    g = pl.program_id(0)
    slot = lax.rem(g, 2)

    def dma(step, sl, j, li):
        grp = (g0 + step) // bpg
        row = (grp * group + j) * run_rows + meta_ref[j, step]
        dst = bufs[li].at[sl, j]
        return pltpu.make_async_copy(
            hbm[li].at[pl.ds(row, dst.shape[0])], dst, sems.at[sl, j, li])

    def start_all(step, sl):
        for j in range(group):
            for li in range(nl):
                dma(step, sl, j, li).start()

    @pl.when(g == 0)
    def _():
        start_all(g, slot)

    @pl.when(g + 1 < G)
    def _():
        start_all(g + 1, 1 - slot)            # prefetch the next block

    for j in range(group):
        for li in range(nl):
            dma(g, slot, j, li).wait()

    key_dtype = hbm[0].dtype
    _, last_k = bound_keys(key_dtype, descending)
    fills = (last_k, jnp.int32(INVALID_RANK)) if kv else (last_k,)

    def leaf_reader(j):
        srow = meta_ref[j, g]

        def read(r):
            row = jnp.minimum(r, Ha - 1)
            valid = srow + r < run_rows       # rows past the run are pads
            return tuple(
                jnp.where(valid, bufs[li][slot, j, pl.ds(row, 1), :], f)
                for li, f in enumerate(fills))

        return read

    def get_rot(idx):
        return meta_ref[group + 2 * idx, g], meta_ref[group + 2 * idx + 1, g]

    def write_chunk(t, chunk):
        for ref, c in zip(outs, chunk):
            ref[0, pl.ds(t, 1), :] = c

    tree_dataflow(get_rot, leaf_reader, write_chunk, acc, w=w, L=L, C=C,
                  kv=kv, descending=descending, key_dtype=key_dtype)


def _stream_call(buf, ranks, *, runs: int, run_len: int, fan_in: int,
                 w: int, block_out: int, out_slack: int, descending: bool,
                 interpret: bool):
    kv = ranks is not None
    assert fan_in >= 2 and fan_in & (fan_in - 1) == 0, "fan_in must be 2^L"
    assert runs % fan_in == 0, "run count must be a multiple of fan_in"
    assert run_len >= w and run_len & (run_len - 1) == 0 and w & (w - 1) == 0
    L = fan_in.bit_length() - 1
    n_val = runs * run_len
    slack = stream_slack(fan_in, w, block_out)

    def with_slack(x, fill):
        need = n_val + slack
        if x.shape[0] < need:
            pad = jnp.full((need - x.shape[0],), fill, x.dtype)
            x = jnp.concatenate([x, pad])
        return x

    _, last_k = bound_keys(buf.dtype, descending)
    buf = with_slack(buf, last_k)
    if kv:
        ranks = with_slack(ranks.astype(jnp.int32), INVALID_RANK)

    C = _block(block_out, run_len, fan_in, w)
    Ha = C // w + L + 2
    run_rows = run_len // w
    bpg = fan_in * run_len // C               # blocks per group
    n_groups = runs // fan_in
    G = n_groups * bpg
    ROWS = (buf.shape[0] // w) * w // w
    kview = buf[:ROWS * w].reshape(ROWS, w)
    rview = ranks[:ROWS * w].reshape(ROWS, w) if kv else None

    # --- host nested co-rank partition, one column per grid step ----------
    steps = max(1, math.ceil(math.log2(max(fan_in * run_len, 2))) + 1)
    gsteps = jnp.arange(G, dtype=jnp.int32)
    meta = jax.vmap(lambda gr, oo: _stream_meta_one(
        gr, oo, buf, ranks if kv else None, runs=runs, run_len=run_len,
        fan_in=fan_in, w=w, steps=steps, descending=descending))(
            gsteps // bpg, (gsteps % bpg) * C)
    meta = meta.T.astype(jnp.int32)                       # (n_meta, G)

    # --- one pallas_call: sequential grid, cross-step double buffering ----
    out_blocks = -(-(n_val + out_slack) // C)
    dtypes = (buf.dtype, jnp.int32) if kv else (buf.dtype,)
    scratch = [pltpu.VMEM((2, 1 << L, pad_rows(Ha), w), d) for d in dtypes]
    scratch += tree_scratch(w=w, L=L, C=C, key_dtype=buf.dtype, kv=kv)
    scratch.append(pltpu.SemaphoreType.DMA((2, 1 << L, len(dtypes))))
    nl = len(dtypes)
    out = [jnp.zeros(row_blocks(out_blocks, C, w, d)[1].shape, d)
           for d in dtypes]
    # the grid runs in calls of at most _CALL_STEPS steps: each scalar-
    # prefetches only its own slice of the meta table (SMEM is 1 MiB) and
    # writes its blocks in place into the one output buffer
    for g0 in range(0, G, _CALL_STEPS):
        Gc = min(_CALL_STEPS, G - g0)
        out_spec = pl.BlockSpec((1, C // w, w),
                                lambda g, m, g0=g0: (g + g0, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Gc,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (2 * nl),
            out_specs=[out_spec] * nl,
            scratch_shapes=scratch,
        )
        kern = functools.partial(_stream_kernel, w=w, L=L, C=C, Ha=Ha,
                                 bpg=bpg, run_rows=run_rows, G=Gc, g0=g0,
                                 kv=kv, descending=descending)
        out = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=[row_blocks(out_blocks, C, w, d)[1] for d in dtypes],
            input_output_aliases={1 + nl + i: i for i in range(nl)},
            interpret=interpret,
            name="flims_stream_merge",
        )(meta[:, g0:g0 + Gc], *((kview, rview) if kv else (kview,)), *out)

    # Blocks past G are never written; the caller only reads [:n_val] and
    # the kernel only reads windows inside [0, n_val + slack).
    if kv:
        return out[0].reshape(-1), out[1].reshape(-1)
    return out[0].reshape(-1)


@functools.partial(jax.jit, static_argnames=("runs", "run_len", "fan_in",
                                             "w", "block_out", "out_slack",
                                             "interpret"))
@obs.scoped("kernels.stream_merge")
def stream_merge_runs(buf, *, runs: int, run_len: int, fan_in: int,
                      w: int = 32, block_out: int = 1024,
                      out_slack: int = 0, interpret: bool = True):
    """Merge consecutive groups of ``fan_in = 2^L`` descending HBM-resident
    runs of uniform ``run_len`` (a power of two ``>= w``) in ONE streaming
    ``pallas_call``. ``buf`` is the flat concatenation of the runs; if it
    carries fewer than ``stream_slack`` trailing elements past
    ``runs * run_len`` they are (copied and) sentinel-padded first — size
    the buffer up front to chain passes copy-free. Returns a flat buffer
    whose ``[:runs * run_len]`` prefix is the concatenation of the merged
    groups, itself carrying ``>= out_slack`` trailing elements."""
    return _stream_call(buf, None, runs=runs, run_len=run_len,
                        fan_in=fan_in, w=w, block_out=block_out,
                        out_slack=out_slack, descending=True,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("runs", "run_len", "fan_in",
                                             "w", "block_out", "out_slack",
                                             "descending", "interpret"))
@obs.scoped("kernels.stream_merge_kv")
def stream_merge_runs_kv(buf, ranks, *, runs: int, run_len: int,
                         fan_in: int, w: int = 32, block_out: int = 1024,
                         out_slack: int = 0, descending: bool = True,
                         interpret: bool = True):
    """Stable KV variant of ``stream_merge_runs``: int32 rank lanes ride
    the same DMA windows and the whole tree compares the compound
    ``(key, rank)`` order (paper algorithm 3), so with ranks assigned in
    priority order the streamed reduction is stable; ascending is sorted
    natively via the static direction flag. Returns ``(keys, ranks)``."""
    return _stream_call(buf, ranks, runs=runs, run_len=run_len,
                        fan_in=fan_in, w=w, block_out=block_out,
                        out_slack=out_slack, descending=descending,
                        interpret=interpret)

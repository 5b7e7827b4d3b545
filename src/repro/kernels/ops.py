"""Jit'd public wrappers for the Pallas kernels.

Every wrapper takes ``interpret`` from its caller; ``None`` asks the one
backend predicate, ``engine.schedule.default_interpret`` (Mosaic on a TPU
backend, the Pallas interpreter elsewhere). ``kernel_sort`` is the
end-to-end two-level sorter: Pallas chunk sort + fused FLiMS merge-tree
passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.flims import sentinel_for
from repro.core.lanes import INVALID_RANK
from repro.kernels.bitonic_sort import sort_chunks_kv_pallas, sort_chunks_pallas
from repro.kernels.flims_merge import bound_keys, flims_merge_pallas

from repro import obs


def _interpret(interpret):
    from repro.engine.schedule import default_interpret
    return default_interpret() if interpret is None else interpret


def merge(a: jnp.ndarray, b: jnp.ndarray, *, w: int = 128,
          block_out: int = 4096, interpret=None) -> jnp.ndarray:
    """Descending merge of two sorted 1-D arrays (Pallas FLiMS kernel)."""
    return flims_merge_pallas(a, b, w=w, block_out=block_out,
                              interpret=_interpret(interpret))


def sort_rows(x: jnp.ndarray, *, rows_per_block: int = 8,
              interpret=None) -> jnp.ndarray:
    """Descending per-row sort of an (m, c) array (Pallas bitonic kernel)."""
    return sort_chunks_pallas(x, rows_per_block=rows_per_block,
                              interpret=_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "w", "descending",
                                             "levels", "interpret"))
@obs.scoped("kernels.kernel_sort")
def kernel_sort(x: jnp.ndarray, *, chunk: int = 512, w: int = 128,
                descending: bool = True, levels: int = 2,
                interpret=None) -> jnp.ndarray:
    """Full sort of a 1-D array: chunk kernel + fused FLiMS merge-tree passes.

    The merge phase executes a ``tree_pallas`` MergeSchedule (DESIGN.md §5):
    each pass collapses ``levels`` tree levels in one ``pallas_call``, with
    the intermediate runs resident in kernel scratch instead of HBM.
    """
    from repro.engine.schedule import MergeSchedule, reduce_rows
    interpret = _interpret(interpret)
    n = x.shape[0]
    if n <= 1:
        return x
    c = 1
    while c < min(chunk, n):
        c *= 2
    n_pad = -(-n // c) * c
    # pad rows to a power of two for clean pairwise passes
    m = n_pad // c
    m2 = 1
    while m2 < m:
        m2 *= 2
    n_pad = m2 * c
    with obs.kernel_scope("kernels.kernel_sort.pad"):
        xp = jnp.pad(x, (0, n_pad - n),
                     constant_values=sentinel_for(x.dtype))
    with obs.kernel_scope("kernels.kernel_sort.chunk_sort"):
        rows = sort_chunks_pallas(xp.reshape(-1, c), interpret=interpret)
    ww = min(w, c)
    sched = MergeSchedule("tree_pallas", levels_per_pass=levels, w=ww,
                          block_out=max(ww, 4096))
    merged = reduce_rows(rows, schedule=sched, interpret=interpret)
    with obs.kernel_scope("kernels.kernel_sort.trim"):
        out = merged[:n]
        return out if descending else out[::-1]


@functools.partial(jax.jit, static_argnames=("chunk", "w", "descending",
                                             "interpret", "levels"))
@obs.scoped("kernels.kernel_argsort")
def kernel_argsort(keys: jnp.ndarray, *, chunk: int = 256, w: int = 32,
                   descending: bool = True, levels: int = 2,
                   interpret=None) -> jnp.ndarray:
    """Stable argsort of a 1-D array, entirely in Pallas KV kernels.

    The two-level sorter of ``kernel_sort`` over (key, rank) lanes: one KV
    chunk-sort ``pallas_call``, then fused KV merge-tree passes (a
    ``tree_pallas`` MergeSchedule carrying the rank lane through every
    level). The rank lane (original positions) breaks ties and *is* the
    result — matches ``jnp.argsort(stable=True)`` bit-for-bit in either
    direction (ascending is sorted natively by flipping the key comparison,
    not by mirroring).
    """
    from repro.engine.schedule import MergeSchedule, reduce_rows
    interpret = _interpret(interpret)
    n = keys.shape[0]
    if n <= 1:
        return jnp.zeros((n,), jnp.int32)
    c = 1
    while c < min(chunk, n):
        c *= 2
    m2 = 1
    while m2 < -(-n // c):
        m2 *= 2
    n_pad = m2 * c
    _, last = bound_keys(keys.dtype, descending)
    with obs.kernel_scope("kernels.kernel_argsort.pad"):
        kp = jnp.pad(keys, (0, n_pad - n), constant_values=last)
        rp = jnp.where(jnp.arange(n_pad) < n,
                       jnp.arange(n_pad, dtype=jnp.int32), INVALID_RANK)
    with obs.kernel_scope("kernels.kernel_argsort.chunk_sort"):
        k2, r2 = sort_chunks_kv_pallas(kp.reshape(-1, c), rp.reshape(-1, c),
                                       descending=descending,
                                       interpret=interpret)
    ww = min(w, c)
    sched = MergeSchedule("tree_pallas", levels_per_pass=levels, w=ww,
                          block_out=max(ww, 4096))
    _, perm = reduce_rows(k2, ranks=r2, schedule=sched,
                          descending=descending, interpret=interpret)
    with obs.kernel_scope("kernels.kernel_argsort.trim"):
        return perm[:n]

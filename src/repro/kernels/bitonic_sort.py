"""Pallas TPU kernel: in-register bitonic sort of fixed-width chunks.

The sort-in-chunks stage of the paper's complete sorter (§8.2, chunk=512).
Each grid step sorts a (rows_per_block, chunk) VMEM tile descending along the
trailing axis with the full bitonic network — log2(c)(log2(c)+1)/2 stages of
native lane rotates + min/max, i.e. pure VPU work with no dynamic shuffles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flims_merge import lane_first, lane_partner


def _stages(n: int):
    """The bitonic network's (k, d) stages for n (a power of two) keys."""
    k = 2
    while k <= n:
        d = k // 2
        while d >= 1:
            yield k, d
            d //= 2
        k *= 2


def _bitonic_rows_desc(x: jnp.ndarray) -> jnp.ndarray:
    """Sort each row of (m, c) descending; c a power of two. Static network:
    each stage compares every key with its XOR-partner lane; blocks whose
    bit ``k`` is set sort ascending, so the final pass (``k == c``) leaves
    every row descending."""
    i = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    for k, d in _stages(x.shape[1]):
        hi = (i & d) != 0
        p = lane_partner(x, d, hi, 1)
        asc = (i & k) != 0
        x = jnp.where(hi != asc, jnp.minimum(x, p), jnp.maximum(x, p))
    return x


def _keep_first(first, k, r, pk, pr, hi, asc):
    """One compare-exchange of the compound network: every element keeps
    itself or takes its partner so the lower index of each pair holds the
    one that orders first (last, in ascending blocks)."""
    top_first = (hi & first(pk, pr, k, r)) | (~hi & first(k, r, pk, pr))
    keep = top_first ^ asc
    return jnp.where(keep, k, pk), jnp.where(keep, r, pr)


def _bitonic_rows_kv(k: jnp.ndarray, r: jnp.ndarray,
                     descending: bool = True):
    """Stable row-wise bitonic sort of (key, rank) lane pairs.

    Orders each row by (key desc-or-asc, rank asc); with ranks assigned in
    input order this is a stable sort. Same static network as
    ``_bitonic_rows_desc``, with the compound comparator on both lanes.
    """
    first = lane_first(descending)
    i = lax.broadcasted_iota(jnp.int32, k.shape, 1)
    for kk, d in _stages(k.shape[1]):
        hi = (i & d) != 0
        pk, pr = lane_partner(k, d, hi, 1), lane_partner(r, d, hi, 1)
        k, r = _keep_first(first, k, r, pk, pr, hi, (i & kk) != 0)
    return k, r


def sort_flat_ref(k_ref, r_ref=None, *, descending: bool = True) -> None:
    """Sort an (R, 128) key ref in place as ONE row-major sequence — with
    ``r_ref``, (key, rank) pairs by the compound order — through the
    bitonic network over all ``R * 128`` elements (a power of two),
    streamed through (8, 128) tiles so the code is one small loop per stage
    however large the bank:

    - partners less than a row apart: lane rotates inside the tile;
    - partners less than a tile apart: sublane rotates inside the tile;
    - partners whole tiles apart: a compare-exchange of the two tiles.
    """
    R, c = k_ref.shape
    TR = 8                                   # rows of one (8, 128) tile
    refs = (k_ref,) if r_ref is None else (k_ref, r_ref)
    if r_ref is None:
        first = (lambda a, b: a[0] > b[0]) if descending else \
            (lambda a, b: a[0] < b[0])
    else:
        kv_first = lane_first(descending)
        first = lambda a, b: kv_first(a[0], a[1], b[0], b[1])
    shape = (TR, c)
    row = lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)

    def tile(t):
        return pl.ds(pl.multiple_of(t * TR, TR), TR)

    for kk, d in _stages(R * c):
        if d < c * TR:                       # partner inside the tile
            def body(t, carry, kk=kk, d=d):
                sl = tile(t)
                x = [ref[sl, :] for ref in refs]
                i = (t * TR + row) * c + lane
                hi = (i & d) != 0
                axis, sh = (1, d) if d < c else (0, d // c)
                p = [lane_partner(v, sh, hi, axis) for v in x]
                top_first = (hi & first(p, x)) | (~hi & first(x, p))
                keep = top_first ^ ((i & kk) != 0)
                for ref, v, pv in zip(refs, x, p):
                    ref[sl, :] = jnp.where(keep, v, pv)
                return carry
            lax.fori_loop(0, R // TR, body, 0)
        else:                                # partner tile t ^ D
            D = d // (c * TR)

            def body(u, carry, kk=kk, D=D):
                t = (u // D) * 2 * D + u % D  # the pair's lower tile
                sa, sb = tile(t), tile(t + D)
                a = [ref[sa, :] for ref in refs]
                b = [ref[sb, :] for ref in refs]
                asc = (((t * TR + row) * c + lane) & kk) != 0
                keep = first(a, b) ^ asc
                for ref, va, vb in zip(refs, a, b):
                    ref[sa, :] = jnp.where(keep, va, vb)
                    ref[sb, :] = jnp.where(keep, vb, va)
                return carry
            lax.fori_loop(0, R // TR // 2, body, 0)


def _sort_kernel(x_ref, o_ref):
    o_ref[...] = _bitonic_rows_desc(x_ref[...])


def _sort_kv_kernel(k_ref, r_ref, ok_ref, or_ref, *, descending: bool):
    ok, orr = _bitonic_rows_kv(k_ref[...], r_ref[...], descending=descending)
    ok_ref[...] = ok
    or_ref[...] = orr


@functools.partial(jax.jit, static_argnames=("rows_per_block", "interpret"))
def sort_chunks_pallas(x: jnp.ndarray, *, rows_per_block: int = 8,
                       interpret: bool = True) -> jnp.ndarray:
    """Sort each row of a (m, c) array descending. c must be a power of 2."""
    m, c = x.shape
    assert c & (c - 1) == 0, "chunk width must be a power of two"
    rb = min(rows_per_block, m)
    while m % rb:
        rb -= 1
    grid = (m // rb,)
    return pl.pallas_call(
        _sort_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rb, c), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rb, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, c), x.dtype),
        interpret=interpret,
        name="bitonic_sort_chunks",
    )(x)


@functools.partial(jax.jit, static_argnames=("rows_per_block", "descending",
                                             "interpret"))
def sort_chunks_kv_pallas(k: jnp.ndarray, r: jnp.ndarray, *,
                          rows_per_block: int = 8, descending: bool = True,
                          interpret: bool = True):
    """Stable row-wise sort of (key, rank) lane rows in one ``pallas_call``.

    ``k``/``r`` are (m, c) key and int32 rank banks; each row is ordered by
    the compound (key ``descending``, rank asc) comparator and both lanes are
    returned permuted identically.
    """
    m, c = k.shape
    assert k.shape == r.shape
    assert c & (c - 1) == 0, "chunk width must be a power of two"
    rb = min(rows_per_block, m)
    while m % rb:
        rb -= 1
    grid = (m // rb,)
    spec = pl.BlockSpec((rb, c), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_sort_kv_kernel, descending=descending),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((m, c), k.dtype),
                   jax.ShapeDtypeStruct((m, c), r.dtype)],
        interpret=interpret,
        name="bitonic_sort_chunks_kv",
    )(k, r)

"""FLiMS-based top-k selection.

Observation: one FLiMS "cycle" (MAX selector + butterfly, paper fig. 9) maps
two descending k-lists to the sorted top-k of their union. Top-k of an
arbitrary array is therefore: bitonic-sort rows of width c=k, then a binary
tree reduction where every node is a *single* selector+butterfly — i.e. a
parallel merge tree (paper §2.1) specialised to fixed-k streams.

Every network runs over key+rank lanes (`core/lanes.py`): the rank lane both
breaks ties by input position (lax.top_k order) and *is* the returned index;
an optional ``values`` payload pytree rides extra lanes through the same
comparators (KV top-k — used by the serving sampler and MoE router).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.butterfly import bitonic_sort
from repro.core.lanes import (KEY, RANK, VAL, sentinel_for, stable_compare,
                              topk_node)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


#: widest padded row one top-k network sorts at once; wider rows go through
#: ``lax.map`` one chunk at a time, so the network's stage temporaries stay
#: at one chunk's size (a 2^24-key row otherwise needs more than 16 GB)
CHUNK = 1 << 18


def _reduce_rows(rows):
    """Tree-reduce sorted k-rows pairwise along axis -2 to one row."""
    while rows[KEY].shape[-2] > 1:
        m = rows[KEY].shape[-2]
        if m % 2 == 1:  # carry odd row through
            carry = jax.tree.map(lambda r: r[..., -1:, :], rows)
            rows = jax.tree.map(lambda r: r[..., :-1, :], rows)
        else:
            carry = None
        a = jax.tree.map(lambda r: r[..., 0::2, :], rows)
        b = jax.tree.map(lambda r: r[..., 1::2, :], rows)
        rows = topk_node(a, b, stable_compare)
        if carry is not None:
            rows = jax.tree.map(lambda r, c: jnp.concatenate([r, c], axis=-2),
                                rows, carry)
    return rows


@partial(jax.jit, static_argnames=("k",))
def flims_topk(x: jnp.ndarray, k: int, values=None):
    """Top-k of the trailing axis: ``(vals, inds)`` — or, with a ``values``
    payload pytree of ``x``-shaped leaves, ``(vals, inds, payload_topk)``.

    Values descending; ties broken by lower index first (matches lax.top_k).
    Works on any 1-D or batched (..., n) array over the trailing axis.
    When fewer than ``k`` elements exist (``k > n`` after the power-of-two
    padding) the tail is masked by rank validity: indices are clamped to 0
    and the values/payload report the dtype sentinel / zeros, so no returned
    index ever points at padding.
    """
    kk = _next_pow2(k)
    n = x.shape[-1]
    n_pad = max(_next_pow2(n), kk)
    sent = sentinel_for(x.dtype)
    pad = [(0, 0)] * (x.ndim - 1) + [(0, n_pad - n)]
    xp = jnp.pad(x, pad, constant_values=sent)
    idx = jnp.arange(n_pad, dtype=jnp.int32)
    idx = jnp.broadcast_to(idx, xp.shape)
    rows = {KEY: xp.reshape(x.shape[:-1] + (n_pad // kk, kk)),
            RANK: idx.reshape(x.shape[:-1] + (n_pad // kk, kk))}
    if values is not None:
        rows[VAL] = jax.tree.map(
            lambda v: jnp.pad(v, pad).reshape(x.shape[:-1] + (n_pad // kk, kk)),
            values)
    if kk < CHUNK < n_pad:
        # one chunk's network at a time: top-k of a union is the top-k of
        # the parts' top-k, and the rank lane orders every tie, so the
        # chunked result is the same to the bit
        c = n_pad // CHUNK
        parts = jax.tree.map(lambda r: jnp.moveaxis(
            r.reshape(r.shape[:-2] + (c, CHUNK // kk, kk)), -3, 0), rows)
        tops = lax.map(lambda p: _reduce_rows(
            bitonic_sort(p, compare=stable_compare)), parts)
        rows = jax.tree.map(lambda r: jnp.moveaxis(r[..., 0, :], 0, -2),
                            tops)
    else:
        rows = bitonic_sort(rows, compare=stable_compare)
    rows = _reduce_rows(rows)
    vals = rows[KEY][..., 0, :k]
    inds = rows[RANK][..., 0, :k]
    # rank validity: padding carries ranks >= n, so it can only surface when
    # k exceeds the real element count — mask it out of the results.
    valid = inds < n
    vals = jnp.where(valid, vals, sent)
    inds = jnp.where(valid, inds, 0)
    if values is None:
        return vals, inds
    pay = jax.tree.map(
        lambda r: jnp.where(valid, r[..., 0, :k], jnp.zeros((), r.dtype)),
        rows[VAL])
    return vals, inds, pay

"""Plain reference for MoE routing with a capacity cut, and its control.

The unfused pipeline, written out here so that it imports nothing of the
system under test: top-k of each token's router logits, softmax over the
k selected logits, a stable ascending sort of the (token, slot) pairs by
expert id, and GShard's capacity cut (each expert keeps its first
``capacity`` pairs in that order).
"""
import jax
import jax.numpy as jnp
from jax import lax


def reference(logits, k: int, capacity: int, dtype=jnp.float32):
    """Returns (experts, tokens, perm, weights, slabs, keep), each (T*k,),
    in stable sorted pair order; ``slabs`` is ``e * capacity + rank`` for
    a kept pair and ``E * capacity`` for a dropped one."""
    T, E = logits.shape
    vals, idx = lax.top_k(logits.astype(dtype), k)
    weights = jax.nn.softmax(vals, axis=-1).astype(jnp.float32).reshape(-1)
    experts = idx.reshape(-1).astype(jnp.int32)
    perm = jnp.argsort(experts, stable=True).astype(jnp.int32)
    e_sorted = experts[perm]
    first = jnp.searchsorted(e_sorted, e_sorted, side="left").astype(
        jnp.int32)
    rank = jnp.arange(T * k, dtype=jnp.int32) - first
    keep = rank < capacity
    slabs = jnp.where(keep, e_sorted * capacity + rank, E * capacity)
    return e_sorted, perm // k, perm, weights[perm], slabs, keep


def control(logits, k: int, capacity: int):
    """The same pipeline on bfloat16 logits: the precision below the
    configuration's float32 router."""
    return reference(logits, k, capacity, dtype=jnp.bfloat16)

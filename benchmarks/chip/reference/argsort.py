"""Plain reference for a stable argsort, and its control.

Imports nothing of the system under test.
"""
import jax.numpy as jnp


def reference(keys, descending: bool):
    """Stable permutation: equal keys keep table order."""
    return jnp.argsort(keys, stable=True, descending=descending).astype(
        jnp.int32)


def control(keys, descending: bool):
    """Breaks the stability guarantee: the stable sort in the other
    direction, reversed, puts every run of equal keys in reverse order."""
    return jnp.argsort(keys, stable=True,
                       descending=not descending)[::-1].astype(jnp.int32)

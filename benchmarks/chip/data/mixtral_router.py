"""Router logits of one prefill chunk through every MoE layer:
(layers, tokens, experts) float32, i.i.d. N(0, 1).

The router's matmul is not the engine's work, so its output is drawn
directly; the distribution is an assumption recorded in the configuration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(key, cfg: dict, traffic: dict) -> dict:
    shape = (int(cfg["num_hidden_layers"]), int(traffic["tokens"]),
             int(cfg["num_local_experts"]))
    return {"logits": jax.random.normal(key, shape, jnp.float32)}

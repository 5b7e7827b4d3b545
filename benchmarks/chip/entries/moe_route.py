"""``repro.engine.moe_route`` for every layer of a model, in one
``jax.jit`` program, the way a forward pass routes a prefill chunk: each
layer's (T, E) router logits in, every ``RouteResult`` lane out, one
layer after another through ``lax.map``.

Routing is dropless, as the configuration's model is: each expert's
capacity is T * k, so every pair fits and nothing can drop."""
import jax
import jax.numpy as jnp
from jax import lax

OP = "moe_route"
#: the integer lanes must match exactly; the combine weights may differ
#: from the float32 reference by the rounding of another float32 formula
#: of a 2-term softmax (a few ulps, under 1e-6), while bfloat16 weights
#: would differ by 1e-3 or more
LIMITS = {"route_lane_mismatch": 0, "route_weight_gap": 1e-5}


def capacity(cfg, traffic):
    """Dropless: T * k slots per expert hold every pair of the chunk."""
    return int(traffic["tokens"]) * int(cfg["num_experts_per_tok"])


def args(data, cfg, traffic):
    return (data["logits"],)


def units(args, cfg, traffic):
    """Tokens routed: each layer routes every token of the chunk."""
    L, T, _ = args[0].shape
    return int(L) * int(T)


def shapes(args, cfg, traffic):
    L, T, E = args[0].shape
    return {"L": int(L), "T": int(T), "E": int(E),
            "k": int(cfg["num_experts_per_tok"])}


def key_args(args, cfg, traffic):
    """Arguments the planner keys this call on: one layer's logits."""
    return (args[0][0], int(cfg["num_experts_per_tok"]))


def timed(cfg, traffic):
    from repro import engine
    k, cap = int(cfg["num_experts_per_tok"]), capacity(cfg, traffic)

    def route_layers(logits):
        return lax.map(lambda x: tuple(engine.moe_route(x, k, cap)), logits)

    return jax.jit(route_layers)


def reference(args, cfg, traffic, control=False):
    from benchmarks.chip.reference import moe_route as ref
    fn = ref.control if control else ref.reference
    k, cap = int(cfg["num_experts_per_tok"]), capacity(cfg, traffic)
    return jax.vmap(lambda x: fn(x, k, cap))(args[0])


def compare(out, ref):
    lanes = sum(int(jnp.sum(o.astype(jnp.int32) != r.astype(jnp.int32)))
                for j, (o, r) in enumerate(zip(out, ref)) if j != 3)
    gap = float(jnp.max(jnp.abs(out[3] - ref[3])))
    return {"route_lane_mismatch": lanes, "route_weight_gap": gap}

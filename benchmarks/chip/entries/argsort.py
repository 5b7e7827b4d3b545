"""``repro.engine.argsort`` called eagerly from the host, as a query engine
calls a library: one column in, the full row-id permutation out."""
import jax.numpy as jnp

OP = "argsort"
#: compared numbers and their limits: an exact comparison
LIMITS = {"perm_mismatch": 0}


def args(data, cfg, traffic):
    return (data[traffic["column"]],)


def units(args, cfg, traffic):
    return int(args[0].shape[0])


def shapes(args, cfg, traffic):
    return {"n": int(args[0].shape[0])}


def timed(cfg, traffic):
    from repro import engine
    descending = bool(traffic["descending"])
    return lambda keys: engine.argsort(keys, descending=descending)


def reference(args, cfg, traffic, control=False):
    from benchmarks.chip.reference import argsort as ref
    fn = ref.control if control else ref.reference
    return fn(args[0], bool(traffic["descending"]))


def compare(out, ref):
    return {"perm_mismatch": int(jnp.sum(out != ref))}

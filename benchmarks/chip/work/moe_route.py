"""Least HBM traffic of routing T tokens over E experts, top-k, in each of
L layers: read the T*E float32 logits, write T*k routed pairs of 21 bytes
each (int32 expert, token, perm and slab, float32 weight, bool keep)."""


def count(L: int, T: int, E: int, k: int) -> dict:
    return {"bytes": L * (4 * T * E + (4 + 4 + 4 + 4 + 4 + 1) * T * k),
            "flops": 0}

"""Pallas TPU megakernel: fused MoE routing — logits → capacity slabs.

The engine's flagship consumer (MoE dispatch) used to bracket ONE
``engine.segment_sort`` with ~5 separate XLA ops: router softmax, top-k,
pair flattening, the capacity rank scan, and the slab-index select — every
intermediate (logits, weights, ranks, slab indices) round-tripping HBM
between ops. This kernel executes the whole routing pipeline per token
chunk inside ONE ``pallas_call``:

1. **top-k in registers** — ``k`` iterative arg-max sweeps over the
   experts, ties to the lower expert index (bit-for-bit ``lax.top_k``).
   The logits arrive transposed, (E, T/128, 128): every token is a lane
   and the sweeps are elementwise across expert slabs;
2. **softmax in registers** over the k selected logits (``jax.nn.softmax``
   op-for-op, so combine weights match the unfused path exactly);
3. **stable expert sort in one bitonic network** — each (token, expert)
   pair is encoded as the compound key ``e * Np + p`` (``p`` the pair's
   input position), so a plain ascending sort IS the stable-by-expert
   order of the dispatch contract, whatever order the pairs are laid out
   in. Keys are distinct, which frees the KV network's int32 rank lane to
   carry the combine weight's bits (``bitcast``) as an inert payload; the
   whole group's (Np/128, 128) bank sorts in VMEM through one bitonic
   network streamed over (8, 128) tiles (``sort_flat_ref``) and no
   intermediate leaves the kernel;
4. **capacity-drop by stable rank in-kernel** — per expert, the count of
   sorted pairs routed to lower experts is its first-occurrence offset,
   so ``pos_in_e = i - first[e]`` reproduces the unfused path's
   searchsorted rank, and GShard drop semantics (``pos_in_e < cap``) are
   bit-for-bit identical to ``moe_apply_grouped``.

Outputs per group, all in sorted pair order: expert ids, source token ids,
the stable pair permutation, combine weights, slab indices
(``e*cap + pos`` or the ``E*cap`` overflow slot), and the keep mask.

The ``xla`` reference variant below is the unfused pipeline verbatim
(``lax.top_k`` → ``jax.nn.softmax`` → stable argsort → searchsorted) — the
oracle the fused kernel is tested bit-for-bit against, and the planner's
CPU/GPU serving path where interpret-mode Pallas is correctness-only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.flims import next_pow2 as _next_pow2
from repro.core.lanes import INVALID_RANK
from repro.kernels.bitonic_sort import sort_flat_ref

_I32_MAX = jnp.iinfo(jnp.int32).max

#: scoped VMEM for one routing group: the (E, T/128, 128) logits block,
#: double-buffered, plus the (Np/128, 128) sort banks (v5e has 128 MiB)
_VMEM_LIMIT = 96 * 1024 * 1024


def _topk_softmax(l_ref, k: int, E: int):
    """In-register top-k + softmax over one group's (E, R, 128) logits —
    every token its own lane, the experts along the leading axis.

    ``k`` arg-max sweeps over the experts, ties to the lower expert index —
    value-and-index identical to ``lax.top_k`` (which Mosaic cannot
    lower) — then the ``jax.nn.softmax`` combine weights over the selected
    logits. The sweeps compare the monotone int32 bit transform of the
    floats, not the floats: ``top_k`` orders by IEEE *total order*
    (``-0.0 < +0.0``), which float ``==``/``max`` cannot see. Returns
    per-slot lists of (R, 128) weights and expert ids.
    """
    untwist = lambda b: b ^ ((b >> 31) & jnp.int32(0x7FFFFFFF))
    okey = lambda e: untwist(lax.bitcast_convert_type(l_ref[0, e],
                                                      jnp.int32))
    shape = l_ref.shape[2:]
    neg = jnp.full(shape, jnp.iinfo(jnp.int32).min, jnp.int32)
    vals, idxs = [], []
    for _ in range(k):
        def sweep(e, mi, idxs=tuple(idxs)):
            m, ij = mi
            v = okey(e)
            for prev in idxs:                   # experts already selected
                v = jnp.where(prev == e, neg, v)
            better = v > m                      # strict: ties keep lower e
            return jnp.where(better, v, m), jnp.where(better, e, ij)

        m, ij = lax.fori_loop(0, E, sweep,
                              (neg, jnp.full(shape, E, jnp.int32)))
        vals.append(lax.bitcast_convert_type(untwist(m), jnp.float32))
        idxs.append(ij)
    x_max = functools.reduce(jnp.maximum, vals)
    unnorm = [jnp.exp(v - x_max) for v in vals]
    total = functools.reduce(lambda a, b: a + b, unnorm)
    return [u / total for u in unnorm], idxs


def _route_kernel(l_ref, e_ref, t_ref, p_ref, w_ref, s_ref, m_ref,
                  ks_ref, rs_ref, *, k: int, E: int, cap: int, T: int,
                  Np: int):
    R = l_ref.shape[2]                                  # token rows per slot
    shape = (R, 128)
    wgt, eix = _topk_softmax(l_ref, k, E)
    N = T * k

    # ---- compound sort key: e * Np + pair-position (distinct, ascending
    # order == stable-by-expert), weight bits riding the inert rank lane;
    # slot j's tokens fill rows [j*R, (j+1)*R) of the (Np/128, 128) bank,
    # pad tokens and pad rows hold the fill and sort to the tail ----------
    tok = (lax.broadcasted_iota(jnp.int32, shape, 0) * 128
           + lax.broadcasted_iota(jnp.int32, shape, 1))
    real = tok < T
    ks_ref[...] = jnp.full(ks_ref.shape, _I32_MAX, jnp.int32)
    rs_ref[...] = jnp.full(rs_ref.shape, INVALID_RANK, jnp.int32)
    for j in range(k):
        key = eix[j] * Np + tok * k + j
        ks_ref[pl.ds(j * R, R), :] = jnp.where(real, key, _I32_MAX)
        rs_ref[pl.ds(j * R, R), :] = jnp.where(
            real, lax.bitcast_convert_type(wgt[j], jnp.int32), INVALID_RANK)

    # ---- the whole group through one in-register bitonic network ---------
    sort_flat_ref(ks_ref, rs_ref, descending=False)
    ks, rs = ks_ref[...], rs_ref[...]

    # ---- decode + capacity drop by stable rank ---------------------------
    lg = Np.bit_length() - 1
    i = (lax.broadcasted_iota(jnp.int32, ks.shape, 0) * 128
         + lax.broadcasted_iota(jnp.int32, ks.shape, 1))
    valid = i < N                 # real pairs sort before the pad/fill tail
    e_s = jnp.where(valid, ks >> lg, E)
    p_s = jnp.where(valid, ks & (Np - 1), 0)
    w_s = jnp.where(valid, lax.bitcast_convert_type(rs, jnp.float32), 0.0)

    def first_of(e, first):       # pairs of experts < e precede expert e
        n_before = jnp.sum((e_s < e).astype(jnp.int32))
        return jnp.where(e_s == e, n_before, first)

    first = lax.fori_loop(0, E, first_of, jnp.zeros(ks.shape, jnp.int32))
    pos = i - first
    keep = valid & (pos < cap)
    e_ref[0] = e_s
    t_ref[0] = p_s // k
    p_ref[0] = p_s
    w_ref[0] = w_s
    s_ref[0] = jnp.where(keep, e_s * cap + pos, E * cap)
    m_ref[0] = keep.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "capacity", "interpret"))
@obs.scoped("kernels.route_fuse")
def moe_route_pallas(logits, k: int, capacity: int, *,
                     interpret: bool = True):
    """Fused routing of (G, T, E) f32 router logits: one ``pallas_call``,
    one grid step per token group. Returns, each (G, T*k) in stable sorted
    pair order: ``(experts, tokens, perm, weights, slabs, keep_i32)``.
    """
    G, T, E = logits.shape
    N = T * k
    Tp = -(-T // 1024) * 1024               # token rows: whole sublane tiles
    R = Tp // 128
    Np = _next_pow2(k * Tp)
    assert E * Np < 2 ** 31, (
        f"moe_route: compound key e*{Np}+p overflows int32 at E={E}; "
        "shrink the token chunk")
    cap = int(capacity)
    # tokens along the lanes, experts along the leading axis
    lt = jnp.pad(logits, ((0, 0), (0, Tp - T), (0, 0)))
    lt = jnp.swapaxes(lt, 1, 2).reshape(G, E, R, 128)

    kern = functools.partial(_route_kernel, k=k, E=E, cap=cap, T=T, Np=Np)
    out_spec = pl.BlockSpec((1, Np // 128, 128), lambda g: (g, 0, 0))
    shape = lambda dt: jax.ShapeDtypeStruct((G, Np // 128, 128), dt)
    outs = pl.pallas_call(
        kern,
        grid=(G,),
        in_specs=[pl.BlockSpec((1, E, R, 128), lambda g: (g, 0, 0, 0))],
        out_specs=[out_spec] * 6,
        out_shape=[shape(jnp.int32), shape(jnp.int32), shape(jnp.int32),
                   shape(jnp.float32), shape(jnp.int32), shape(jnp.int32)],
        scratch_shapes=[pltpu.VMEM((Np // 128, 128), jnp.int32),
                        pltpu.VMEM((Np // 128, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flims_route_fuse",
    )(lt)
    return tuple(o.reshape(G, Np)[:, :N] for o in outs)


@obs.scoped("kernels.route_xla")
def moe_route_xla(logits, k: int, capacity: int):
    """The unfused reference pipeline — the exact op sequence
    ``moe_apply_grouped`` ran before fusion (``lax.top_k`` →
    ``jax.nn.softmax`` → stable ascending argsort of expert ids →
    searchsorted capacity ranks). Oracle for the fused kernel and the
    serving path on backends where interpret-mode Pallas is not a win.
    """
    G, T, E = logits.shape
    N = T * k
    cap = int(capacity)
    vals, idx = lax.top_k(logits, k)
    wgt = jax.nn.softmax(vals, axis=-1)
    e = idx.reshape(G, N).astype(jnp.int32)
    wf = wgt.reshape(G, N)
    perm = jnp.argsort(e, axis=-1, stable=True).astype(jnp.int32)
    e_s = jnp.take_along_axis(e, perm, axis=-1)
    w_s = jnp.take_along_axis(wf, perm, axis=-1)
    iota = jnp.arange(N, dtype=jnp.int32)[None, :]
    first = jax.vmap(
        lambda es: jnp.searchsorted(es, es, side="left"))(e_s).astype(
            jnp.int32)
    pos = iota - first
    keep = pos < cap
    slab = jnp.where(keep, e_s * cap + pos, E * cap)
    return e_s, perm // k, perm, w_s, slab, keep.astype(jnp.int32)

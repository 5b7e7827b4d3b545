"""engine.moe_route: the fused routing megakernel vs the unfused pipeline.

The contract under test (DESIGN.md §9): for any (T, E) logits the fused
Pallas variant is BIT-FOR-BIT identical to the unfused xla variant, and both
reproduce the frozen legacy dispatch pipeline (``lax.top_k`` →
``jax.nn.softmax`` → stable ascending expert sort → searchsorted capacity
ranks) that ``moe_apply_grouped`` ran before the fusion — permutation, keep
mask, combine weights, slab indices, all of it.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import engine, obs
from repro.kernels.route_fuse import moe_route_pallas, moe_route_xla

RNG = np.random.default_rng(7)


def _legacy_route(logits, k, cap):
    """Frozen copy of the pre-fusion dispatch pipeline (the seed behaviour
    of ``moe_apply_grouped``) — the oracle both variants must match."""
    G, T, E = logits.shape
    N = T * k
    vals, idx = jax.lax.top_k(logits, k)
    w = jax.nn.softmax(vals, axis=-1)
    e = idx.reshape(G, N).astype(jnp.int32)
    wf = w.reshape(G, N)
    perm = jnp.argsort(e, axis=-1, stable=True).astype(jnp.int32)
    e_s = jnp.take_along_axis(e, perm, axis=-1)
    w_s = jnp.take_along_axis(wf, perm, axis=-1)
    pos = jnp.arange(N, dtype=jnp.int32)[None, :] - jax.vmap(
        lambda es: jnp.searchsorted(es, es, side="left"))(e_s).astype(
            jnp.int32)
    keep = pos < cap
    slab = jnp.where(keep, e_s * cap + pos, E * cap)
    return e_s, perm // k, perm, w_s, slab, keep.astype(jnp.int32)


SHAPES = [
    # (G, T, E, k, cap) — pow2 and non-pow2 lanes, k=1, E non-pow2, tight
    # and slack capacities
    (1, 64, 8, 2, 10),
    (2, 64, 8, 2, 10),
    (1, 100, 6, 3, 5),      # non-pow2 T*k and E
    (1, 16, 4, 1, 2),       # k=1
    (3, 33, 5, 2, 1),       # cap=1: every expert keeps exactly one pair
    (1, 32, 8, 4, 1000),    # cap >= T*k: nothing dropped
    (2, 128, 16, 6, 20),    # moonshot-shaped top-6
]


def _logits(G, T, E, seed=0, tied=False):
    rng = np.random.default_rng(seed)
    lg = rng.standard_normal((G, T, E)).astype(np.float32)
    if tied:
        # heavy ties incl. the -0.0/+0.0 pair: lax.top_k orders by IEEE
        # total order, which float == cannot see (regression)
        lg = np.round(lg * 2) / 2
        lg[lg == 0.0] = np.where(rng.random((lg == 0.0).sum()) < 0.5,
                                 -0.0, 0.0)
    return jnp.asarray(lg)


class TestFusedVsReference:
    @pytest.mark.parametrize("G,T,E,k,cap", SHAPES)
    @pytest.mark.parametrize("tied", [False, True])
    def test_bit_for_bit(self, G, T, E, k, cap, tied):
        lg = _logits(G, T, E, seed=G * T + E + k, tied=tied)
        ref = moe_route_xla(lg, k, cap)
        got = moe_route_pallas(lg, k, cap)
        for name, a, b in zip(
                ("experts", "tokens", "perm", "weights", "slabs",
                 "keep"), got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{name} lane")

    @pytest.mark.parametrize("G,T,E,k,cap", SHAPES)
    def test_matches_frozen_legacy_pipeline(self, G, T, E, k, cap):
        lg = _logits(G, T, E, seed=3)
        legacy = _legacy_route(lg, k, cap)
        for route in (moe_route_xla(lg, k, cap),
                      moe_route_pallas(lg, k, cap)):
            for a, b in zip(route, legacy):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestCapacityDropSemantics:
    """GShard drop properties, verified against an independent numpy rank
    computation (not the sort-based pipeline under test)."""

    def _numpy_ranks(self, lg, k):
        """Per-pair (expert, stable rank within expert) from first
        principles: pairs in original (token, slot) order, rank = count of
        earlier pairs routed to the same expert."""
        _, idx = jax.lax.top_k(lg, k)
        e = np.asarray(idx).reshape(-1)
        rank = np.zeros_like(e)
        seen = {}
        for i, ei in enumerate(e):
            rank[i] = seen.get(ei, 0)
            seen[ei] = rank[i] + 1
        return e, rank

    @pytest.mark.parametrize("variant", ["xla", "fused"])
    def test_drops_exactly_highest_stable_ranks(self, variant):
        T, E, k, cap = 96, 4, 2, 7             # guaranteed over capacity
        lg = _logits(1, T, E, seed=5)
        r = engine.moe_route(lg[0], k, cap, variant=variant)
        e, rank = self._numpy_ranks(lg[0], k)
        perm = np.asarray(r.perm)
        keep = np.asarray(r.keep)
        # keep iff the pair's first-principles stable rank is under cap
        np.testing.assert_array_equal(keep, rank[perm] < cap)
        # and the slab position IS that rank for every kept pair
        slabs = np.asarray(r.slabs)
        np.testing.assert_array_equal(slabs[keep] % cap, rank[perm][keep])
        np.testing.assert_array_equal(slabs[keep] // cap, e[perm][keep])
        # dropped pairs all rank >= cap: the kept set is exactly the cap
        # FIRST pairs of each expert in original order
        assert (rank[perm][~keep] >= cap).all()

    @pytest.mark.parametrize("variant", ["xla", "fused"])
    def test_cap_one_keeps_first_pair_per_expert(self, variant):
        lg = _logits(1, 64, 8, seed=6)
        r = engine.moe_route(lg[0], 2, 1, variant=variant)
        e, rank = self._numpy_ranks(lg[0], 2)
        perm = np.asarray(r.perm)
        np.testing.assert_array_equal(np.asarray(r.keep), rank[perm] == 0)
        # at most one kept pair per expert
        kept_e = np.asarray(r.experts)[np.asarray(r.keep)]
        assert len(kept_e) == len(set(kept_e.tolist()))

    @pytest.mark.parametrize("variant", ["xla", "fused"])
    def test_slack_capacity_drops_nothing(self, variant):
        T, k = 50, 3
        lg = _logits(1, T, 6, seed=8)
        r = engine.moe_route(lg[0], k, T * k, variant=variant)
        assert np.asarray(r.keep).all()
        # the permutation is a true permutation and weights sum to 1/token
        perm = np.asarray(r.perm)
        assert (np.sort(perm) == np.arange(T * k)).all()
        tok_w = np.zeros(T)
        np.add.at(tok_w, np.asarray(r.tokens), np.asarray(r.weights))
        np.testing.assert_allclose(tok_w, 1.0, rtol=1e-5)


class TestEngineOp:
    def test_values_gather(self):
        lg = _logits(2, 32, 4, seed=9)
        r, pay = engine.moe_route(lg, 2, 5,
                                  values=jnp.arange(64).reshape(2, 32))
        np.testing.assert_array_equal(
            np.asarray(pay),
            np.take_along_axis(np.arange(64).reshape(2, 32),
                               np.asarray(r.tokens), axis=-1))

    def test_2d_squeeze(self):
        lg = _logits(1, 32, 4, seed=10)
        r3 = engine.moe_route(lg, 2, 5)
        r2 = engine.moe_route(lg[0], 2, 5)
        for a, b in zip(r2, r3):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[0])

    def test_validation(self):
        lg = _logits(1, 8, 4, seed=0)
        with pytest.raises(ValueError, match="capacity"):
            engine.moe_route(lg, 2, 0)
        with pytest.raises(ValueError, match="k="):
            engine.moe_route(lg, 5, 3)
        with pytest.raises(ValueError, match="logits"):
            engine.moe_route(lg[0, 0], 2, 3)

    def test_fused_is_one_pallas_call(self):
        """The fusion claim: the fused variant lowers the WHOLE routing
        pipeline — softmax, top-k, sort, capacity cut — to exactly one
        pallas_call per chunk (the xla variant lowers to none)."""
        lg = _logits(2, 64, 8, seed=11)
        for variant, want in (("fused", 1), ("xla", 0)):
            jaxpr = jax.make_jaxpr(
                lambda x: engine.moe_route(x, 2, 10, variant=variant))(lg)
            count = str(jaxpr).count("pallas_call")
            assert count == want, (variant, count)

    def test_obs_route_event_and_drop_counter(self):
        lg = _logits(1, 64, 4, seed=12)
        obs.enable()
        try:
            r = engine.moe_route(lg, 2, 3)      # over capacity: drops
            snap = obs.snapshot()
        finally:
            obs.disable()
        ev = [e for e in snap["events"] if e["kind"] == "moe.route"]
        assert len(ev) == 1
        assert ev[0]["data"]["capacity"] == 3
        assert ev[0]["data"]["n_pairs"] == 128
        # dropped = pairs past capacity, counted by the caller from keep;
        # nothing is staged into the program to count them
        want = int((~np.asarray(engine.moe_route(lg, 2, 3).keep)).sum())
        assert want > 0
        assert int((~np.asarray(r.keep)).sum()) == want
        assert "moe.dropped_tokens" not in snap["counters"]

    def test_planner_and_autotune(self):
        lg = _logits(1, 64, 8, seed=13)
        key = engine.plan_key("moe_route", n=128, dtype=jnp.float32,
                              segments=1)
        assert engine.heuristic_plan("moe_route", key).variant in (
            "fused", "xla")
        from repro.engine.planner import candidate_plans
        cands = candidate_plans("moe_route", key)
        assert {c.variant for c in cands} == {"fused", "xla"}
        # the fused kernel sorts a whole group in one network: no tile dof
        assert len([c for c in cands if c.variant == "fused"]) == 1
        plan = engine.autotune("moe_route", lg, 2, 10)
        assert plan.variant in ("fused", "xla")
        # the tuned plan is installed and serves subsequent calls
        assert engine.default_planner.lookup(key) == plan


class TestDispatchRewire:
    """The models-layer rewiring: ``moe_apply_sorted`` on the fused op must
    equal the frozen legacy dispatch bit-for-bit (same scatter, same
    combine arithmetic — only the routing pipeline changed)."""

    def _legacy_apply_sorted(self, p, x, cfg, capacity_factor=1.25):
        from repro.models.moe import expert_capacity, router_probs
        B, S, d = x.shape
        T, k, E = B * S, cfg.n_experts_active, cfg.n_experts
        w, idx = router_probs(p, x, cfg)
        xf = x.reshape(T, d)
        flat_e = idx.reshape(T * k).astype(jnp.int32)
        flat_w = w.reshape(T * k)
        tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        e_sorted, (t_sorted, w_sorted) = engine.sort(
            flat_e, values=(tok, flat_w), stable=True, descending=False)
        cap = expert_capacity(capacity_factor, T, k, E)
        pos = jnp.arange(T * k) - jnp.searchsorted(e_sorted, e_sorted,
                                                   side="left")
        keep = pos < cap
        slab = jnp.where(keep, e_sorted * cap + pos, E * cap)
        xin = jnp.zeros((E * cap + 1, d), x.dtype).at[slab].set(xf[t_sorted])
        xin = xin[:-1].reshape(E, cap, d)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["wg"]))
        h = h * jnp.einsum("ecd,edf->ecf", xin, p["wi"])
        ys = jnp.einsum("ecf,efd->ecd", h, p["wo"]).reshape(E * cap, d)
        contrib = ys[jnp.where(keep, slab, 0)] * (w_sorted * keep)[:, None]
        return jnp.zeros((T, d), x.dtype).at[t_sorted].add(
            contrib).reshape(B, S, d)

    def test_moe_apply_sorted_unchanged(self):
        from repro.configs import get_config
        from repro.models.moe import moe_apply_sorted, moe_init
        cfg = get_config("mixtral_8x22b").reduced(
            d_model=64, moe_d_ff=128, n_experts=8, n_experts_active=2)
        p = moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
        got = moe_apply_sorted(p, x, cfg)
        want = self._legacy_apply_sorted(p, x, cfg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

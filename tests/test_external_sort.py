"""Out-of-core two-phase sort: ``engine.external_sort`` + the streaming
machinery under it (DESIGN.md §8).

Oracle suite: both variants (``xla``, ``stream_pallas``) bit-for-bit against
``jnp.sort`` / ``jnp.argsort(stable=True)`` across directions, dtypes, tile
misalignment, heavy ties; the edge contracts (single-tile delegation,
fan-in larger than the run count, int32 lane guard); the observable
``ceil(log_fan_in(runs))`` pass-count claim; the streaming kernel and the
``stream_xla``/``stream_pallas`` MergeSchedule executors directly; and the
roofline traffic model and the peaks table keyed by device kind.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import engine, obs
from repro.engine.planner import Plan
from repro.engine.schedule import MergeSchedule, merge_runs, stream_pass
from repro.kernels.stream_merge import (stream_merge_runs,
                                        stream_merge_runs_kv, stream_slack)

RNG = np.random.default_rng(23)


@pytest.fixture(scope="module", autouse=True)
def _drop_compile_cache():
    # this module compiles ~50 distinct multi-pass programs; release the
    # jitted executables on the way out so later modules' compiles don't
    # run on top of the accumulated XLA/LLVM JIT state
    yield
    jax.clear_caches()


def _ext(x, **kw):
    kw.setdefault("tile_elems", 1024)
    kw.setdefault("fan_in", 4)
    return engine.external_sort(jnp.asarray(x), **kw)


def _events(kind):
    return [e["data"] for e in obs.snapshot()["events"] if e["kind"] == kind]


# --------------------------------------------------------------------------
# oracle: keys only
# --------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["xla", "stream_pallas"])
@pytest.mark.parametrize("descending", [True, False])
@pytest.mark.parametrize("n", [1500, 4096, 10_000])
def test_external_sort_matches_jnp_sort(variant, descending, n):
    x = RNG.standard_normal(n).astype(np.float32)
    out = _ext(x, descending=descending, variant=variant)
    ref = jnp.sort(jnp.asarray(x), descending=descending)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("variant", ["xla", "stream_pallas"])
def test_external_sort_int_keys_with_ties(variant):
    x = RNG.integers(-3, 3, 9000).astype(np.int32)
    out = _ext(x, variant=variant)
    np.testing.assert_array_equal(np.asarray(out), -np.sort(-x))


def test_external_sort_n_not_multiple_of_tile():
    # 2500 = 2 full tiles + a ragged tail; sentinel padding must not leak
    x = RNG.standard_normal(2500).astype(np.float32)
    for variant in ("xla", "stream_pallas"):
        out = _ext(x, variant=variant, descending=False)
        np.testing.assert_array_equal(np.asarray(out), np.sort(x))


def test_external_sort_rejects_2d():
    with pytest.raises(ValueError, match="1-D"):
        engine.external_sort(jnp.zeros((4, 4)))


# --------------------------------------------------------------------------
# oracle: stable KV
# --------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["xla", "stream_pallas"])
@pytest.mark.parametrize("descending", [True, False])
def test_external_sort_stable_perm_bitforbit(variant, descending):
    keys = RNG.integers(0, 5, 6000).astype(np.int32)   # heavy ties
    kj = jnp.asarray(keys)
    ks, perm = _ext(keys, variant=variant, descending=descending,
                    values=jnp.arange(keys.shape[0], dtype=jnp.int32))
    ref = jnp.argsort(kj, stable=True, descending=descending)
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(ks), keys[np.asarray(ref)])


@pytest.mark.parametrize("variant", ["xla", "stream_pallas"])
def test_external_sort_all_equal_keys_stable(variant):
    keys = np.zeros(5000, np.float32)
    ks, perm = _ext(keys, variant=variant, stable=True,
                    values=jnp.arange(5000, dtype=jnp.int32))
    # all-equal: the stable permutation is the identity
    np.testing.assert_array_equal(
        np.asarray(perm),
        np.asarray(jnp.argsort(jnp.asarray(keys), stable=True,
                               descending=True)))
    np.testing.assert_array_equal(np.asarray(ks), keys)


def test_external_sort_payload_pytree():
    keys = RNG.standard_normal(3000).astype(np.float32)
    vals = {"a": jnp.arange(3000, dtype=jnp.int32),
            "b": jnp.asarray(keys) * 2.0}
    ks, vs = _ext(keys, values=vals)
    p = np.argsort(-keys, kind="stable")
    np.testing.assert_array_equal(np.asarray(vs["a"]), p.astype(np.int32))
    np.testing.assert_allclose(np.asarray(vs["b"]), keys[p] * 2.0)


# --------------------------------------------------------------------------
# edge contracts
# --------------------------------------------------------------------------

def test_single_tile_delegates_to_engine_sort():
    x = RNG.standard_normal(700).astype(np.float32)
    obs.enable()
    obs.reset()
    try:
        out = engine.external_sort(jnp.asarray(x), tile_elems=1024)
        assert len(_events("external.delegate")) == 1
        assert not _events("external.run_form")    # no out-of-core machinery
        # and a `sort` plan was resolved — proof the direct path served it
        assert any(e["op"] == "sort" for e in _events("plan.resolve"))
    finally:
        obs.disable()
    np.testing.assert_array_equal(np.asarray(out), -np.sort(-x))


def test_fan_in_larger_than_run_count():
    # 4 runs, fan_in 64 -> one pass merges everything
    x = RNG.standard_normal(4 * 1024).astype(np.float32)
    obs.enable()
    obs.reset()
    try:
        out = _ext(x, fan_in=64)
        passes = _events("external.pass")
    finally:
        obs.disable()
    assert len(passes) == 1 and passes[0]["fan_in"] == 4  # clamped to pow2(R)
    np.testing.assert_array_equal(np.asarray(out), -np.sort(-x))


@pytest.mark.parametrize("variant", ["xla", "stream_pallas"])
def test_pass_count_is_ceil_log_fan_in(variant):
    from repro.launch.roofline import external_passes
    n, tile, fan = 16 * 1024, 1024, 4       # 16 runs, fan 4 -> 2 passes
    x = RNG.standard_normal(n).astype(np.float32)
    obs.enable()
    obs.reset()
    try:
        _ext(x, variant=variant, tile_elems=tile, fan_in=fan)
        passes = _events("external.pass")
        form = _events("external.run_form")
    finally:
        obs.disable()
    assert len(passes) == external_passes(16, fan) == 2
    assert all(p["level_kind"] == "hbm_run" for p in passes)
    assert form[0]["runs"] == 16 and form[0]["bytes_streamed"] > 0
    assert all(p["bytes_streamed"] == 2 * n * 4 for p in passes)


def test_lane_guard_rejects_int32_overflow_sizes():
    big = jax.ShapeDtypeStruct((2 ** 31,), jnp.float32)
    with pytest.raises(ValueError, match="2\\*\\*31"):
        engine.external_sort(big)
    off = np.asarray([0, 2 ** 31], np.int64)   # guard fires before any cast
    with pytest.raises(ValueError, match="2\\*\\*31"):
        engine.merge_runs(jax.ShapeDtypeStruct((2 ** 31,), jnp.float32), off)


def test_plan_dof_resolution_and_cache_fields():
    # tile/fan clamp to powers of two and survive a plan round trip
    from repro.engine.external import resolve_dofs
    p = resolve_dofs(Plan("xla", w=32), 10 ** 6, tile_elems=3000, fan_in=5)
    assert p.tile_elems == 4096 and p.fan_in == 8
    p2 = Plan.from_dict(p.to_dict())
    assert p2.tile_elems == 4096 and p2.fan_in == 8
    # legacy dicts without the new fields still parse
    d = p.to_dict()
    del d["tile_elems"], d["fan_in"]
    assert Plan.from_dict(d).tile_elems == 0


# --------------------------------------------------------------------------
# the streaming kernel + executors directly
# --------------------------------------------------------------------------

def _uniform_runs(runs, run_len, dtype=np.float32, descending=True, ties=0):
    if ties:
        x = RNG.integers(0, ties, (runs, run_len)).astype(dtype)
    else:
        x = RNG.standard_normal((runs, run_len)).astype(dtype)
    x = np.sort(x, axis=1)
    return x[:, ::-1].copy() if descending else x


@pytest.mark.parametrize("geom", [(8, 64, 4, 8, 128), (4, 32, 2, 8, 32),
                                  (16, 128, 16, 32, 256)])
def test_stream_kernel_key_only(geom):
    runs, run_len, fan, w, block_out = geom
    x = _uniform_runs(runs, run_len)
    out = stream_merge_runs(jnp.asarray(x.ravel()), runs=runs,
                            run_len=run_len, fan_in=fan, w=w,
                            block_out=block_out)
    out = np.asarray(out)[:runs * run_len].reshape(runs // fan, -1)
    for g in range(runs // fan):
        ref = -np.sort(-x[g * fan:(g + 1) * fan].ravel())
        np.testing.assert_array_equal(out[g], ref)


@pytest.mark.parametrize("descending", [True, False])
def test_stream_kernel_kv_stable(descending):
    runs, run_len, fan = 8, 64, 4
    k = _uniform_runs(runs, run_len, np.int32, descending, ties=3)
    r = np.arange(runs * run_len, dtype=np.int32).reshape(runs, run_len)
    ok, orr = stream_merge_runs_kv(
        jnp.asarray(k.ravel()), jnp.asarray(r.ravel()), runs=runs,
        run_len=run_len, fan_in=fan, w=8, block_out=64,
        descending=descending)
    ok = np.asarray(ok)[:runs * run_len].reshape(runs // fan, -1)
    orr = np.asarray(orr)[:runs * run_len].reshape(runs // fan, -1)
    sgn = -1 if descending else 1
    for g in range(runs // fan):
        kk = k[g * fan:(g + 1) * fan].ravel()
        rr = r[g * fan:(g + 1) * fan].ravel()
        p = np.lexsort((rr, sgn * kk))
        np.testing.assert_array_equal(ok[g], kk[p])
        np.testing.assert_array_equal(orr[g], rr[p])


def test_stream_kernel_chains_with_slack():
    # two passes over the same allocation contract: out_slack of pass 1
    # satisfies the input-slack requirement of pass 2 (no re-pack)
    w, block_out = 8, 128
    runs, run_len, fan = 16, 64, 4
    x = _uniform_runs(runs, run_len)
    slack = stream_slack(fan, w, block_out)
    buf = jnp.concatenate([jnp.asarray(x.ravel()),
                           jnp.full((slack,), -np.inf, jnp.float32)])
    b1 = stream_merge_runs(buf, runs=runs, run_len=run_len, fan_in=fan,
                           w=w, block_out=block_out, out_slack=slack)
    assert b1.shape[0] >= runs * run_len + slack
    b2 = stream_merge_runs(b1, runs=runs // fan, run_len=run_len * fan,
                           fan_in=fan, w=w, block_out=block_out)
    np.testing.assert_array_equal(np.asarray(b2)[:runs * run_len],
                                  -np.sort(-x.ravel()))


@pytest.mark.parametrize("executor", ["stream_xla", "stream_pallas"])
def test_stream_pass_helper(executor):
    runs, run_len, fan = 8, 32, 8
    x = _uniform_runs(runs, run_len)
    out, _ = stream_pass(jnp.asarray(x.ravel()), None, runs=runs,
                         run_len=run_len, fan_in=fan, executor=executor,
                         w=8, block_out=64, descending=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out)[:runs * run_len],
                                  -np.sort(-x.ravel()))


@pytest.mark.parametrize("variant", ["stream_xla", "stream_pallas"])
@pytest.mark.parametrize("descending", [True, False])
@pytest.mark.parametrize("kv", [False, True])
def test_stream_executors_ragged_merge_runs(variant, descending, kv):
    # ragged + empty runs, 2 groups of 3, through the schedule entry point
    lens = [13, 0, 40, 7, 25, 1]
    sgn = -1 if descending else 1
    ks = [sgn * np.sort(sgn * RNG.integers(0, 4, l).astype(np.int32))
          for l in lens]
    keys = np.concatenate(ks).astype(np.int32)
    off = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    ranks = np.arange(keys.shape[0], dtype=np.int32) if kv else None
    sched = MergeSchedule(variant, levels_per_pass=2, w=8, block_out=64)
    out = merge_runs(jnp.asarray(keys), jnp.asarray(off),
                     ranks=None if ranks is None else jnp.asarray(ranks),
                     schedule=sched, runs_per_group=3, descending=descending)
    for g in range(2):
        lo, hi = off[g * 3], off[(g + 1) * 3]
        kk = keys[lo:hi]
        if kv:
            rr = ranks[lo:hi]
            p = np.lexsort((rr, sgn * kk))
            np.testing.assert_array_equal(np.asarray(out[0])[lo:hi], kk[p])
            np.testing.assert_array_equal(np.asarray(out[1])[lo:hi], rr[p])
        else:
            np.testing.assert_array_equal(np.asarray(out)[lo:hi],
                                          sgn * np.sort(sgn * kk))


def test_stream_variants_registered_for_merge_runs():
    assert "stream_pallas" in engine.registry.variants("merge_runs")
    assert "stream_xla" in engine.registry.variants("merge_runs")
    assert engine.registry.variants("external_sort") == ("stream_pallas",
                                                         "xla")
    # through the public op, variant pinned
    lens = [32, 32, 32, 32]
    vals = np.sort(RNG.standard_normal(128).astype(np.float32))[::-1]
    keys = np.concatenate([np.sort(vals[i * 32:(i + 1) * 32])[::-1]
                           for i in range(4)])
    off = np.arange(5, dtype=np.int32) * 32
    out = engine.merge_runs(jnp.asarray(keys), jnp.asarray(off),
                            variant="stream_xla")
    np.testing.assert_array_equal(np.asarray(out), -np.sort(-keys))


# --------------------------------------------------------------------------
# roofline satellites
# --------------------------------------------------------------------------

def test_external_traffic_model():
    from repro.launch.roofline import external_passes, external_sort_bytes
    assert external_passes(1, 8) == 0
    assert external_passes(8, 8) == 1
    assert external_passes(9, 8) == 2
    assert external_passes(13, 4) == 2
    assert external_passes(128, 4) == 4        # 128 -> 32 -> 8 -> 2 -> 1
    # 1 formation pass + 2 merge passes, 2 bytes/elem/direction
    assert external_sort_bytes(16 * 1024, 4, 1024, 4) == \
        2 * 16 * 1024 * 4 * 3


def test_mem_bw_by_device_kind(monkeypatch):
    from repro.launch import roofline
    monkeypatch.setattr(roofline, "device_kind", lambda: "TPU v5 lite")
    assert roofline.mem_bw() == 819e9
    monkeypatch.setattr(roofline, "device_kind", lambda: "cpu")
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.mem_bw()

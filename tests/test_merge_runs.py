"""MergeSchedule subsystem: every executor vs the sorted-concat oracle.

Property tests for ``engine.merge_runs`` (and the schedule executors under
it): every variant — ``xla``, ``tree_vmapped``, ``tree_pallas`` at 1/2/3
fused levels — with and without payloads, both directions, bit-for-bit
against the oracle on heavy-tie inputs with ragged run lengths and empty
runs. Plus the fused merge-tree kernel directly, the any-K PMT wrappers,
skew tie plumbing, and schedule-field persistence.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # deterministic fallback sweep (see the module)
    from _hypothesis_compat import given, settings, st

from repro import engine
from repro.engine.planner import Plan, plan_key
from repro.engine.schedule import MergeSchedule, merge_runs, reduce_rows

RNG = np.random.default_rng(17)

settings.register_profile("ci", deadline=None, max_examples=15)
settings.load_profile("ci")

SCHEDULES = [
    MergeSchedule("xla"),
    MergeSchedule("tree_vmapped", w=8),
    MergeSchedule("tree_pallas", levels_per_pass=1, w=8, block_out=64),
    MergeSchedule("tree_pallas", levels_per_pass=2, w=8, block_out=64),
    MergeSchedule("tree_pallas", levels_per_pass=3, w=8, block_out=64),
]


def _runs(lens, dtype=np.int32, lo=0, hi=4, descending=True):
    """Heavy-tie sorted runs: flat buffer + (K+1,) offsets."""
    if np.issubdtype(np.dtype(dtype), np.integer):
        segs = [np.sort(RNG.integers(lo, hi, n).astype(dtype)) for n in lens]
    else:
        segs = [np.sort(RNG.choice([0.0, 1.5, 2.5], n).astype(dtype))
                for n in lens]
    if descending:
        segs = [s[::-1] for s in segs]
    flat = (np.concatenate(segs) if sum(lens) else np.zeros((0,), dtype))
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    return flat, offs


LENS = [
    [5, 0, 33, 7, 2],            # ragged with an empty run, K=5
    [64],                        # K=1 (identity)
    [0, 0, 0],                   # all empty
    [7, 19, 3],                  # K=3
    [1] * 9,                     # many tiny, K=9
    [100, 1, 0, 55, 23, 8, 90, 4],   # K=8 pow2 ragged
]


@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("lens", LENS)
@pytest.mark.parametrize("sched", SCHEDULES,
                         ids=lambda s: f"{s.variant}@{s.levels_per_pass}")
@pytest.mark.parametrize("descending", [True, False])
def test_merge_runs_matches_oracle(dtype, lens, sched, descending):
    buf, offs = _runs(lens, dtype, descending=descending)
    keys, offsets = jnp.array(buf), jnp.array(offs)
    exp = np.sort(buf)[::-1] if descending else np.sort(buf)

    got = np.array(merge_runs(keys, offsets, schedule=sched,
                              descending=descending))
    np.testing.assert_array_equal(got, exp)
    assert got.dtype == dtype

    # KV: ranks are flat positions -> the merged rank lane must equal the
    # stable argsort bit-for-bit (heavy ties make this the hard part)
    ranks = jnp.arange(buf.shape[0], dtype=jnp.int32)
    gk, gr = merge_runs(keys, offsets, ranks=ranks, schedule=sched,
                        descending=descending)
    perm = np.array(jnp.argsort(keys, stable=True, descending=descending))
    np.testing.assert_array_equal(np.array(gr), perm)
    np.testing.assert_array_equal(np.array(gk), buf[perm] if buf.size
                                  else exp)


@given(st.lists(st.integers(0, 60), min_size=1, max_size=7),
       st.booleans(), st.sampled_from([1, 2, 3]))
def test_merge_runs_property(lens, descending, levels):
    buf, offs = _runs(lens, np.int32, descending=descending)
    keys, offsets = jnp.array(buf), jnp.array(offs)
    sched = MergeSchedule("tree_pallas", levels_per_pass=levels, w=8,
                          block_out=64)
    ranks = jnp.arange(buf.shape[0], dtype=jnp.int32)
    gk, gr = merge_runs(keys, offsets, ranks=ranks, schedule=sched,
                        descending=descending)
    perm = np.array(jnp.argsort(keys, stable=True, descending=descending))
    np.testing.assert_array_equal(np.array(gr), perm)


@pytest.mark.parametrize("variant", ["xla", "tree_vmapped", "tree_pallas"])
def test_engine_merge_runs_api(variant):
    buf, offs = _runs([30, 0, 12, 7], np.int32)
    keys, offsets = jnp.array(buf), jnp.array(offs)
    got = np.array(engine.merge_runs(keys, offsets, variant=variant))
    np.testing.assert_array_equal(got, np.sort(buf)[::-1])
    # payload pytree rides the rank lanes (runs sorted in the call's
    # direction: ascending merge takes ascending runs)
    abuf, aoffs = _runs([30, 0, 12, 7], np.int32, descending=False)
    akeys = jnp.array(abuf)
    vals = {"ids": jnp.arange(abuf.shape[0], dtype=jnp.int32)}
    mk, mv = engine.merge_runs(akeys, jnp.array(aoffs), values=vals,
                               variant=variant, descending=False)
    perm = np.array(jnp.argsort(akeys, stable=True, descending=False))
    np.testing.assert_array_equal(np.array(mk), abuf[perm])
    np.testing.assert_array_equal(np.array(mv["ids"]), perm)


def test_merge_runs_grouped_reduction():
    """Consecutive groups reduce independently (the two-phase shape)."""
    rows = np.sort(RNG.integers(0, 6, (8, 16)).astype(np.int32),
                   axis=1)[:, ::-1].copy()
    exp = np.concatenate([np.sort(rows[:4].reshape(-1))[::-1],
                          np.sort(rows[4:].reshape(-1))[::-1]])
    for sched in SCHEDULES:
        got = np.array(reduce_rows(jnp.array(rows), schedule=sched,
                                   runs_per_group=4))
        np.testing.assert_array_equal(got, exp, err_msg=str(sched))


@pytest.mark.parametrize("kv", [False, True])
def test_merge_runs_grouped_ascending_keeps_group_order(kv):
    """Regression: the ascending mirror path must un-mirror per GROUP —
    reversing the whole buffer flipped group order when runs_per_group < K."""
    rows = np.sort(RNG.integers(0, 50, (4, 8)).astype(np.int32), axis=1)
    rows[2:] += 100                       # make group order observable
    exp = np.concatenate([np.sort(rows[:2].reshape(-1)),
                          np.sort(rows[2:].reshape(-1))])
    for sched in SCHEDULES:
        ranks = jnp.arange(32, dtype=jnp.int32).reshape(4, 8) if kv else None
        out = reduce_rows(jnp.array(rows), schedule=sched, ranks=ranks,
                          runs_per_group=2, descending=False)
        got = np.array(out[0] if kv else out)
        np.testing.assert_array_equal(got, exp, err_msg=str(sched))


# --------------------------------------------------------------------------
# the fused merge-tree kernel directly
# --------------------------------------------------------------------------

@pytest.mark.parametrize("group,w,block_out", [(4, 8, 64), (8, 16, 128)])
def test_merge_tree_kernel_heavy_duplicates(group, w, block_out):
    """Duplicates crossing (group, block, level) boundaries: the nested
    co-rank partition must agree with the in-kernel selectors exactly."""
    from repro.kernels.merge_tree import merge_tree_runs
    lens = [300, 0, 150, 700, 41, 260, 5, 123][:group] * 2
    buf, offs = _runs(lens, np.int32, lo=0, hi=3)
    got = np.array(merge_tree_runs(
        jnp.array(buf), jnp.array(offs[:-1]), jnp.array(np.diff(offs)),
        group=group, n_out=int(sum(lens)), w=w, block_out=block_out))
    half = sum(lens[:group])
    np.testing.assert_array_equal(got[:half], np.sort(buf[:half])[::-1])
    np.testing.assert_array_equal(got[half:], np.sort(buf[half:])[::-1])


def test_merge_tree_kernel_single_pallas_call(monkeypatch):
    """levels_per_pass=2 over 4 runs must be exactly ONE pallas_call."""
    from jax.experimental import pallas as pl
    from repro.kernels.merge_tree import merge_tree_runs
    calls = []
    orig = pl.pallas_call

    def counting(*a, **k):
        calls.append(k.get("name", ""))
        return orig(*a, **k)

    monkeypatch.setattr(pl, "pallas_call", counting)
    buf, offs = _runs([40, 17, 0, 25], np.int32)
    got = np.array(merge_tree_runs(
        jnp.array(buf), jnp.array(offs[:-1]), jnp.array(np.diff(offs)),
        group=4, n_out=int(sum([40, 17, 0, 25])), w=8, block_out=64))
    np.testing.assert_array_equal(got, np.sort(buf)[::-1])
    assert calls == ["flims_merge_tree"]


def test_merge_tree_kernel_kv_stable_both_directions():
    from repro.kernels.merge_tree import merge_tree_runs_kv
    for descending in (True, False):
        buf, offs = _runs([64, 33, 0, 200], np.int32, descending=descending)
        ranks = np.arange(buf.shape[0], dtype=np.int32)
        gk, gr = merge_tree_runs_kv(
            jnp.array(buf), jnp.array(ranks), jnp.array(offs[:-1]),
            jnp.array(np.diff(offs)), group=4, n_out=buf.shape[0], w=8,
            block_out=64, descending=descending)
        perm = np.array(jnp.argsort(jnp.array(buf), stable=True,
                                    descending=descending))
        np.testing.assert_array_equal(np.array(gr), perm)
        np.testing.assert_array_equal(np.array(gk), buf[perm])


# --------------------------------------------------------------------------
# PMT wrappers: any K (the old power-of-two assert is gone)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("K", [1, 3, 5])
def test_pmt_merge_any_k(K):
    from repro.core import pmt_merge
    rows = np.sort(RNG.integers(-99, 99, (K, 32)).astype(np.int32),
                   axis=1)[:, ::-1].copy()
    got = np.array(pmt_merge(jnp.array(rows), w=8))
    np.testing.assert_array_equal(got, np.sort(rows.reshape(-1))[::-1])


@pytest.mark.parametrize("K", [1, 3, 5])
def test_pmt_merge_kv_any_k(K):
    from repro.core.merge_tree import pmt_merge_kv
    rows = np.sort(RNG.integers(0, 4, (K, 16)).astype(np.int32),
                   axis=1)[:, ::-1].copy()
    pay = np.arange(K * 16, dtype=np.int32).reshape(K, 16)
    mk, mp = pmt_merge_kv(jnp.array(rows), jnp.array(pay), w=8)
    flat = rows.reshape(-1)
    perm = np.array(jnp.argsort(jnp.array(flat), stable=True,
                                descending=True))
    np.testing.assert_array_equal(np.array(mk), flat[perm])
    np.testing.assert_array_equal(np.array(mp), pay.reshape(-1)[perm])


def test_pmt_merge_fused_schedule_matches_vmapped():
    from repro.core import pmt_merge
    rows = np.sort(RNG.integers(0, 3, (8, 64)).astype(np.int32),
                   axis=1)[:, ::-1].copy()
    jr = jnp.array(rows)
    base = np.array(pmt_merge(jr, w=8))
    fused = np.array(pmt_merge(jr, w=8, schedule=MergeSchedule(
        "tree_pallas", levels_per_pass=2, w=8, block_out=128)))
    np.testing.assert_array_equal(base, fused)


# --------------------------------------------------------------------------
# skew tie policy: lanes -> ref/banked -> engine
# --------------------------------------------------------------------------

def test_skew_tie_same_keys_everywhere():
    from repro.core.flims import flims_merge_banked, flims_merge_ref
    a = np.sort(RNG.choice([1, 2], 400).astype(np.int32))[::-1].copy()
    b = np.sort(RNG.choice([1, 2], 300).astype(np.int32))[::-1].copy()
    ja, jb = jnp.array(a), jnp.array(b)
    exp = np.sort(np.concatenate([a, b]))[::-1]
    for fn in (flims_merge_ref, flims_merge_banked):
        np.testing.assert_array_equal(np.array(fn(ja, jb, 16, tie="skew")),
                                      exp)
    np.testing.assert_array_equal(
        np.array(engine.merge(ja, jb, tie="skew", variant="ref")), exp)
    runs = jnp.concatenate([ja, jb])
    offs = jnp.array([0, 400, 700], jnp.int32)
    np.testing.assert_array_equal(
        np.array(engine.merge_runs(runs, offs, tie="skew",
                                   variant="tree_vmapped")), exp)


def test_skew_balances_dequeue_rate():
    """Algorithm 2's point: on all-equal keys the oscillating dir bit
    alternates whole-row dequeues instead of draining B first."""
    from repro.core.flims import flims_merge_banked
    n, w = 1 << 10, 16
    x = jnp.full((n,), 7, jnp.int32)
    ks_b = flims_merge_banked(x, x, w, tie="b", with_stats=True).k_per_cycle
    ks_s = flims_merge_banked(x, x, w, tie="skew",
                              with_stats=True).k_per_cycle
    cyc = n // w
    imb = lambda ks: float(jnp.abs(
        ks[:cyc].astype(jnp.float32).reshape(-1, 4).mean(axis=1)
        - w / 2).mean())
    assert imb(ks_s) < imb(ks_b)


def test_skew_rejected_on_stable_paths():
    a = jnp.array([3, 1], jnp.int32)
    b = jnp.array([2], jnp.int32)
    with pytest.raises(AssertionError):
        engine.merge(a, b, stable=True, tie="skew")


# --------------------------------------------------------------------------
# plan persistence: MergeSchedule fields round-trip the JSON table
# --------------------------------------------------------------------------

def test_schedule_fields_roundtrip_plan_table(tmp_path):
    engine.clear_plans()
    key = plan_key("merge_runs", n=512, dtype=np.int32, segments=8)
    plan = Plan("tree_pallas", w=16, levels=3, tie="skew")
    engine.default_planner.put(key, plan)
    path = tmp_path / "plans.json"
    engine.save_plans(str(path))
    engine.clear_plans()
    engine.load_plans(str(path))
    back = engine.default_planner.lookup(key)
    assert back == plan and back.levels == 3 and back.tie == "skew"
    # and the lifted MergeSchedule carries them
    sched = MergeSchedule.from_plan(back)
    assert sched.levels_per_pass == 3 and sched.tie == "skew"
    assert sched.variant == "tree_pallas"
    engine.clear_plans()


def test_autotune_merge_runs_installs_plan():
    buf, offs = _runs([50, 20, 0, 30], np.float32)
    engine.clear_plans()
    plan = engine.autotune("merge_runs", jnp.array(buf), jnp.array(offs),
                           repeats=1)
    assert plan.variant in engine.registry.variants("merge_runs")
    key = plan_key("merge_runs", n=buf.shape[0], dtype=np.float32,
                   segments=4)
    assert engine.default_planner.lookup(key) == plan
    got = np.array(engine.merge_runs(jnp.array(buf), jnp.array(offs)))
    np.testing.assert_array_equal(got, np.sort(buf)[::-1])
    engine.clear_plans()


# --------------------------------------------------------------------------
# regression: reduce_rows under jit must not fall off the uniform fast path
# --------------------------------------------------------------------------

def test_reduce_rows_uniform_fast_path_under_jit():
    """Inside a jit trace the arange-built offsets are tracers (ambient
    tracing), so concreteness sniffing alone sent the vmapped tree down the
    padded-bank path — padding every run to next_pow2(total): quadratic
    memory and an int32-overflow crash at n=2^20/chunk=512. reduce_rows now
    passes the statically-known uniform run length through explicitly."""
    from repro.engine.schedule import MergeSchedule, reduce_rows

    K, n = 64, 32
    rng = np.random.default_rng(11)
    rows = np.sort(rng.integers(-99, 99, (K, n)).astype(np.int32),
                   axis=1)[:, ::-1].copy()

    calls = []
    import repro.engine.schedule as sch
    orig = sch._vmapped_reduce

    def spy(keys, offsets, ranks, m, sched, uniform_len=None):
        calls.append(uniform_len)
        return orig(keys, offsets, ranks, m, sched, uniform_len=uniform_len)

    sch._vmapped_reduce = spy
    try:
        out = jax.jit(lambda r: reduce_rows(
            r, schedule=MergeSchedule("tree_vmapped", w=16)))(jnp.array(rows))
    finally:
        sch._vmapped_reduce = orig
    np.testing.assert_array_equal(np.array(out),
                                  np.sort(rows.reshape(-1))[::-1])
    assert calls == [n], "reduce_rows must pass its static uniform_len"


def test_flims_sort_large_n_no_padded_bank_blowup():
    """flims_sort at a size where the padded-bank fallback used to overflow
    int32 index bounds (2^17 keeps CI fast; the blowup was size-independent
    in kind, n=2^20 in degree)."""
    from repro.core import flims_sort
    n = 1 << 17
    x = np.random.default_rng(12).integers(-2**31, 2**31 - 1, n)
    out = flims_sort(jnp.array(x.astype(np.int32)), chunk=512, w=64)
    np.testing.assert_array_equal(np.array(out), np.sort(x)[::-1])


# --------------------------------------------------------------------------
# uniform runs: dense bank, exact grid and reshape unpad vs the gathers
# --------------------------------------------------------------------------

@pytest.fixture
def fresh_executables():
    """Release the module's compiled programs before the test. Each CPU
    executable holds hundreds of memory maps, and by this point of the
    module the earlier tests' programs have used most of the process's
    map limit (65,530): the next compile would fail in the native
    compiler. The tests below share no program with those above."""
    jax.clear_caches()


#: (run length ℓ, w, output block C, tree levels L): ℓ = w, ℓ < C, ℓ > C
UNIFORM_GEOMS = [(8, 8, 64, 2), (16, 8, 64, 1), (128, 8, 32, 2),
                 (64, 16, 32, 3)]
#: (dtype, fill) of each lane: key banks take the direction's last key,
#: rank banks INVALID_RANK
UNIFORM_LANES = {
    "int32_desc": (np.int32, True), "int32_asc": (np.int32, False),
    "float32_desc": (np.float32, True), "float32_asc": (np.float32, False),
    "rank": (np.int32, None),
}


@pytest.mark.usefixtures("fresh_executables")
@pytest.mark.parametrize("layout", ["tree", "pair"])
@pytest.mark.parametrize("lane", sorted(UNIFORM_LANES))
@pytest.mark.parametrize("ell,w,C,L", UNIFORM_GEOMS)
def test_uniform_bank_matches_gathered_bank(ell, w, C, L, lane, layout):
    """The dense pad of uniform runs is ``_build_bank``'s layout bit for
    bit: the merge tree's one bank of every run, and the pair merge's
    A-bank of the even runs and B-bank of the odd ones."""
    from repro.core.lanes import INVALID_RANK
    from repro.kernels.flims_merge import bound_keys, pad_rows
    from repro.kernels.segmented_merge import _build_bank, _uniform_bank
    dtype, descending = UNIFORM_LANES[lane]
    fill = (INVALID_RANK if descending is None
            else bound_keys(dtype, descending)[1])
    R = 2 << L                                   # two groups of 2^L runs
    n = R * ell
    # the kernels' window heights: merge tree C/w + L + 2, pair C/w + 2
    Hp = pad_rows(C // w + (L if layout == "tree" else 0) + 2)
    rows = ell // w + Hp + 2
    step = 1 if layout == "tree" else 2          # pair banks: every other run
    Rs = R // step                               # runs in each bank
    cap_rows = n // w + Rs * (Hp + 3)

    @jax.jit
    def banks(buf):
        """(dense, gathered, row0) of each bank of ``layout``."""
        out = []
        for side in range(step):
            runs = buf.reshape(Rs, step, ell // w, w)[:, side]
            ln = jnp.full((Rs,), ell, jnp.int32)
            starts = (jnp.arange(Rs, dtype=jnp.int32) * step + side) * ell
            row0 = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                    jnp.cumsum(-(-ln // w) + Hp + 2)])
            out.append((_uniform_bank(runs, Hp + 2, cap_rows, fill=fill),
                        _build_bank(buf, starts, ln, row0, cap_rows, w,
                                    fill=fill), row0))
        return out

    buf = jnp.array(RNG.integers(-50, 50, n).astype(dtype))
    for got, want, row0 in banks(buf):
        np.testing.assert_array_equal(np.array(row0), np.arange(Rs + 1) * rows)
        got, want = np.array(got), np.array(want)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got.view(np.int32),
                                      want.view(np.int32))


@pytest.mark.usefixtures("fresh_executables")
@pytest.mark.parametrize("n,glen,C", [(1 << 10, 64, 64), (1 << 10, 256, 64),
                                      (1 << 11, 512, 256)])
def test_uniform_unpad_matches_gather(n, glen, C):
    """For groups of one length, a multiple of ``C``, ``_unpad_blocks``
    reads only the first ``n / C`` blocks, in order: a reshape of the exact
    grid's output; ``_uniform_grid`` names those blocks' groups/offsets."""
    from repro.kernels.segmented_merge import _uniform_grid, _unpad_blocks
    n_groups = n // glen
    G = n // C + n_groups                        # the gather path's grid
    out = jnp.array(RNG.integers(-99, 99, (G, C)).astype(np.int32))
    lo_len = jnp.full((n_groups,), glen, jnp.int32)
    blk0 = jnp.arange(n_groups + 1, dtype=jnp.int32) * (glen // C)
    (want,) = _unpad_blocks([out], lo_len, blk0, n, G, C)
    np.testing.assert_array_equal(np.array(out[:n // C].reshape(-1)),
                                  np.array(want))
    grp, o = _uniform_grid(n // C, glen, C)
    g = np.arange(n // C)
    np.testing.assert_array_equal(np.array(grp), g // (glen // C))
    np.testing.assert_array_equal(np.array(o), (g % (glen // C)) * C)


def _uniform_call(kernel, keys, ranks, ell, block_out, descending, ul):
    """One uniform-runs call of a pass kernel, with ``uniform_len=ul``."""
    from repro.kernels.merge_tree import merge_tree_runs, merge_tree_runs_kv
    from repro.kernels.segmented_merge import (segmented_merge_runs,
                                               segmented_merge_runs_kv)
    n = keys.shape[0]
    K = n // ell
    starts = jnp.arange(K, dtype=jnp.int32) * ell
    lens = jnp.full((K,), ell, jnp.int32)
    kw = dict(n_out=n, w=8, block_out=block_out, uniform_len=ul)
    pair = (starts[0::2], lens[0::2], starts[1::2], lens[1::2])
    if kernel == "merge_tree":
        return (merge_tree_runs(keys, starts, lens, group=4, **kw),)
    if kernel == "merge_tree_kv":
        return merge_tree_runs_kv(keys, ranks, starts, lens, group=4,
                                  descending=descending, **kw)
    if kernel == "segmented":
        return (segmented_merge_runs(keys, keys, *pair, **kw),)
    return segmented_merge_runs_kv(keys, ranks, keys, ranks, *pair,
                                   descending=descending, **kw)


@pytest.mark.usefixtures("fresh_executables")
@pytest.mark.parametrize("ell,block_out", [(32, 64), (64, 32)])
@pytest.mark.parametrize("kernel,descending", [
    ("merge_tree", True), ("merge_tree_kv", True), ("merge_tree_kv", False),
    ("segmented", True), ("segmented_kv", True), ("segmented_kv", False)])
def test_uniform_path_matches_gather_path(kernel, descending, ell,
                                          block_out):
    """With and without ``uniform_len``, the same uniform heavy-tie runs
    merge to identical keys and ranks (interpret mode)."""
    K = 8
    rows = np.sort(RNG.integers(0, 3, (K, ell)).astype(np.int32), axis=1)
    if descending:
        rows = rows[:, ::-1]
    keys = jnp.array(rows.reshape(-1))
    ranks = jnp.arange(K * ell, dtype=jnp.int32)
    gather = _uniform_call(kernel, keys, ranks, ell, block_out, descending,
                           None)
    dense = _uniform_call(kernel, keys, ranks, ell, block_out, descending,
                          ell)
    for g, d in zip(gather, dense):
        np.testing.assert_array_equal(np.array(d), np.array(g))
    group = 2 if kernel.startswith("segmented") else 4
    want = np.concatenate([np.sort(x)[::-1] if descending else np.sort(x)
                           for x in rows.reshape(-1, group * ell)])
    np.testing.assert_array_equal(np.array(dense[0]), want)


@pytest.mark.usefixtures("fresh_executables")
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
@pytest.mark.parametrize("descending", [True, False])
def test_kernel_argsort_uniform_passes_ragged_n(dtype, descending):
    """``kernel_argsort`` at an n that is no multiple of its chunk (its
    merge passes all take the dense path) is ``jnp.argsort(stable=True)``
    bit for bit on heavy ties."""
    from repro.kernels.ops import kernel_argsort
    keys = jnp.array(RNG.integers(0, 4, 1000).astype(dtype))
    got = kernel_argsort(keys, chunk=64, w=32, descending=descending,
                         interpret=True)
    np.testing.assert_array_equal(
        np.array(got),
        np.array(jnp.argsort(keys, stable=True, descending=descending)))


@pytest.mark.usefixtures("fresh_executables")
def test_pallas_passes_report_their_bank_path():
    """Under obs, each ``tree_pallas`` pass says which bank it built: every
    pass of ``kernel_argsort`` (traced under jit) the dense ``uniform`` one,
    a ragged ``merge_runs`` the ``gather`` one; the two counters add up to
    the passes."""
    from repro import obs
    from repro.kernels.ops import kernel_argsort

    def passes():
        snap = obs.snapshot()
        ev = [e["data"]["bank"] for e in snap["events"]
              if e["kind"] == "schedule.pass"
              and e["data"]["executor"] == "tree_pallas"]
        c = snap["counters"]
        assert (c.get("schedule.bank.uniform", 0)
                + c.get("schedule.bank.gather", 0)) == len(ev)
        return ev

    obs.reset()
    obs.enable()
    try:
        kernel_argsort.clear_cache()         # the events fire at trace time
        keys = jnp.array(RNG.integers(0, 4, 1000).astype(np.int32))
        kernel_argsort(keys, chunk=64, w=32, interpret=True)
        ev = passes()
        assert ev and set(ev) == {"uniform"}
        obs.reset()
        buf, offs = _runs([30, 0, 12, 7, 64, 9, 1, 40], np.int32)
        engine.merge_runs(jnp.array(buf), jnp.array(offs),
                          variant="tree_pallas")
        ev = passes()
        assert ev and set(ev) == {"gather"}
    finally:
        obs.disable()
        obs.reset()

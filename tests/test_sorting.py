"""Full-sort / argsort / top-k / merge-tree / packing (paper §8.2, §2.1)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # deterministic fallback sweep (see the module)
    from _hypothesis_compat import given, settings, st

from repro.core import (flims_argsort, flims_sort, flims_sort_kv, flims_topk,
                        merge_k, pmt_merge, sort_chunks)
from repro.data.pipeline import pack_by_length

settings.register_profile("ci", deadline=None, max_examples=20)
settings.load_profile("ci")


@given(st.lists(st.integers(-10**6, 10**6), min_size=0, max_size=2000))
def test_flims_sort(vals):
    x = np.asarray(vals, np.int32)
    got = np.array(flims_sort(jnp.array(x)))
    np.testing.assert_array_equal(got, np.sort(x)[::-1])


@given(st.lists(st.integers(0, 9), min_size=1, max_size=500),
       st.booleans())
def test_flims_argsort_stable(vals, descending):
    x = np.asarray(vals, np.int32)
    got = np.array(flims_argsort(jnp.array(x), descending=descending))
    exp = np.argsort(-x if descending else x, kind="stable")
    np.testing.assert_array_equal(got, exp)


def test_flims_sort_kv():
    k = np.array([3, 1, 3, 2, 1], np.int32)
    v = np.arange(5, dtype=np.int32)
    mk, mv = flims_sort_kv(jnp.array(k), jnp.array(v))
    np.testing.assert_array_equal(np.array(mk), [3, 3, 2, 1, 1])
    np.testing.assert_array_equal(np.array(mv), [0, 2, 3, 1, 4])


@given(st.lists(st.integers(-100, 100), min_size=1, max_size=300),
       st.integers(1, 20))
def test_flims_topk(vals, k):
    x = np.asarray(vals, np.int32)
    k = min(k, len(x))
    v, i = flims_topk(jnp.array(x), k)
    ev, ei = jax.lax.top_k(jnp.array(x), k)
    np.testing.assert_array_equal(np.array(v), np.array(ev))
    np.testing.assert_array_equal(np.array(i), np.array(ei))


def test_flims_topk_batched():
    x = np.random.default_rng(0).integers(-99, 99, (3, 4, 100)).astype(np.int32)
    v, i = flims_topk(jnp.array(x), 8)
    ev, ei = jax.lax.top_k(jnp.array(x), 8)
    np.testing.assert_array_equal(np.array(v), np.array(ev))
    np.testing.assert_array_equal(np.array(i), np.array(ei))


@pytest.mark.parametrize("shape,k", [((3 << 18) + 5, 64), ((2, 1 << 19), 8)])
def test_flims_topk_chunked(shape, k):
    """Rows wider than one network chunk reduce chunk by chunk: the result
    (and the payload riding it) is still ``lax.top_k``'s, ties included."""
    from repro.core.topk import CHUNK
    x = np.random.default_rng(1).integers(-50, 50, shape).astype(np.int32)
    assert x.shape[-1] > CHUNK
    pos = np.broadcast_to(np.arange(x.shape[-1], dtype=np.int32), x.shape)
    v, i, p = flims_topk(jnp.array(x), k, values=jnp.array(pos))
    ev, ei = jax.lax.top_k(jnp.array(x), k)
    np.testing.assert_array_equal(np.array(v), np.array(ev))
    np.testing.assert_array_equal(np.array(i), np.array(ei))
    np.testing.assert_array_equal(np.array(p), np.array(ei))


@pytest.mark.parametrize("K,n", [(2, 64), (8, 128), (16, 32)])
def test_pmt_merge(K, n):
    rng = np.random.default_rng(K)
    rows = np.sort(rng.integers(-999, 999, (K, n)).astype(np.int32),
                   axis=1)[:, ::-1].copy()
    got = np.array(pmt_merge(jnp.array(rows), w=8))
    np.testing.assert_array_equal(got, np.sort(rows.reshape(-1))[::-1])


def test_merge_k_unequal():
    rng = np.random.default_rng(1)
    arrays = [np.sort(rng.integers(0, 99, n).astype(np.int32))[::-1].copy()
              for n in [3, 17, 0, 200, 1, 64]]
    got = np.array(merge_k([jnp.array(a) for a in arrays], w=8))
    np.testing.assert_array_equal(
        got, np.sort(np.concatenate(arrays))[::-1])


def test_sort_chunks():
    rng = np.random.default_rng(2)
    x = rng.integers(-99, 99, 1024).astype(np.int32)
    got = np.array(sort_chunks(jnp.array(x), 256))
    exp = np.sort(x.reshape(4, 256), axis=1)[:, ::-1]
    np.testing.assert_array_equal(got, exp)


def test_pack_by_length():
    lens = jnp.array([100, 900, 300, 700, 500, 500], jnp.int32)
    order, bins = pack_by_length(lens, bin_size=1000)
    lens_np = np.asarray(lens)
    order, bins = np.asarray(order), np.asarray(bins)
    # visiting order is longest-first
    assert (np.diff(lens_np[order]) <= 0).all()
    # no bin overflows
    fills = {}
    for o, b in zip(order, bins):
        fills[b] = fills.get(b, 0) + lens_np[o]
    assert all(v <= 1000 for v in fills.values())
    # next-fit-decreasing on this instance packs into 4 bins (optimal: 3)
    assert len(fills) <= 4


def test_merge_k_empty_dtype():
    """merge_k([]) honours the requested dtype (regression: always f32)."""
    from repro.core.merge_tree import merge_k as mk
    assert mk([], dtype=jnp.int32).dtype == jnp.int32
    assert mk([]).dtype == jnp.float32
    assert mk([jnp.zeros((0,), jnp.int16)]).dtype == jnp.int16
    assert mk([jnp.array([3, 1], jnp.int16)]).dtype == jnp.int16


def test_pmt_merge_kv_stable_and_padded():
    """KV merge trees: payload rides along; ties order row-major; in the
    padded variant padding sorts behind even real sentinel-valued keys."""
    from repro.core.merge_tree import pmt_merge_kv, pmt_merge_kv_padded
    rows = jnp.array([[3, 2, 1, 1], [3, 3, 1, 0]], jnp.int32)
    pay = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    mk, mp = pmt_merge_kv(rows, pay, w=4)
    np.testing.assert_array_equal(np.array(mk), [3, 3, 3, 2, 1, 1, 1, 0])
    np.testing.assert_array_equal(np.array(mp), [0, 4, 5, 1, 2, 3, 6, 7])
    m = np.iinfo(np.int32).min
    rows = jnp.array([[5, m, 777, 777], [2, 1, m, 777]], jnp.int32)
    pay = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    counts = jnp.array([2, 3], jnp.int32)
    mk, mp = pmt_merge_kv_padded(rows, counts, pay, w=4)
    np.testing.assert_array_equal(np.array(mk)[:5], [5, 2, 1, m, m])
    np.testing.assert_array_equal(np.array(mp)[:5], [0, 4, 5, 1, 6])


def test_pmt_merge_padded_enforces_counts():
    """counts/valid_is_count are honoured: garbage beyond the valid region
    must not leak into the merged prefix (sentinel contract)."""
    from repro.core.merge_tree import pmt_merge_padded
    rows = jnp.array([[9, 5, 777, 777], [8, 2, 1, 777]], jnp.int32)
    counts = jnp.array([2, 3], jnp.int32)
    out = np.array(pmt_merge_padded(rows, counts, w=4))
    np.testing.assert_array_equal(out[:5], [9, 8, 5, 2, 1])
    mask = jnp.array([[1, 1, 0, 0], [1, 1, 1, 0]], bool)
    out2 = np.array(pmt_merge_padded(rows, mask, w=4, valid_is_count=False))
    np.testing.assert_array_equal(out2[:5], [9, 8, 5, 2, 1])

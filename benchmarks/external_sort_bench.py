"""DESIGN.md §8: the out-of-core two-phase sort (``engine.external_sort``).

Sweeps n × fan_in through the TopSort two-phase driver and prices every row
against the ``external_sort_bytes`` traffic model: one run-formation pass
plus ``ceil(log_fan_in(runs))`` streamed run-merge passes, 2·n·itemsize
each. The ``gbps``/``roof_frac`` columns are achieved streaming bandwidth
vs the device's published HBM ceiling (none on a device without published
peaks), and every row is oracle-checked — the
``exact`` column is a hard bit-for-bit comparison against ``np.sort`` /
stable argsort, not a statistic.

Default rows stay CI-smoke sized (n ≤ 2^22). ``REPRO_BENCH_BIG=1`` adds
the acceptance-scale rows — 2^27 keys, key-only and KV, far past what one
``pallas_call``'s scratch could hold — timed as single shots because one
call is minutes on a 1-core CPU container.
"""
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import bw_fields, row, time_fn
from repro import engine
from repro.launch.roofline import external_passes, external_sort_bytes


def _passes(n, tile, fan):
    return external_passes(max(-(-n // tile), 1), fan)


def _key_row(rng, name, n, tile, fan, *, variant=None, repeats=3, warmup=1,
             check=True):
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    fn = lambda: engine.external_sort(x, tile_elems=tile, fan_in=fan,
                                      descending=False, variant=variant)
    if repeats:
        us = time_fn(fn, repeats=repeats, warmup=warmup)
        out = fn()
    else:                                   # single shot (compile included)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        us = (time.perf_counter() - t0) * 1e6
    exact = bool((np.asarray(out) == np.sort(np.asarray(x))).all()) \
        if check else True
    assert exact, f"{name}: external_sort mismatch vs np.sort"
    return row(name, us, n=n, tile=tile, fan_in=fan, kv=False,
               passes=_passes(n, tile, fan), exact=exact, Melem_s=n / us,
               **bw_fields(external_sort_bytes(n, 4, tile, fan), us))


def _kv_row(rng, name, n, tile, fan, *, repeats=3, warmup=1):
    keys = jnp.asarray(rng.integers(0, 1 << 16, n).astype(np.int32))
    vals = jnp.arange(n, dtype=jnp.int32)
    fn = lambda: engine.external_sort(keys, values=vals, tile_elems=tile,
                                      fan_in=fan, descending=False)
    if repeats:
        us = time_fn(fn, repeats=repeats, warmup=warmup)
        _, perm = fn()
    else:
        t0 = time.perf_counter()
        _, perm = jax.block_until_ready(fn())
        us = (time.perf_counter() - t0) * 1e6
    ref = np.argsort(np.asarray(keys), kind="stable")
    exact = bool((np.asarray(perm) == ref).all())
    assert exact, f"{name}: external_sort KV mismatch vs stable argsort"
    return row(name, us, n=n, tile=tile, fan_in=fan, kv=True,
               passes=_passes(n, tile, fan), exact=exact, Melem_s=n / us,
               **bw_fields(external_sort_bytes(n, 8, tile, fan), us))


def run():
    rng = np.random.default_rng(11)
    out = []

    # --- fan-in sweep at fixed n: pass count vs per-pass width -------------
    n, tile = 1 << 20, 1 << 18                          # 4 runs
    for fan in (2, 4):                                  # 2 passes vs 1
        out.append(_key_row(rng, f"external/n2^20/t2^18/f{fan}",
                            n, tile, fan))

    # --- n sweep at the planner's shape -------------------------------------
    out.append(_key_row(rng, "external/n2^22/t2^19/f8", 1 << 22, 1 << 19, 8,
                        repeats=1))

    # --- KV lanes: stable compound merges, 2 lanes streamed -----------------
    out.append(_kv_row(rng, "external_kv/n2^20/t2^18/f4", 1 << 20, 1 << 18,
                       4, repeats=1))

    # --- the Pallas streaming kernel itself (interpret off-TPU) -------------
    out.append(_key_row(rng, "external/n2^17/t2^15/f4/stream_pallas",
                        1 << 17, 1 << 15, 4, variant="stream_pallas",
                        repeats=1))

    # --- acceptance scale: 2^27 keys, key-only and KV -----------------------
    # One pallas_call's scratch cannot hold these; single-shot timed.
    if os.environ.get("REPRO_BENCH_BIG"):
        out.append(_key_row(rng, "external/n2^27/t2^23/f16", 1 << 27,
                            1 << 23, 16, repeats=0))
        out.append(_kv_row(rng, "external_kv/n2^27/t2^23/f16", 1 << 27,
                           1 << 23, 16, repeats=0))
    return out

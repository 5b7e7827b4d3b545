"""§8.2 sharded sample sort: engine.sharded_sort sweep on a mesh over every
device this process holds — uniform vs zipf-skewed keys, regular vs
histogram-refined splitters, the old single-shot fixed cap vs in-graph
overflow recovery, plus sharded_topk.

The sweep runs in the harness's own process: a device belongs to one
process, so no child may need it once the parent has touched JAX. On a CPU
host, set ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before
starting the harness to sweep a virtual 8-device mesh. Derived columns
report elements/s, the bucket-count imbalance (max/mean of per-device
counts — 1.0 is perfectly balanced), and whether the fixed cap overflowed.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import row
from repro import engine
from repro.core.distributed import sample_sort
from repro.parallel.sharding import collect_sorted, data_shard_1d


def _timed(fn):
    jax.block_until_ready(fn())
    jax.block_until_ready(fn())
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def run():
    P = len(jax.devices())
    n = P * 4096
    mesh = jax.make_mesh((P,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.default_rng(7)
    datasets = [
        ("uniform", rng.integers(-10**6, 10**6, n).astype(np.int32)),
        # heavy duplicates: 60% of keys share one value -> one indivisible
        # bucket overflows any fixed cap (the recovery-ladder showcase)
        ("zipf", np.minimum(rng.zipf(2.0, n), 10**6).astype(np.int32)),
        # heavy-tailed but distinct keys: splitter QUALITY decides balance
        ("pareto", rng.pareto(1.5, n).astype(np.float32)),
    ]
    for name, x in datasets:
        xs = data_shard_1d(jnp.array(x), mesh)
        oracle = np.sort(x)[::-1]
        # the old contract-breaking baseline: fixed cap, no recovery
        res0 = sample_sort(xs, mesh, axis="data", w=32, retries=0)
        ovf0 = bool(np.asarray(res0.overflow).any())
        us0 = _timed(lambda: sample_sort(xs, mesh, axis="data", w=32,
                                         retries=0))
        yield row(f"sharded_sort/{name}/single_shot", us0,
                  devices=P, Melem_s=round(n / us0, 1), overflow=ovf0)
        for splitter in ("regular", "hist"):
            plan = engine.Plan("xla", w=32, splitter=splitter)
            res = engine.sharded_sort(xs, mesh, plan=plan)
            cnts = np.asarray(res.count).astype(np.float64)
            assert not np.asarray(res.overflow).any()
            assert (collect_sorted(res) == oracle).all(), (name, splitter)
            imb = float(cnts.max() / max(cnts.mean(), 1.0))
            us = _timed(lambda p=plan: engine.sharded_sort(xs, mesh, plan=p))
            yield row(f"sharded_sort/{name}/{splitter}", us, devices=P,
                      Melem_s=round(n / us, 1), imbalance=round(imb, 2))

    x = datasets[0][1]
    xs = data_shard_1d(jnp.array(x), mesh)
    ev = np.asarray(jax.lax.top_k(jnp.array(x), 64)[0])
    v, _ = engine.sharded_topk(xs, 64, mesh)
    assert (np.asarray(v) == ev).all()
    us = _timed(lambda: engine.sharded_topk(xs, 64, mesh))
    yield row("sharded_topk/uniform/k64", us, devices=P,
              Melem_s=round(n / us, 1))

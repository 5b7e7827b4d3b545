"""The TPC-H LINEITEM generator keeps the specification's rules
(clause 4.2.3) at a small row count."""
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[3]))

import jax  # noqa: E402

from benchmarks.chip.data import tpch_lineitem as gen  # noqa: E402

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "tpch-sf1-lineitem.json").read_text())


def small(rows=40_000):
    cfg = dict(CFG, rows=rows, orders_drawn=rows // 4 + 2_000)
    return cfg, {k: np.asarray(v) for k, v in
                 jax.jit(lambda k: gen.make(k, cfg, {}))(
                     jax.random.key(3)).items()}


def test_sf1_sizes_are_the_specifications():
    assert CFG["rows"] == 6_001_215 and CFG["orders"] == 1_500_000
    assert CFG["parts"] == 200_000
    # the drawn orders cover the row count by a wide margin (mean 4 lines)
    mean, sd = 4 * CFG["orders_drawn"], (CFG["orders_drawn"] * 4) ** 0.5
    assert mean - 10 * sd > CFG["rows"]


def test_shipdate_range_and_orders_share_orderdate():
    cfg, cols = small()
    ship = cols["l_shipdate"]
    first = gen.days("1992-01-01")
    last_order = gen.days("1998-12-31") - 151
    assert ship.min() >= first + 1 and ship.max() <= last_order + 121
    # regenerate the order structure the same way and check that every
    # order's lines ship within 121 days of one another (one orderdate)
    k_lines = jax.random.split(jax.random.key(3), 5)[0]
    lines = np.asarray(jax.random.randint(k_lines, (cfg["orders_drawn"],),
                                          1, 8))
    assert lines.min() >= 1 and lines.max() <= 7
    ends = np.cumsum(lines)
    starts = np.concatenate([[0], ends[:-1]])
    used = starts < cfg["rows"]
    for s, e in zip(starts[used][:2000], ends[used][:2000]):
        seg = ship[s:min(e, cfg["rows"])]
        assert seg.max() - seg.min() <= 120
    # about 2,526 distinct ship dates at SF1: heavy ties
    assert len(np.unique(ship)) > 2_000


def test_quantity_partkey_and_retail_price():
    pk = np.array([1, 10, 999, 1000, 12345, 200000])
    want = np.array([90000 + (p // 10) % 20001 + 100 * (p % 1000)
                     for p in pk])
    assert (np.asarray(gen.retail_price_cents(pk)) == want).all()
    # clause 4.2.3's example: partkey 1 costs $901.00
    assert int(gen.retail_price_cents(1)) == 90100
    _, cols = small()
    price = cols["l_extendedprice"]
    lo = 1 * int(gen.retail_price_cents(np.arange(1, 200_001)).min())
    hi = 50 * int(gen.retail_price_cents(np.arange(1, 200_001)).max())
    assert price.min() >= lo and price.max() <= hi
    assert price.dtype == np.int32


def test_same_seed_same_table():
    _, a = small(5_000)
    _, b = small(5_000)
    assert all((a[k] == b[k]).all() for k in a)

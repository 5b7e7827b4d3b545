"""TPC-H LINEITEM key columns at a given scale, generated on the device.

The rules are the specification's own (TPC-H v3.0.1, clause 4.2.3):

- each order has 1 to 7 lines, drawn per order, and its lines are stored
  together in order-key order;
- ``o_orderdate`` is uniform over [STARTDATE, ENDDATE - 151 days], with
  STARTDATE = 1992-01-01 and ENDDATE = 1998-12-31;
- ``l_shipdate = o_orderdate + uniform[1, 121]`` days;
- ``l_quantity`` is uniform in [1, 50] and ``l_partkey`` in [1, SF * 200,000];
- ``p_retailprice = (90000 + ((partkey / 10) mod 20001)
  + 100 * (partkey mod 1000)) / 100`` dollars;
- ``l_extendedprice = l_quantity * p_retailprice``.

Dates are int32 days since 1970-01-01 and prices int32 cents: the
scaled-decimal form a columnar database stores. The random numbers are
JAX's, not dbgen's, and the table is cut at exactly ``rows`` lines, so the
last order may lose lines.
"""
from __future__ import annotations

import datetime

import jax
import jax.numpy as jnp

_EPOCH = datetime.date(1970, 1, 1)


def days(iso: str) -> int:
    """Days from 1970-01-01 to an ISO date."""
    return (datetime.date.fromisoformat(iso) - _EPOCH).days


def retail_price_cents(partkey):
    """``p_retailprice`` of a part key, in cents (clause 4.2.3)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def make(key, cfg: dict, traffic: dict) -> dict:
    """One LINEITEM table's device columns: ``l_shipdate``, ``l_extendedprice``
    (row ids are the positions). Traceable: sizes come from ``cfg``."""
    rows = int(cfg["rows"])
    orders = int(cfg["orders_drawn"])
    lo_lines, hi_lines = cfg["lines_per_order"]
    lo_ship, hi_ship = cfg["ship_days_after_order"]
    lo_qty, hi_qty = cfg["quantity"]
    first_day = days(cfg["start_date"])
    last_day = days(cfg["end_date"]) - int(cfg["order_date_end_offset_days"])
    parts = int(cfg["parts"])
    k_lines, k_date, k_ship, k_qty, k_part = jax.random.split(key, 5)
    lines = jax.random.randint(k_lines, (orders,), lo_lines, hi_lines + 1,
                               jnp.int32)
    # row r belongs to the order whose line range holds it: count the order
    # starts at or before r (ends[i] is where order i + 1 starts)
    ends = jnp.cumsum(lines)
    starts = jnp.zeros((rows,), jnp.int32).at[ends].add(1, mode="drop")
    order_of_row = jnp.cumsum(starts)
    orderdate = jax.random.randint(k_date, (orders,), first_day, last_day + 1,
                                   jnp.int32)
    shipdate = orderdate[order_of_row] + jax.random.randint(
        k_ship, (rows,), lo_ship, hi_ship + 1, jnp.int32)
    quantity = jax.random.randint(k_qty, (rows,), lo_qty, hi_qty + 1, jnp.int32)
    partkey = jax.random.randint(k_part, (rows,), 1, parts + 1, jnp.int32)
    return {"l_shipdate": shipdate,
            "l_extendedprice": quantity * retail_price_cents(partkey)}

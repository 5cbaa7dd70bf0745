"""TPC-H Query 1, the pricing summary report, as a MapReduce job, and
lineitem's Q1 columns made on the device by the spec's generator.

The job is Q1 as a user of the engine writes it (TPC-H spec, clause
2.4.1): the map drops the rows shipped after ``1998-12-01 - DELTA days``
and keys each kept row by ``(l_returnflag, l_linestatus)``; the reduce
returns the four decimal sums, the three averages and, through the
table's counts, ``count_order``.  Decimals are scaled integers: quantity,
discount and tax in hundredths, prices in cents.  The map widens them to
int64, so the charge ``price x (100 - disc) x (100 + tax)`` (up to
1.1e11, in 1e-6 dollars) and its sums over a group (about 1e18 at SF 10)
are exact; the averages are float32, computed once from the exact sum.

Key ``2 f + s``: ``f`` the index of ``l_returnflag`` in "ANR", ``s`` that
of ``l_linestatus`` in "FO", so the key order is Q1's ``ORDER BY``; 6
keys, of which the generator fills 4 (A and R rows all ship before the
status date, so they are all F).
"""

from __future__ import annotations

import datetime

import jax
import jax.numpy as jnp

EPOCH = datetime.date(1970, 1, 1)


def day(iso: str) -> int:
    """A date as the columns hold it: days since 1970-01-01."""
    return (datetime.date.fromisoformat(iso) - EPOCH).days


STARTDATE = day("1992-01-01")
#: ENDDATE (1998-12-31) - 151 days: the last order date (clause 4.2.3)
LAST_ORDERDATE = day("1998-12-31") - 151
CURRENTDATE = day("1995-06-17")
FLAGS, STATUSES = "ANR", "FO"


def cutoff(cfg) -> int:
    """The last ship date Q1 keeps: 1998-12-01 - DELTA days."""
    return day("1998-12-01") - cfg["delta_days"]


def make_app(cfg):
    from repro.core import MapReduceApp

    last = cutoff(cfg)

    class TpchQ1(MapReduceApp):
        key_space = len(FLAGS) * len(STATUSES)
        value_aval = jax.ShapeDtypeStruct((4,), jnp.int64)
        emit_capacity = 1
        # the reduce flow's value windows: never this job's plan, which
        # folds into holders (a group holds millions of rows)
        max_values_per_key = 64

        def map(self, row, emit):
            rf, ls = row["l_returnflag"], row["l_linestatus"]
            f = ((rf == ord("N")).astype(jnp.int32)
                 + 2 * (rf == ord("R")).astype(jnp.int32))
            s = (ls == ord("O")).astype(jnp.int32)
            vals = jnp.stack([row["l_quantity"], row["l_extendedprice"],
                              row["l_discount"], row["l_tax"]])
            emit(2 * f + s, vals.astype(jnp.int64),
                 valid=row["l_shipdate"] <= last)

        def reduce(self, key, values, count):
            qty, price = values[:, 0], values[:, 1]
            disc, tax = values[:, 2], values[:, 3]
            disc_price = price * (100 - disc)
            sum_qty, sum_price = jnp.sum(qty), jnp.sum(price)
            n = count.astype(jnp.float32)

            def avg(total):  # hundredths -> units, 0 for an empty group
                mean = total.astype(jnp.float32) / n / jnp.float32(100)
                return jnp.where(count > 0, mean, jnp.float32(0))

            return (sum_qty, sum_price, jnp.sum(disc_price),
                    jnp.sum(disc_price * (100 + tax)),
                    avg(sum_qty), avg(sum_price), avg(jnp.sum(disc)))

    return TpchQ1()


def items_shape(cfg):
    n = (cfg["rows"],)
    i32, u8 = jnp.dtype(jnp.int32), jnp.dtype(jnp.uint8)
    return {"l_quantity": (n, i32), "l_extendedprice": (n, i32),
            "l_discount": (n, i32), "l_tax": (n, i32),
            "l_shipdate": (n, i32), "l_returnflag": (n, u8),
            "l_linestatus": (n, u8)}


def pairs(cfg) -> int:
    return cfg["rows"]


def retail_price(partkey):
    """``p_retailprice`` in cents (clause 4.2.3)."""
    return (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000))


def generate(cfg, key):
    """lineitem's Q1 columns, one row per line item (clause 4.2.3)."""
    n = (cfg["rows"],)
    ks = jax.random.split(key, 9)

    def uniform(k, lo, hi):  # inclusive, as the spec's random ranges
        return jax.random.randint(k, n, lo, hi + 1, jnp.int32)

    partkey = uniform(ks[0], 1, cfg["parts"])
    quantity = uniform(ks[1], 1, 50)
    orderdate = uniform(ks[2], STARTDATE, LAST_ORDERDATE)
    shipdate = orderdate + uniform(ks[3], 1, 121)
    receiptdate = shipdate + uniform(ks[4], 1, 30)
    returned = jax.random.bernoulli(ks[5], 0.5, n)
    flag = jnp.where(receiptdate <= CURRENTDATE,
                     jnp.where(returned, ord("R"), ord("A")), ord("N"))
    status = jnp.where(shipdate > CURRENTDATE, ord("O"), ord("F"))
    return {"l_quantity": quantity * 100,
            "l_extendedprice": quantity * retail_price(partkey),
            "l_discount": uniform(ks[6], 0, 10),
            "l_tax": uniform(ks[7], 0, 8),
            "l_shipdate": shipdate,
            "l_returnflag": flag.astype(jnp.uint8),
            "l_linestatus": status.astype(jnp.uint8)}


def key_space(cfg) -> int:
    return len(FLAGS) * len(STATUSES)

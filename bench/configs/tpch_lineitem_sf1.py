"""Plain generator of TPC-H ``lineitem`` over dbgen's domains and
distributions (TPC-H specification v3.0.1, section 4.2.3).

* Orders: ``1,500,000 * scale_factor`` of them, with dbgen's sparse
  ``o_orderkey`` (the first 8 of every 32 keys) and 1..7 lines each,
  uniform.  The line counts are then nudged, one line at a time on
  orders drawn from the seed, until the relation has exactly
  ``config["rows"]`` lines (dbgen's own stream gives 6,001,215 at SF1).
* The key is the composite (l_orderkey, l_linenumber), packed as
  ``orderkey * 8 + linenumber``.
* ``l_partkey`` uniform on 1..200,000 * SF; ``l_suppkey`` one of the
  part's four suppliers by dbgen's ``PART_SUPP_BRIDGE``; ``l_quantity``
  uniform on 1..50.
* ``o_orderdate`` uniform from 1992-01-01 to 151 days before
  1998-12-31; ``l_shipdate`` = orderdate + 1..121 days, ``l_commitdate``
  = orderdate + 30..90, ``l_receiptdate`` = shipdate + 1..30.
* ``l_returnflag`` is R or A at random where the receipt date is on or
  before 1995-06-17 (dbgen's CURRENTDATE), else N; ``l_linestatus`` is O
  where the ship date is after it, else F.
* ``l_shipinstruct`` and ``l_shipmode`` uniform over their lists.

The float columns (``l_extendedprice``, ``l_discount``, ``l_tax``) are
dropped, as in the DeepMapping paper (arXiv:2307.05861, section V-A1),
and so is the free-text ``l_comment`` (see the configuration's
``reduced``).  Columns come from ``seed``, as ``(domain, index)`` pairs:
the value of row ``i`` is ``domain[index[i]]``.
"""

from __future__ import annotations

import numpy as np

START = np.datetime64("1992-01-01")
CURRENT = np.datetime64("1995-06-17")
END = np.datetime64("1998-12-31")
#: every date a column can hold, as dbgen prints it
DATES = np.datetime_as_string(START + np.arange((END - START).astype(int) + 1)).astype("U10")
INSTRUCTIONS = np.array(["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"])
MODES = np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"])
RETURNFLAGS = np.array(["A", "N", "R"])
LINESTATUSES = np.array(["F", "O"])


def line_counts(rng: np.random.Generator, orders: int, rows: int) -> np.ndarray:
    """1..7 lines per order, uniform, then moved by single lines on
    orders drawn from ``rng`` until they sum to ``rows``."""
    if not orders <= rows <= 7 * orders:
        raise ValueError(f"{rows} lines do not fit {orders} orders of 1..7 lines")
    counts = rng.integers(1, 8, orders)
    diff = rows - int(counts.sum())
    while diff:
        room = np.flatnonzero(counts < 7) if diff > 0 else np.flatnonzero(counts > 1)
        pick = rng.choice(room, min(abs(diff), room.size), replace=False)
        counts[pick] += 1 if diff > 0 else -1
        diff -= int(np.sign(diff)) * pick.size
    return counts


def sparse_orderkeys(orders: int) -> np.ndarray:
    """dbgen's ``mk_sparse``: keep the low 3 bits of the order's index
    and leave 2 unused bits above them."""
    i = np.arange(1, orders + 1, dtype=np.int64)
    return ((i >> 3) << 5) + (i & 7)


def generate(config: dict, seed: int):
    """``(keys, {column: (domain, index)})`` for ``config["rows"]``
    line items at ``config["scale_factor"]``."""
    rows, sf = int(config["rows"]), float(config["scale_factor"])
    orders = max(1, round(1_500_000 * sf))
    parts = max(1, round(200_000 * sf))
    suppliers = max(1, round(10_000 * sf))
    rng = np.random.default_rng(seed)
    counts = line_counts(rng, orders, rows)
    order_of = np.repeat(np.arange(orders), counts)
    first = np.repeat(np.cumsum(counts) - counts, counts)
    linenumber = np.arange(rows, dtype=np.int64) - first + 1
    keys = sparse_orderkeys(orders)[order_of] * 8 + linenumber

    day = lambda d: int((d - START).astype(int))  # noqa: E731
    orderdate = rng.integers(0, day(END - np.timedelta64(151, "D")) + 1, orders)[order_of]
    shipdate = orderdate + rng.integers(1, 122, rows)
    commitdate = orderdate + rng.integers(30, 91, rows)
    receiptdate = shipdate + rng.integers(1, 31, rows)
    returned = rng.integers(0, 2, rows)  # R or A, where the line has been received
    returnflag = np.where(receiptdate <= day(CURRENT), np.where(returned == 1, 2, 0), 1)
    linestatus = (shipdate > day(CURRENT)).astype(np.int32)

    partkey = rng.integers(1, parts + 1, rows)
    supp_i = rng.integers(0, 4, rows)
    suppkey = (partkey + supp_i * (suppliers // 4 + (partkey - 1) // suppliers)) % suppliers + 1

    def ints(lo, hi, values):
        return np.arange(lo, hi + 1, dtype=np.int32), (values - lo).astype(np.int32)

    columns = {
        "l_partkey": ints(1, parts, partkey),
        "l_suppkey": ints(1, suppliers, suppkey),
        "l_quantity": ints(1, 50, rng.integers(1, 51, rows)),
        "l_returnflag": (RETURNFLAGS, returnflag.astype(np.int32)),
        "l_linestatus": (LINESTATUSES, linestatus),
        "l_shipdate": (DATES, shipdate.astype(np.int32)),
        "l_commitdate": (DATES, commitdate.astype(np.int32)),
        "l_receiptdate": (DATES, receiptdate.astype(np.int32)),
        "l_shipinstruct": (INSTRUCTIONS, rng.integers(0, 4, rows).astype(np.int32)),
        "l_shipmode": (MODES, rng.integers(0, 7, rows).astype(np.int32)),
    }
    return keys, columns

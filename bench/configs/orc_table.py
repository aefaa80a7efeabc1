"""The plain reference of ``orc_scan``: TPC-H ``lineitem`` rows, the columns
that TPC-H Q1 reads.

Every value follows the data generation rules of the TPC-H specification
(v3.0.1, clause 4.2.3) at the configuration's scale factor:

| column            | dtype  | rule (clause 4.2.3)                                    |
|-------------------|--------|--------------------------------------------------------|
| `l_quantity`      | int64  | random [1 .. 50], decimal(15,2) stored unscaled        |
| `l_extendedprice` | int64  | l_quantity * p_retailprice of a random part, in cents  |
| `l_discount`      | int64  | random [0.00 .. 0.10], unscaled                        |
| `l_tax`           | int64  | random [0.00 .. 0.08], unscaled                        |
| `l_returnflag`    | uint8  | `R` or `A` at random where receipt <= CURRENTDATE, else `N` |
| `l_linestatus`    | uint8  | `O` where l_shipdate > CURRENTDATE, else `F`           |
| `l_shipdate`      | int32  | o_orderdate + random [1 .. 121], days since 1970-01-01 |

with ``p_retailprice = (90000 + (p_partkey / 10) mod 20001 + 100 *
(p_partkey mod 1000)) / 100``, ``p_partkey`` random [1 .. SF * 200,000],
each order holding random [1 .. 7] lines, ``o_orderdate`` random between
STARTDATE and ENDDATE - 151 days, and ``l_receiptdate = l_shipdate +
random [1 .. 30]``.  Decimals are stored as ORC stores them, as unscaled
integers; dates as days; the one-letter flags as their byte.

A stripe is ``rows`` consecutive lines, drawn from its own stream
``default_rng([base_seed, stripe])``, so a stripe can be rebuilt after the
measured window without touching anything the program made.  The run's
seed orders the data and does not change it (``column``).

Imports nothing of the program: it is what a lossless decode must give back.
"""
from __future__ import annotations

import numpy as np

COLUMNS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
           "l_returnflag", "l_linestatus", "l_shipdate")


def _day(iso: str) -> int:
    return int(np.datetime64(iso, "D").astype(np.int64))


STARTDATE, CURRENTDATE, ENDDATE = (_day("1992-01-01"), _day("1995-06-17"),
                                   _day("1998-12-31"))


def stripe(base_seed: int, stripe_no: int, rows: int,
           scale_factor: float) -> dict:
    """``{column: values}`` of stripe ``stripe_no``: ``rows`` lineitem
    lines."""
    rng = np.random.default_rng([base_seed, stripe_no])
    lines = rng.integers(1, 8, rows)                     # lines per order
    n_orders = int(np.searchsorted(np.cumsum(lines), rows)) + 1
    orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1, n_orders)
    odate = np.repeat(orderdate, lines[:n_orders])[:rows]
    quantity = rng.integers(1, 51, rows)
    partkey = rng.integers(1, int(scale_factor * 200_000) + 1, rows)
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    shipdate = odate + rng.integers(1, 122, rows)
    receiptdate = shipdate + rng.integers(1, 31, rows)
    r_or_a = np.where(rng.random(rows) < 0.5, ord("R"), ord("A"))
    return {
        "l_quantity": (quantity * 100).astype(np.int64),
        "l_extendedprice": (quantity * retail_cents).astype(np.int64),
        "l_discount": rng.integers(0, 11, rows).astype(np.int64),
        "l_tax": rng.integers(0, 9, rows).astype(np.int64),
        "l_returnflag": np.where(receiptdate <= CURRENTDATE, r_or_a,
                                 ord("N")).astype(np.uint8),
        "l_linestatus": np.where(shipdate > CURRENTDATE, ord("O"),
                                 ord("F")).astype(np.uint8),
        "l_shipdate": shipdate.astype(np.int32),
    }


def chunk_elems(dtype, chunk_bytes: int) -> int:
    """Values per compression chunk: ``chunk_bytes`` of the (at most 4-byte)
    lanes a column is cut in; 8-byte values go as two 4-byte planes."""
    return chunk_bytes // min(np.dtype(dtype).itemsize, 4)


def column(base_seed: int, seed: int, stripe_no: int, name: str, rows: int,
           scale_factor: float, chunk_bytes: int) -> np.ndarray:
    """Column ``name`` of stripe ``stripe_no`` of the base table, its
    compression chunks shuffled by ``seed``.  Chunks are compressed on
    their own, so every seed gives the program the same chunks in another
    order: the same table shapes and the same work."""
    col = stripe(base_seed, stripe_no, rows, scale_factor)[name]
    chunk = chunk_elems(col.dtype, chunk_bytes)
    if rows % chunk:
        raise ValueError(f"{name}: {rows} rows do not split into chunks of "
                         f"{chunk} values")
    k = COLUMNS.index(name)
    order = np.random.default_rng([seed, stripe_no, k]).permutation(
        rows // chunk)
    return col.reshape(-1, chunk)[order].reshape(-1)

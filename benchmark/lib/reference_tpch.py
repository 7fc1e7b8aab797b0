"""The plain reference of TPC-H Q6, the Forecasting Revenue Change Query
(clause 2.4.6), over LINEITEM files. numpy + pyarrow only: never imports the
program, never jax.

    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= date '[DATE]' and l_shipdate < date '[DATE]' + interval '1' year
      and l_discount between [DISCOUNT] - 0.01 and [DISCOUNT] + 0.01 and l_quantity < [QUANTITY]

A query is its substitution parameters as text, {"date": "1994-01-01",
"discount": "0.06", "quantity": "24"}: DATE the first of January of 1993..1997,
DISCOUNT 0.02..0.09, QUANTITY 24 or 25 (`PARAMETERS`: 80 combinations). Every
bound is exact: dates are `datetime.date`s, decimals `decimal.Decimal`s taken
from the text. `q6` answers one table with pyarrow.compute over the decimal
columns; `q6_integers` is the second witness, the same sum in Python integers
over the columns' unscaled values; a table's answers add up over its files as
Python Decimals (`merge`). Each corpus worker answers its own file's share of
every query from pyarrow's read of the file it has just written
(`file_shares`): the reference reads what the program will read.
"""

from __future__ import annotations

import datetime
from decimal import Decimal

COLUMNS = ("l_shipdate", "l_discount", "l_quantity", "l_extendedprice")
REVENUE = "sum(l_extendedprice*l_discount)"  # the result key the daemon gives the aggregate
PARAMETERS = [
    {"date": f"{year}-01-01", "discount": f"0.{cents:02d}", "quantity": str(quantity)}
    for year in range(1993, 1998) for cents in range(2, 10) for quantity in (24, 25)
]


def bounds(query: dict) -> dict:
    """The five comparisons' exact bounds."""
    date = datetime.date.fromisoformat(query["date"])
    discount = Decimal(query["discount"])
    return {
        "ship_from": date, "ship_before": date.replace(year=date.year + 1),
        "discount_low": discount - Decimal("0.01"), "discount_high": discount + Decimal("0.01"),
        "quantity_below": Decimal(query["quantity"]),
    }


def filters(query: dict) -> list:
    """Q6's predicate as the five [column, op, value] triples a request
    carries: dates as ISO strings, decimals as numeric strings."""
    b = bounds(query)
    return [
        ["l_shipdate", ">=", b["ship_from"].isoformat()], ["l_shipdate", "<", b["ship_before"].isoformat()],
        ["l_discount", ">=", str(b["discount_low"])], ["l_discount", "<=", str(b["discount_high"])],
        ["l_quantity", "<", str(b["quantity_below"])],
    ]


def _keep(table, query: dict):
    import pyarrow as pa
    import pyarrow.compute as pc

    b = bounds(query)
    dec = lambda v: pa.scalar(v, type=pa.decimal128(15, 2))  # noqa: E731
    terms = [
        pc.greater_equal(table["l_shipdate"], pa.scalar(b["ship_from"], type=pa.date32())),
        pc.less(table["l_shipdate"], pa.scalar(b["ship_before"], type=pa.date32())),
        pc.greater_equal(table["l_discount"], dec(b["discount_low"])),
        pc.less_equal(table["l_discount"], dec(b["discount_high"])),
        pc.less(table["l_quantity"], dec(b["quantity_below"])),
    ]
    keep = terms[0]
    for t in terms[1:]:
        keep = pc.and_(keep, t)
    return keep


def q6(table, query: dict) -> dict:
    """{"count": matching rows, "revenue": the sum as a Decimal of scale 4,
    or None where no row matches} by pyarrow.compute over the decimal columns."""
    import pyarrow.compute as pc

    t = table.filter(_keep(table, query))
    return {"count": t.num_rows, "revenue": pc.sum(pc.multiply(t["l_extendedprice"], t["l_discount"])).as_py()}


def unscaled(column):
    """A decimal128 column of precision <= 18 as its unscaled int64 values."""
    import numpy as np

    return np.concatenate([np.frombuffer(c.buffers()[1], dtype=np.int64)[2 * c.offset:2 * (c.offset + len(c)):2]
                           for c in column.chunks] or [np.zeros(0, dtype=np.int64)])


def q6_integers(table, query: dict) -> dict:
    """The same answer in Python integers over the unscaled values."""
    import numpy as np

    b = bounds(query)
    epoch = datetime.date(1970, 1, 1)
    ship = table["l_shipdate"].cast("int32").to_numpy()
    discount, quantity, price = (unscaled(table[c]) for c in ("l_discount", "l_quantity", "l_extendedprice"))
    keep = ((ship >= (b["ship_from"] - epoch).days) & (ship < (b["ship_before"] - epoch).days)
            & (discount >= int(b["discount_low"] * 100)) & (discount <= int(b["discount_high"] * 100))
            & (quantity < int(b["quantity_below"] * 100)))
    rows = np.flatnonzero(keep)
    total = sum(int(p) * int(d) for p, d in zip(price[rows].tolist(), discount[rows].tolist()))
    return {"count": len(rows), "revenue": Decimal(total).scaleb(-4) if len(rows) else None}


def file_shares(path: str, queries: list) -> list:
    """One file's share of each query, JSON-ready (the revenue as text)."""
    import pyarrow.parquet as pq

    table = pq.read_table(path, columns=list(COLUMNS))
    shares = [q6(table, q) for q in queries]
    return [{"count": s["count"], "revenue": None if s["revenue"] is None else str(s["revenue"])} for s in shares]


def merge(shares: list) -> dict:
    """The shares of one query added up: the daemon's `result` for it, the
    revenue rendered as the daemon renders a decimal (its text)."""
    revenues = [Decimal(s["revenue"]) for s in shares if s["revenue"] is not None]
    return {"count": sum(s["count"] for s in shares), REVENUE: str(sum(revenues)) if revenues else None}


def expected(facts: dict, n_queries: int) -> list:
    """One merged answer per query, from the corpus facts."""
    return [merge([f["shares"][q] for f in facts["files"]]) for q in range(n_queries)]
